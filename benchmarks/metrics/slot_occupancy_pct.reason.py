"""Share of the decode slots that held a live request, averaged over
the window's decode boundaries (``slot_occupancy_pct.chat``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("slot_occupancy_pct.chat")
