"""Padding's share of the positions bucketed prefill ran: the program's
``serve_prefill_positions_total`` counters, pad over prompt plus pad,
over the whole run (harness/program_tape.py says why not the window)."""

from benchmarks.harness.program_tape import prefill_pad_pct as read  # noqa: F401
