"""Share of the window the serving loop spent inside
``DecodeEngine.prefill_many`` (``prefill_share_pct.chat``'s rule): about
an eighth here by the traffic's shape — prompts of ~0.8 k against
outputs of ~3 k."""

from benchmarks.harness.twins import reader

read = reader("prefill_share_pct.chat")
