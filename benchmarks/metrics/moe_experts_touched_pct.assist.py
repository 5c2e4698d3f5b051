"""Share of the held experts that got at least one pair in a decode
step (``moe_experts_touched_pct.chat``'s rule): at 192 slots x 10 picks
over 72 experts, all of them."""

from benchmarks.harness.twins import reader

read = reader("moe_experts_touched_pct.chat")
