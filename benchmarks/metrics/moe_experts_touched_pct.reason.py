"""Share of the held experts that got at least one pair in a decode
step (``moe_experts_touched_pct.chat``'s rule): at 80 slots x 8 picks
over 384 experts, 20 pairs a layer on 12 experts, about four in five."""

from benchmarks.harness.twins import reader

read = reader("moe_experts_touched_pct.chat")
