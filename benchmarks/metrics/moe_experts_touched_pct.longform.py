"""Share of the held experts that got at least one pair in a decode
step (``moe_experts_touched_pct.chat``'s rule): at 256 slots x 8 picks
over 512 experts, nearly all."""

from benchmarks.harness.twins import reader

read = reader("moe_experts_touched_pct.chat")
