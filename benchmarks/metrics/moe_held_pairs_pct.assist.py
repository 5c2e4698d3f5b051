"""Share of the (token, expert) pairs that landed on experts this chip
holds (``moe_held_pairs_pct.chat``'s rule): 12.5 under even routing with
9 of 72 experts held."""

from benchmarks.harness.twins import reader

read = reader("moe_held_pairs_pct.chat")
