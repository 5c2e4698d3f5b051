"""Share of the (token, expert) pairs that landed on experts this chip
holds (``moe_held_pairs_pct.chat``'s rule): 3.125 under even routing with
12 of 384 experts held."""

from benchmarks.harness.twins import reader

read = reader("moe_held_pairs_pct.chat")
