"""Share of the (token, expert) pairs that landed on experts this chip
holds (``moe_held_pairs_pct.chat``'s rule): 12.5 under even routing with
64 of 512 experts held — the held experts are exactly one of the eight
routing groups, of which a token keeps four."""

from benchmarks.harness.twins import reader

read = reader("moe_held_pairs_pct.chat")
