"""Share of the engine's cache that is recurrent state and not K/V rows
(``cache_state_pct.chat``'s rule): 82 at 192 slots x 2,048 rows (7.33 GB
of state beside 1.61 GB of rows)."""

from benchmarks.harness.twins import reader

read = reader("cache_state_pct.chat")
