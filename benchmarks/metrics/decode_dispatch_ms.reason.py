"""Host time of the decode program's dispatch in
``kimi_k2_5_ep32.serve_reasoning_backlog``
(``decode_dispatch_ms.backlog``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_dispatch_ms.backlog")
