"""Host time of the decode program's dispatch in ``trinity_large_ep8.serve_mixed_backlog``
(``decode_dispatch_ms.backlog``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_dispatch_ms.backlog")
