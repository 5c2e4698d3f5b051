"""Host time of the decode program's dispatch in
``granite4_h_small_ep8.serve_assist_backlog``
(``decode_dispatch_ms.backlog``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_dispatch_ms.backlog")
