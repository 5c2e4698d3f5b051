"""Host time of the decode program's dispatch in ``ling3_flash_ep8.serve_longform_backlog``
(``decode_dispatch_ms.backlog``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_dispatch_ms.backlog")
