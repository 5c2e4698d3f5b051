"""Host time of the decode program's dispatch in ``qwen3_next_ep8.serve_chat_backlog``
(``decode_dispatch_ms.backlog``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_dispatch_ms.backlog")
