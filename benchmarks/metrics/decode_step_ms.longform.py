"""Host clock across one ``DecodeEngine.decode()`` call, the token
read-back included (``decode_step_ms.chat``'s rule)."""

from benchmarks.harness.twins import reader

read = reader("decode_step_ms.chat")
