"""Padding's share of the positions bucketed prefill ran
(``prefill_pad_pct.chat``'s rule, harness/program_tape.py)."""

from benchmarks.harness.program_tape import prefill_pad_pct as read  # noqa: F401
