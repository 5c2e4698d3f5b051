"""The decode step's share of its roofline (harness/readers.py)."""

from benchmarks.harness.readers import decode_roofline_pct as read  # noqa: F401
