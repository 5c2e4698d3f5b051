"""Device idle share of the traced window (harness/readers.py)."""

from benchmarks.harness.readers import device_idle_pct as read  # noqa: F401
