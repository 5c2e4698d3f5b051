"""A per-layer metric that exists once per kind of cell
(``<metric>.chat``, ``<metric>.longform``) and reads the same thing in
each: the later cell's reader file names the earlier one's rule here
instead of carrying a copy of its body."""

from __future__ import annotations

import importlib.util
import os

_METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def reader(name: str):
    """``read`` of ``benchmarks/metrics/<name>.py`` (a reader's file is
    named for its metric, dots and all, so it is found by path, as
    ``run.py`` finds it)."""
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "__"),
        os.path.join(_METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
