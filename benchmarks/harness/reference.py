"""Each configuration names its plain reference (``"reference"`` in its
file): a module under ``benchmarks/reference/`` that imports nothing of
the program."""

from __future__ import annotations

import importlib


def load_reference(config: dict):
    return importlib.import_module(
        "benchmarks.reference." + config["reference"])
