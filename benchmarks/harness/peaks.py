"""The table of device peaks, keyed by ``device_kind`` as jax reports it,
and the least time a device with those peaks needs for given work.  A
device that is not in the table is an error: no peak is ever taken from
a default."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no row in "
                       f"{_TABLE}; add its published peaks with their "
                       f"source before measuring on it")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound) on a device with ``peaks``."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
