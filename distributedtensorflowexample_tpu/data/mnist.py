"""MNIST input pipeline (component C10 in SURVEY.md §2).

Reference behavior [RECONSTRUCTED — reference tree was empty]: an
``input_data.read_data_sets(data_dir)``-style download + minibatch feed.
TPU-native rebuild: pure-numpy IDX parsing with no TF dependency; if the
standard IDX files are absent (no network in this environment) we fall back
to deterministic synthetic data with the same shapes (see ``synthetic.py``).
"""

from __future__ import annotations

import gzip
import os
import struct

import numpy as np

from distributedtensorflowexample_tpu.data.dequant import U8_UNIT_SCALE
from distributedtensorflowexample_tpu.data.synthetic import make_synthetic

_FILES = {
    "train": ("train-images-idx3-ubyte", "train-labels-idx1-ubyte"),
    "test": ("t10k-images-idx3-ubyte", "t10k-labels-idx1-ubyte"),
}
_SYNTH_SIZES = {"train": 60000, "test": 10000}


def _open_maybe_gz(path: str):
    if os.path.exists(path + ".gz"):
        return gzip.open(path + ".gz", "rb")
    return open(path, "rb")


def _read_idx_images(path: str) -> np.ndarray:
    with _open_maybe_gz(path) as f:
        raw = f.read()
    from distributedtensorflowexample_tpu import native
    if native.available():
        return native.parse_idx_images(raw)
    magic, n, rows, cols = struct.unpack(">IIII", raw[:16])
    if magic != 2051:
        raise ValueError(f"bad IDX image magic {magic} in {path}")
    data = np.frombuffer(raw, dtype=np.uint8, count=n * rows * cols, offset=16)
    # Multiply by the canonical f32 1/255, NOT divide: the affine form is
    # the repo-wide byte->float convention (data.dequant), so the in-step
    # affine dequant of the uint8-resident split is bitwise-identical to
    # these floats.  (An f32 division rounds differently on 126/256 byte
    # values — it was what forced the 4.1x-slower LUT dequant.)
    return data.reshape(n, rows, cols, 1).astype(np.float32) * U8_UNIT_SCALE


def _read_idx_labels(path: str) -> np.ndarray:
    with _open_maybe_gz(path) as f:
        raw = f.read()
    from distributedtensorflowexample_tpu import native
    if native.available():
        return native.parse_idx_labels(raw)
    magic, n = struct.unpack(">II", raw[:8])
    if magic != 2049:
        raise ValueError(f"bad IDX label magic {magic} in {path}")
    return np.frombuffer(raw, dtype=np.uint8, count=n, offset=8).astype(np.int32)


def load_mnist(data_dir: str, split: str = "train",
               synthetic_size: int | None = None,
               seed: int = 0,
               source: str = "real") -> tuple[np.ndarray, np.ndarray]:
    """Return (images [N,28,28,1] float32 in [0,1], labels [N] int32).

    ``source`` selects where the bytes come from (VERDICT r4 #5 — no
    silent substitution on the user surface):

    - ``"real"`` (default): the standard IDX(.gz) files must exist in
      ``data_dir``; a missing file is a crisp ``FileNotFoundError`` that
      names ``--dataset synthetic`` as the opt-in.
    - ``"synthetic"``: the deterministic synthetic split, explicitly
      requested — no warning.
    - ``"fallback"``: real if present, else synthetic with a LOUD
      once-per-split warning (for harnesses that must run with or
      without the bytes, e.g. on a data-less chip host).
    """
    if source not in ("real", "synthetic", "fallback"):
        raise ValueError(f"unknown source {source!r}")
    img_name, lbl_name = _FILES[split]
    img_path = os.path.join(data_dir, img_name)
    lbl_path = os.path.join(data_dir, lbl_name)
    have = os.path.exists(img_path) or os.path.exists(img_path + ".gz")
    if source != "synthetic" and have:
        return _read_idx_images(img_path), _read_idx_labels(lbl_path)
    if source == "real":
        raise FileNotFoundError(
            f"MNIST {split!r} bytes not found in {data_dir!r} (expected "
            f"{img_name}[.gz]). Point --data_dir at the IDX files, or pass "
            f"--dataset synthetic to train on the deterministic synthetic "
            f"split instead.")
    if source == "fallback":
        from distributedtensorflowexample_tpu.data.synthetic import (
            warn_synthetic)
        warn_synthetic("MNIST", split, data_dir, img_name)
    num = synthetic_size or _SYNTH_SIZES[split]
    # Same class templates for both splits; disjoint sample draws — so a
    # model trained on "train" genuinely generalizes to "test".
    return make_synthetic(num, (28, 28, 1), 10, seed=seed,
                          sample_seed=seed * 2 + (1 if split == "train" else 2))
