"""The expert walk's grouped products (ops/moe.py): which kernel runs one
is chosen from the backend, the block's rows and the expert matrix's
shape and type; the tiled kernel (JAX's ``megablox.gmm`` by whole
experts or by whole-lane splits of their columns, interpreted here) gives
what ``jax.lax.ragged_dot`` gives on the same sorted rows, a split of the
columns changes no bit of it, the walk never reads a row the kernel left
unwritten, and a counter says which kernel each traced product took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.ops import moe

BF16_TOL = 2 ** -7      # one bfloat16 rounding of results of size ~1


# ---- the chooser -----------------------------------------------------------

#: (backend, rows, (k, n) of an expert, dtype) -> the kernel's tiles, None
#: for ``ragged_dot``.
CHOICES = {
    "ling gate/up, token step": ("tpu", 512, (2560, 768), jnp.bfloat16,
                                 (128, 2560, 768)),
    "ling down, token step": ("tpu", 512, (768, 2560), jnp.bfloat16,
                              (128, 768, 2560)),
    "ling gate/up, a full block": ("tpu", 2048, (2560, 768), jnp.bfloat16,
                                   (128, 2560, 768)),
    "qwen gate/up, token step": ("tpu", 640, (2048, 512), jnp.bfloat16,
                                 (128, 2048, 512)),
    "qwen down, a full block": ("tpu", 2048, (512, 2048), jnp.bfloat16,
                                (128, 512, 2048)),
    # 18.9 MB: no whole expert fits; in halves the tile is 9.4 MB, twice
    # over the limit; in thirds 6.3 MB, 15.7 of the 16.8 MB.
    "trinity, token step": ("tpu", 128, (3072, 3072), jnp.bfloat16,
                            (128, 3072, 1024)),
    "trinity, a full block": ("tpu", 2048, (3072, 3072), jnp.bfloat16,
                              (128, 3072, 1024)),
    # 29.4 MB: in two and in four tiles it does not fit, in eight it would
    # (3.7 MB a tile), and eight is more than the rule splits into.
    "kimi gate/up, token step": ("tpu", 128, (7168, 2048), jnp.bfloat16,
                                 None),
    # 7,168 = 7 x 1,024: two and four tiles are over the limit, seven fit
    # and are too many.
    "kimi down, a full block": ("tpu", 2048, (2048, 7168), jnp.bfloat16,
                                None),
    # In two tiles 10.5 MB, twice over the limit; in four 5.2 MB.
    "four tiles, the most": ("tpu", 512, (2560, 4096), jnp.bfloat16,
                             (128, 2560, 1024)),
    # Five tiles of 1,024 would fit as Trinity's three do.
    "five tiles would fit": ("tpu", 128, (3072, 5120), jnp.bfloat16, None),
    "granite down, token step": ("tpu", 512, (768, 4096), jnp.bfloat16,
                                 (128, 768, 2048)),
    "granite gate/up, token step": ("tpu", 512, (4096, 768), jnp.bfloat16,
                                    (128, 4096, 768)),
    # 3,000 columns are no whole lane tiles, in any number of parts.
    "no whole-lane split": ("tpu", 128, (3072, 3000), jnp.bfloat16, None),
    # One lane tile of 40,000 rows is 10 MB: twice that fits nowhere, and
    # K is never split.
    "rows of an expert longer than any tile": (
        "tpu", 128, (40000, 256), jnp.bfloat16, None),
    "no whole row tiles": ("tpu", 200, (2048, 512), jnp.bfloat16, None),
    "ling on the cpu": ("cpu", 512, (2560, 768), jnp.bfloat16, None),
    "ling on a gpu": ("gpu", 512, (2560, 768), jnp.bfloat16, None),
    "float16": ("tpu", 512, (2560, 768), jnp.float16, None),
    "float32, which no served configuration computes in": (
        "tpu", 512, (2560, 768), jnp.float32, None),
    # 6.6 MB: twice that, the row tiles and the accumulator are 16.4 of
    # the 16.8 MB; at 7.9 MB the matrix alone fits twice and no more.
    "the largest that fits beside its row tiles": (
        "tpu", 512, (2560, 1280), jnp.bfloat16, (128, 2560, 1280)),
    # ... so the split steps from one to two: halves of 3.9 MB.
    "a matrix that fits twice and no more": ("tpu", 512, (2560, 1536),
                                             jnp.bfloat16, (128, 2560, 768)),
    "trinity in float32": ("tpu", 128, (3072, 3072), jnp.float32, None),
    "trinity, no whole row tiles": ("tpu", 100, (3072, 3072), jnp.bfloat16,
                                    None),
}


@pytest.mark.parametrize("case", CHOICES)
def test_the_kernel_is_chosen_from_what_the_call_observes(monkeypatch, case):
    backend, rows, (k, n), dtype, want = CHOICES[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert moe.product_tiling(rows, k, n, dtype) == want


def _tiled(monkeypatch, poison=False, tn=None):
    """The chooser as a TPU program would answer it (the kernel runs in
    the Pallas interpreter here), by whole experts or, where an expert
    has whole tiles of ``tn`` columns, by those; ``poison``: what the
    kernel leaves unwritten reads as NaN."""
    monkeypatch.setattr(moe, "product_tiling", lambda rows, k, n, dtype: (
        None if rows % moe.ROW_TILE
        else (moe.ROW_TILE, k, tn if tn and n % tn == 0 else n)))
    if poison:
        product = moe.grouped_product

        def poisoned(x, w, sizes):
            out = product(x, w, sizes)
            past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
            return jnp.where(past[:, None], jnp.nan, out)
        monkeypatch.setattr(moe, "grouped_product", poisoned)


def _taken() -> dict:
    """``moe_grouped_products_total`` by kernel, so far."""
    got = obs_metrics.registry().snapshot()["counters"]
    return {kernel: got.get(
        'moe_grouped_products_total{kernel="%s"}' % kernel, 0)
        for kernel in ("gmm", "ragged_dot")}


# ---- the tiled kernel against ragged_dot on the same sorted rows -----------

def _operands(rows, k, n, sizes, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)) * k ** -0.5,
                    jnp.bfloat16)
    return x, w, jnp.asarray(sizes, jnp.int32)


#: rows, (k, n), rows a group[, columns of a tile: narrower than the
#: expert].
PRODUCTS = {
    "ling gate/up, four experts": (256, (2560, 768), [40, 70, 30, 116]),
    "ling down, four experts": (256, (768, 2560), [40, 70, 30, 116]),
    "qwen gate/up, four experts": (256, (2048, 512), [100, 1, 150, 5]),
    "qwen down, four experts": (256, (512, 2048), [100, 1, 150, 5]),
    "empty experts": (256, (256, 128), [0, 90, 0, 0, 166, 0]),
    "held pairs end mid-tile": (384, (256, 128), [50, 0, 60, 31]),
    "a tile of rows no group reaches": (384, (256, 128), [3, 0, 2, 1]),
    "no pair at all": (128, (256, 128), [0, 0, 0]),
    "every row on one expert": (256, (256, 512), [0, 256, 0]),
    "two tiles of columns": (256, (256, 512), [40, 70, 30, 116], 256),
    "three tiles of columns": (256, (384, 768), [100, 1, 150, 5], 256),
    "three tiles, a token step's few pairs": (128, (640, 384),
                                              [3, 0, 9, 0, 0, 4], 128),
    "two tiles, empty experts": (256, (256, 256), [0, 90, 0, 0, 166, 0], 128),
    "three tiles, held pairs end mid-tile": (384, (256, 384),
                                             [50, 0, 60, 31], 128),
    "four tiles, a tile of rows no group reaches": (
        384, (128, 512), [3, 0, 2, 1], 128),
    "two tiles, no pair at all": (128, (256, 256), [0, 0, 0], 128),
    "seven tiles, every row on one expert": (256, (128, 896), [0, 256, 0],
                                             128),
}


@pytest.mark.parametrize("case", PRODUCTS)
def test_the_tiled_kernel_is_ragged_dot_on_the_held_rows(monkeypatch, case):
    """... to bfloat16's rounding (XLA:CPU's ``ragged_dot`` sums in
    another order; on the chip the two are bit-equal, PERF.md section 6),
    and in tiles narrower than the expert it is the whole-expert kernel's
    result bit for bit: K stays whole, so a column's sum is the same
    sum."""
    rows, (k, n), sizes, *tn = PRODUCTS[case]
    x, w, g = _operands(rows, k, n, sizes, seed=len(case))
    want = moe.grouped_product(x, w, g)     # the CPU's: ragged_dot
    _tiled(monkeypatch)
    got = moe.grouped_product(x, w, g)
    held = sum(sizes)
    if tn:
        whole = got
        _tiled(monkeypatch, tn=tn[0])
        assert moe.product_tiling(rows, k, n, x.dtype) == (128, k, tn[0])
        got = moe.grouped_product(x, w, g)
        assert np.array_equal(np.asarray(got[:held], np.float32),
                              np.asarray(whole[:held], np.float32))
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.bfloat16
    gap = np.abs(np.asarray(got[:held], np.float32)
                 - np.asarray(want[:held], np.float32))
    assert gap.max(initial=0.0) <= BF16_TOL * max(
        1.0, float(np.abs(np.asarray(want[:held], np.float32)).max(initial=0)))


# ---- the walk over either kernel -------------------------------------------

def _layer(seed, tokens, experts, d=256, f=128):
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
    return (normal(tokens, d), normal(experts, d, f) * d ** -0.5,
            normal(experts, d, f) * d ** -0.5,
            normal(experts, f, d) * f ** -0.5)


#: tokens, picks, experts held, experts known, the first held, picks that
#: every token puts on one held expert, rows of a block, trips.
WALKS = {
    "one trip, pairs end mid-tile": (96, 4, 6, 24, 3, 0, 256, 1),
    "two trips, skewed onto two experts": (200, 3, 4, 32, 8, 2, 256, 2),
    "three trips of one row tile": (120, 4, 2, 16, 6, 3, 128, 3),
    "one block of every pair": (64, 2, 4, 4, 0, 0, 128, 1),
}


@pytest.mark.parametrize("tiles", [1, 2])
@pytest.mark.parametrize("poison", [False, True])
@pytest.mark.parametrize("case", WALKS)
def test_the_walk_is_the_same_over_either_kernel(monkeypatch, case, poison,
                                                 tiles):
    """``expert_ffn`` with the tiled kernel in place of ``ragged_dot``,
    by whole experts and with every expert's columns in ``tiles`` tiles:
    the same result to bfloat16's rounding, the same counts, and each of
    a walk's three products counted once under the kernel it took; with
    NaN in every row past the held pairs the result is still the same:
    such a row is selected away, never multiplied by zero."""
    tokens, k, held, known, first, skewed, rows, trips = WALKS[case]
    m, gate, up, down = _layer(len(case), tokens, held, f=128 * tiles)
    rng = np.random.default_rng(tokens)
    sel = rng.integers(0, known, (tokens, k))
    sel[:, :skewed] = first + np.arange(skewed) % held
    sel = jnp.asarray(sel, jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    live = jnp.arange(tokens) < tokens - 7
    run = lambda: moe.expert_ffn(m, sel, w, gate, up, down,
                                 first_expert=first, experts_known=known,
                                 live=live)
    assert moe.block_rows(tokens * k, held, known) == rows
    before = _taken()
    want, counts = run()
    assert counts.tolist()[3] == trips * rows
    assert _taken() == {**before, "ragged_dot": before["ragged_dot"] + 3}
    _tiled(monkeypatch, poison, tn=128 if tiles > 1 else None)
    got, stats = run()
    assert stats.tolist() == counts.tolist()
    assert _taken() == {"gmm": before["gmm"] + 3,
                        "ragged_dot": before["ragged_dot"] + 3}
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 2 * BF16_TOL * np.abs(want).max()


def test_a_block_of_no_whole_row_tiles_keeps_ragged_dot(monkeypatch):
    """Built for a TPU at Qwen's widths, but 25 tokens x 8 picks are 200
    pairs in one block of 200 rows: ``ragged_dot``, three times."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m, gate, up, down = _layer(3, 25, 2, d=2048, f=512)
    sel = jnp.asarray(np.random.default_rng(3).integers(0, 2, (25, 8)),
                      jnp.int32)
    assert moe.block_rows(200, 2, 2) == 200
    before = _taken()
    text = str(jax.make_jaxpr(lambda *a: moe.expert_ffn(
        *a, first_expert=0, experts_known=2))(
        m, sel, jnp.ones((25, 8), jnp.float32), gate, up, down))
    assert text.count("= ragged_dot_general[") == 3
    assert "pallas_call" not in text
    assert _taken() == {**before, "ragged_dot": before["ragged_dot"] + 3}
