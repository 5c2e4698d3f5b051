"""The percentile rule, the seed-independent draws, and the FLOP/byte
functions against GPT-2 small worked by hand."""

import json
import os

import numpy as np
import pytest

from benchmarks.families import gpt2
from benchmarks.harness import stats
from benchmarks.harness.peaks import roofline_seconds
from benchmarks.tests.conftest import ROOT


def test_percentile_needs_ten_samples_beyond_it():
    assert stats.percentile(list(range(199)), 0.95) is None
    assert stats.percentile(list(range(200)), 0.95) == 189.0
    assert stats.percentile([], 0.95) is None
    # 20 samples support the median (10 beyond), not the 95th.
    assert stats.percentile(list(range(20)), 0.5) == 9.0
    assert stats.percentile(list(range(20)), 0.95) is None


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(4 / 4)


def test_lengths_are_one_multiset_whatever_the_seed():
    prompt = dict(median=192, sigma=0.8, min=16, max=768)
    output = dict(median=96, sigma=0.6, min=8, max=256)
    pl, ol = stats.length_pairs(256, prompt, output, 1024)
    assert (pl + ol).max() <= 1024 and ol.min() >= 1
    assert pl.min() >= 16 and pl.max() == 768 and ol.max() == 256
    # The marginals sit at the distribution's quantiles.
    assert abs(np.median(pl) - 192) <= 2 and abs(np.median(ol) - 96) <= 2
    orders = [np.random.default_rng([s, 2]).permutation(256)
              for s in (1, 2)]
    a, b = [(tuple(pl[o]), tuple(ol[o])) for o in orders]
    assert a != b                                   # another order
    assert sorted(zip(*a)) == sorted(zip(*b))       # the same work


def test_arrival_gaps_have_the_exact_mean():
    g = stats.exponential_quantiles(256, 0.125)
    assert g.sum() == pytest.approx(256 * 0.125)
    assert (np.diff(g) > 0).all() and g.min() > 0


def test_seed31_takes_the_drivers_large_seeds():
    assert 0 <= stats.seed31(2 ** 31 + 12345) < 2 ** 31
    assert stats.seed31(7) != stats.seed31(8)


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(ROOT, "benchmarks/configs/gpt2_124m.json")) as f:
        return json.load(f)


def test_train_flops_of_gpt2_small_by_hand(small):
    # Per layer and token: qkv 2*768*2304, out 2*768*768, MLP
    # 2*2*768*3072 = 14,155,776; attention over (1024+1)/2 keys:
    # 4*768*512.5 = 1,574,400.  Head 2*768*50257 = 77,194,752.
    fwd = 12 * (14_155_776 + 1_574_400) + 77_194_752
    assert gpt2.forward_flops_per_token(small, 1024) == fwd == 265_956_864
    assert gpt2.train_flops_per_token(small, 1024) == 3 * fwd


def test_decode_step_of_gpt2_small_by_hand(small):
    # 96 slots, 30,000 live rows: weights 12*14,155,776 + head per slot,
    # 4*768 per live row and layer.
    f = 96 * (12 * 14_155_776 + 77_194_752) + 12 * 4 * 768 * 30_000
    assert gpt2.decode_step_flops(small, 30_000, 96) == f
    # f32 weights once (124,439,808 * 4) and K and V rows in bf16.
    b = 497_759_232 + 30_000 * 12 * 2 * 768 * 2
    assert gpt2.decode_step_bytes(small, 30_000) == b
    peaks = dict(bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9)
    t, bound = roofline_seconds(f, b, peaks)
    assert bound == "bandwidth" and t == pytest.approx(b / 819e9)
