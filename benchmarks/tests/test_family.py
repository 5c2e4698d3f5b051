"""The seam between the harness and an architecture: a configuration
names its family (``benchmarks/families/<family>.py``), and nothing
else under ``benchmarks/`` knows a size of it.

1. A family the harness has never seen, whose configuration uses other
   key names, runs through both kinds of cell with no harness file
   aware of it.
2. ``families/gpt2.py`` gives the counts and the weights that
   ``harness/weights.py`` and ``harness/flops.py`` gave before they
   moved there (numbers pinned from the parent commit, 4460711).
3. No GPT-2 key and no import of the program's model outside the
   family, the reference and the configuration files.
4. The schema check refuses a configuration whose family or reference
   has no module."""

import hashlib
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.families import gpt2
from benchmarks.harness import schema
from benchmarks.tests import hf_names
from benchmarks.tests.conftest import (ROOT, TINY_CONFIG, TINY_SERVE,
                                       TINY_TRAIN)

BENCH = os.path.join(ROOT, "benchmarks")

#: The toy sizes of conftest.TINY_CONFIG under the fixture family's own
#: key names.  The GPT-2 keys of the file the override lands on stay at
#: their published sizes, so a harness file that read one would build
#: or count a model of another size than the family's.
TINY_HF_CONFIG = dict(
    family="hf_names", reference="hf_names", hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, intermediate_size=256,
    max_position_embeddings=64, vocab_size=300)


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell, params", [
    ("gpt2_124m.train_seq1024", TINY_TRAIN),
    ("gpt2_124m.serve_backlog", TINY_SERVE),
])
def test_an_unknown_family_runs_through_both_kinds(monkeypatch, cell, params):
    monkeypatch.setitem(sys.modules, "benchmarks.families.hf_names",
                        hf_names)
    monkeypatch.setitem(sys.modules, "benchmarks.reference.hf_names",
                        hf_names)
    result = bench_run.run_cell(
        cell, 2 ** 31 + 9, 1.0, False, require_tpu=False,
        overrides={"config": TINY_HF_CONFIG, "params": params})
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0


#: What the parent's weights.param_count, 4 * param_count,
#: flops.forward_flops_per_token(cfg, 1024), train_flops_per_token(cfg,
#: 1024), decode_step_flops(cfg, 30000, 96) and decode_step_bytes(cfg,
#: 30000, 4 * param_count) returned.
PARENT_COUNTS = {
    "gpt2_124m": (124_439_808, 497_759_232, 265_956_864.0, 797_870_592.0,
                  24_824_070_144, 1_603_679_232),
    "gpt2_774m": (774_030_080, 3_096_120_320, 1_638_699_520.0,
                  4_916_098_560.0, 153_776_209_920, 8_625_720_320),
}


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_the_gpt2_family_counts_what_the_parent_counted(name):
    cfg = _config(name)
    assert (gpt2.param_count(cfg), gpt2.weight_bytes(cfg),
            gpt2.forward_flops_per_token(cfg, 1024),
            gpt2.train_flops_per_token(cfg, 1024),
            gpt2.decode_step_flops(cfg, 30_000, 96),
            gpt2.decode_step_bytes(cfg, 30_000)) == PARENT_COUNTS[name]


def test_the_gpt2_family_makes_the_parents_weights_bit_for_bit():
    # sha256 over every leaf's path and bytes, in tree order, of the
    # parent's weights.init_params at these sizes and this seed (CPU,
    # jax 0.9.0): the fold-in order of the leaves is part of it.
    import jax
    cfg = {**_config("gpt2_124m"), **TINY_CONFIG}
    leaves = jax.tree_util.tree_flatten_with_path(
        gpt2.init_params(cfg, 1234))[0]
    digest = hashlib.sha256()
    for path, leaf in leaves:
        assert leaf.dtype == np.float32
        digest.update("/".join(k.key for k in path).encode())
        digest.update(np.asarray(leaf).tobytes())
    assert len(leaves) == 28 and digest.hexdigest() == (
        "f4c68374e3f8d11216c4aadfe69e7d82b9709b59bd50a5a2fa3cfc87f78ee3d4")


def test_no_size_of_gpt2_outside_its_family_reference_and_configs():
    # The tests are left out: they override the GPT-2 files' own keys.
    may = {os.path.join(BENCH, p) for p in (
        "families/gpt2.py", "reference/gpt2.py", "configs/gpt2_124m.json",
        "configs/gpt2_774m.json")}
    word = re.compile(r"\b(n_embd|n_layer|n_head|n_inner|n_positions)\b"
                      r"|models\.transformer_lm|\bTransformerLM\b")
    found = []
    for folder, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("tests", "__pycache__")]
        for name in files:
            path = os.path.join(folder, name)
            if path in may or name.endswith(".pyc"):
                continue
            with open(path) as f:
                found += [f"{os.path.relpath(path, ROOT)}:{i}: {line.strip()}"
                          for i, line in enumerate(f, 1) if word.search(line)]
    assert not found, "\n".join(found)


@pytest.mark.parametrize("key", ["family", "reference"])
def test_a_configuration_without_its_module_is_refused(tmp_path, key):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = schema.load_and_check(str(tmp_path))        # whole: passes
    path = tmp_path / "benchmarks/configs/gpt2_774m.json"
    config = json.loads(path.read_text())
    path.write_text(json.dumps({**config, key: "no_such_module"}))
    with pytest.raises(schema.SchemaError, match=f"{key} 'no_such_module'"):
        schema.check(bench, str(tmp_path))
    del config[key]
    path.write_text(json.dumps(config))
    with pytest.raises(schema.SchemaError, match=f"must name its {key}"):
        schema.check(bench, str(tmp_path))
