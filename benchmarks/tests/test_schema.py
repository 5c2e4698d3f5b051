"""The schema check the harness makes before any measurement."""

import copy
import json
import os

import pytest

from benchmarks.harness import schema
from benchmarks.tests.conftest import ROOT


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_committed_benchmark_passes(bench):
    schema.check(bench, ROOT)


@pytest.mark.parametrize("breaker, says", [
    (lambda b: b["workloads"][0].update(chips=2), "chips is 1 or 4"),
    (lambda b: b["workloads"][0].update(name="has space"), "bad name"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"),
     "bad unit"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["per_layer"][2].update(moves="serve_tokens_per_s"),
     "does not report"),
    (lambda b: b["per_layer"][0].update(why="no such key"), "keys"),
    (lambda b: b["workloads"][0].update(why="x" * 201), "200 characters"),
    (lambda b: b["configs"][0].update(file="tests/x.json"), "under paths"),
    (lambda b: b["workloads"].append(dict(
        b["workloads"][0], name="again")), "twice"),
    (lambda b: b.update(extra=1), "keys must be exactly"),
])
def test_a_broken_benchmark_is_refused(bench, breaker, says):
    broken = copy.deepcopy(bench)
    breaker(broken)
    with pytest.raises(schema.SchemaError, match=says):
        schema.check(broken, ROOT)
