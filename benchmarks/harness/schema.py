"""The check made before any measurement: ``BENCHMARK.json`` and the
files its names lead to, held to the rules a later PR must also keep.
Stdlib only — it runs before jax is imported."""

from __future__ import annotations

import importlib
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
#: The modules a configuration's file names, by key, and the folder of
#: each: its family (families/__init__.py says what one provides) and
#: its plain reference, which imports nothing of the program.
MODULES = {"family": "families", "reference": "reference"}


class SchemaError(ValueError):
    pass


def _need(ok: bool, why: str) -> None:
    if not ok:
        raise SchemaError(why)


def _line(text, what: str) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text,
          f"{what} must be 1 to 200 characters on one line")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def check(bench: dict, root: str) -> None:
    _need(set(bench) == TOP_KEYS, f"BENCHMARK.json keys must be exactly "
          f"{sorted(TOP_KEYS)}, got {sorted(bench)}")
    _need(isinstance(bench["run_seconds"], int)
          and 1 <= bench["run_seconds"] <= 51, "run_seconds: 1 to 51")
    paths = bench["paths"]
    _need(1 <= len(paths) <= 16, "1 to 16 paths")
    under = lambda f: any(f == p or f.startswith(p.rstrip("/") + "/")
                          for p in paths)

    configs = {}
    for c in bench["configs"]:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config entry keys: {sorted(c)}")
        _need(NAME.match(c["name"]) is not None, f"bad name {c['name']!r}")
        _need(c["name"] not in configs, f"config {c['name']} twice")
        _line(c["source"], f"source of {c['name']}")
        _line(c["why"], f"why of {c['name']}")
        _need(under(c["file"]), f"{c['file']} is not under paths")
        _need(os.path.isfile(os.path.join(root, c["file"])),
              f"configuration file {c['file']} does not exist")
        _need(len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"]), "reduced: <= 16 names")
        configs[c["name"]] = c
    _need(len({c["file"] for c in configs.values()}) == len(configs),
          "two configurations share a file")

    e2e = {}
    for m in bench["end_to_end"]:
        _need(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"},
              f"end_to_end entry keys: {sorted(m)}")
        _need(NAME.match(m["name"]) is not None, f"bad name {m['name']!r}")
        _need(UNIT.match(m["unit"]) is not None, f"bad unit {m['unit']!r}")
        _need(m["better"] in ("lower", "higher"), "better: lower | higher")
        _need(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: an end-to-end metric is taken by the "
              f"benchmark itself (host_clock or device_trace)")
        _need(0 < m["bound"] <= 0.1, f"{m['name']}: bound in (0, 0.1]")
        _need(m["name"] not in e2e, f"metric {m['name']} twice")
        e2e[m["name"]] = m
    _need("setup_s" in e2e, "end_to_end must hold setup_s")

    cells, pairs = {}, set()
    for w in bench["workloads"]:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload entry keys: {sorted(w)}")
        for k in ("name", "config", "traffic"):
            _need(NAME.match(w[k]) is not None, f"bad {k} {w[k]!r}")
        _need(w["name"] not in cells, f"cell {w['name']} twice")
        _need(w["config"] in configs, f"{w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
        _line(w["why"], f"why of {w['name']}")
        _need((w["config"], w["traffic"]) not in pairs,
              f"pair {w['config']}/{w['traffic']} twice")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    _need(sum(w["chips"] == 4 for w in cells.values())
          <= max(1, len(cells) // 4), "too many four-chip cells")
    for c in configs:
        _need(any(w["config"] == c for w in cells.values()),
              f"configuration {c} is used by no cell")

    def reports(metric, cell):
        return cell in metric.get("workloads", list(cells))

    per = {}
    for m in bench["per_layer"]:
        _need(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"},
              f"per_layer entry keys: {sorted(m)}")
        _need(NAME.match(m["name"]) is not None, f"bad name {m['name']!r}")
        _need(UNIT.match(m["unit"]) is not None, f"bad unit {m['unit']!r}")
        _need(m["better"] in ("lower", "higher"), "better: lower | higher")
        _need(m["source"] in SOURCES, f"{m['name']}: unknown source")
        _line(m["layer"], f"layer of {m['name']}")
        _need(m["moves"] in e2e, f"{m['name']} moves an unknown metric")
        _need(m["name"] not in per and m["name"] not in e2e,
              f"metric {m['name']} twice")
        _need(os.path.isfile(os.path.join(
            root, paths[0], "metrics", m["name"] + ".py")),
            f"per-layer metric {m['name']} has no reader file")
        for cell in m.get("workloads", list(cells)):
            _need(cell in cells, f"{m['name']}: unknown cell {cell}")
            _need(reports(e2e[m["moves"]], cell),
                  f"{m['name']} moves {m['moves']}, which cell {cell} "
                  f"does not report")
        per[m["name"]] = m
    for name, w in cells.items():
        _need(sum(reports(m, name) for m in e2e.values()) >= 2,
              f"cell {name} reports no end-to-end metric besides setup_s")
        _need(any(reports(m, name) for m in per.values()),
              f"cell {name} reports no per-layer metric")

    # Each cell's own file: the configuration it names exists, and the
    # entry in BENCHMARK.json says the same as the file.
    for name, w in cells.items():
        path = os.path.join(root, paths[0], "workloads", name + ".json")
        _need(os.path.isfile(path), f"cell file {path} does not exist")
        cell = _load(path)
        for k in ("config", "traffic", "chips", "why"):
            _need(cell.get(k) == w[k], f"{path}: {k} differs from "
                  f"BENCHMARK.json")
        _need(os.path.isfile(os.path.join(
            root, paths[0], "traffic", w["traffic"] + ".json")),
            f"traffic file of {name} does not exist")

    # Each configuration's file names its source, and the family and the
    # plain reference it is run with: one module each, found by name.
    for c in configs.values():
        src = _load(os.path.join(root, c["file"]))
        _line(src.get("source"), f"source in {c['file']}")
        for key, folder in MODULES.items():
            name = src.get(key)
            _need(isinstance(name, str) and NAME.match(name) is not None,
                  f"{c['file']} must name its {key}")
            _need(os.path.isfile(os.path.join(
                root, paths[0], folder, name + ".py")),
                f"{c['file']}: {key} {name!r} has no module "
                f"{paths[0]}/{folder}/{name}.py")


def load_module(config: dict, key: str):
    """The module ``config`` names under ``key`` ("family" or
    "reference"), found by name: nothing else knows which there are."""
    return importlib.import_module(
        f"benchmarks.{MODULES[key]}.{config[key]}")


def load_and_check(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    _need(os.path.getsize(path) <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    bench = _load(path)
    check(bench, root)
    return bench


def cell_files(root: str, bench: dict, workload: str) -> tuple:
    """(cell, config, traffic) of ``workload``, each from its own file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SchemaError(f"unknown workload {workload!r}; BENCHMARK.json "
                          f"has {sorted(cells)}")
    w = cells[workload]
    base = os.path.join(root, bench["paths"][0])
    cell = _load(os.path.join(base, "workloads", workload + ".json"))
    cfile = next(c["file"] for c in bench["configs"]
                 if c["name"] == w["config"])
    return (cell, _load(os.path.join(root, cfile)),
            _load(os.path.join(base, "traffic", w["traffic"] + ".json")))
