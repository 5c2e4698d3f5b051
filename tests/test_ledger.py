"""obs/ledger.py + obs/serve.py + tools/obs_query.py (ISSUE 10
tentpole): the cross-run ledger's append/torn-tail/rotation semantics
and row-schema goldens, the live HTTP scrape surface against a real
serving thread (including the fleet's HTTP-scrape-with-file-fallback
monitor path), obs_query list/show/diff CLI smokes, obs_report's
--ledger section, the whole-package stdlib-only import guard, and the
overhead guard keeping ledger sampling + serve idle cost under the
MetricsHook budget (< 1% of the CPU step it is measured beside).

Single-device, no collectives.
"""

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from distributedtensorflowexample_tpu.obs import ledger as obs_ledger
from distributedtensorflowexample_tpu.obs import metrics as obs_metrics
from distributedtensorflowexample_tpu.obs import recorder as obs_recorder
from distributedtensorflowexample_tpu.obs import serve as obs_serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

pytestmark = [pytest.mark.ledger, pytest.mark.obs]


def _fetch(url: str, timeout: float = 5.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture()
def fresh_registry(monkeypatch):
    """An isolated registry so cross-test counter state can't leak into
    snapshots/deltas under assertion."""
    reg = obs_metrics.MetricsRegistry()
    return reg


# --- ledger: append / read / schema ----------------------------------------

def test_ledger_roundtrip_and_row_schema_golden(tmp_path, fresh_registry,
                                                monkeypatch):
    """The three row kinds carry exactly the documented fields — the
    schema golden obs_query and every future reader rely on."""
    monkeypatch.setattr(obs_metrics, "_wall", lambda: 1700000000.0)
    monkeypatch.setattr(obs_metrics, "_now", lambda: 50.0)
    monkeypatch.setenv("OBS_RANK", "1")
    monkeypatch.setenv("SUPERVISE_ATTEMPT", "2")
    path = str(tmp_path / "RUNS.jsonl")
    led = obs_ledger.RunLedger(path, sample_min_s=0,
                               registry=fresh_registry)
    assert led.run_id.endswith("-r1-a2")
    led.start("trainer:softmax", config={"seed": 0, "train_steps": 8},
              platform="cpu", mesh_size=4)
    fresh_registry.counter("train_steps_total").inc(5)
    assert led.sample(step=5)
    led.end(rc=0, final_step=8)
    rows, torn = obs_ledger.read_rows(path)
    assert torn == 0
    start, sample, end = rows
    assert set(start) == {"v", "ts", "event", "run", "entrypoint",
                          "config", "config_digest", "pid", "argv",
                          "rank", "attempt", "phase", "platform",
                          "mesh_size"}
    assert start["event"] == "run_start"
    assert start["rank"] == 1 and start["attempt"] == 2
    assert start["config_digest"] == obs_ledger.config_digest(
        {"seed": 0, "train_steps": 8})
    assert set(sample) == {"v", "ts", "event", "run", "step", "delta"}
    assert sample["delta"]["counters"] == {"train_steps_total": 5}
    assert set(end) == {"v", "ts", "event", "run", "rc", "final_step",
                        "loss_tail", "anomaly_flags", "flight",
                        "counters", "samples"}
    assert end["rc"] == 0 and end["final_step"] == 8
    assert end["counters"]["train_steps_total"] == 5
    assert {r["run"] for r in rows} == {led.run_id}
    # end() is idempotent: a second call (the atexit safety) is a no-op.
    led.end(rc=1)
    assert len(obs_ledger.read_rows(path)[0]) == 3


def test_ledger_heals_torn_tail_and_reader_skips(tmp_path,
                                                 fresh_registry):
    path = str(tmp_path / "RUNS.jsonl")
    led = obs_ledger.RunLedger(path, sample_min_s=0,
                               registry=fresh_registry)
    led.start("a")
    # A row that died mid-write: no trailing newline.
    with open(path, "a") as f:
        f.write('{"event": "run_end", "run": "torn-vic')
    led.sample(step=1, force=True)
    rows, torn = obs_ledger.read_rows(path)
    # The fragment is skipped AND the live sample row survived intact —
    # healing prepended the newline before appending.
    assert torn == 1
    assert [r["event"] for r in rows] == ["run_start", "sample"]


def test_ledger_rotation_and_cross_file_read(tmp_path, fresh_registry,
                                             monkeypatch):
    monkeypatch.setenv("OBS_LEDGER_MAX_BYTES", "2000")
    path = str(tmp_path / "RUNS.jsonl")
    led = obs_ledger.RunLedger(path, sample_min_s=0,
                               registry=fresh_registry)
    led.start("rotates")
    # Sample until the size bound trips ONE rotation, then a few more
    # rows into the fresh live file.
    n = 0
    while not os.path.exists(path + ".1"):
        led.sample(step=n, force=True)
        n += 1
        assert n < 200, "rotation never triggered"
    for _ in range(3):
        led.sample(step=n, force=True)
        n += 1
    led.end(rc=0, final_step=n)
    # The reader spans the rotation edge: run_start (rotated out) and
    # run_end (live file) fold back into ONE run with every sample.
    folded = obs_ledger.runs(path)
    assert folded["order"] == [led.run_id]
    group = folded["runs"][led.run_id]
    assert group["start"] is not None and group["end"] is not None
    assert len(group["samples"]) == n
    # Without the rotated file only the live half remains.
    live_rows, _ = obs_ledger.read_rows(path, include_rotated=False)
    assert 0 < len(live_rows) < n + 2


def test_ledger_sampling_is_time_bounded(tmp_path, fresh_registry):
    path = str(tmp_path / "RUNS.jsonl")
    led = obs_ledger.RunLedger(path, sample_min_s=3600,
                               registry=fresh_registry)
    led.start("bounded")
    assert led.sample(step=1)           # first always lands
    for step in range(2, 50):
        assert not led.sample(step=step)    # inside the bound: skipped
    assert led.sample(step=99, force=True)
    rows, _ = obs_ledger.read_rows(path)
    assert [r.get("step") for r in rows
            if r["event"] == "sample"] == [1, 99]


def test_maybe_begin_env_gate_and_log_event(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_LEDGER", raising=False)
    monkeypatch.setattr(obs_ledger, "_GLOBAL", None)
    assert obs_ledger.maybe_begin("gated") is None
    obs_ledger.log_event("resume_agreement", agreed=4)     # no-op
    path = str(tmp_path / "RUNS.jsonl")
    monkeypatch.setenv("OBS_LEDGER", path)
    led = obs_ledger.maybe_begin("gated", config={"x": 1})
    assert led is not None
    assert obs_ledger.maybe_begin("other") is led          # idempotent
    obs_ledger.log_event("resume_agreement", agreed=4,
                         per_rank={"0": [4], "1": [4]})
    obs_ledger.end_global(rc=0)
    monkeypatch.setattr(obs_ledger, "_GLOBAL", None)
    folded = obs_ledger.runs(path)
    assert [e["event"] for e in folded["events"]] == ["resume_agreement"]
    table = obs_ledger.run_table(path)
    assert len(table) == 1 and table[0]["outcome"] == "ok"


def test_run_table_outcome_classes(tmp_path):
    path = str(tmp_path / "RUNS.jsonl")
    for run, rc in (("r-ok", 0), ("r-preempt", 143), ("r-crash", 7),
                    ("r-unreported", None)):
        obs_ledger.log_event("run_start", path=path, run=run,
                             entrypoint="t")
        obs_ledger.log_event("run_end", path=path, run=run, rc=rc)
    obs_ledger.log_event("run_start", path=path, run="r-live",
                         entrypoint="t")
    table = {r["run"]: r["outcome"] for r in obs_ledger.run_table(path)}
    assert table == {"r-ok": "ok", "r-preempt": "preempted",
                     "r-crash": "rc=7", "r-unreported": "unreported",
                     "r-live": "running/lost"}


def test_tail_rows_reads_a_bounded_chunk(tmp_path):
    """The /ledger/tail handler runs inside the observed process: it
    must read a bounded tail chunk, drop the (almost surely partial)
    first line of a mid-file seek, and still return the last n rows."""
    path = str(tmp_path / "RUNS.jsonl")
    with open(path, "w") as f:
        for i in range(200):
            f.write(json.dumps({"event": "sample", "run": "r",
                                "step": i, "pad": "x" * 64}) + "\n")
    rows, torn = obs_ledger.tail_rows(path, 5, max_bytes=1024)
    assert torn == 0
    assert [r["step"] for r in rows] == [195, 196, 197, 198, 199]
    # Small file, no seek: nothing dropped.
    rows, _ = obs_ledger.tail_rows(path, 500, max_bytes=10**7)
    assert len(rows) == 200
    assert obs_ledger.tail_rows(str(tmp_path / "missing"), 5) == ([], 0)


# --- serve: endpoint smokes against a live thread --------------------------

def test_serve_endpoints_smoke(tmp_path, monkeypatch):
    path = str(tmp_path / "RUNS.jsonl")
    obs_ledger.log_event("run_start", path=path, run="r1",
                         entrypoint="serve-smoke")
    monkeypatch.setenv("OBS_LEDGER", path)
    monkeypatch.setattr(obs_serve, "_health_source",
                        lambda: {"version": 1, "kind": "rank", "step": 7})
    rec = obs_recorder.FlightRecorder()
    rec.record_loss(3, 0.5)
    monkeypatch.setattr(obs_recorder, "_GLOBAL", rec)
    server = obs_serve.ObsServer(0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _fetch(f"{base}/metrics")
        assert code == 200
        text = body.decode()
        assert "# TYPE anomaly_flags_total counter" in text
        code, body = _fetch(f"{base}/health")
        assert code == 200
        assert json.loads(body) == {"version": 1, "kind": "rank",
                                    "step": 7}
        code, body = _fetch(f"{base}/flight")
        assert code == 200
        flight = json.loads(body)
        assert flight["reason"] == "http"
        assert flight["loss_tail"] == [[3, 0.5]]
        code, body = _fetch(f"{base}/ledger/tail?n=5")
        assert code == 200
        tail = json.loads(body)
        assert [r["event"] for r in tail["rows"]] == ["run_start"]
        code, body = _fetch(f"{base}/nope")
        assert code == 404
        assert "/metrics" in json.loads(body)["paths"]
    finally:
        server.stop()


def test_serve_health_falls_back_to_file_then_503(tmp_path, monkeypatch):
    monkeypatch.setattr(obs_serve, "_health_source", None)
    hp = tmp_path / "health.json"
    hp.write_text(json.dumps({"version": 1, "step": 3}))
    monkeypatch.setenv("OBS_HEALTH", str(hp))
    server = obs_serve.ObsServer(0).start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        code, body = _fetch(f"{base}/health")
        assert code == 200 and json.loads(body)["step"] == 3
        monkeypatch.delenv("OBS_HEALTH")
        code, body = _fetch(f"{base}/health")
        assert code == 503 and "no health source" in json.loads(
            body)["error"]
    finally:
        server.stop()


def test_serve_maybe_start_env_gate(monkeypatch, capsys):
    monkeypatch.setattr(obs_serve, "_GLOBAL", None)
    monkeypatch.delenv("OBS_HTTP_PORT", raising=False)
    assert obs_serve.maybe_start() is None
    monkeypatch.setenv("OBS_HTTP_PORT", "notaport")
    assert obs_serve.maybe_start() is None
    assert "not a port" in capsys.readouterr().err
    monkeypatch.setenv("OBS_HTTP_PORT", "0")
    assert obs_serve.maybe_start() is None      # 0/neg = explicit off
    # Out-of-range port: socket.bind raises OverflowError (NOT an
    # OSError) — the refusal must still be a stderr note, never a raise.
    monkeypatch.setenv("OBS_HTTP_PORT", "70000")
    assert obs_serve.maybe_start() is None
    assert "out of range" in capsys.readouterr().err
    monkeypatch.setattr(obs_serve, "_GLOBAL", None)


# --- fleet monitor: HTTP scrape with file fallback -------------------------

@pytest.mark.fleet
def test_fleet_health_scrape_prefers_http_falls_back_to_file(tmp_path,
                                                             monkeypatch):
    """The monitor's transport choice: a rank with a live endpoint is
    scraped over HTTP (journaled mode=http), a rank whose server is
    gone degrades to the per-rank file (journaled mode=file) — the
    detection pass never goes dark because a port died."""
    from distributedtensorflowexample_tpu.obs import anomaly as obs_anomaly
    from distributedtensorflowexample_tpu.resilience.fleet import (
        FleetSupervisor)
    from distributedtensorflowexample_tpu.resilience.supervisor import (
        Journal)
    monkeypatch.setattr(obs_serve, "_health_source",
                        lambda: {"version": 1, "kind": "rank", "rank": 0,
                                 "step": 9, "via": "http"})
    server = obs_serve.ObsServer(0).start()
    try:
        journal_path = str(tmp_path / "fleet.jsonl")
        fleet = FleetSupervisor(
            2, journal=Journal(journal_path),
            workdir=str(tmp_path / "wd"), http=True, seed=0)
        # Rank 0's endpoint is the live server; rank 1's port has no
        # listener (freshly picked free port, nothing bound).
        fleet._http_ports[0] = server.port
        fleet._scrape_logged = set()
        obs_anomaly.write_health(
            fleet._health_path(1),
            {"version": 1, "kind": "rank", "rank": 1, "step": 4,
             "via": "file"})
        p0 = fleet._read_rank_health(0, "drill", 0)
        p1 = fleet._read_rank_health(1, "drill", 0)
        assert p0["via"] == "http" and p0["step"] == 9
        assert p1["via"] == "file" and p1["step"] == 4
        # Second read: journal events stay once-per-(rank, mode).
        fleet._read_rank_health(0, "drill", 0)
        # The failed endpoint earned a backoff (serial urlopens must
        # not stall the monitor loop on a wedged rank every pass);
        # the healthy one did not.
        assert 1 in fleet._http_backoff and 0 not in fleet._http_backoff
        with open(journal_path) as f:
            scrapes = [json.loads(line) for line in f
                       if '"health_scrape"' in line]
        assert [(s["rank"], s["mode"]) for s in scrapes] == [
            (0, "http"), (1, "file")]
        assert scrapes[0]["port"] == server.port
    finally:
        server.stop()


def test_fleet_exports_ledger_and_http_port(tmp_path, monkeypatch):
    """The spawn env surface: children inherit OBS_LEDGER (workdir
    default) and, under http=True, a per-rank OBS_HTTP_PORT — the
    contract the live drill scrapes against."""
    monkeypatch.delenv("OBS_LEDGER", raising=False)
    from distributedtensorflowexample_tpu.resilience.fleet import (
        FleetSupervisor)
    captured = {}
    import subprocess as sp
    real_popen = sp.Popen

    def fake_popen(argv, env=None, **kw):
        captured["env"] = env
        return real_popen([sys.executable, "-c", "pass"], env=env, **kw)

    fleet = FleetSupervisor(1, workdir=str(tmp_path / "wd"), http=True,
                            seed=0)
    import unittest.mock as mock
    with mock.patch.object(sp, "Popen", fake_popen):
        proc = fleet._spawn_rank(0, 0, ["127.0.0.1:1"], ["true"],
                                 "t", 0, None, None, None)
    proc.wait()
    env = captured["env"]
    assert env["OBS_LEDGER"] == os.path.join(str(tmp_path / "wd"),
                                             "RUNS.jsonl")
    assert int(env["OBS_HTTP_PORT"]) == fleet._http_ports[0]


def test_fleet_ledger_dest_follows_env_and_none_disables(tmp_path,
                                                         monkeypatch):
    """One drill, ONE file: a box-wide OBS_LEDGER export routes the
    fleet's gang rows to the same ledger the ranks inherit (not the
    workdir default), and a disabled ledger writes nothing — the env
    fallback inside log_event must not resurrect it."""
    from distributedtensorflowexample_tpu.resilience.fleet import (
        FleetSupervisor)
    box = str(tmp_path / "box_RUNS.jsonl")
    monkeypatch.setenv("OBS_LEDGER", box)
    fleet = FleetSupervisor(1, workdir=str(tmp_path / "wd"), seed=0)
    assert fleet._ledger_dest() == box
    fleet._ledger_event("run_start", run="gang:t:a0", entrypoint="t")
    rows, _ = obs_ledger.read_rows(box)
    assert rows and rows[0]["src"] == "fleet"
    assert not os.path.exists(os.path.join(str(tmp_path / "wd"),
                                           "RUNS.jsonl"))
    # A PRESENT-but-empty export means "disabled" to the children
    # (setdefault skips a present key, maybe_begin treats "" as off) —
    # the fleet must read it the same way, not fall to its default.
    monkeypatch.setenv("OBS_LEDGER", "")
    assert fleet._ledger_dest() == ""
    monkeypatch.delenv("OBS_LEDGER")
    off = FleetSupervisor(1, workdir=str(tmp_path / "wd2"),
                          ledger_path="", seed=0)
    assert off._ledger_dest() == ""
    off._ledger_event("run_start", run="gang:t:a0")
    assert not os.path.exists(os.path.join(str(tmp_path / "wd2"),
                                           "RUNS.jsonl"))


# --- obs_query CLI ---------------------------------------------------------

def _obs_query(*argv):
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_query.py"), *argv],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout


def _two_run_ledger(path: str) -> tuple[str, str]:
    for run, cfg, rc, digest in (
            ("aaa1-1", {"seed": 0, "lr": 0.1}, 0, "d1"),
            ("bbb2-2", {"seed": 1, "lr": 0.1}, 143, "d2")):
        obs_ledger.log_event("run_start", path=path, run=run,
                             entrypoint="trainer:softmax", config=cfg,
                             config_digest=obs_ledger.config_digest(cfg))
        obs_ledger.log_event(
            "run_end", path=path, run=run, rc=rc, final_step=8,
            counters={"train_steps_total": 8 if rc == 0 else 5},
            loss_tail={"n": 3, "last": [8, 0.1], "sha256": digest})
    obs_ledger.log_event("resume_agreement", path=path, agreed=4,
                         per_rank={"0": [2, 4], "1": [4]},
                         discarded={"0": [], "1": [6]})
    return "aaa1-1", "bbb2-2"


def test_obs_query_list_show_diff_smoke(tmp_path):
    path = str(tmp_path / "RUNS.jsonl")
    run_a, run_b = _two_run_ledger(path)
    text = _obs_query("list", "--ledger", path)
    assert "trainer:softmax" in text and "preempted" in text
    assert "agreed step **4**" in text
    payload = json.loads(_obs_query("list", "--ledger", path,
                                    "--format", "json"))
    assert [r["run"] for r in payload["runs"]] == [run_a, run_b]
    assert payload["agreements"][0]["agreed"] == 4
    # outcome filter
    payload = json.loads(_obs_query("list", "--ledger", path,
                                    "--outcome", "ok",
                                    "--format", "json"))
    assert [r["run"] for r in payload["runs"]] == [run_a]
    # show by unique prefix
    text = _obs_query("show", "--ledger", path, "aaa")
    assert "run_start" in text and "run_end" in text
    # diff: config + counter deltas + trajectory verdict
    diff = json.loads(_obs_query("diff", "--ledger", path, "aaa", "bbb",
                                 "--format", "json"))
    assert diff["config_diff"] == {"seed": {"a": 0, "b": 1}}
    assert diff["counter_deltas"]["train_steps_total"]["delta"] == -3
    assert diff["outcome"]["b"]["rc"] == 143
    assert diff["loss_tail"]["same_trajectory"] is False
    md = _obs_query("diff", "--ledger", path, "aaa", "bbb")
    assert "| seed | 0 | 1 |" in md


# --- obs_report --ledger ----------------------------------------------------

def test_obs_report_renders_ledger_section(tmp_path):
    path = str(tmp_path / "RUNS.jsonl")
    _two_run_ledger(path)
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_report.py"),
         "--ledger", path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "## Run ledger" in out.stdout
    assert "trainer:softmax" in out.stdout
    assert "resume agreement" in out.stdout
    # Missing ledger renders a note, never a crash (mid-outage rule).
    out = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "obs_report.py"),
         "--ledger", str(tmp_path / "missing.jsonl")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "does not exist" in out.stdout


# --- whole-package stdlib-only import guard --------------------------------

def test_obs_import_graph_is_stdlib_only_statically():
    """PR 4's "importing obs never pulls jax" contract, upgraded from a
    per-module subprocess walk to graftlint's whole-import-graph proof
    (PR 13, analysis/src_lint.py): every obs/ module — including ones
    later PRs add, which join the graph's roots automatically — is
    statically shown to never reach jax/numpy through any module-level
    import chain.  Stronger than the probe it replaces: a violation
    names the chain, and modules nothing imports yet are still covered.
    Load-bearing for bench.py's handler-before-import ordering;
    ledger.py and serve.py are born under it."""
    from distributedtensorflowexample_tpu.analysis import src_lint
    findings = src_lint.check_stdlib_only(REPO,
                                          "distributedtensorflowexample_tpu")
    assert findings == [], "\n".join(f.message for f in findings)
    # The graph must actually cover the package (8 obs modules as of
    # PR 10): an empty-roots bug would vacuously pass.
    obs_dir = os.path.join(REPO, "distributedtensorflowexample_tpu", "obs")
    mods = [f for f in os.listdir(obs_dir) if f.endswith(".py")]
    assert len(mods) >= 8


def test_obs_package_import_is_stdlib_only_subprocess_smoke():
    """Belt-and-braces runtime smoke behind the static proof above: ONE
    clean interpreter imports every obs module (list computed from the
    directory HERE, so modules later PRs add — re-exported by __init__
    or not — stay covered) and asserts jax/numpy never entered
    sys.modules.  Catches what static analysis can't by construction —
    dynamic imports, import-time side effects."""
    obs_dir = os.path.join(REPO, "distributedtensorflowexample_tpu", "obs")
    names = sorted(f[:-3] for f in os.listdir(obs_dir)
                   if f.endswith(".py") and f != "__init__.py")
    assert len(names) >= 8, names
    imports = "\n".join(
        f"import distributedtensorflowexample_tpu.obs.{n}" for n in names)
    code = (
        "import sys\n"
        "import distributedtensorflowexample_tpu.obs\n"
        f"{imports}\n"
        "banned = sorted(m for m in sys.modules\n"
        "                if m == 'jax' or m.startswith('jax.')\n"
        "                or m == 'numpy' or m.startswith('numpy.'))\n"
        "assert not banned, f'obs import pulled {banned}'\n"
        "print('OK')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
        env={**os.environ, "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "OK"


# --- overhead guard ---------------------------------------------------------

def test_ledger_and_serve_overhead_under_1pct_of_bench_step(tmp_path,
                                                            monkeypatch):
    """Same budget, same methodology as MetricsHook's guard
    (tests/test_obs.py): the full production boundary stack — Metrics +
    Anomaly hooks — WITH a global ledger armed and an idle serve thread
    bound must stay under 1% of the measured CPU bench step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributedtensorflowexample_tpu.data.synthetic import (
        make_synthetic)
    from distributedtensorflowexample_tpu.models import build_model
    from distributedtensorflowexample_tpu.parallel.sync import (
        make_train_step)
    from distributedtensorflowexample_tpu.training.hooks import (
        AnomalyHook, MetricsHook)
    from distributedtensorflowexample_tpu.training.state import TrainState

    step_fn = make_train_step()
    state = TrainState.create(build_model("mnist_cnn"),
                              optax.sgd(0.1, momentum=0.9),
                              jnp.zeros((8, 28, 28, 1), jnp.float32),
                              seed=0)
    x, y = make_synthetic(8, (28, 28, 1), 10, seed=3)
    batch = {"image": jnp.asarray(x), "label": jnp.asarray(y)}
    state, metrics = step_fn(state, batch)      # compile
    jax.block_until_ready(metrics)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics)
        times.append(time.perf_counter() - t0)
    step_s = min(times)

    led = obs_ledger.RunLedger(str(tmp_path / "RUNS.jsonl"))
    led.start("overhead-guard")
    monkeypatch.setattr(obs_ledger, "_GLOBAL", led)
    server = obs_serve.ObsServer(0).start()     # idle: bound, unscraped

    class _FakeLoop:
        start_step = 0

    try:
        hook = MetricsHook(every=100)
        anom = AnomalyHook(every=100)
        hook.begin(_FakeLoop())
        anom.begin(_FakeLoop())
        fetched = {"loss": np.asarray(metrics["loss"])}
        n = 1000
        t0 = time.perf_counter()
        for i in range(1, n + 1):
            hook.after_step(i, state, fetched)
            anom.after_step(i, state, fetched)
        hook_s = (time.perf_counter() - t0) / n
    finally:
        server.stop()
    # The default 30 s sample bound means ~1 ledger append across the
    # 1000 boundaries — the amortized cost the budget must absorb.
    assert led.samples >= 1
    assert hook_s < 0.01 * step_s, (
        f"hooks+ledger+serve {hook_s * 1e6:.2f}us/boundary >= 1% of "
        f"the {step_s * 1e3:.1f}ms CPU bench step")
