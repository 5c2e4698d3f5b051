"""bench.py machinery smoke tests (CPU, tiny sizes).

The driver runs bench.py exactly once per round on the real chip; a crash
there silently costs the round's numbers (round 2 lost its headline to a
mid-run backend outage).  These tests execute every bench helper — build,
measure, roofline probe, flops probe, collective parsing — on the virtual
mesh so breakage surfaces in CI, not at measurement time.
"""

import json

import pytest

import bench
import bench_scaling
from distributedtensorflowexample_tpu.parallel import make_mesh


@pytest.fixture()
def tiny_mnist(small_synthetic, tmp_path):
    """Shared synthetic shrink (conftest.small_synthetic) + an empty data
    dir so a real MNIST download in /tmp/data can never bypass it."""
    return str(tmp_path)


def test_make_and_measure_sync(tiny_mnist):
    mesh = make_mesh()
    step, ds, state, u = bench._make("softmax", "mnist", 8, 4, mesh,
                                     momentum=0.0, lr=0.5,
                                     data_dir=tiny_mnist)
    assert u == 4
    with mesh:
        best, rates, state = bench._measure(step, ds, state, 8, u,
                                            warmup_calls=1)
    assert best > 0 and len(rates) == bench.REPEATS
    # 1 warmup call + REPEATS x (8 // 4) calls, 4 steps each.
    assert int(state.step) == (1 + bench.REPEATS * 2) * 4


def test_make_async_variant(tiny_mnist):
    mesh = make_mesh()
    step, ds, state, u = bench._make("softmax", "mnist", 8, 4, mesh,
                                     sync=False, data_dir=tiny_mnist)
    with mesh:
        best, rates, _ = bench._measure(step, ds, state, 4, u,
                                        warmup_calls=1)
    assert best > 0


def test_make_pallas_and_fused_variants(tiny_mnist):
    mesh = make_mesh()
    for kw in ({"ce_impl": "pallas"}, {"fused_opt": True}):
        step, ds, state, u = bench._make("softmax", "mnist", 8, 4, mesh,
                                         data_dir=tiny_mnist, **kw)
        with mesh:
            best, _, _ = bench._measure(step, ds, state, 4, u,
                                        warmup_calls=1)
        assert best > 0


def test_flops_probe_uses_peek(tiny_mnist):
    mesh = make_mesh()
    step, ds, state, u = bench._make("softmax", "mnist", 8, 4, mesh,
                                     data_dir=tiny_mnist)
    with mesh:
        before = ds._step
        flops = bench._flops_per_step(step, state, ds.peek(), u)
        assert ds._step == before          # probe must not consume
    # cost_analysis works on the CPU backend: a None here means the probe
    # itself broke (the thing this test exists to catch pre-chip).
    assert flops is not None and flops > 0


def test_roofline_probe(tiny_mnist):
    mesh = make_mesh()
    cost = {}
    with mesh:
        rates = bench._roofline_probe(mesh, 4, length=4, cost_out=cost)
    assert len(rates) == bench.REPEATS and all(r > 0 for r in rates)
    # The probe's own per-step cost — the denominator of the measured-
    # vs-roofline byte decomposition (VERDICT r3 #5 softmax attribution).
    assert cost.get("flops", 0) > 0
    assert cost.get("bytes_accessed", 0) > 0


def test_sweep_fault_isolation(tiny_mnist):
    """_sweep records a failing point into errors and keeps going; the
    all-fail case returns best_unroll=None (config4 then emits nothing)."""
    mesh = make_mesh()

    def mk(unroll):
        if unroll == 2:
            raise RuntimeError("boom")
        return bench._make("softmax", "mnist", 8, unroll, mesh,
                           momentum=0.0, lr=0.5, data_dir=tiny_mnist)

    errors = {}
    with mesh:
        best, best_u, rates, sweep = bench._sweep(
            {2, 4}, mk, lambda u: u, "p_", errors)
    assert best > 0 and best_u == 4 and list(sweep) == ["4"]
    assert "p_2" in errors and "boom" in errors["p_2"]

    errors = {}
    best, best_u, rates, sweep = bench._sweep(
        {2}, mk, lambda u: u, "p_", errors)
    assert best == 0.0 and best_u is None and sweep == {} and "p_2" in errors


def test_affine_dequant_not_slower_than_lut_gather():
    """The round-5 regression guard, as a CPU microbench: the fused
    affine dequant of a fixed headline-sized batch must not be slower
    than the elementwise LUT gather it replaced (the round-4 default the
    on-chip window measured at 4.1x the step time — AB_quantize_r05).  A
    refactor that silently re-routes the default back through the gather
    shows up here as a timing inversion, before it costs a TPU window.
    CPU magnitudes differ from TPU but the ordering holds at this batch
    shape on the per-channel spec (measured ~5x; 1.5x slack for CI
    noise)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflowexample_tpu.data.dequant import (
        make_dequant_affine, make_dequant_lut)
    from distributedtensorflowexample_tpu.data.device_dataset import (
        apply_dequant_affine, apply_dequant_gather)

    u = jnp.asarray(np.random.RandomState(0).randint(
        0, 256, (bench.BATCH["resnet"], 32, 32, 3), dtype=np.uint8))
    s, b = (jnp.asarray(v) for v in make_dequant_affine("cifar"))
    lut = jnp.asarray(make_dequant_lut("cifar"))
    f_affine = jax.jit(lambda u: apply_dequant_affine(u, s, b))
    f_gather = jax.jit(lambda u: apply_dequant_gather(u, lut))

    def best_of(f, reps=7):
        f(u).block_until_ready()           # compile + warm
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f(u).block_until_ready()
            times.append(time.perf_counter() - t0)
        return min(times)

    t_affine, t_gather = best_of(f_affine), best_of(f_gather)
    # 3x slack: the regression this guards against is a ≥4x tax (the
    # gather re-appearing in the fast path), and min-of-7 on the
    # contended shared CI host still jitters — the deterministic
    # no-256-gather jaxpr check in test_dequant.py catches structure;
    # this one only has to catch a wholesale speed inversion.
    assert t_affine <= t_gather * 3.0, (
        f"affine dequant ({t_affine * 1e6:.0f}us) slower than the LUT "
        f"gather ({t_gather * 1e6:.0f}us): the round-5 dequant tax is "
        f"back — check the auto lowering in data.device_dataset")


def test_dequant_ab_auto_selects_winning_impl(monkeypatch, capsys):
    """--dequant auto promotes tools/ab_quantize.py's sweep into the
    official record: the alternatives are measured at the winning unroll,
    the fastest supersedes the resolved default (detail.dequant names
    it), every alternative's repeats land in detail.dequant_ab, and the
    promoted line re-probes its roofline in its own window."""
    probes = []

    class FakeDs:
        def __init__(self, impl):
            self.dequant_impl = impl

    def fake_make(model, dataset, b, unroll, mesh, **kw):
        impl = kw.get("dequant_impl", "auto")
        if impl in bench.DEQUANT_AB_IMPLS:
            return ("step", FakeDs(impl), "state", unroll)
        raise RuntimeError("side workload down")   # sides fail fast

    def fake_measure(step, ds, state, steps, u, warmup_calls=2):
        rate = {"onehot": 60.0, "lut": 5.0, "pallas": 55.0}[ds.dequant_impl]
        return rate, [rate], state

    def fake_roofline(*a, **k):
        probes.append(1)
        return [80.0] if len(probes) == 1 else [120.0]

    def fake_sweep(unrolls, make_fn, steps_for, err_prefix, errors):
        if err_prefix != "sweep_":
            return (0.0, None, [], {})      # resnet's sweep: fail
        return (50.0, 16, [50.0], {"16": [50.0]})

    monkeypatch.setattr(bench, "_sweep", fake_sweep)
    monkeypatch.setattr(bench, "_make", fake_make)
    monkeypatch.setattr(bench, "_measure", fake_measure)
    monkeypatch.setattr(bench, "_roofline_probe", fake_roofline)

    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    line = lines[-1]
    assert line["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    # onehot (60) beat the held default (50) and pallas (55): promoted.
    assert line["value"] == round(60.0 / make_mesh().size, 2)
    assert line["detail"]["dequant"] == "onehot"
    assert line["detail"]["dequant_ab"] == {
        "onehot": [60.0], "lut": [5.0], "pallas": [55.0]}
    # Fresh same-window probe for the promoted line: 60/120 = 0.5.
    assert line["detail"]["vs_roofline"] == 0.5
    assert len(probes) == 2


def test_dequant_forced_impl_skips_ab(monkeypatch, capsys):
    """A named --dequant impl forces the kernel and runs NO A/B (each
    alternative is a compile the operator asked to skip)."""
    def fake_make(*a, **k):
        raise RuntimeError("side workload down")

    def fake_sweep(unrolls, make_fn, steps_for, err_prefix, errors):
        if err_prefix != "sweep_":
            return (0.0, None, [], {})
        return (50.0, 16, [50.0], {"16": [50.0]})

    monkeypatch.setattr(bench, "DEQUANT", "affine")
    monkeypatch.setattr(bench, "_sweep", fake_sweep)
    monkeypatch.setattr(bench, "_make", fake_make)
    monkeypatch.setattr(bench, "_roofline_probe", lambda *a, **k: [100.0])

    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    line = lines[-1]
    assert line["unit"] == "steps/sec/chip"
    assert "dequant_ab" not in line["detail"]
    assert not any(k.startswith("dequant_ab") for k in
                   line["detail"].get("errors", {}))


def test_emit_shape(capsys):
    bench._emit("some_metric", 123.456, {"some_metric": 100.0},
                {"repeats": [1.0]})
    line = json.loads(capsys.readouterr().out.strip())
    assert line["metric"] == "some_metric"
    assert line["value"] == 123.46
    assert line["unit"] == "steps/sec/chip"
    assert line["vs_baseline"] == pytest.approx(1.2346, abs=1e-4)
    assert line["detail"]["repeats"] == [1.0]


def test_scaling_async_mode(monkeypatch, capsys):
    """bench_scaling --mode async end-to-end on tiny sizes: emits per-count
    lines with period-amortized collective bytes and the summary line."""
    monkeypatch.setattr("sys.argv", [
        "bench_scaling.py", "--mode", "async", "--async_period", "2",
        "--max_devices", "2", "--batch_per_chip", "4", "--unroll", "2",
        "--steps", "4"])
    bench_scaling.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    summary = lines[-1]
    assert summary["metric"] == "async_sgd_weak_scaling"
    assert summary["detail"]["mode"] == "async"
    per_count = [l for l in lines[:-1] if l.get("mode") == "async"]
    assert [l["devices"] for l in per_count] == [1, 2]
    two = per_count[-1]
    # The 2-device worker average is an all-reduce in the program; its
    # sustained cost is parsed bytes / period.
    assert "all-reduce" in two["collectives_per_step"]
    assert two["amortized_bytes_per_step"]["all-reduce"] == round(
        two["collectives_per_step"]["all-reduce"]["bytes"] / 2)


def test_bench_input_stages(capsys):
    """bench_input's three stages run end-to-end on tiny sizes (each
    asserts native/numpy bit-identity itself before timing)."""
    import bench_input
    from distributedtensorflowexample_tpu import native

    if not native.available():
        pytest.skip("native loader unavailable on this host")
    bench_input.bench_cifar_parse(n_records=50)
    bench_input.bench_idx_parse(n=200)
    bench_input.bench_gather_augment(n_src=300, batch=16)
    bench_input.bench_gather_augment_u8(n_src=300, batch=16)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["metric"] for l in lines] == [
        "cifar_parse_native_mb_per_sec", "idx_parse_native_mb_per_sec",
        "gather_augment_native_images_per_sec",
        "gather_augment_native_u8_images_per_sec"]
    assert all(l["value"] > 0 and l["vs_baseline"] > 0 for l in lines)


def test_bench_profile_end_to_end(tiny_mnist, tmp_path, monkeypatch,
                                  capsys):
    """bench_profile.py (the on-chip ResNet attribution harness) runs its
    full pipeline — both augment variants, flops probe, profiler trace,
    roofline, attribution summary — on the virtual mesh, so breakage
    surfaces in CI rather than mid-availability-window on the chip."""
    import bench_profile
    from distributedtensorflowexample_tpu.data import cifar10

    monkeypatch.setattr(cifar10, "_SYNTH_SIZES",
                        {"train": 256, "test": 128})
    monkeypatch.setattr("sys.argv", [
        "bench_profile.py", "--unroll", "2", "--steps", "4",
        "--batch_per_chip", "4", "--roofline_length", "4",
        "--trace_dir", str(tmp_path / "trace")])
    bench_profile.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    by_metric = {l["metric"]: l for l in lines}
    assert by_metric["resnet20_profile_augment"]["value"] > 0
    assert by_metric["resnet20_profile_no_augment"]["value"] > 0
    assert by_metric["resnet20_roofline"]["value"] > 0
    aug_detail = by_metric["resnet20_profile_augment"]["detail"]
    assert aug_detail["flops_per_step"]
    # PR-2 bytes attribution rides every variant line: per-op table +
    # the effective (phantom-corrected) bandwidth roofline.
    audit = aug_detail["bytes_audit"]
    assert audit["bytes_effective_per_step"] > 0
    assert audit["phantom_gather_bytes_per_step"] > 0
    assert audit["by_category_per_step"].get("conv", 0) > 0
    assert audit["top_ops"]
    # Effective vs raw compares within the PARSED convention only (the
    # raw bw_roofline key uses XLA's aggregate, which this tiny program
    # undershoots — agreement is size-dependent, see test_bytes.py).
    assert audit["bytes_effective_per_step"] <= audit["bytes_per_step"]
    assert aug_detail["bw_roofline_effective_steps_per_sec"] > 0
    traced = by_metric["resnet20_traced_window"]
    assert traced["value"] > 0 and traced["detail"]["trace_bytes"] > 0
    att = by_metric["resnet20_attribution"]["detail"]
    assert "augment_share" in att and "input_dispatch_share" in att


def test_main_emits_headline_when_backend_unreachable(monkeypatch, capsys):
    """A mid-outage driver run must still print one valid headline line —
    with the sentinel unit "unavailable" so it can never be read as a
    measured 100% regression — pointing at the recorded manual run."""
    from distributedtensorflowexample_tpu import parallel

    def boom(*a, **k):
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    monkeypatch.setattr(parallel, "make_mesh", boom)
    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    # Line 0 is the always-first provisional sentinel (VERDICT r3 #1a);
    # the real record is the LAST line — the order the driver parses.
    assert len(lines) == 2
    assert lines[0]["detail"]["provisional"] is True
    assert lines[0]["unit"] == "unavailable"
    last = lines[-1]
    assert last["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert last["value"] == 0.0
    assert last["unit"] == "unavailable"
    assert "provisional" not in last["detail"]
    assert "UNAVAILABLE" in last["detail"]["error"]
    assert "BENCH_manual_r02" in last["detail"]["see"]
    assert last["detail"]["probe_attempts"]  # skip notice (cpu pin)


def test_main_emits_sentinel_when_backend_dies_mid_run(monkeypatch, capsys):
    """Round-3 failure shape: the up-front probe succeeds, then the backend
    dies DURING the run so every sweep point fails.  The headline must be
    the explicit unavailable sentinel (not a measured-looking 0.0), with
    the per-point errors attached for diagnosis."""
    def boom(*a, **k):
        raise RuntimeError("UNAVAILABLE: remote_compile connection refused")

    monkeypatch.setattr(bench, "_make", boom)
    monkeypatch.setattr(bench, "_roofline_probe", boom)
    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    # provisional sentinel + ONE final sentinel, no workload lines
    assert len(lines) == 2
    line = lines[-1]
    assert line["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert line["unit"] == "unavailable" and line["value"] == 0.0
    assert "every headline sweep point failed" in line["detail"]["error"]
    # The HEADLINE sweep's own per-point errors must survive (sweep_16 is
    # a headline key; resnet's are prefixed resnet_sweep_) alongside the
    # earlier workloads' errors.
    assert "sweep_16" in line["detail"]["errors"]
    assert any(k.startswith("resnet_sweep_") for k in line["detail"]["errors"])


def test_watchdog_fires_on_wedged_measurement():
    """Round-3 failure the probe can't catch: the backend dies minutes
    AFTER a successful probe and the next call blocks >60 min without
    raising.  The watchdog thread must emit the sentinel headline and
    hard-exit 3 (observable only from a real subprocess — os._exit)."""
    import os
    import subprocess
    import sys

    code = (
        "import sys, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import bench\n"
        "bench.TOTAL_BUDGET_S = 1.0\n"
        "bench._make = lambda *a, **k: time.sleep(600)\n"
        "bench._roofline_probe = lambda *a, **k: time.sleep(600)\n"
        "bench.main()\n"
    )
    # FORCE_WATCHDOG: the CPU pin would otherwise (correctly) skip arming.
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_FORCE_WATCHDOG="1")
    p = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 3, (p.returncode, p.stdout, p.stderr[-500:])
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert last["unit"] == "unavailable" and last["value"] == 0.0
    assert "watchdog" in last["detail"]["error"]


def test_headline_promoted_when_first_sweep_point_fails(monkeypatch, capsys):
    """The deepest-unroll point runs first (short-window priority); if it
    fails but a later point succeeds, the later point must be promoted to
    the headline with its own same-window roofline attached."""
    calls = []

    def fake_sweep(unrolls, make_fn, steps_for, err_prefix, errors):
        calls.append(err_prefix)
        if err_prefix != "sweep_":
            return (0.0, None, [], {})            # resnet's sweep: fail
        if len([c for c in calls if c == "sweep_"]) == 1:
            return (0.0, None, [], {})            # deepest point failed
        return (50.0, 4, [50.0], {"4": [50.0]})   # a later point landed

    monkeypatch.setattr(bench, "_sweep", fake_sweep)
    monkeypatch.setattr(bench, "_roofline_probe", lambda *a, **k: [100.0])

    def boom(*a, **k):
        raise RuntimeError("side workload down")
    monkeypatch.setattr(bench, "_make", boom)

    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 2       # provisional + headline (sides failed fast)
    line = lines[-1]
    assert line["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert line["unit"] == "steps/sec/chip"
    assert line["value"] == round(50.0 / make_mesh().size, 2)
    assert line["detail"]["best_unroll"] == 4
    assert line["detail"]["vs_roofline"] == 0.5
    assert line["detail"]["errors"]      # side-workload failures attached
    assert calls.count("sweep_") == 2    # both headline sweep halves ran


def test_headline_promotion_reprobes_roofline(monkeypatch, capsys):
    """First point succeeds, a later point beats it: the promoted line
    must RE-probe the roofline in its own window (a stale probe from the
    first point's window can make vs_roofline a cross-window artifact,
    even > 1.0)."""
    sweeps, probes = [], []

    def fake_sweep(unrolls, make_fn, steps_for, err_prefix, errors):
        sweeps.append(err_prefix)
        if err_prefix != "sweep_":
            return (0.0, None, [], {})
        if len([c for c in sweeps if c == "sweep_"]) == 1:
            return (40.0, 16, [40.0], {"16": [40.0]})   # first point
        return (50.0, 4, [50.0], {"4": [50.0]})         # later, faster

    def fake_roofline(*a, **k):
        probes.append(1)
        return [80.0] if len(probes) == 1 else [100.0]

    monkeypatch.setattr(bench, "_sweep", fake_sweep)
    monkeypatch.setattr(bench, "_roofline_probe", fake_roofline)

    def boom(*a, **k):
        raise RuntimeError("side workload down")
    monkeypatch.setattr(bench, "_make", boom)

    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 2
    line = lines[-1]
    assert line["value"] == round(50.0 / make_mesh().size, 2)
    assert line["detail"]["best_unroll"] == 4
    # Fresh probe (100.0), not the first window's 80.0: 50/100 = 0.5.
    assert line["detail"]["roofline_probe"] == [100.0]
    assert line["detail"]["vs_roofline"] == 0.5
    assert len(probes) == 2


def test_watchdog_emits_held_headline_when_side_workload_wedges():
    """The headline is measured first and held; if a LATER side workload
    wedges, the watchdog must emit the real measured headline (tagged
    with detail.watchdog), never discard it for the 0.0 sentinel."""
    import os
    import subprocess
    import sys

    code = (
        "import sys, time\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import bench\n"
        "bench.TOTAL_BUDGET_S = 8.0\n"   # > make_mesh+fakes, << sleep(600)
        "bench._sweep = lambda *a, **k: (100.0, 16, [100.0],"
        " {'16': [100.0]})\n"
        "bench._roofline_probe = lambda *a, **k: [200.0]\n"
        "bench._make = lambda *a, **k: time.sleep(600)\n"
        "bench.main()\n"
    )
    env = _bench_subprocess_env(BENCH_FORCE_WATCHDOG="1")
    p = subprocess.run([sys.executable, "-c", code],
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=120, env=env)
    assert p.returncode == 3, (p.returncode, p.stdout, p.stderr[-500:])
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert last["unit"] == "steps/sec/chip" and last["value"] == 100.0
    assert "watchdog" in last["detail"]
    assert last["detail"]["vs_roofline"] == 0.5


def test_watchdog_disarmed_on_completion():
    """A normal completion sets the event before the budget expires; the
    armed thread must not fire afterwards (no spurious sentinel).  The
    exit is injected so a regression can't take down the test process."""
    import time
    fired, exits = [], []
    done = bench._arm_watchdog(0.2, lambda: fired.append(1),
                               _exit=lambda code: exits.append(code))
    done.set()
    time.sleep(0.4)
    assert not fired and not exits

    fired2, exits2 = [], []
    bench._arm_watchdog(0.05, lambda: fired2.append(1),
                        _exit=lambda code: exits2.append(code))
    time.sleep(0.3)
    assert fired2 == [1] and exits2 == [3]


def _bench_subprocess_env(**extra):
    """Env for a real bench.main() subprocess: CPU-pinned, with any
    device-count pin inherited from THIS pytest process stripped: these
    tests model the driver's clean shell, where bench sees ONE cpu
    device (the per-chip division then leaves the mocked rates
    unscaled)."""
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def _spawn_bench(extra_code: str):
    """Run the REAL bench.main() in a subprocess (CPU-pinned via
    jax.config, like the other subprocess tests) with ``extra_code``
    applied between import and main().  Pipes kept open for
    deterministic kill timing."""
    import os
    import subprocess
    import sys

    code = ("import sys, time\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import bench\n" + extra_code + "bench.main()\n")
    env = _bench_subprocess_env()
    return subprocess.Popen(
        [sys.executable, "-c", code],
        cwd=os.path.dirname(os.path.dirname(__file__)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)


def test_sigterm_mid_probe_retry_still_leaves_parseable_record():
    """THE round-3 official-record killer (VERDICT r3 #1): the driver's
    outer `timeout` TERM/KILLed bench while it slept in the probe-retry
    loop with nothing yet on stdout (BENCH_r03.json: rc=124, parsed
    null).  Same kill mechanism (SIGTERM to the process), deterministic
    timing: TERM lands after the provisional line, which mirrors the
    driver (its ~23-min budget dwarfs startup).  Captured stdout must
    parse — provisional line first, SIGTERM sentinel last, rc=143."""
    import signal as sig
    import time

    p = _spawn_bench(
        "bench._cpu_pinned = lambda: False\n"   # enter the real retry loop
        "bench._probe_backend = "
        "lambda timeout_s=None: (False, 'down (test)')\n"
        "bench.PROBE_TIMEOUT_S = 0.0\n"
        "bench.RETRY_INTERVAL_S = 600.0\n"      # guarantee death mid-sleep
        "bench.RETRY_BUDGET_S = 3600.0\n")
    first = p.stdout.readline()          # blocks until the provisional line
    assert json.loads(first)["detail"]["provisional"] is True
    time.sleep(1.0)                      # probe fails instantly -> sleeping
    p.send_signal(sig.SIGTERM)
    out, err = p.communicate(timeout=60)
    assert p.returncode == 143, (p.returncode, out, err[-500:])
    # The handler prints a blank guard line first (torn-line terminator).
    lines = [json.loads(l) for l in ([first] + out.splitlines())
             if l.strip()]
    last = lines[-1]
    assert last["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert last["unit"] == "unavailable" and last["value"] == 0.0
    assert "sigterm" in last["detail"]["error"]
    # The failed probe attempt made it into the record.
    assert any("down (test)" in a for a in last["detail"]["probe_attempts"])


def test_sigkill_leaves_provisional_record():
    """Survival layer 1 alone: a straight SIGKILL (no handler can run)
    must still leave a parseable stdout, because the provisional
    sentinel is flushed before any backend touch."""
    p = _spawn_bench(
        "bench._cpu_pinned = lambda: False\n"
        "bench._probe_backend = "
        "lambda timeout_s=None: (time.sleep(600), (False, 'x'))[1]\n")
    first = p.stdout.readline()
    p.kill()
    out, _ = p.communicate(timeout=60)
    assert p.returncode == -9
    line = json.loads(first)
    assert line["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert line["unit"] == "unavailable" and line["value"] == 0.0
    assert line["detail"]["provisional"] is True


def test_sigterm_emits_held_measured_headline():
    """A kill AFTER the headline measured but before the normal emit
    must put the MEASURED line on stdout (tagged detail.sigterm), never
    discard it for the sentinel — the driver's timeout can land during
    any side workload."""
    import signal as sig

    p = _spawn_bench(
        "bench._sweep = lambda *a, **k: "
        "(100.0, 16, [100.0], {'16': [100.0]})\n"
        "bench._roofline_probe = lambda *a, **k: [200.0]\n"
        "def _wedge(*a, **k):\n"
        "    print('WEDGED', file=sys.stderr, flush=True)\n"
        "    time.sleep(600)\n"
        "bench._make = _wedge\n")
    first = p.stdout.readline()          # provisional
    assert json.loads(first)["detail"]["provisional"] is True
    line = ""
    for _ in range(500):                 # skip jax warnings on stderr
        line = p.stderr.readline()
        if not line or "WEDGED" in line:
            break
    assert "WEDGED" in line              # headline held, side wedged
    p.send_signal(sig.SIGTERM)
    out, err = p.communicate(timeout=60)
    assert p.returncode == 143, (p.returncode, out, err[-500:])
    last = json.loads(out.splitlines()[-1])
    assert last["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert last["unit"] == "steps/sec/chip" and last["value"] == 100.0
    assert "sigterm" in last["detail"]
    assert last["detail"]["vs_roofline"] == 0.5


def test_probe_skipped_when_cpu_pinned():
    """The CPU-pinned test process must never spawn a backend-probe
    subprocess (conftest pins via jax.config as well as JAX_PLATFORMS)."""
    assert bench._cpu_pinned()
    ok, attempts = bench._wait_for_backend()
    assert ok and "skipped" in attempts[0]


def test_probe_backend_subprocess(monkeypatch):
    """_probe_backend runs real code in a real subprocess with a hard
    timeout; exercise success, failure, and timeout via the probe code."""
    monkeypatch.setattr(bench, "_PROBE_CODE", "print('PROBE_OK 1')")
    ok, info = bench._probe_backend(timeout_s=30)
    assert ok and "PROBE_OK" in info

    monkeypatch.setattr(bench, "_PROBE_CODE",
                        "raise RuntimeError('UNAVAILABLE: down')")
    ok, info = bench._probe_backend(timeout_s=30)
    assert not ok and "UNAVAILABLE" in info

    monkeypatch.setattr(bench, "_PROBE_CODE", "import time; time.sleep(60)")
    ok, info = bench._probe_backend(timeout_s=1)
    assert not ok and "timed out" in info


def test_wait_for_backend_retries_within_budget(monkeypatch):
    """Failure path: retries on the interval, gives up inside the budget,
    and returns the attempt log; success path: returns on first OK."""
    monkeypatch.setattr(bench, "_cpu_pinned", lambda: False)
    monkeypatch.setattr(bench, "RETRY_BUDGET_S", 10.0)
    monkeypatch.setattr(bench, "RETRY_INTERVAL_S", 0.01)
    monkeypatch.setattr(bench, "PROBE_TIMEOUT_S", 0.01)
    calls = []

    def probe(timeout_s=None):
        calls.append(1)
        return (len(calls) >= 3), f"attempt {len(calls)}"
    monkeypatch.setattr(bench, "_probe_backend", probe)
    ok, attempts = bench._wait_for_backend()
    assert ok and len(calls) == 3 and len(attempts) == 3

    calls.clear()
    monkeypatch.setattr(bench, "_probe_backend",
                        lambda timeout_s=None: (False, "down"))
    monkeypatch.setattr(bench, "RETRY_BUDGET_S", 0.05)
    ok, attempts = bench._wait_for_backend()
    assert not ok and attempts


def test_collective_traffic_parsing():
    hlo = """
  %x = f32[256,10]{1,0} all-reduce(f32[256,10]{1,0} %a), replica_groups={}
  %y = (f32[64]{0}, bf16[128]{0}) all-reduce(%b, %c), channel_id=1
  %z = f32[8,4]{1,0} all-gather(f32[8,2]{1,0} %d), dimensions={1}
  %notacollective = f32[2]{0} add(f32[2]{0} %e, f32[2]{0} %f)
"""
    out = bench_scaling.collective_traffic(hlo)
    assert out["all-reduce"]["count"] == 2
    assert out["all-reduce"]["bytes"] == 256 * 10 * 4 + 64 * 4 + 128 * 2
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 8 * 4 * 4
    assert "collective-permute" not in out


def test_headline_only_mode(monkeypatch, capsys):
    """BENCH_HEADLINE_ONLY=1 (capture phase 1): the contract metric +
    same-window roofline only — one sweep half, no side workloads — so
    a short recovery window spends its first minutes on the headline
    and the never-yet-captured ResNet profile, not the full run."""
    calls = []

    def fake_sweep(unrolls, make_fn, steps_for, err_prefix, errors):
        calls.append(err_prefix)
        return (50.0, 16, [50.0], {"16": [50.0]})

    def boom(*a, **k):
        raise AssertionError("side workload must not run in headline-only")

    monkeypatch.setattr(bench, "HEADLINE_ONLY", True)
    monkeypatch.setattr(bench, "_sweep", fake_sweep)
    monkeypatch.setattr(bench, "_roofline_probe", lambda *a, **k: [100.0])
    monkeypatch.setattr(bench, "_make", boom)
    bench.main()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 2          # provisional + headline, nothing else
    line = lines[-1]
    assert line["metric"] == "mnist_cnn_sync_steps_per_sec_per_chip"
    assert line["unit"] == "steps/sec/chip"
    assert line["detail"]["headline_only"] is True
    assert line["detail"]["vs_roofline"] == 0.5
    assert "errors" not in line["detail"]   # no side workload ever ran
    assert calls == ["sweep_"]              # exactly one sweep half
