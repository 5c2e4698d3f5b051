"""The trace reducer on a synthetic ``.xplane.pb``: one device, five
operations, three host spans."""

import pytest

from benchmarks.harness import trace as tr

# Times in microseconds on the device line (timestamp 0):
#   fusion.1        0..20
#   copy.7         30..40      (shape recorded in long_name)
#   all-reduce.2   40..60      exposed 40..50, hidden 50..60 behind
#   fusion.3       50..70
#   copy.9         90..100
# Host spans: serve_decode_boundary 0..100 holding serve_prefill 20..28
# and serve_decode 70..95.
SPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 90000000 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "%multiply_add_fusion.1 = (f32[768]{0:T(1024)}, f32[8]{0}) fusion(%a, %b), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.7 = bf16[1,96,1024,12,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.2 = f32[768]{0} all-reduce(%g), replica_groups={}" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.3" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.9 = bf16[1,96,1024,12,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%q)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_step(123)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 5 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 70000000 duration_ps: 25000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:serve_decode_boundary" } }
  event_metadata { key: 2 value { id: 2 name: "bench:serve_prefill" } }
  event_metadata { key: 3 value { id: 3 name: "bench:serve_decode" } }
  event_metadata { key: 4 value { id: 4 name: "not_ours" } }
}
'''
US = 1e-6


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return tr.from_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(SPACE)))


def test_only_ops_and_own_spans_are_read(trace):
    assert list(trace.device_ops) == [0]
    assert [(e.name, e.opcode, e.shape) for e in trace.device_ops[0]] == [
        ("multiply_add_fusion", "fusion", "f32[768]"),
        ("copy", "copy", "bf16[1,96,1024,12,64]"),
        ("all-reduce", "all-reduce", "f32[768]"),
        ("fusion", "fusion", ""),
        ("copy", "copy", "bf16[1,96,1024,12,64]")]
    assert [e.name for e in trace.device_modules[0]] == ["jit_step(123)"]
    assert sorted(e.name for e in trace.host_spans) == [
        "serve_decode", "serve_decode_boundary", "serve_prefill"]


def test_busy_is_the_union_not_the_sum(trace):
    assert tr.window_of(trace) == pytest.approx((0.0, 100 * US))
    # 0..20, 30..70, 90..100: the overlap of all-reduce and fusion.3
    # counts once.
    assert tr.busy(trace)[0] == pytest.approx(70 * US)
    assert tr.busy(trace, (10 * US, 60 * US))[0] == pytest.approx(40 * US)


def test_operation_sums_are_labelled_by_kind_shape_and_count(trace):
    sums = dict((k, v) for k, v in tr.op_sums(trace))
    assert sums["copy_copy_bf16_1_96_1024_12_64__x2"] == pytest.approx(
        20 * US)
    assert sums["multiply_add_fusion_fusion_f32_768__x1"] == pytest.approx(
        20 * US)
    assert sums["fusion_fusion__x1"] == pytest.approx(20 * US)
    assert sums["all_reduce_all_reduce_f32_768__x1"] == pytest.approx(
        20 * US)
    assert tr.kind_seconds(trace, tr.COPIES) == pytest.approx(20 * US)


def test_exposed_collective_time_excludes_what_compute_hides(trace):
    assert tr.exposed_collective_seconds(trace) == pytest.approx(10 * US)


def test_idle_gaps_go_to_the_innermost_host_span(trace):
    # Idle: 20..30 (8 of it inside serve_prefill, 2 in the boundary)
    # and 70..90 (all inside serve_decode).
    gaps = dict((k, v) for k, v in tr.idle_gaps(trace))
    assert gaps["serve_decode"] == pytest.approx(20 * US)
    assert gaps["serve_prefill"] == pytest.approx(8 * US)
    assert gaps["serve_decode_boundary"] == pytest.approx(2 * US)
    assert sum(gaps.values()) == pytest.approx(30 * US)


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
