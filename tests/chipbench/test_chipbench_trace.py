"""The reduction from a profiler trace to busy time, module time, top
operations and named idle gaps."""
import pytest

from chipbench import manifest, trace

RECORDED = manifest.HERE / "testdata" / "v5e_decode_slice.xplane.pb"

SYNTHETIC = """
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" events { metadata_id: 1 duration_ps: 5 } } }
planes { id: 2 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "jit_multi_fn(111)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_step_fn(222)" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  event_metadata { key: 4 value { id: 4 name: "custom-call.7" } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 14000000000 duration_ps: 4000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 10000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000000 }
    events { metadata_id: 4 offset_ps: 5000000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 14000000000 duration_ps: 4000000000 }
    events { metadata_id: 3 offset_ps: 20000000000 duration_ps: 9000000000 } }
}
"""


def test_union_merges_overlaps_and_lists_gaps():
    busy, gaps = trace.union_s([(0.0, 2.0), (1.0, 2.0), (5.0, 1.0),
                                (5.5, 0.2), (7.0, 1.0)])
    assert busy == pytest.approx(5.0)
    assert gaps == [(3.0, 2.0), (6.0, 1.0)]
    assert trace.union_s([]) == (0.0, [])


def test_reduce_synthetic_trace(tmp_path):
    path = tmp_path / "t.txt"
    path.write_text(SYNTHETIC)
    planes = trace.load(str(path))
    assert list(planes) == ["/device:TPU:0"]  # host planes are not devices
    r = trace.reduce(planes)
    # ops cover [0,10] + [14,18] + [20,29] ms; the window is the trace's
    # own, first to last device event
    assert r["busy_s"] == pytest.approx(0.023)
    assert r["window_s"] == pytest.approx(0.029)
    assert r["modules"]["jit_multi_fn"] == {
        "count": 2, "seconds": pytest.approx(0.020)}
    assert r["modules"]["jit_step_fn"]["count"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.019)]
    assert r["device_ops"][1] == ["custom-call.7", pytest.approx(0.005)]
    assert r["idle_gaps"] == [
        ["jit_multi_fn->jit_step_fn", pytest.approx(0.004)],
        ["jit_step_fn->jit_multi_fn", pytest.approx(0.002)],
    ]
    assert trace.reduce({}) is None  # a CPU rehearsal has no device plane


def test_reduce_recorded_v5e_trace():
    """A quarter second of qwen2-longgen on the chip (my chip run, PR 23),
    cut down by testdata/trim_xplane.py: three mixed steps. The numbers
    are pinned so that a change to the reduction shows."""
    planes = trace.load(str(RECORDED))
    assert trace.describe(planes)["/device:TPU:0"]["XLA Modules"] == 3
    r = trace.reduce(planes)
    assert r["busy_s"] == pytest.approx(0.174228470, abs=1e-8)
    assert r["window_s"] == pytest.approx(0.242956835, abs=1e-8)
    assert r["modules"] == {"jit_mixed_fn": {
        "count": 3, "seconds": pytest.approx(0.174231268, abs=1e-8)}}
    assert [n for n, _s in r["device_ops"][:3]] == [
        "%while.7", "%while.8", "%closed_call.18"]
    assert len(r["device_ops"]) == 10
    assert all(len(n) <= 80 for n, _s in r["device_ops"])
    assert r["idle_gaps"] == [
        ["jit_mixed_fn->jit_mixed_fn", pytest.approx(0.0389351, abs=1e-7)],
        ["jit_mixed_fn->jit_mixed_fn", pytest.approx(0.029791009, abs=1e-7)],
    ]
    assert r["idle_gap_total_s"] == pytest.approx(
        r["window_s"] - r["modules"]["jit_mixed_fn"]["seconds"], abs=1e-6)


def test_find_xplane(tmp_path):
    assert trace.find_xplane(str(tmp_path)) is None
    d = tmp_path / "plugins" / "profile" / "2026_09_27"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert trace.find_xplane(str(tmp_path)) == str(d / "host.xplane.pb")
