"""chipbench/hostspans.py: the engine loop's spans and the device's
operations on one clock, on a recorded v5e trace that keeps host planes
and the operations' scope paths, and on small made-up ones."""
import json
import shutil

import pytest

from chipbench import hostspans, manifest, trace

RECORDED = manifest.HERE / "testdata" / "v5e_hostspans_slice.xplane.pb"
#: PR 23's recorded trace: device planes only, no stats, no host planes
BARE = manifest.HERE / "testdata" / "v5e_decode_slice.xplane.pb"

HOSTSPANS_READERS = [
    "idle_in_intake_share", "idle_in_schedule_share", "idle_in_stage_share",
    "idle_in_launch_share", "idle_in_readback_share",
    "idle_in_postprocess_share", "idle_in_emit_share",
    "idle_unattributed_share", "decode_attn_ms_per_step",
    "decode_mlp_ms_per_step", "decode_head_ms_per_step",
]
#: what each new reader gives on the recorded trace (my chip run, PR 24:
#: 0.43 s of qwen2-longgen, two mixed steps about one fused decode
#: dispatch), pinned so that a change to the reduction shows
ON_RECORDED = {
    "idle_in_intake_share": 0.029189,
    "idle_in_schedule_share": 0.374290,
    "idle_in_stage_share": 3.019372,
    "idle_in_launch_share": 0.000012,
    "idle_in_readback_share": 1.441173,
    "idle_in_postprocess_share": 4.571545,
    "idle_in_emit_share": 4.605078,
    "idle_unattributed_share": 0.553872,
    "decode_attn_ms_per_step": 18.007138,
    "decode_mlp_ms_per_step": 7.782138,
    "decode_head_ms_per_step": 3.354601,
    "mixed_step_device_ms": 63.104622,
    "queue_wait_p50_ms": 6500.0,
    "rollbacks_per_admission": 0.25,
}


def ctx_for(path) -> dict:
    """What run.py hands a reader, as far as the new readers look."""
    return {
        "trace": trace.reduce(trace.load(str(path))),
        "flight": [{"kind": "mixed", "admit_wait_ms": [6000.0, 7000.0]},
                   {"kind": "decode"},
                   {"kind": "mixed", "admit_wait_ms": [6500.0]}],
        "engine": {"overlap_rollbacks": 1, "mixed_dispatches": 3,
                   "prefill_dispatches": 1, "decode_dispatches": 6,
                   "time_stage_ms": 70.0, "time_decode_dispatch_ms": 75.0,
                   "time_emit_ms": 50.0, "time_decode_host_ms": 65.0},
    }


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    """A RUN_DIR of this test's own, with `place(trace)` to put a trace
    where run.py would have written this run's."""
    monkeypatch.setattr(manifest, "RUN_DIR", tmp_path)
    hostspans._THIS_RUN.clear()

    def place(src):
        d = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
        d.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, d / "host.xplane.pb")
        hostspans._THIS_RUN.clear()

    yield place
    hostspans._THIS_RUN.clear()


@pytest.mark.parametrize("name", sorted(ON_RECORDED))
def test_reader_on_the_recorded_trace(name, run_dir, capsys):
    run_dir(RECORDED)
    value = manifest.layer_reader(name)(ctx_for(RECORDED))
    assert value == pytest.approx(ON_RECORDED[name], abs=2e-6)
    if name in HOSTSPANS_READERS:  # the free-form note, once a run
        manifest.layer_reader(name)(ctx_for(RECORDED))
        lines = [x for x in capsys.readouterr().out.splitlines()
                 if '"note": "hostspans"' in x]
        assert len(lines) == 1
        # beside the traced slice, the whole window on the host's clock
        assert json.loads(lines[0])["window_host_ms_per_dispatch"] == {
            "intake": 0.0, "schedule": 0.0, "stage": 7.0, "readback": 0.0,
            "postprocess": 6.5, "emit": 5.0, "launch": 0.5}


@pytest.mark.parametrize("name", sorted(ON_RECORDED))
def test_reader_gives_none_not_an_error_without_spans(name, run_dir):
    """The parent commit's trace (no `engine.*` span, no scope, no new
    counter) and a run that wrote no trace at all: the metric is left
    out of the line."""
    ctx = ctx_for(BARE)
    ctx["flight"] = [{"kind": "mixed"}, {"kind": "decode"}]
    ctx["engine"] = {"mixed_dispatches": 3}
    read = manifest.layer_reader(name)
    if name == "mixed_step_device_ms":  # the device alone says this one
        assert read(ctx) == pytest.approx(58.07709, abs=1e-4)
        assert read({**ctx, "trace": None}) is None
        return
    assert read(ctx) is None  # no trace file
    run_dir(BARE)
    assert read(ctx) is None  # a trace with device planes alone


def test_idle_shares_sum_to_device_idle_share():
    loaded = hostspans.load(str(RECORDED))
    idle = hostspans.idle_by_phase(loaded)
    r = trace.reduce(trace.load(str(RECORDED)))
    device_idle_share = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert device_idle_share == pytest.approx(14.59456, abs=1e-4)
    assert sum(idle["shares"].values()) == pytest.approx(
        device_idle_share, abs=1e-3)
    assert set(idle["shares"]) == {*hostspans.PHASES, "unattributed"}
    assert idle["shares"]["unattributed"] < device_idle_share / 10
    assert idle["window_s"] == pytest.approx(r["window_s"], abs=1e-6)


def test_longest_gaps_name_the_host_spans_and_the_launch_that_ended_them():
    idle = hostspans.idle_by_phase(hostspans.load(str(RECORDED)))
    first, second = idle["gaps"][:2]
    # after a mixed step: readback's tail, postprocess, emit, the next
    # step's schedule and stage, and no rollback anywhere
    assert first["ms"] == pytest.approx(40.709, abs=1e-3)
    assert list(first["host_ms"])[:4] == [
        "emit", "postprocess", "stage", "readback"]
    assert first["host_ms"]["emit"] == pytest.approx(15.457, abs=1e-3)
    assert sum(first["host_ms"].values()) == pytest.approx(
        first["ms"], abs=2e-3)
    assert first["ended_by"] == {
        "kind": "decode_multi", "rows": 64, "k": 8, "speculative": 0}
    assert first["next_module"] == "jit_multi_fn"
    assert second["ms"] == pytest.approx(21.916, abs=1e-3)
    assert second["ended_by"]["kind"] == "mixed"
    assert second["next_module"] == "jit_mixed_fn"
    assert all(g["ms"] >= 0.05 for g in idle["gaps"])
    names = {s[0] for s in hostspans.load(str(RECORDED))["spans"]}
    assert "engine.rollback" not in names


def test_scope_self_times_sum_to_the_modules_busy_time():
    loaded = hostspans.load(str(RECORDED))
    r = trace.reduce(trace.load(str(RECORDED)))
    for module, count in (("jit_multi_fn", 1), ("jit_mixed_fn", 2)):
        per = hostspans.scope_self_s(loaded, module)
        assert per["_count"] == count
        assert per["_seconds"] == pytest.approx(
            r["modules"][module]["seconds"], abs=1e-7)
        own = sum(v for k, v in per.items() if not k.startswith("_"))
        # operations leave nanoseconds between them
        assert own == pytest.approx(per["_seconds"], abs=5e-6)
        assert all(v >= 0 for v in per.values())
    multi = hostspans.scope_self_s(loaded, "jit_multi_fn")
    assert multi["attn/paged"] == pytest.approx(0.134632328, abs=1e-8)
    assert multi["mlp"] == pytest.approx(0.062257107, abs=1e-8)
    named = sum(v for k, v in multi.items()
                if hostspans.in_scope(k, "attn", "mlp", "lm_head", "sample"))
    assert named / multi["_seconds"] > 0.9
    mixed = hostspans.scope_self_s(loaded, "jit_mixed_fn")
    assert mixed["attn/flash"] > 0  # the prompt's own attention
    assert hostspans.scope_self_s(loaded, "jit_nothing") is None


def test_fused_steps_are_read_from_the_launch_span():
    loaded = hostspans.load(str(RECORDED))
    assert hostspans.fused_steps(loaded) == [8]
    assert hostspans.fused_steps(loaded, "jit_mixed_fn") == [1, 1]
    assert hostspans.fused_steps(loaded, "jit_kstep_fn") is None
    assert hostspans.ms_per_step(loaded, ("attn",)) == pytest.approx(
        1e3 * sum(v for k, v in hostspans.scope_self_s(
            loaded, "jit_multi_fn").items() if k.startswith("attn")) / 8)


def test_engine_thread_is_found_by_its_events():
    spans = hostspans.load(str(RECORDED))["spans"]
    assert [s[0] for s in spans[:6]] == [
        "engine.intake", "engine.step", "engine.schedule", "engine.stage",
        "engine.launch", "engine.readback"]
    assert spans[1][3]["step_num"] == 165
    assert spans[4][3] == {"kind": "mixed", "rows": 64, "t": 512, "k": 1,
                           "speculative": 0}
    bare = hostspans.load(str(BARE))
    assert bare["spans"] == [] and list(bare["devices"]) == ["/device:TPU:0"]
    assert hostspans.idle_by_phase(bare) is None
    assert hostspans.scope_self_s(bare, "jit_mixed_fn") is None


# -- made-up traces -----------------------------------------------------------


def make_trace(path, host=(), modules=(), ops=()):
    """host: (name, start_us, dur_us, {arg: value}); modules: (name,
    start_us, dur_us); ops: (name, start_us, dur_us, tf_op)."""
    space = hostspans._xspace_class()()

    def add(plane, line, mid, name, start_us, dur_us):
        if not any(e.key == mid for e in plane.event_metadata):
            entry = plane.event_metadata.add(key=mid)
            entry.value.id, entry.value.name = mid, name
        return line.events.add(metadata_id=mid, offset_ps=start_us * 10**6,
                               duration_ps=dur_us * 10**6)

    def stat_id(plane, name):
        for e in plane.stat_metadata:
            if e.value.name == name:
                return e.key
        e = plane.stat_metadata.add(key=len(plane.stat_metadata) + 1)
        e.value.id, e.value.name = e.key, name
        return e.key

    if host:
        plane = space.planes.add(id=1, name="/host:CPU")
        line = plane.lines.add(id=7, name="python3", timestamp_ns=1000)
        ids: dict = {}
        for name, start, dur, args in host:
            ev = add(plane, line, ids.setdefault(name, len(ids) + 1), name,
                     start, dur)
            for k, v in args.items():
                st = ev.stats.add(metadata_id=stat_id(plane, k))
                if isinstance(v, str):
                    st.str_value = v
                else:
                    st.int64_value = v
    if modules:
        plane = space.planes.add(id=2, name="/device:TPU:0")
        ml = plane.lines.add(id=1, name="XLA Modules", timestamp_ns=1000)
        ol = plane.lines.add(id=2, name="XLA Ops", timestamp_ns=1000)
        ids = {}
        for name, start, dur in modules:
            add(plane, ml, ids.setdefault(name, len(ids) + 1), name, start,
                dur)
        for name, start, dur, tf_op in ops:
            mid = ids.setdefault(name, len(ids) + 1)
            add(plane, ol, mid, name + " = f32[] op()", start, dur)
            md = next(e.value for e in plane.event_metadata if e.key == mid)
            if tf_op and not md.stats:
                md.stats.add(metadata_id=stat_id(plane, "tf_op"),
                             str_value=tf_op)
    path.write_bytes(space.SerializeToString())
    return str(path)


def test_innermost_span_wins_and_uncovered_idle_is_unattributed(tmp_path):
    p = make_trace(
        tmp_path / "t.xplane.pb",
        host=[("engine.step", 100, 300, {"step_num": 1}),
              ("engine.stage", 120, 50, {}),
              ("engine.launch", 170, 100, {"kind": "decode_multi", "k": 4}),
              ("engine.compile", 180, 80, {"key": "x"}),
              ("engine.rollback", 300, 0, {"why": "y"}),
              ("engine.wait", 450, 100, {}),
              ("engine.emit", 560, 20, {"posted": 3})],
        modules=[("jit_multi_fn(1)", 0, 100), ("jit_multi_fn(1)", 600, 100)],
        ops=[("%fusion.1", 0, 100, "jit(multi_fn)/mlp/dot_general:"),
             ("%fusion.1", 600, 100, "jit(multi_fn)/mlp/dot_general:")])
    loaded = hostspans.load(p)
    assert hostspans.flatten(loaded["spans"]) == [
        (pytest.approx(s * 1e-6), pytest.approx(e * 1e-6), n)
        for s, e, n in [
            (100, 120, "engine.step"), (120, 170, "engine.stage"),
            (170, 270, "engine.launch"),  # the compile counts as launch
            (270, 400, "engine.step"), (450, 550, "engine.wait"),
            (560, 580, "engine.emit")]]
    idle = hostspans.idle_by_phase(loaded)
    # one gap of 500 us in a window of 700: stage 50, launch 100, emit
    # 20; step's own 150, the wait 100 and 80 under nothing: unattributed
    assert idle["shares"] == {
        "intake": 0.0, "schedule": 0.0,
        "stage": pytest.approx(100 * 50 / 700),
        "launch": pytest.approx(100 * 100 / 700),
        "readback": 0.0, "postprocess": 0.0,
        "emit": pytest.approx(100 * 20 / 700),
        "unattributed": pytest.approx(100 * 330 / 700)}
    assert sum(idle["shares"].values()) == pytest.approx(100 * 500 / 700)
    assert idle["gaps"][0]["ended_by"] == {"kind": "decode_multi", "k": 4}
    assert idle["gaps"][0]["host_ms"]["none"] == pytest.approx(0.08)


def test_self_time_leaves_out_what_children_cover(tmp_path):
    p = make_trace(
        tmp_path / "t.xplane.pb",
        host=[("engine.launch", 0, 5, {"kind": "decode_multi", "k": 2})],
        modules=[("jit_multi_fn(1)", 10, 100), ("jit_other(2)", 200, 50)],
        ops=[("%while.1", 10, 90, "jit(multi_fn)/while:"),
             ("%fusion.2", 10, 30, "jit(multi_fn)/while/body/attn/qkv/dot:"),
             ("%kernel.3", 40, 40, "jit(multi_fn)/while/body/attn/paged/k:"),
             ("%while.4", 80, 20, "jit(multi_fn)/while/body/mlp/while:"),
             ("%fusion.5", 85, 10, "jit(multi_fn)/while/body/mlp/while/x:"),
             ("%fusion.6", 100, 10, "jit(multi_fn)/sample/argmax:"),
             ("%fusion.7", 200, 50, "jit(other)/mlp/dot:")])
    loaded = hostspans.load(p)
    per = hostspans.scope_self_s(loaded, "jit_multi_fn")
    assert per.pop("_count") == 1
    assert {k: round(v * 1e6) for k, v in per.items()} == {
        "unscoped": 0, "attn/qkv": 30, "attn/paged": 40, "mlp": 20,
        "sample": 10, "_seconds": 100}  # us; the whiles own nothing
    assert hostspans.ms_per_step(loaded, ("attn",)) == pytest.approx(0.035)
    assert hostspans.ms_per_step(loaded, ("lm_head", "sample")) == \
        pytest.approx(0.005)


def test_dispatches_pair_with_their_launches_when_pipelined(tmp_path):
    """The overlap pipeline launches dispatch N+1 while N runs; one that
    was launched before the capture takes the commonest k."""
    p = make_trace(
        tmp_path / "t.xplane.pb",
        host=[("engine.launch", 250, 5, {"kind": "decode_multi", "k": 8}),
              ("engine.launch", 320, 5, {"kind": "mixed", "k": 1}),
              ("engine.launch", 330, 5, {"kind": "decode_multi", "k": 4}),
              ("engine.launch", 610, 5, {"kind": "decode_multi", "k": 8})],
        modules=[("jit_multi_fn(1)", 0, 200), ("jit_multi_fn(1)", 260, 200),
                 ("jit_multi_fn(1)", 470, 100), ("jit_multi_fn(1)", 620, 50)],
        ops=[("%f.1", s, d, "jit(multi_fn)/mlp/dot:")
             for s, d in ((0, 200), (260, 200), (470, 100), (620, 50))])
    assert hostspans.fused_steps(hostspans.load(p)) == [8, 8, 4, 8]


def test_a_cpu_rehearsal_has_spans_and_no_device(tmp_path, run_dir):
    p = make_trace(tmp_path / "cpu.xplane.pb",
                   host=[("engine.step", 0, 10, {"step_num": 0})])
    loaded = hostspans.load(p)
    assert len(loaded["spans"]) == 1 and loaded["devices"] == {}
    assert hostspans.idle_by_phase(loaded) is None
    assert hostspans.ms_per_step(loaded, ("attn",)) is None
    run_dir(p)
    assert hostspans.idle_share({}, "emit") is None
    assert hostspans.step_ms({}, "mlp") is None


def test_scope_of_reads_the_first_named_part_of_a_path():
    f = hostspans.scope_of
    assert f("jit(multi_fn)/while/body/closed_call/attn/qkv/dot:") == \
        "attn/qkv"
    assert f("jit(multi_fn)/while/body/closed_call/mlp/mul:") == "mlp"
    assert f("jit(mixed_fn)/attn/kv_update/paged_kv_write:") == \
        "attn/kv_update"
    assert f("jit(multi_fn)/while/body/sample/attn/x:") == "sample"
    assert f("jit(multi_fn)/while:") == f("") == "unscoped"
    assert hostspans.in_scope("attn/paged", "attn", "mlp")
    assert not hostspans.in_scope("attn/paged", "mlp")
