"""The readers of the dispatch timeline (ISSUE 38): per-layer metrics read
from the flight records of the part of the window the profiler never
touched (chipbench/timeline.py), on hand-built `ctx`: the slice cut and
its margin, the pad and busy arithmetic, None on records without the
fields, and that every new manifest entry has its reader file."""

import json

import pytest

from chipbench import manifest, timeline

NEW = (
    "late_launch_share", "device_dry_share", "device_dry_share.traced",
    "dry_in_stage_share", "dry_in_emit_share", "host_turn_ms_p50",
    "host_turn_ms_p95", "mixed_steps_per_s", "mixed_pad_share",
    "mixed_step_ms_p50", "mixed_busy_share",
)
LOOP = "engine loop (engine/engine.py)"
STEP = "model step (models/llama.py step programs)"

#: the slice of a 30 s window that opened at wall clock 1000
INFO = {"wall_start": 1013.0, "wall_stop": 1017.0}


def disp(seq, at, kind="decode_multi", dry=None, **more):
    e = {"seq": seq, "kind": kind, "rows": 64, "n_rows": 61, "k": 8,
         "ahead": 1, "t_launch": at, **more}
    if dry is not None:
        e.update(dry_before_ms=dry, dry_phase="stage", slack_ms=dry / 100)
    return e


def mixed(seq, at, tokens, b_pre=1, t_bucket=512, dry=None):
    return disp(seq, at, "mixed", dry, k=1, t=t_bucket, b_pre=b_pre,
                chunk_tokens=tokens)


def ready(seq, t, kind="decode_multi", blocked=5.0, dev=None):
    e = {"seq": seq, "kind": kind, "t_ready": t, "blocked_ms": blocked}
    if dev is not None:
        e["dev_ms"] = dev
    return e


def rec(ts, disp_=(), ready_=(), **deltas):
    r = {"seq": int(ts * 10), "ts": ts, "kind": "decode", **deltas}
    if disp_:
        r["disp"] = list(disp_)
    if ready_:
        r["ready"] = list(ready_)
    return r


def window() -> list:
    """Host clock 500.0 = wall clock 1000.0. Before the slice: four
    launches over 10 s, two of them dry (30 + 70 ms after the first)."""
    return [
        rec(1001.0, [disp(0, 501.0, dry=900.0)], dry_stage_ms=800.0),
        rec(1003.0, [mixed(1, 502.9, 400)],
            [ready(0, 502.95, dev=120.0)], dry_stage_ms=10.0, tokens=512),
        rec(1006.0, [disp(2, 505.9, dry=30.0)],
            [ready(1, 505.95, "mixed", dev=34.0)],
            dry_stage_ms=20.0, dry_emit_ms=5.0, tokens=64),
        rec(1011.0, [mixed(3, 511.0, 1000, b_pre=2, dry=70.0)],
            [ready(2, 510.98, dev=126.0)],
            dry_stage_ms=40.0, dry_emit_ms=30.0, tokens=512),
        # inside the margin before the slice: in no part
        rec(1012.7, [disp(4, 512.6, dry=400.0)],
            [ready(3, 512.65, "mixed", dev=36.0)], dry_stage_ms=300.0),
        # inside the slice: two launches 2 s apart, 500 ms dry
        rec(1013.5, [disp(5, 513.4)], [ready(4, 513.45)]),
        rec(1015.5, [mixed(6, 515.4, 512, dry=500.0)],
            [ready(5, 515.45, dev=130.0)], dry_stage_ms=450.0),
        rec(1017.0, [], [ready(6, 516.9, "mixed", dev=50.0)]),
        # after: the stall of stop_trace
        rec(1021.0, [disp(7, 520.9, dry=3900.0)]),
        rec(1021.2, [disp(8, 521.1)], [ready(7, 521.15, dev=121.0)]),
    ]


def ctx_of(records, info=INFO) -> dict:
    return {"flight": records, "trace_info": info, "engine": {}}


def read(name, ctx):
    return manifest.layer_reader(name)(ctx)


def test_the_window_is_cut_by_ts_with_a_margin_before_the_slice():
    ctx = ctx_of(window())
    assert [r["ts"] for r in timeline.part(ctx, "before")] == [
        1001.0, 1003.0, 1006.0, 1011.0]
    assert timeline.MARGIN_S == 0.5
    assert [r["ts"] for r in timeline.part(ctx, "inside")] == [
        1013.5, 1015.5, 1017.0]
    assert [r["ts"] for r in timeline.part(ctx, "after")] == [1021.0, 1021.2]
    # a record exactly at the margin is out; just earlier is in
    edge = ctx_of([rec(1012.5), rec(1012.49)])
    assert [r["ts"] for r in timeline.part(edge, "before")] == [1012.49]
    # the slice's ends are its own
    ends = ctx_of([rec(1013.0), rec(1017.0), rec(1017.001)])
    assert len(timeline.part(ends, "inside")) == 2


def test_an_untraced_run_or_a_failed_slice_reads_none():
    for info in ({}, None, {"wall_start": 1013.0}):
        ctx = ctx_of(window(), info)
        assert all(read(name, ctx) is None for name in NEW)


def test_the_parents_records_read_none_and_never_raise():
    plain = [{"seq": i, "ts": 1000.0 + i, "kind": "decode", "n_decode": 64,
              "tokens": 512, "stage_ms": 6.0} for i in range(30)]
    ctx = ctx_of(plain)
    assert all(read(name, ctx) is None for name in NEW)
    assert all(read(name, ctx_of([])) is None for name in NEW)
    assert all(read(name, {"trace_info": INFO}) is None for name in NEW)


def test_late_launches_and_the_dry_share_before_the_slice():
    ctx = ctx_of(window())
    # three of the four launches before the slice were made dry
    assert read("late_launch_share", ctx) == pytest.approx(75.0)
    # 30 + 70 ms of the 10 s between the first and the last launch: the
    # first launch's own 900 ms lie before those seconds
    assert read("device_dry_share", ctx) == pytest.approx(1.0)
    assert read("device_dry_share.traced", ctx) == pytest.approx(25.0)
    one = ctx_of(window()[:1])
    assert read("device_dry_share", one) is None  # under two launches
    assert read("late_launch_share", one) == 100.0


def test_a_counters_deltas_over_the_records_seconds():
    ctx = ctx_of(window())
    # 10 + 20 + 40 ms over the 10 s from the first record to the last
    assert read("dry_in_stage_share", ctx) == pytest.approx(0.7)
    assert read("dry_in_emit_share", ctx) == pytest.approx(0.35)
    # a counter that stayed 0 reads 0, not None, where the clock ran
    quiet = ctx_of([rec(1001.0, [disp(0, 501.0)]),
                    rec(1002.0, [disp(1, 502.0)])])
    assert read("dry_in_emit_share", quiet) == 0.0


def test_a_host_turn_runs_from_the_readback_before_a_launch_to_it():
    ctx = ctx_of(window())
    turns = timeline.host_turns_ms(timeline.part(ctx, "before"))
    # launch 0 has no readback before it; 2 follows the read of 1 ...
    assert turns == pytest.approx([
        (505.9 - 502.95) * 1e3, (511.0 - 510.98) * 1e3], abs=1e-6)
    assert read("host_turn_ms_p50", ctx) == pytest.approx(
        sum(turns) / 2, abs=1e-6)
    assert read("host_turn_ms_p95", ctx) == pytest.approx(
        turns[1] + 0.95 * (turns[0] - turns[1]), abs=1e-6)
    assert read("host_turn_ms_p95", ctx) >= read("host_turn_ms_p50", ctx)


def test_mixed_steps_are_counted_and_their_padding_summed():
    ctx = ctx_of(window())
    # one mixed launch after the first launch, over 10 s
    assert read("mixed_steps_per_s", ctx) == pytest.approx(0.2 / 2 * 2)
    # 400 of 1 x 512 and 1000 of 2 x 512
    assert read("mixed_pad_share", ctx) == pytest.approx(
        100.0 * (1 - 1400 / 1536))
    full = ctx_of([rec(1001.0, [mixed(0, 501.0, 512)]),
                   rec(1002.0, [mixed(1, 502.0, 1024, b_pre=2)])])
    assert read("mixed_pad_share", full) == 0.0
    none = ctx_of([rec(1001.0, [disp(0, 501.0)]),
                   rec(1002.0, [disp(1, 502.0)])])
    assert read("mixed_pad_share", none) is None
    assert read("mixed_steps_per_s", none) == 0.0


def test_the_mixed_step_and_its_share_of_the_busy_time():
    ctx = ctx_of(window())
    assert read("mixed_step_ms_p50", ctx) == pytest.approx(34.0)
    assert read("mixed_busy_share", ctx) == pytest.approx(
        100.0 * 34.0 / (120.0 + 34.0 + 126.0))
    inside = timeline.part(ctx, "inside")
    assert timeline.mixed_step_ms_p50(inside) == pytest.approx(50.0)
    # a dispatch whose ends are not known is in neither sum
    unknown = ctx_of([
        rec(1001.0, [mixed(0, 501.0, 512)]),
        rec(1002.0, [disp(1, 502.0)], [ready(0, 501.9, "mixed")]),
        rec(1003.0, [disp(2, 503.0)], [ready(1, 502.9, dev=100.0)])])
    assert read("mixed_step_ms_p50", unknown) is None
    assert read("mixed_busy_share", unknown) == 0.0


def test_the_note_prints_once_with_the_three_parts(capsys):
    ctx = ctx_of(window())
    read("device_dry_share", ctx)
    read("late_launch_share", ctx)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    notes = [ln for ln in lines if ln.get("note") == "timeline"]
    assert len(notes) == 1
    note = notes[0]
    assert note["before"]["by_kind"]["mixed"] == {
        "launches": 2, "late": 1, "ahead": 2, "dev_ms_n": 1,
        "dev_ms_p50": 34.0, "dev_ms_mean": 34.0}
    assert note["before"]["counters"]["dry_stage_ms"] == 870.0
    assert note["before"]["dry_ms_by_phase_it_began_under"] == {
        "stage": 1000.0}
    assert note["before"]["slack_ms_by_phase_it_began_under"] == {
        "stage": 10.0}
    assert [e["seq"] for e in note["before"]["largest_slacks"]] == [0, 3, 2]
    assert note["inside"]["device_dry_share"] == pytest.approx(25.0)
    # the stall of stop_trace shows as the longest gap between records
    assert note["after"]["launch_span_s"] == pytest.approx(0.2)
    assert note["after"]["by_kind"]["decode_multi"]["late"] == 1


@pytest.mark.parametrize("name", NEW)
def test_every_new_entry_has_a_reader_file_and_every_file_an_entry(name):
    man = manifest.load()
    entry = {m["name"]: m for m in man["per_layer"]}[name]
    assert "workloads" not in entry and entry["moves"] == "output_tok_s"
    assert entry["layer"] == (STEP if name.startswith("mixed_") else LOOP)
    assert entry["source"] in ("program_counter", "program_span")
    path = manifest.HERE / "layer_metrics" / f"{name}.py"
    assert path.is_file()
    assert "timeline" in path.read_text()
    assert callable(manifest.layer_reader(name))
    # the manifest's tail is these eleven, in this order
    assert [m["name"] for m in man["per_layer"][-len(NEW):]] == list(NEW)
