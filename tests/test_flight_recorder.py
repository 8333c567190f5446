"""Flight recorder (ISSUE 7): bounded per-step ring, counter deltas,
engine integration, wire shape, and the bit-identical-off guarantee."""

import dataclasses
import json

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import EngineMetrics, JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.telemetry.flight import FlightRecorder


def test_ring_is_bounded_and_ordered():
    fl = FlightRecorder(capacity=4)
    m = EngineMetrics()
    for i in range(10):
        m.generated_tokens += 1
        fl.record_step(m, kind="decode", step_ms=1.0, n_decode=1)
    recs = fl.snapshot()
    assert len(recs) == 4 == len(fl)
    assert [r["seq"] for r in recs] == [6, 7, 8, 9]
    # n= trims from the newest end
    assert [r["seq"] for r in fl.snapshot(2)] == [8, 9]
    assert fl.to_wire(1)[0]["seq"] == 9
    # n=0 is an empty window, not the whole ring ([-0:] off-by-zero)
    assert fl.snapshot(0) == []


def test_records_carry_counter_deltas_not_cumulatives():
    fl = FlightRecorder()
    m = EngineMetrics()
    m.compiles = 3
    m.compile_ms = 120.0
    m.preemptions = 1
    fl.record_step(m, kind="prefill", step_ms=5.0)
    # first record sees the whole cumulative as its delta (boot window)
    r0 = fl.snapshot()[-1]
    assert r0["compiles"] == 3 and r0["preempted"] == 1
    # a quiet step records NO delta keys at all (compact records)
    fl.record_step(m, kind="decode", step_ms=1.0)
    r1 = fl.snapshot()[-1]
    assert "compiles" not in r1 and "preempted" not in r1
    m.compiles += 1
    m.overlap_hits += 2
    fl.record_step(m, kind="decode", step_ms=1.0)
    r2 = fl.snapshot()[-1]
    assert r2["compiles"] == 1 and r2["overlap_hits"] == 2


def test_engine_steps_append_records_with_buckets_and_compiles():
    eng = JaxEngine(EngineConfig.for_tests())
    for i in range(3):
        eng.add_request(
            f"r{i}", [1 + i, 2, 3, 4, 5],
            SamplingParams(temperature=0.0, max_tokens=4),
        )
    eng.run_to_completion()
    recs = eng.flight.snapshot()
    assert recs, "engine steps must append flight records"
    kinds = {r["kind"] for r in recs}
    assert "prefill" in kinds and ("decode" in kinds or "mixed" in kinds)
    pre = next(r for r in recs if r["kind"] == "prefill")
    assert pre["n_prefill"] == 3 and pre["t_bucket"] >= 5
    assert pre["prefill_tokens"] == 15
    dec = next(r for r in recs if r["kind"] in ("decode", "mixed"))
    assert dec["n_decode"] == 3 and dec["b_decode"] == 4  # bucket of 3
    # the shortest sequence among the decode rows (prompts of 5 tokens
    # decoding 4): only where rows decode
    assert 6 <= dec["ctx_min"] <= 9 and "ctx_min" not in pre
    # the first steps carry the jit-compile events
    assert sum(r.get("compiles", 0) for r in recs) == eng.metrics.compiles
    assert all(r["step_ms"] > 0 for r in recs)
    # records are json-safe (they ride the metrics frame wire)
    json.dumps(recs)


def test_flight_off_is_bit_identical_and_recorder_absent():
    outs = {}
    for on in (True, False):
        cfg = dataclasses.replace(
            EngineConfig.for_tests(), flight_recorder=on
        )
        eng = JaxEngine(cfg)
        for i in range(3):
            eng.add_request(
                f"r{i}", [1 + i, 2, 3, 4],
                SamplingParams(temperature=0.8, top_p=0.9, max_tokens=6),
            )
        outs[on] = eng.run_to_completion()
        if on:
            assert eng.flight is not None and len(eng.flight) > 0
        else:
            assert eng.flight is None
    assert outs[True] == outs[False]


def test_records_carry_the_dispatch_timeline_and_the_dry_deltas():
    """ISSUE 38: `disp` (one entry per program the step launched) and
    `ready` (one per dispatch whose ids it read), json-safe like the
    rest, beside the per-step deltas of the dry clock's counters."""
    eng = JaxEngine(EngineConfig.for_tests())
    for i in range(3):
        eng.add_request(
            f"r{i}", [1 + i, 2, 3, 4, 5],
            SamplingParams(temperature=0.0, max_tokens=9),
        )
    eng.run_to_completion()
    recs = eng.flight.snapshot()
    json.dumps(recs)
    disp = [e for r in recs for e in r.get("disp", ())]
    ready = [e for r in recs for e in r.get("ready", ())]
    assert len(disp) == eng.metrics.launches == sum(
        r.get("launches", 0) for r in recs)
    assert {"seq", "kind", "rows", "n_rows", "k", "ahead",
            "t_launch"} <= set(disp[0])
    assert {"seq", "kind", "t_ready", "blocked_ms"} <= set(ready[0])
    assert {e["seq"] for e in ready} <= {e["seq"] for e in disp}
    # the prompt's step launched a prefill and read it at once
    pre = next(r for r in recs if r["kind"] == "prefill")
    assert pre["disp"][0]["kind"] == "prefill"
    assert pre["disp"][0]["chunk_tokens"] == pre["prefill_tokens"] == 15
    assert pre["ready"][0]["seq"] == pre["disp"][0]["seq"]
    # a step that launched nothing and read nothing has neither key
    assert all(r.get("disp", True) and r.get("ready", True) for r in recs)
    assert sum(r.get("dry_launches", 0) for r in recs) == (
        eng.metrics.dry_launches) >= 1


def test_record_step_takes_a_timeline_and_leaves_empty_ones_out():
    fl = FlightRecorder()
    m = EngineMetrics()
    m.dry_ms, m.dry_stage_ms, m.launches, m.dry_launches = 7.5, 5.0, 2, 1
    rec = fl.record_step(
        m, kind="mixed", step_ms=3.0,
        timeline={"ready": [{"seq": 4, "t_ready": 9.5, "blocked_ms": 0.1}]},
    )
    assert rec["ready"][0]["seq"] == 4 and "disp" not in rec
    assert (rec["dry_ms"], rec["dry_stage_ms"]) == (7.5, 5.0)
    assert (rec["launches"], rec["dry_launches"]) == (2, 1)
    quiet = fl.record_step(m, kind="decode", step_ms=1.0, timeline={})
    assert not {"disp", "ready", "dry_ms", "launches"} & set(quiet)
