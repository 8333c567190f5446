"""The engine loop on the profiler's clock: every loop phase is one
`phase(...)` (engine/engine.py) — a `jax.profiler.TraceAnnotation` span
in a running capture AND the cumulative-ms counter it always was. A
capture on the CPU already holds the host spans (the TraceAnnotation is
the host tracer's), so all of this is tier 1."""

import asyncio
import glob
import time

import jax
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.async_engine import AsyncEngineRunner
from dynamo_tpu.engine.engine import EngineMetrics, JaxEngine, phase
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
from dynamo_tpu.runtime.context import Context


def make_engine(**overrides) -> JaxEngine:
    base = EngineConfig.for_tests()
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


def engine_spans(trace_dir) -> list[dict]:
    """The `engine.*` events of a capture, in start order, of the host
    thread that ran the loop (or built the engine: a capture may begin
    before the boot) — found by its events, not by a thread id."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [
                {"name": e.name, "start": e.start_ns,
                 "end": e.start_ns + e.duration_ns,
                 "ms": e.duration_ns * 1e-6, "line": line.name,
                 **{k: v for k, v in e.stats}}
                for e in line.events if e.name.startswith("engine.")
            ]
            if any(e["name"] in ("engine.step", "engine.boot") for e in evs):
                out += evs
    return sorted(out, key=lambda e: (e["start"], -e["end"]))


class Capture:
    def __init__(self, trace_dir, metrics):
        self.dir, self.metrics = str(trace_dir), metrics

    def __enter__(self):
        self.m0 = self.metrics.to_dict()
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        m1 = self.metrics.to_dict()
        self.delta = {
            k: m1[k] - v for k, v in self.m0.items()
            if isinstance(v, (int, float))
        }
        self.spans = engine_spans(self.dir)

    def ms(self, name, **where) -> float:
        return sum(
            e["ms"] for e in self.spans if e["name"] == name
            and all(e.get(k) == v for k, v in where.items())
        )


WORK = [
    ("a", [5, 17, 42, 9], SamplingParams(max_tokens=19, ignore_eos=True)),
    ("b", [7, 3, 11], SamplingParams(max_tokens=11, ignore_eos=True)),
    ("c", [8, 1, 2, 3, 4], SamplingParams(
        max_tokens=14, ignore_eos=True, temperature=0.8, top_p=0.9, seed=5)),
]


def run(eng, work=WORK) -> dict:
    for rid, prompt, s in work:
        eng.add_request(rid, list(prompt), s)
    return eng.run_to_completion()


@pytest.fixture(scope="module")
def decode_capture(tmp_path_factory):
    """A capture around the decode steps of three requests (the prompts
    are processed before it opens, and every program is warm)."""
    eng = make_engine(overlap_decode=True, decode_steps=4)
    run(eng)  # warm every program: no engine.compile inside the capture
    for rid, prompt, s in WORK:
        eng.add_request(rid + "2", list(prompt), s)
    while eng.scheduler.waiting or any(
        not r.prefill_done for r in eng.scheduler.running
    ):
        eng.step()
    cap = Capture(tmp_path_factory.mktemp("decode_capture"), eng.metrics)
    with cap:
        cap.outputs = eng.run_to_completion()
    return cap


def steps_of(spans):
    """[(step span, [its children in start order])]."""
    steps = [e for e in spans if e["name"] == "engine.step"]
    return [
        (s, [e for e in spans if e is not s and e["line"] == s["line"]
             and s["start"] <= e["start"] and e["end"] <= s["end"]])
        for s in steps
    ]


def test_step_span_holds_the_phases_in_loop_order(decode_capture):
    cap = decode_capture
    assert cap.delta["steps"] >= 3
    steps = steps_of(cap.spans)
    # one engine.step per step() call, numbered by metrics.steps
    dispatched = [s for s, kids in steps if any(
        k["name"] == "engine.launch" for k in kids)]
    assert len(dispatched) >= cap.delta["steps"] - 1
    nums = [s["step_num"] for s, _ in steps]
    assert nums == sorted(nums)
    in_steps = {id(k) for _s, kids in steps for k in kids}
    for e in cap.spans:  # all phases of step() are children of a step
        if e["name"] in ("engine.schedule", "engine.stage", "engine.launch",
                         "engine.readback", "engine.postprocess"):
            assert id(e) in in_steps, e
    for s, kids in steps:
        names = [k["name"] for k in kids if k["name"] != "engine.rollback"]
        assert names[0] == "engine.schedule"
        if "engine.readback" not in names:
            continue  # the last step found nothing left to run
        assert names[-1] == "engine.postprocess"
        # every launch has its inputs staged before it, and the result
        # is read back before it is postprocessed
        for i, n in enumerate(names):
            if n == "engine.launch":
                assert names[i - 1] == "engine.stage"
        assert names.index("engine.readback") < names.index(
            "engine.postprocess")
        real = [k for k in kids if k["name"] == "engine.launch"
                and not k["speculative"]]
        if real:  # not a consumed speculation: stage, launch, readback
            assert names.index("engine.launch") < names.index(
                "engine.readback")


def test_launch_span_names_kind_rows_and_fused_steps(decode_capture):
    launches = [e for e in decode_capture.spans
                if e["name"] == "engine.launch"]
    assert launches
    for e in launches:
        assert e["kind"] in ("decode", "decode_multi")
        assert e["rows"] in (1, 2, 4, 8)
        assert e["k"] == (1 if e["kind"] == "decode" else e["k"]) >= 1
        assert e["speculative"] in (0, 1)
    assert any(e["kind"] == "decode_multi" and e["k"] > 1 for e in launches)
    # the overlap pipeline's dispatches are marked, and its readbacks lag
    m = decode_capture.delta
    assert sum(e["speculative"] for e in launches) == m["overlap_dispatches"]
    lagged = [e["lagged"] for e in decode_capture.spans
              if e["name"] == "engine.readback"]
    assert sum(lagged) == m["overlap_hits"]
    sched = [e for e in decode_capture.spans
             if e["name"] == "engine.schedule"]
    assert {e["kind"] for e in sched} <= {"decode", "none"}
    assert all(e["waiting"] == 0 and e["running"] <= 3 for e in sched)
    post = [e for e in decode_capture.spans
            if e["name"] == "engine.postprocess"]
    assert sum(e["tokens"] for e in post) == m["generated_tokens"]
    assert sum(e["finished"] for e in post) == 3


@pytest.mark.parametrize("span, counter", [
    ("engine.schedule", "time_schedule_ms"),
    ("engine.stage", "time_stage_ms"),
    ("engine.readback", "time_decode_sync_ms"),
    ("engine.postprocess", "time_decode_host_ms"),
])
def test_counter_delta_is_the_spans_summed(decode_capture, span, counter):
    cap = decode_capture
    assert cap.delta[counter] > 0
    assert cap.delta[counter] == pytest.approx(cap.ms(span), abs=1.0)


def test_dispatch_counter_is_stage_plus_launch(decode_capture):
    cap = decode_capture
    assert cap.delta["time_decode_dispatch_ms"] == pytest.approx(
        cap.ms("engine.stage") + cap.ms("engine.launch"), abs=1.0)
    assert cap.delta["time_decode_dispatch_ms"] > cap.delta["time_stage_ms"]


def test_rollback_is_a_zero_length_span_with_its_reason(tmp_path):
    eng = make_engine(overlap_decode=True, decode_steps=1)
    run(eng)
    cap = Capture(tmp_path, eng.metrics)
    with cap:
        # an abort in the middle of a wave changes the batch under the
        # dispatch launched ahead (an admission no longer does: the
        # loop launches the batch that comes next, ISSUE 28)
        eng.add_request("a2", *WORK[0][1:])
        eng.add_request("b2", *WORK[1][1:])
        for _ in range(4):
            eng.step()
        assert eng.abort_request("b2")
        run(eng, [("c2", *WORK[2][1:])])
    rollbacks = [e for e in cap.spans if e["name"] == "engine.rollback"]
    assert cap.delta["overlap_rollbacks"] >= 1
    assert len(rollbacks) == cap.delta["overlap_rollbacks"]
    assert all(isinstance(e["why"], str) and e["why"] for e in rollbacks)
    assert all(e["ms"] < 1.0 for e in rollbacks)
    # a capture with prompts in it: prefill launches say so
    kinds = {e["kind"] for e in cap.spans if e["name"] == "engine.launch"}
    assert "prefill" in kinds or "mixed" in kinds


def test_launches_about_an_admission_are_marked_speculative(tmp_path):
    """More requests than slots, max_tokens ends: inside the capture
    every decode-carrying launch is made ahead of its batch while a
    request waits, the mixed steps that admit a successor among them
    (`speculative=1`), and their readbacks lag."""
    cfg = dict(overlap_decode=True, decode_steps=1, max_seqs=2,
               decode_buckets=(1, 2))
    work = [(f"s{i}", [3 + i, 5, 8, 13], SamplingParams(
        max_tokens=4 + (i % 3), ignore_eos=True)) for i in range(6)]
    eng = make_engine(**cfg)
    run(eng, work)  # warm every program
    for rid, prompt, s in work:
        eng.add_request(rid + "b", list(prompt), s)
    eng.step()  # the two prompts that find a slot
    with Capture(tmp_path, eng.metrics) as cap:
        eng.run_to_completion()
    launches = [e for e in cap.spans if e["name"] == "engine.launch"]
    mixed = [e for e in launches if e["kind"] == "mixed"]
    assert len(mixed) >= 3 and all(e["speculative"] == 1 for e in mixed)
    # not ahead: the first, and the one after the row that ends with
    # nobody left waiting for its slot
    assert [e["speculative"] for e in launches].count(0) <= 2
    assert cap.delta["overlap_rollbacks"] == 0
    assert cap.delta["overlap_hits"] >= (
        cap.delta["decode_dispatches"] + cap.delta["mixed_dispatches"] - 2)
    lagged = [e["lagged"] for e in cap.spans
              if e["name"] == "engine.readback"]
    assert sum(lagged) == cap.delta["overlap_hits"]


def test_first_call_of_a_program_is_a_compile_span_inside_its_launch(
        tmp_path):
    eng = make_engine(overlap_decode=False, decode_steps=1)
    cap = Capture(tmp_path, eng.metrics)
    with cap:
        run(eng, WORK[:1])
    compiles = [e for e in cap.spans if e["name"] == "engine.compile"]
    assert len(compiles) == cap.delta["compiles"] >= 2
    assert cap.delta["compile_ms"] == pytest.approx(
        cap.ms("engine.compile"), abs=1.0)
    launches = [e for e in cap.spans if e["name"] == "engine.launch"]
    for c in compiles:
        assert c["key"].startswith("('")
        assert any(l["start"] <= c["start"] and c["end"] <= l["end"]
                   for l in launches)


def test_tokens_are_identical_with_and_without_a_capture(tmp_path):
    """Names and spans change nothing that is computed; with no capture
    running the counters accumulate all the same."""
    plain = make_engine(overlap_decode=True, decode_steps=4)
    ref = run(plain)
    m = plain.metrics
    for counter in ("time_schedule_ms", "time_stage_ms",
                    "time_decode_dispatch_ms", "time_decode_sync_ms",
                    "time_decode_host_ms", "compile_ms"):
        assert getattr(m, counter) > 0, counter
    assert m.time_decode_dispatch_ms > m.time_stage_ms
    traced = make_engine(overlap_decode=True, decode_steps=4)
    cap = Capture(tmp_path, traced.metrics)
    with cap:
        got = run(traced)
    assert got == ref
    assert any(e["name"] == "engine.launch" for e in cap.spans)


def test_phase_helper_counts_with_no_capture_and_tolerates_no_metrics():
    m = EngineMetrics()
    with phase(m, "engine.stage", "time_stage_ms",
               "time_decode_dispatch_ms") as ph:
        time.sleep(0.002)
        ph.note(rows=4)
    assert m.time_stage_ms == m.time_decode_dispatch_ms >= 2.0
    with phase(None, "engine.wait"):
        pass
    with pytest.raises(ValueError):  # an error leaves through the phase
        with phase(m, "engine.emit", "time_emit_ms"):
            raise ValueError("x")
    assert m.time_emit_ms > 0


def test_queue_wait_is_counted_for_untraced_requests():
    eng = make_engine(overlap_decode=True, decode_steps=4)
    for rid, prompt, s in WORK:
        req = eng.add_request(rid, list(prompt), s)
        assert req.trace_id is None
    time.sleep(0.03)
    eng.run_to_completion()
    m = eng.metrics
    assert m.admissions == 3
    assert m.queue_wait_ms_total >= 3 * 30.0
    waits = [w for r in eng.flight.snapshot()
             for w in r.get("admit_wait_ms", ())]
    assert len(waits) == 3 and all(w >= 30.0 for w in waits)
    assert sum(waits) == pytest.approx(m.queue_wait_ms_total, abs=0.01)
    # absent, not empty, on the steps that admitted nobody
    assert sum("admit_wait_ms" in r for r in eng.flight.snapshot()) <= 3
    assert all(r.get("admit_wait_ms", [1]) for r in eng.flight.snapshot())


def test_flight_records_carry_the_loop_phase_deltas():
    eng = make_engine(overlap_decode=True, decode_steps=4)
    run(eng)
    recs = eng.flight.snapshot()
    m = eng.metrics
    assert sum(r.get("sched_ms", 0) for r in recs) == pytest.approx(
        m.time_schedule_ms, abs=0.5)
    assert sum(r.get("stage_ms", 0) for r in recs) == pytest.approx(
        m.time_stage_ms, abs=0.5)
    assert all(r.get("stage_ms", 0) <= r.get("disp_ms", 0) + 0.01
               for r in recs if r["kind"] == "decode")


def _pre(rid: str, n: int = 6) -> PreprocessedRequest:
    return PreprocessedRequest(
        request_id=rid, token_ids=[5, 17, 42], max_tokens=n,
        temperature=0.0, ignore_eos=True,
    )


def test_runner_loop_spans_intake_emit_and_wait(tmp_path):
    """The runner's part of the loop, around step(): intake before it,
    emit after it, wait when there is nothing to run."""
    eng = make_engine(overlap_decode=True, decode_steps=1)
    run(eng, WORK[:1])  # warm

    async def main():
        runner = AsyncEngineRunner(eng)
        runner.start()
        assert runner._thread.name == "engine"
        cap = Capture(tmp_path, eng.metrics)
        try:
            with cap:
                await asyncio.sleep(0.12)  # idle: the loop waits
                got = []
                async for item in runner.generate(Context(), _pre("r1")):
                    got += item["token_ids"]
                await asyncio.sleep(0.06)
        finally:
            runner.stop()
        return cap, got

    cap, got = asyncio.run(main())
    assert len(got) == 6
    names = [e["name"] for e in cap.spans]
    assert "engine.wait" in names
    assert cap.delta["time_intake_ms"] == pytest.approx(
        cap.ms("engine.intake"), abs=1.0)
    assert cap.delta["time_emit_ms"] == pytest.approx(
        cap.ms("engine.emit"), abs=1.0)
    assert cap.delta["time_emit_ms"] > 0 and cap.delta["time_intake_ms"] > 0
    assert sum(e["added"] for e in cap.spans
               if e["name"] == "engine.intake") == 1
    emits = [e for e in cap.spans if e["name"] == "engine.emit"]
    assert sum(e["posted"] for e in emits) >= 6
    # every step is followed by its emit and preceded by an intake, all
    # on the one thread
    top = [e for e in cap.spans if e["name"] in (
        "engine.intake", "engine.step", "engine.emit", "engine.wait")]
    assert len({e["line"] for e in top}) == 1
    for i, e in enumerate(top):
        if e["name"] == "engine.step":
            assert top[i - 1]["name"] == "engine.intake"
            assert top[i + 1]["name"] == "engine.emit"
    # the flight record of a later step carries the emit before it
    assert any(r.get("emit_ms") for r in eng.flight.snapshot())
    assert any(r.get("intake_ms") for r in eng.flight.snapshot())
