"""The dry clock and the dispatch timeline (ISSUE 38): at every boundary
of its loop phases the engine thread asks the output of its newest
launch whether it is ready (dynamo_tpu/telemetry/flight.py `DryClock`),
so the loop knows, with no profiler, when it let the device run dry, and
the flight record of a step carries one timeline entry per dispatch. No
case here asserts a wall time: the clock is driven by an injected time
source and an injected readiness source, or only counts and orders are
read."""

import dataclasses

import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import EngineMetrics, JaxEngine, phase
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.telemetry.flight import (
    BLOCKED_MS, PHASES, DryClock, FlightRecorder,
)


class Script:
    """A time source the test sets and a device the test finishes."""

    def __init__(self):
        self.t = 100.0
        self.done: set = set()
        self.asked = 0

    def now(self) -> float:
        return self.t

    def is_ready(self, out) -> bool:
        self.asked += 1
        return out in self.done

    def clock(self) -> tuple:
        m = EngineMetrics()
        return DryClock(m, now=self.now, is_ready=self.is_ready), m


def ph(clock, sc: Script, name: str, ms: float, seq=None) -> None:
    clock.enter(name)
    sc.t += ms / 1e3
    clock.exit(name, seq)


def launch(clock, sc, out, ms_before: float = 1.0, **args) -> int:
    """An `engine.launch` phase whose program call returns `ms_before`
    into it and which ends 0.5 ms later."""
    clock.enter("launch")
    sc.t += ms_before / 1e3
    seq = clock.launched(out, {"kind": "decode_multi", "rows": 8, **args})
    sc.t += 0.5e-3
    clock.exit("launch")
    return seq


def dry_fields(m) -> dict:
    return {p: round(getattr(m, f"dry_{p}_ms"), 6) for p in PHASES}


def test_a_scripted_loop_counts_dry_time_phase_by_phase():
    sc = Script()
    clock, m = sc.clock()
    # nothing to run: neither a wait nor an intake is dry time
    ph(clock, sc, "intake", 3.0)
    sc.t += 0.050  # `_idle_wait` passes metrics=None: no boundary at all
    ph(clock, sc, "intake", 2.0)
    assert m.dry_ms == 0 and m.dry_slack_ms == 0
    # work arrived: from `engine.schedule` on the device is dry
    ph(clock, sc, "schedule", 2.0)
    sc.t += 0.25e-3  # between two phases: in dry_ms alone
    ph(clock, sc, "stage", 4.0)
    a = launch(clock, sc, "A", ms_before=1.0)
    assert a == 0 and m.launches == 1 and m.dry_launches == 1
    assert dry_fields(m) == {**dict.fromkeys(PHASES, 0.0), "schedule": 2.0,
                             "stage": 4.0, "launch": 1.0}
    assert m.dry_ms == pytest.approx(7.25)
    # A runs: busy boundaries add nothing
    ph(clock, sc, "stage", 3.0)
    b = launch(clock, sc, "B", ms_before=1.0, speculative=1)
    assert m.dry_launches == 1 and m.launches == 2
    # reading A blocks 6 ms; B, the newest, is still busy
    ph(clock, sc, "readback", 6.0, seq=a)
    ph(clock, sc, "postprocess", 2.0)
    assert m.dry_ms == pytest.approx(7.25) and m.dry_slack_ms == 0
    # B finishes somewhere inside an 8 ms emit: the stretch is slack
    clock.enter("emit")
    sc.t += 3e-3
    sc.done.add("B")
    sc.t += 5e-3
    clock.exit("emit")
    assert m.dry_slack_ms == pytest.approx(8.0)
    assert m.dry_emit_ms == 0
    # ... and from there on every phase is dry until the next launch
    ph(clock, sc, "intake", 1.0)
    ph(clock, sc, "schedule", 2.0)
    ph(clock, sc, "stage", 4.0)
    c = launch(clock, sc, "C", ms_before=1.5)
    assert m.dry_launches == 2 and m.launches == 3
    assert dry_fields(m) == {
        "intake": 1.0, "schedule": 4.0, "stage": 8.0, "launch": 2.5,
        "readback": 0.0, "postprocess": 0.0, "emit": 0.0}
    assert m.dry_ms == pytest.approx(7.25 + 8.5)
    assert m.dry_ms == pytest.approx(
        sum(dry_fields(m).values()) + m.dry_wait_ms + 0.25)
    line = clock.take()
    assert [e["seq"] for e in line["disp"]] == [a, b, c]
    assert "dry_before_ms" not in line["disp"][1]
    assert line["disp"][1]["ahead"] == 1
    assert line["disp"][0]["dry_phase"] == "idle"
    assert line["disp"][0]["dry_before_ms"] == pytest.approx(7.25)
    assert line["disp"][2]["dry_before_ms"] == pytest.approx(8.5)
    assert line["disp"][2]["dry_phase"] == "emit"
    # each late launch carries its own share of `dry_slack_ms`
    assert line["disp"][0]["slack_ms"] == 0
    assert line["disp"][2]["slack_ms"] == pytest.approx(8.0)
    assert "slack_ms" not in line["disp"][1]
    assert clock.take() == {}


def test_the_wait_for_takers_counts_and_the_idle_wait_does_not():
    sc = Script()
    clock, m = sc.clock()
    ph(clock, sc, "schedule", 1.0)
    a = launch(clock, sc, "A")
    sc.done.add("A")
    ph(clock, sc, "readback", 0.1, seq=a)
    # engine.wait under `_await_takers` is a boundary pair like a phase
    ph(clock, sc, "wait", 4.0)
    assert m.dry_wait_ms == pytest.approx(4.0)
    before = m.dry_ms
    clock.park()  # the engine ran out of work
    ph(clock, sc, "intake", 5.0)
    sc.t += 1.0
    ph(clock, sc, "intake", 5.0)
    assert m.dry_ms == before
    # the next schedule unparks; the first launch after it is "idle"
    ph(clock, sc, "schedule", 1.0)
    launch(clock, sc, "B")
    entry = clock.take()["disp"][-1]
    assert entry["dry_phase"] == "idle"
    assert entry["dry_before_ms"] == pytest.approx(2.0)
    assert m.dry_launches == 2


def test_a_blocked_readback_of_the_newest_launch_leaves_no_slack():
    sc = Script()
    clock, m = sc.clock()
    ph(clock, sc, "schedule", 1.0)
    a = launch(clock, sc, "A")
    slack0 = m.dry_slack_ms
    asked = sc.asked
    # nothing launched ahead: the readback waits the dispatch out
    ph(clock, sc, "readback", 30.0, seq=a)
    assert m.dry_slack_ms == slack0 and m.dry_readback_ms == 0
    ph(clock, sc, "postprocess", 2.0)
    assert m.dry_postprocess_ms == pytest.approx(2.0)
    # its return said the queue is empty: nobody asked the array again
    assert sc.asked == asked + 1  # the enter of the readback alone
    b = launch(clock, sc, "B")
    line = clock.take()
    assert line["disp"][1]["dry_phase"] == "readback"
    ready = line["ready"][0]
    assert ready["seq"] == a and ready["blocked_ms"] == pytest.approx(30.0)
    # launched dry, read blocked: both ends known
    assert ready["dev_ms"] == pytest.approx(30.5)
    assert b == a + 1


def test_dev_ms_is_finish_less_start_and_absent_where_an_end_is_unknown():
    sc = Script()
    clock, m = sc.clock()
    ph(clock, sc, "schedule", 1.0)
    a = launch(clock, sc, "A")  # dry: starts at its own launch
    b = launch(clock, sc, "B", speculative=1)  # queued behind A
    ph(clock, sc, "readback", 10.0, seq=a)  # blocked: A's finish
    c = launch(clock, sc, "C", speculative=1)  # queued behind B
    ph(clock, sc, "readback", 20.0, seq=b)  # blocked: B ran finish A -> now
    sc.done.update("ABC")
    ph(clock, sc, "readback", BLOCKED_MS / 2, seq=c)  # landed long ago
    d = launch(clock, sc, "D")
    ph(clock, sc, "readback", 5.0, seq=d)  # dry launch, blocked
    ready = {r["seq"]: r for r in clock.take()["ready"]}
    t_launch = lambda n: 100.0 + 1e-3 + n * 1.5e-3 + 1e-3  # noqa: E731
    assert ready[a]["dev_ms"] == pytest.approx(
        (ready[a]["t_ready"] - t_launch(0)) * 1e3, abs=1e-3)
    assert ready[b]["dev_ms"] == pytest.approx(
        (ready[b]["t_ready"] - ready[a]["t_ready"]) * 1e3, abs=1e-3)
    assert ready[b]["dev_ms"] == pytest.approx(21.5, abs=1e-3)
    assert "dev_ms" not in ready[c]  # the host never waited for it
    assert ready[d]["dev_ms"] == pytest.approx(5.5, abs=1e-3)
    assert [ready[s]["kind"] for s in (a, b, c, d)] == ["decode_multi"] * 4
    # a dispatch queued behind one whose finish is unknown has no start
    e = launch(clock, sc, "E", speculative=1)
    f = launch(clock, sc, "F", speculative=1)
    ph(clock, sc, "readback", 9.0, seq=f)  # E was never read (rolled back)
    assert "dev_ms" not in clock.take()["ready"][0]
    assert m.launches == 6 and e == 4


def test_poll_is_a_boundary_inside_a_phase_and_free_once_dry():
    sc = Script()
    clock, m = sc.clock()
    ph(clock, sc, "schedule", 1.0)
    launch(clock, sc, "A")
    clock.enter("emit")
    sc.t += 2e-3
    clock.poll()  # busy
    sc.t += 1e-3
    sc.done.add("A")
    sc.t += 1e-3
    clock.poll()  # found ready: 2 ms of slack, not the whole emit
    asked = sc.asked
    sc.t += 4e-3
    clock.poll()
    clock.poll()
    clock.exit("emit")
    assert sc.asked == asked
    assert m.dry_slack_ms == pytest.approx(2.0)
    assert m.dry_emit_ms == pytest.approx(4.0)
    launch(clock, sc, "B")
    assert clock.take()["disp"][-1]["dry_phase"] == "emit"


def test_phase_drives_the_clock_the_metrics_carry_and_none_otherwise():
    m = EngineMetrics()
    assert m.dry_clock is None
    with phase(m, "engine.stage", "time_stage_ms"):
        pass  # no clock: the phase is what it was
    sc = Script()
    m.dry_clock = DryClock(m, now=sc.now, is_ready=sc.is_ready)
    assert "dry_clock" not in m.to_dict()
    with phase(m, "engine.schedule", "time_schedule_ms"):
        sc.t += 2e-3
    with phase(m, "engine.compile", kind="decode"):
        sc.t += 50e-3  # transparent: no boundary of its own
    with phase(m, "engine.launch", kind="mixed", rows=8, t=32, k=1,
               speculative=0, n_rows=5, b_pre=2, chunk_tokens=40) as p:
        sc.t += 1e-3
        seq = p.launched("A")
    assert seq == 0 and m.launches == 1 and m.dry_launches == 1
    assert m.dry_schedule_ms == pytest.approx(2.0)
    assert m.dry_ms == pytest.approx(53.0)
    with phase(m, "engine.readback", "time_decode_sync_ms", lagged=0,
               seq=seq):
        sc.t += 7e-3
    line = m.dry_clock.take()
    assert line["disp"] == [{
        "seq": 0, "kind": "mixed", "rows": 8, "n_rows": 5, "k": 1,
        "ahead": 0, "t_launch": pytest.approx(100.053), "t": 32, "b_pre": 2,
        "chunk_tokens": 40, "dry_before_ms": pytest.approx(53.0),
        "dry_phase": "idle", "slack_ms": 0.0}]
    assert line["ready"][0]["seq"] == 0
    assert line["ready"][0]["blocked_ms"] == pytest.approx(7.0)
    # a phase of an engine double that keeps no metrics stays silent
    with phase(None, "engine.wait") as p:
        assert p.launched("x") is None


# -- a tiny engine on the CPU --------------------------------------------


def make_engine(**overrides) -> JaxEngine:
    base = EngineConfig.for_tests()
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


WORK = [
    ("a", [5, 17, 42, 9], SamplingParams(max_tokens=19, ignore_eos=True)),
    ("b", [7, 3, 11], SamplingParams(max_tokens=11, ignore_eos=True)),
    ("c", list(range(1, 21)), SamplingParams(
        max_tokens=10, ignore_eos=True, temperature=0.8, top_p=0.9, seed=5)),
    ("d", [9, 9, 8, 2, 6], SamplingParams(max_tokens=23, ignore_eos=True)),
]
LATE = [
    ("e", list(range(3, 21)), SamplingParams(max_tokens=9, ignore_eos=True)),
    ("f", [4, 4, 4], SamplingParams(
        max_tokens=12, ignore_eos=True, temperature=0.7, seed=11)),
]


class Calls:
    """Every program call of a step kind, with the key it was built
    for: `_get_step_fn` hands out counting wrappers."""

    def __init__(self, eng):
        self.calls: list = []
        inner = eng._get_step_fn

        def get(kind, b, x, **kw):
            fn = inner(kind, b, x, **kw)

            def counted(*args, **kwargs):
                self.calls.append((kind, b, x, kw))
                return fn(*args, **kwargs)

            return counted

        eng._get_step_fn = get


def drive(eng, work=WORK, late=LATE) -> dict:
    """A few admissions: `late` arrives while the first wave decodes."""
    done: dict = {}
    for rid, prompt, s in work:
        eng.add_request(rid, list(prompt), s)
    steps = 0
    late = list(late)
    while eng.has_work:
        for out in eng.step():
            done.setdefault(out.request_id, []).extend(out.new_token_ids)
        steps += 1
        if steps in (3, 5) and late:
            rid, prompt, s = late.pop(0)
            eng.add_request(rid, list(prompt), s)
    return done


@pytest.fixture(scope="module")
def driven():
    eng = make_engine(overlap_decode=True, decode_steps=4, prefill_chunk=16)
    calls = Calls(eng)
    done = drive(eng)
    return eng, calls.calls, done, eng.flight.snapshot()


def test_launches_counts_the_programs_called(driven):
    eng, calls, _done, recs = driven
    m = eng.metrics
    assert m.launches == len(calls) > 10
    assert sum(r.get("launches", 0) for r in recs) == m.launches
    assert sum(len(r.get("disp", ())) for r in recs) == m.launches
    assert sum(r.get("dry_launches", 0) for r in recs) == m.dry_launches
    assert 1 <= m.dry_launches <= m.launches
    # pure prefill programs are in a counter now
    assert any(k.startswith("prefill") for k, *_ in calls)
    assert len({k for k, *_ in calls}) >= 3


def test_seq_runs_without_holes_and_a_read_follows_its_launch(driven):
    _eng, _calls, _done, recs = driven
    disp = [e for r in recs for e in r.get("disp", ())]
    assert [e["seq"] for e in disp] == list(range(len(disp)))
    assert all(a["t_launch"] <= b["t_launch"] for a, b in zip(disp, disp[1:]))
    launched = {}
    reads = 0
    for r in recs:  # in step order: a read never precedes its launch
        for e in r.get("disp", ()):
            launched[e["seq"]] = e
        for e in r.get("ready", ()):
            reads += 1
            assert e["seq"] in launched
            assert launched[e["seq"]]["t_launch"] <= e["t_ready"]
            assert e["kind"] == launched[e["seq"]]["kind"]
            assert e["blocked_ms"] >= 0
            if "dev_ms" in e:
                assert e["blocked_ms"] > BLOCKED_MS and e["dev_ms"] > 0
    seqs = [e["seq"] for r in recs for e in r.get("ready", ())]
    # each dispatch is read once (a prefill beside the dispatch launched
    # ahead is read before it, so reads are not in launch order)
    assert len(set(seqs)) == len(seqs) == reads > 5


def test_an_entry_holds_the_args_its_launch_was_made_with(driven):
    eng, calls, _done, recs = driven
    disp = [e for r in recs for e in r.get("disp", ())]
    assert len(disp) == len(calls)
    kinds = set()
    for e, (kind, b, x, kw) in zip(disp, calls):
        kinds.add(e["kind"])
        assert e["kind"] == kind.removesuffix("_nosample")
        assert e["rows"] == b and 1 <= e["n_rows"] <= e["rows"]
        if kind in ("decode", "decode_multi"):
            assert e["k"] == x and "t" not in e and "chunk_tokens" not in e
        else:
            assert e["t"] == x and e["k"] == 1
            assert e["b_pre"] == kw.get("b_pre", b)
            assert 1 <= e["chunk_tokens"] <= e["b_pre"] * e["t"]
        assert e["ahead"] in (0, 1)
        assert ("dry_before_ms" in e) == ("dry_phase" in e)
        if "dry_phase" in e:
            assert e["dry_phase"] in (*PHASES, "wait", "idle", "none")
            assert e["dry_before_ms"] >= 0
    assert {"prefill", "mixed", "decode_multi"} <= kinds
    assert any(e["ahead"] for e in disp)
    # every real prompt token went through exactly one chunk
    prompt_tokens = sum(len(p) for _r, p, _s in (*WORK, *LATE))
    assert sum(e.get("chunk_tokens", 0) for e in disp) == prompt_tokens
    assert eng.metrics.launches == len(disp)


def test_the_counters_close_over_the_timeline(driven):
    eng, _calls, _done, recs = driven
    m = eng.metrics
    disp = [e for r in recs for e in r.get("disp", ())]
    assert sum("dry_before_ms" in e for e in disp) == m.dry_launches
    per_phase = sum(getattr(m, f"dry_{p}_ms") for p in PHASES)
    # dry_ms is all of it: the eight and the glue between phases
    assert m.dry_ms >= per_phase + m.dry_wait_ms - 1e-6
    # what the dispatches account for never exceeds the counter (the tail
    # of a busy period belongs to no dispatch)
    assert sum(e.get("dry_before_ms", 0) for e in disp) <= m.dry_ms + 0.01
    assert sum(r.get("dry_ms", 0) for r in recs) == pytest.approx(
        m.dry_ms, abs=0.5)
    assert m.dry_slack_ms >= 0


class FakeDevice:
    """Fake time that only `_dev_tree` advances, and a device that has
    finished a dispatch as soon as any time has passed since it was
    first asked about it."""

    def __init__(self):
        self.t = 50.0
        self.first_asked: dict = {}

    def now(self) -> float:
        return self.t

    def is_ready(self, out) -> bool:
        return self.t > self.first_asked.setdefault(id(out), self.t)


def run_with_fake_device(sleep_ms: float) -> tuple:
    eng = make_engine(overlap_decode=True, decode_steps=4, prefill_chunk=16)
    drive(eng)  # warm: no first call inside the run that is read
    dev = FakeDevice()
    clock = DryClock(eng.metrics, now=dev.now, is_ready=dev.is_ready)
    eng.metrics.dry_clock = clock
    m0 = eng.metrics.to_dict()
    naps = []
    if sleep_ms:
        inner = eng._dev_tree

        def slow(tree):
            # a sleep in two naps with the clock asked in between, as
            # the engine asks around the transfer
            naps.append(1)
            dev.t += 1e-3
            clock.poll()
            dev.t += sleep_ms / 1e3
            return inner(tree)

        eng._dev_tree = slow
    seq0 = eng.flight.snapshot()[-1]["seq"]
    done = drive(eng)
    m1 = eng.metrics.to_dict()
    delta = {k: m1[k] - m0[k] for k in m1
             if k.startswith("dry_") or k == "launches"}
    delta["naps"] = len(naps)
    recs = [r for r in eng.flight.snapshot() if r["seq"] > seq0]
    return delta, recs, done


def test_a_slow_dev_tree_dries_the_device_under_stage_and_nowhere_else():
    still, _recs, done0 = run_with_fake_device(0)
    slow, recs, done1 = run_with_fake_device(40.0)
    assert done0 == done1
    assert slow["launches"] == still["launches"]
    # with time standing still the device never finishes on its own:
    # only the reads of a newest launch empty the queue
    assert still["dry_ms"] == 0 and still["dry_slack_ms"] == 0
    assert slow["dry_launches"] > still["dry_launches"]
    # every nap was dry time under `engine.stage`, and nothing else was
    others = {k: v for k, v in slow.items()
              if k.endswith("_ms") and k not in (
                  "dry_ms", "dry_stage_ms", "dry_slack_ms")}
    assert set(others.values()) == {0}
    assert slow["dry_stage_ms"] == pytest.approx(slow["dry_ms"])
    # (the 1 ms before the clock is asked is slack where the device was
    # busy, and dry stage time too where it was dry already)
    n = slow["naps"]
    assert n >= slow["dry_launches"] - still["dry_launches"] > 0
    assert slow["dry_stage_ms"] + slow["dry_slack_ms"] == pytest.approx(
        41.0 * n)
    assert 40.0 * n - 1e-6 <= slow["dry_stage_ms"] <= 41.0 * n + 1e-6
    disp = [e for r in recs for e in r.get("disp", ())]
    staged = [e for e in disp if e.get("dry_phase") == "stage"]
    assert staged and all(
        e["dry_before_ms"] == pytest.approx(40.0) for e in staged)


def test_without_the_recorder_there_is_no_clock_and_the_same_tokens():
    outs = {}
    for on in (True, False):
        eng = make_engine(overlap_decode=True, decode_steps=4,
                          prefill_chunk=16, flight_recorder=on)
        outs[on] = drive(eng)
        m = eng.metrics
        if on:
            assert isinstance(m.dry_clock, DryClock)
            assert m.launches > 0
        else:
            assert m.dry_clock is None and eng.flight is None
            assert m.launches == 0 and m.dry_ms == 0 and m.dry_launches == 0
            assert not any(k == "dry_clock" for k in m.to_dict())
    assert outs[True] == outs[False]


def test_a_record_carries_the_timeline_it_is_given():
    fl = FlightRecorder()
    m = EngineMetrics()
    rec = fl.record_step(m, kind="decode", step_ms=1.0, timeline={
        "disp": [{"seq": 0, "kind": "decode"}]})
    assert rec["disp"] == [{"seq": 0, "kind": "decode"}]
    assert "ready" not in rec
    assert "disp" not in fl.record_step(
        m, kind="decode", step_ms=1.0, timeline={})
    assert dataclasses.is_dataclass(m) and "dry_ms" in m.to_dict()
