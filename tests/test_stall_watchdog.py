"""Stall watchdog (ISSUE 7 tentpole + satellite 3).

- unit: cause judgement (queue_wait / stalled_stream / engine_stuck)
  with an injected clock, threshold from the ITL estimate, hard-deadline
  wedge action, counters.
- e2e: a deliberately WEDGED engine under live streamed traffic yields
  a structured diagnosis within the deadline — flight window present,
  the stalled request's trace/span ids present, all-thread stacks
  present (the engine thread's stack shows where it sits) — and
  `dynamo_tpu_stalls_total{cause}` increments.
- hard-deadline e2e: with `stall_hard_deadline_s` set the client stream
  ERROR-FINISHES instead of hanging forever.
"""

import asyncio
import dataclasses
import re
import threading
import time

import pytest

from dynamo_tpu import telemetry
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.async_engine import AsyncEngineRunner
from dynamo_tpu.engine.engine import EngineMetrics
from dynamo_tpu.engine.request import FinishReason, StepOutput
from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.telemetry.flight import FlightRecorder
from dynamo_tpu.telemetry.watchdog import (
    StallCounters,
    StallWatchdog,
    stall_counters,
    thread_stacks,
)


# -- unit: judgement with an injected clock --------------------------------


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _wd(clock, **kw):
    kw.setdefault("counters", StallCounters())
    return StallWatchdog(clock=clock, **kw)


def test_no_stall_before_threshold():
    clock = _Clock()
    wd = _wd(clock, stall_min_s=5.0)
    wd.track("r1")
    wd.progress("r1")
    clock.t += 4.0
    assert wd.check() == []


def test_stalled_stream_diagnosed_once_and_rearms_on_progress():
    clock = _Clock()
    wd = _wd(clock, stall_min_s=5.0)
    wd.track("r1", {"trace_id": "a" * 32, "span_id": "b" * 16})
    wd.progress("r1")
    clock.t += 6.0
    diags = wd.check()
    assert len(diags) == 1
    d = diags[0]
    assert d["cause"] == "stalled_stream"
    assert d["request_id"] == "r1"
    assert d["trace"]["span_id"] == "b" * 16
    assert d["stalled_s"] == pytest.approx(6.0)
    assert wd.counters.snapshot() == {"stalled_stream": 1}
    # same stall: no duplicate diagnosis
    clock.t += 1.0
    assert wd.check() == []
    # progress re-arms
    wd.progress("r1")
    clock.t += 6.0
    assert len(wd.check()) == 1
    assert wd.counters.snapshot() == {"stalled_stream": 2}


def test_queue_wait_cause_for_requests_with_no_first_token():
    clock = _Clock()
    wd = _wd(clock, stall_min_s=1.0, queue_wait_budget_s=30.0)
    wd.track("r1")
    clock.t += 29.0
    assert wd.check() == []  # within budget: first tokens can take long
    clock.t += 2.0
    diags = wd.check()
    assert [d["cause"] for d in diags] == ["queue_wait"]


def test_engine_stuck_cause_when_dispatch_never_returns():
    clock = _Clock()
    wd = _wd(clock, stall_min_s=2.0)
    wd.track("r1")
    wd.progress("r1")
    wd.step_begin()
    clock.t += 3.0
    diags = wd.check()
    assert [d["cause"] for d in diags] == ["engine_stuck"]
    # a returning dispatch clears the engine-stuck signal
    wd.step_end()
    wd.progress("r1")
    clock.t += 3.0
    assert [d["cause"] for d in wd.check()] == ["stalled_stream"]


def test_threshold_scales_with_itl_estimate():
    clock = _Clock()
    wd = _wd(
        clock, stall_min_s=1.0, stall_factor=10.0,
        itl_estimate_ms=lambda: 500.0,  # p95 ITL 500ms -> threshold 5s
    )
    wd.track("r1")
    wd.progress("r1")
    assert wd.stall_threshold_s() == pytest.approx(5.0)
    clock.t += 4.0
    assert wd.check() == []
    clock.t += 2.0
    assert len(wd.check()) == 1
    # a broken estimator degrades to the floor, never raises
    wd._itl_estimate_ms = lambda: (_ for _ in ()).throw(RuntimeError())
    assert wd.stall_threshold_s() == 1.0


def test_hard_deadline_fires_wedge_action_once():
    clock = _Clock()
    wedged = []
    wd = _wd(
        clock, stall_min_s=1.0, hard_deadline_s=10.0,
        on_wedged=lambda rid, info: wedged.append((rid, info)),
    )
    wd.track("r1")
    wd.progress("r1")
    clock.t += 2.0
    wd.check()  # diagnose-only below the deadline
    assert wedged == []
    clock.t += 9.0
    wd.check()
    assert len(wedged) == 1 and wedged[0][0] == "r1"
    clock.t += 5.0
    wd.check()  # never re-fires for the same request
    assert len(wedged) == 1


def test_hard_deadline_honored_before_first_emission():
    """A deadline BELOW the queue-wait budget must still error-finish a
    request that never got a first token — the client was promised no
    hang past the deadline, whatever the cause heuristics say."""
    clock = _Clock()
    wedged = []
    wd = _wd(
        clock, stall_min_s=1.0, queue_wait_budget_s=120.0,
        hard_deadline_s=10.0,
        on_wedged=lambda rid, info: wedged.append((rid, info)),
    )
    wd.track("r1")  # no progress() — first token never arrives
    clock.t += 11.0
    diags = wd.check()
    assert len(wedged) == 1 and wedged[0][0] == "r1"
    assert wedged[0][1]["cause"] == "queue_wait"
    # the wedge also produces a diagnosis (it would otherwise be silent
    # until the 120s queue budget)
    assert [d["cause"] for d in diags] == ["queue_wait"]


def test_one_wedged_pass_shares_evidence_across_streams():
    """N streams caught in one checker pass share ONE stack dump and
    ONE flight snapshot (the evidence is identical; formatting it N
    times in a tick is the overload failure mode)."""
    clock = _Clock()
    fl = FlightRecorder(8)
    fl.record_step(EngineMetrics(), kind="decode", step_ms=1.0)
    wd = _wd(clock, stall_min_s=1.0, flight=fl)
    for i in range(5):
        wd.track(f"r{i}")
        wd.progress(f"r{i}")
    clock.t += 2.0
    diags = wd.check()
    assert len(diags) == 5
    assert all(d["stacks"] is diags[0]["stacks"] for d in diags)
    assert all(d["flight"] is diags[0]["flight"] for d in diags)


def test_diagnosis_carries_flight_window_and_stacks():
    clock = _Clock()
    fl = FlightRecorder(8)
    m = EngineMetrics()
    for _ in range(3):
        fl.record_step(m, kind="decode", step_ms=1.0, n_decode=2)
    wd = _wd(clock, stall_min_s=1.0, flight=fl)
    wd.track("r1")
    wd.progress("r1")
    clock.t += 2.0
    d = wd.check()[0]
    assert len(d["flight"]) == 3
    assert d["stacks"], "all-thread stacks must be present"
    me = [s for s in d["stacks"].values() if "test_stall_watchdog" in s]
    assert me, "the calling thread's stack should name this test file"


def test_thread_stacks_names_threads():
    ev = threading.Event()
    t = threading.Thread(
        target=lambda: ev.wait(5), name="wedge-probe", daemon=True
    )
    t.start()
    try:
        stacks = thread_stacks()
        key = next(k for k in stacks if k.startswith("wedge-probe"))
        assert "ev.wait" in stacks[key] or "wait" in stacks[key]
    finally:
        ev.set()
        t.join()


# -- e2e: wedged engine under live traffic ---------------------------------


class WedgeEngine:
    """AsyncEngineRunner-compatible fake: emits one token per request
    per step, then WEDGES — step() blocks on an event, exactly like a
    dispatch stuck in a dead device runtime. `release` unwedges it so
    the runner thread can exit at teardown."""

    def __init__(self, config, wedge_after_steps: int = 1):
        self.config = config
        self.metrics = EngineMetrics()
        self.flight = FlightRecorder(64)
        self._reqs: dict[str, int] = {}
        self._steps = 0
        self._wedge_after = wedge_after_steps
        self.release = threading.Event()
        self.wedged = threading.Event()

    def add_request(self, request_id, token_ids, sampling, mm_embeds=None,
                    mm_positions=()):
        self._reqs[request_id] = 0

    def abort_request(self, request_id):
        return self._reqs.pop(request_id, None) is not None

    @property
    def has_work(self):
        return bool(self._reqs)

    def step(self):
        if self._steps >= self._wedge_after:
            self.wedged.set()
            self.release.wait()  # <- the wedge: dispatch never returns
            return []
        self._steps += 1
        outs = []
        for rid in list(self._reqs):
            self._reqs[rid] += 1
            self.metrics.generated_tokens += 1
            outs.append(
                StepOutput(request_id=rid, new_token_ids=(7,),
                           finish_reason=None)
            )
        self.metrics.steps += 1
        self.flight.record_step(
            self.metrics, kind="decode", step_ms=1.0,
            n_decode=len(self._reqs), b_decode=len(self._reqs),
            running=len(self._reqs),
        )
        return outs


def _pre(rid: str) -> PreprocessedRequest:
    return PreprocessedRequest(
        request_id=rid, token_ids=[1, 2, 3], max_tokens=8,
        temperature=0.0, ignore_eos=True,
    )


def _wedge_cfg(**kw) -> EngineConfig:
    return dataclasses.replace(
        EngineConfig.for_tests(),
        stall_min_s=0.3, stall_queue_wait_s=5.0, **kw,
    )


def test_wedged_engine_yields_structured_diagnosis_under_live_traffic():
    """Satellite 3 (first half): wedged engine + live streams ->
    diagnosis within the deadline, with flight window, the stalled
    request's span id, thread stacks, and the stalls counter bumped."""

    async def main():
        telemetry.configure(enabled=True, ring_size=16)
        base_total = stall_counters.total
        eng = WedgeEngine(_wedge_cfg())
        runner = AsyncEngineRunner(eng)
        runner.start()
        assert runner.watchdog is not None
        runner.watchdog.interval_s = 0.05
        # restart the checker at the fast interval
        runner.watchdog.stop()
        runner.watchdog.start()

        async def client(i):
            got = []
            async for item in runner.generate(Context(), _pre(f"wedge-{i}")):
                got.append(item)
            return got

        tasks = [asyncio.create_task(client(i)) for i in range(2)]
        try:
            deadline = time.monotonic() + 10.0
            while (
                not runner.watchdog.diagnoses
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.05)
            diags = runner.watchdog.diagnoses
            assert diags, "watchdog never diagnosed the wedged engine"
            d = diags[0]
            # each stream got its first token, then the engine wedged
            # mid-dispatch: the diagnosis must say the ENGINE is stuck
            assert d["cause"] == "engine_stuck"
            assert d["request_id"].startswith("wedge-")
            # span ids of the wedged request's engine.generate span
            assert re.fullmatch(r"[0-9a-f]{32}", d["trace"]["trace_id"])
            assert re.fullmatch(r"[0-9a-f]{16}", d["trace"]["span_id"])
            # the flight window around the stall (the steps that DID run)
            assert d["flight"], "flight window must ride the diagnosis"
            assert d["flight"][-1]["kind"] == "decode"
            # all-thread stacks, with the engine thread inside the wedge
            eng_stacks = [
                s for name, s in d["stacks"].items()
                if name.startswith("engine")
            ]
            assert eng_stacks and "release.wait" in eng_stacks[0]
            # the process-global counter (both Prometheus surfaces) bumped
            assert stall_counters.total > base_total
            assert "engine_stuck" in stall_counters.snapshot()
            # diagnose-only default: the streams are NOT error-finished
            assert all(not t.done() for t in tasks)
        finally:
            eng.release.set()
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            runner.stop()
            telemetry.configure(enabled=False)

    asyncio.run(main())


def test_hard_deadline_error_finishes_the_stream_instead_of_hanging():
    """Satellite 3 (second half): with a hard deadline set, the client
    stream gets an error frame and ends — no hung client."""

    async def main():
        eng = WedgeEngine(_wedge_cfg(stall_hard_deadline_s=0.8))
        runner = AsyncEngineRunner(eng)
        runner.start()
        runner.watchdog.interval_s = 0.05
        runner.watchdog.stop()
        runner.watchdog.start()

        async def client():
            got = []
            async for item in runner.generate(Context(), _pre("hard-0")):
                got.append(item)
            return got

        try:
            with pytest.raises(RuntimeError, match="hard deadline"):
                # generous outer timeout: the POINT is that the stream
                # errors out long before it
                await asyncio.wait_for(client(), timeout=15.0)
            assert eng.wedged.is_set()
        finally:
            eng.release.set()
            runner.stop()

    asyncio.run(main())


def test_watchdog_absent_when_disabled():
    async def main():
        eng = WedgeEngine(
            dataclasses.replace(
                EngineConfig.for_tests(), stall_watchdog=False
            )
        )
        runner = AsyncEngineRunner(eng)
        runner.start()
        try:
            assert runner.watchdog is None
        finally:
            eng.release.set()
            runner.stop()

    asyncio.run(main())
