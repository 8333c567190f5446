"""Stall watchdog: per-request progress monitoring + structured
diagnosis of wedged streams.

The SLO plane (telemetry/slo.py) says a worker's ITL p95 regressed; the
trace ring says where one request went. Neither fires when a stream
simply STOPS — a hung device dispatch, a deadlocked engine thread, an
admission that never happens — the client just hangs. The watchdog
closes that gap:

- every streamed request is `track()`ed when its output queue opens and
  `progress()`ed on each emission (engine-thread side, a dict write);
- the engine loop brackets each dispatch with `step_begin()/step_end()`
  so a dispatch that never returns is distinguishable from an idle
  engine;
- a checker (asyncio task on the worker's event loop — deliberately NOT
  the engine thread, which is the thing being watched) compares each
  request's last-progress age against N× the SLO plane's live ITL
  estimate (clamped to a floor), and emits a structured diagnosis when
  it trips: the cause, the flight-recorder window around the stall, the
  request's trace/span ids (PR 4), and all-thread Python stacks via
  `sys._current_frames` (the dependency-free sibling of
  `faulthandler.dump_traceback`).

Diagnoses go to the JSONL log plane (logging_config.JsonlFormatter
merges the `stall` extra into the record) and bump the process-global
`dynamo_tpu_stalls_total{cause}` counter exposed on both Prometheus
surfaces. Default is diagnose-only: the stream is left alone (the stall
may be a 40 s XLA compile). With a hard deadline configured
(`EngineConfig.stall_hard_deadline_s` / `--stall-hard-deadline`), a
request stalled past the deadline is error-finished through its output
queue — the client gets an error frame instead of hanging forever —
and aborted from the scheduler.

Causes (machine-readable, the `{cause}` label):
  queue_wait      no first emission within the queue-wait budget
  stalled_stream  emissions started, then stopped for > threshold
  engine_stuck    a dispatch entered the engine and never returned
                  (attributed to every tracked request; the engine
                  thread's stack in the diagnosis says where it sits)
"""

from __future__ import annotations

import logging
import sys
import threading
import time
import traceback
from typing import Callable, Optional

logger = logging.getLogger(__name__)

#: flight records included in a diagnosis window
DIAGNOSIS_FLIGHT_RECORDS = 32

#: cap on formatted stack depth per thread (diagnoses ride the JSONL
#: log plane; an unbounded recursion must not produce a 1 MB record)
_MAX_STACK_FRAMES = 40


class StallCounters:
    """Process-global `dynamo_tpu_stalls_total{cause}` counters —
    the phases-histogram pattern: module-level, appended to every
    Prometheus surface the process serves."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._by_cause: dict[str, int] = {}

    def bump(self, cause: str) -> None:
        with self._lock:
            self._by_cause[cause] = self._by_cause.get(cause, 0) + 1

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self._by_cause)

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self._by_cause.values())

    def reset(self) -> None:
        with self._lock:
            self._by_cause.clear()

    def expose_lines(self) -> list[str]:
        snap = self.snapshot()
        if not snap:
            return []
        name = "dynamo_tpu_stalls_total"
        lines = [f"# TYPE {name} counter"]
        for cause, n in sorted(snap.items()):
            lines.append(f'{name}{{cause="{cause}"}} {n}')
        return lines


stall_counters = StallCounters()


def thread_stacks(max_frames: int = _MAX_STACK_FRAMES) -> dict[str, str]:
    """All-thread Python stacks, keyed `"<name>-<ident>"`. The engine
    thread's entry is the "where is it stuck" evidence when a dispatch
    wedges inside jax/XLA/the device runtime."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: dict[str, str] = {}
    for tid, frame in sys._current_frames().items():
        stack = traceback.format_stack(frame)[-max_frames:]
        out[f"{names.get(tid, 'thread')}-{tid}"] = "".join(stack)
    return out


class _Tracked:
    __slots__ = ("request_id", "trace", "first_seen", "last_progress",
                 "emissions", "diagnosed", "wedged")

    def __init__(self, request_id: str, trace: Optional[dict], now: float):
        self.request_id = request_id
        self.trace = trace
        self.first_seen = now
        self.last_progress: Optional[float] = None  # None until 1st token
        self.emissions = 0
        self.diagnosed = False
        self.wedged = False


class StallWatchdog:
    """One per engine runner. Thread-safe on the ingest side (track/
    progress/done/step_begin/step_end are dict writes under a lock);
    `check()` is pure-ish (reads state, emits diagnoses) so tests can
    drive it with an injected clock without the asyncio wrapper."""

    CAUSES = ("queue_wait", "stalled_stream", "engine_stuck")

    def __init__(
        self,
        itl_estimate_ms: Optional[Callable[[], Optional[float]]] = None,
        flight=None,
        stall_factor: float = 32.0,
        stall_min_s: float = 5.0,
        queue_wait_budget_s: float = 120.0,
        hard_deadline_s: Optional[float] = None,
        on_wedged: Optional[Callable[[str, dict], None]] = None,
        interval_s: float = 1.0,
        clock=time.monotonic,
        counters: Optional[StallCounters] = None,
    ):
        #: live ITL estimate (ms) from the SLO plane; None = no traffic
        #: yet, fall back to the floor
        self._itl_estimate_ms = itl_estimate_ms
        self.flight = flight
        self.stall_factor = stall_factor
        self.stall_min_s = stall_min_s
        self.queue_wait_budget_s = queue_wait_budget_s
        self.hard_deadline_s = hard_deadline_s
        self.on_wedged = on_wedged
        self.interval_s = interval_s
        self._clock = clock
        #: per-watchdog counters (each worker's metrics frame reports its
        #: own); the process-global `stall_counters` is bumped alongside
        #: for the Prometheus surfaces
        self.counters = counters if counters is not None else StallCounters()
        self._lock = threading.Lock()
        self._tracked: dict[str, _Tracked] = {}
        #: engine-dispatch liveness: perf time the current step entered
        #: the engine, or None when no dispatch is in flight
        self._step_started: Optional[float] = None
        self._task = None
        #: diagnoses emitted since boot (bounded; /v1/debug consumers +
        #: tests read it)
        self.diagnoses: list[dict] = []
        self._max_diagnoses = 64

    # -- ingest (any thread) ----------------------------------------------

    def track(self, request_id: str, trace: Optional[dict] = None) -> None:
        with self._lock:
            self._tracked[request_id] = _Tracked(
                request_id, trace, self._clock()
            )

    def progress(self, request_id: str) -> None:
        with self._lock:
            t = self._tracked.get(request_id)
            if t is not None:
                t.last_progress = self._clock()
                t.emissions += 1
                t.diagnosed = False  # recovered: re-arm

    def done(self, request_id: str) -> None:
        with self._lock:
            self._tracked.pop(request_id, None)

    def step_begin(self) -> None:
        with self._lock:
            self._step_started = self._clock()

    def step_end(self) -> None:
        with self._lock:
            self._step_started = None

    # -- judgement ---------------------------------------------------------

    def stall_threshold_s(self) -> float:
        """N× the SLO plane's live ITL estimate, floored at stall_min_s
        (cold engines / first compiles legitimately take seconds)."""
        est = None
        if self._itl_estimate_ms is not None:
            try:
                est = self._itl_estimate_ms()
            except Exception:
                est = None
        if est is None or est <= 0:
            return self.stall_min_s
        return max(self.stall_min_s, self.stall_factor * est / 1000.0)

    def check(self, now: Optional[float] = None) -> list[dict]:
        """One watchdog pass: returns the NEW diagnoses (already logged
        and counted). Hard-deadline wedge actions fire from here too."""
        now = self._clock() if now is None else now
        threshold = self.stall_threshold_s()
        with self._lock:
            step_started = self._step_started
            tracked = list(self._tracked.values())
        engine_stuck = (
            step_started is not None
            and now - step_started > max(threshold, self.stall_min_s)
        )
        out: list[dict] = []
        #: (flight window, stacks) captured ONCE per pass — a wedged
        #: dispatch with N concurrent streams must not format N stack
        #: dumps and N ring snapshots in one checker tick
        evidence: Optional[tuple] = None
        for t in tracked:
            if t.wedged:
                continue
            if t.last_progress is None:
                stalled_s = now - t.first_seen
                if engine_stuck and stalled_s > threshold:
                    cause: Optional[str] = "engine_stuck"
                elif stalled_s > self.queue_wait_budget_s:
                    cause = "queue_wait"
                else:
                    cause = None
            else:
                stalled_s = now - t.last_progress
                if stalled_s <= threshold:
                    cause = None
                else:
                    cause = "engine_stuck" if engine_stuck else "stalled_stream"
            wedge = (
                self.hard_deadline_s is not None
                and stalled_s > self.hard_deadline_s
            )
            if cause is None:
                if not wedge:
                    continue
                # the hard deadline outranks the cause heuristics: a
                # client past it must not keep hanging just because no
                # cause tripped yet (e.g. no first emission with the
                # queue-wait budget above the deadline)
                cause = (
                    "queue_wait" if t.last_progress is None
                    else "stalled_stream"
                )
            if not t.diagnosed:
                t.diagnosed = True
                if evidence is None:
                    evidence = (
                        self.flight.snapshot(DIAGNOSIS_FLIGHT_RECORDS)
                        if self.flight is not None
                        else [],
                        thread_stacks(),
                    )
                out.append(
                    self._diagnose(t, cause, stalled_s, threshold, evidence)
                )
            if wedge:
                t.wedged = True
                self._wedge(t, cause, stalled_s)
        return out

    def _diagnose(
        self, t: _Tracked, cause: str, stalled_s: float,
        threshold_s: float, evidence: tuple,
    ) -> dict:
        flight_window, stacks = evidence
        diag = {
            "request_id": t.request_id,
            "cause": cause,
            "stalled_s": round(stalled_s, 3),
            "threshold_s": round(threshold_s, 3),
            "emissions": t.emissions,
            "trace": t.trace or {},
            "flight": flight_window,
            "stacks": stacks,
        }
        self.counters.bump(cause)
        if self.counters is not stall_counters:
            stall_counters.bump(cause)
        self.diagnoses.append(diag)
        del self.diagnoses[: -self._max_diagnoses]
        # the JSONL log plane is the durable sink: JsonlFormatter merges
        # the extra into the record (and injects trace ids when absent)
        logger.error(
            "stall watchdog: request %s %s for %.1fs (threshold %.1fs)",
            t.request_id, cause, stalled_s, threshold_s,
            extra={"stall": diag},
        )
        return diag

    def _wedge(self, t: _Tracked, cause: str, stalled_s: float) -> None:
        logger.error(
            "stall watchdog: hard deadline (%.1fs) exceeded for %s (%s); "
            "error-finishing the stream",
            self.hard_deadline_s, t.request_id, cause,
        )
        if self.on_wedged is not None:
            try:
                self.on_wedged(
                    t.request_id,
                    {"cause": cause, "stalled_s": round(stalled_s, 3)},
                )
            except Exception:
                logger.exception("stall watchdog wedge action failed")

    # -- asyncio wrapper ---------------------------------------------------

    def start(self) -> None:
        """Spawn the periodic checker on the RUNNING event loop. The
        watchdog must live off the engine thread — that thread is the
        primary suspect."""
        import asyncio

        async def loop():
            while True:
                await asyncio.sleep(self.interval_s)
                try:
                    self.check()
                except Exception:
                    logger.exception("stall watchdog check failed")

        self._task = asyncio.get_running_loop().create_task(loop())

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
