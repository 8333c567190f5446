"""Flight recorder: an always-on bounded ring of per-step scheduler/
engine decisions — the evidence plane for "why is this worker slow".

The aggregate counters (EngineMetrics) and the phase histograms say how
much time went where over the worker's life; neither can say what the
scheduler decided *around second 41 when request r17 stopped emitting*.
The flight recorder can: every engine step appends one small structured
record — batch kind and bucket keys, rows prefilling/decoding, page-pool
deltas and watermark, dispatch/sync/host wall ms, overlap hits and
rollbacks, compile events, queue depths — into a bounded deque. Cost is
one dict build + deque append per step (~µs; bench.py `flight_overhead`
prices it <1% of token throughput), and the plane is host-side only:
with `EngineConfig.flight_recorder=False` the engine holds no recorder
and the token path is bit-identical.

Consumption:
- `GET /v1/debug/flight[?n=]` on whatever HTTP surface the engine's
  process has (the OpenAI frontend in single-process serving), via
  `telemetry.debug`;
- the worker ships its most recent window in every metrics frame
  (`worker.py _publish_loop`), so the metrics service can serve the
  whole fleet's recent windows from one place;
- the stall watchdog (`telemetry/watchdog.py`) snapshots the window
  around a stall into its diagnosis;
- `scripts/doctor.py` folds the windows into rule-based diagnoses
  (compile storm, preemption thrash, prefill-induced decode stall, ...).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

#: EngineMetrics counters whose per-step DELTA rides each record (the
#: cumulative values are already on the metrics plane; the deltas are
#: what localize an event to a step). Keyed by the short record field.
_DELTA_FIELDS = (
    ("disp_ms", "time_decode_dispatch_ms"),
    ("sync_ms", "time_decode_sync_ms"),
    ("host_ms", "time_decode_host_ms"),
    # the rest of the loop on the host's clock, to check step by step
    # against the `engine.*` spans of a profiler capture: schedule and
    # stage are this step's own; intake and emit run between steps, so a
    # record carries the emit of the step before it
    ("sched_ms", "time_schedule_ms"),
    ("stage_ms", "time_stage_ms"),
    ("emit_ms", "time_emit_ms"),
    ("intake_ms", "time_intake_ms"),
    ("overlap_hits", "overlap_hits"),
    ("overlap_rollbacks", "overlap_rollbacks"),
    # a model with state-space layers: slots taken (each from zeros),
    # rollbacks that left surviving rows' state as the last taken
    # dispatch wrote it, prefix hits refused
    ("state_resets", "state_resets"),
    ("state_restores", "state_restores"),
    ("prefix_refused_state", "prefix_hits_refused_state"),
    # speculative decoding (ngram or draft model): drafted/accepted per
    # step — a record with tokens but no spec_drafted is a plain step
    ("spec_drafted", "spec_drafted"),
    ("spec_accepted", "spec_accepted"),
    ("compiles", "compiles"),
    ("compile_ms", "compile_ms"),
    ("preempted", "preemptions"),
    ("tokens", "generated_tokens"),
)

#: default records shipped per metrics frame (a frame goes out ~1/s; 32
#: records cover the last ~32 steps — enough for the doctor's rules
#: without bloating the metrics bus)
WIRE_RECORDS = 32


def tail(records: list, n: Optional[int]) -> list:
    """Most recent `n` records (all when n is None). The single trim
    used by the recorder AND the metrics service's fleet endpoint —
    records[-0:] would be the whole list, so n=0 is special-cased."""
    if n is None or n < 0:
        return records
    return records[-n:] if n else []


class FlightRecorder:
    """Bounded ring of per-step records. The engine thread appends;
    the publish loop / debug endpoints / watchdog snapshot — a small
    lock keeps the snapshot consistent (deque mutation during iteration
    raises)."""

    def __init__(self, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"flight ring capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        #: previous cumulative counter values for the per-step deltas
        self._prev: dict[str, float] = {}
        self._seq = 0

    def record_step(
        self,
        metrics,
        kind: str,
        step_ms: float,
        n_decode: int = 0,
        b_decode: int = 0,
        n_prefill: int = 0,
        t_bucket: int = 0,
        prefill_tokens: int = 0,
        waiting: int = 0,
        running: int = 0,
        free_pages: int = 0,
        active_pages: int = 0,
        watermark: int = 0,
        admit_wait_ms: Optional[list] = None,
    ) -> dict:
        """Append one step record. `metrics` is the engine's
        EngineMetrics — deltas against the previous record are computed
        here so the engine's call site stays one line."""
        rec: dict = {
            "seq": self._seq,
            "ts": round(time.time(), 4),
            "kind": kind,
            "step_ms": round(step_ms, 3),
            "n_decode": n_decode,
            "b_decode": b_decode,
            "n_prefill": n_prefill,
            "t_bucket": t_bucket,
            "prefill_tokens": prefill_tokens,
            "waiting": waiting,
            "running": running,
            "free_pages": free_pages,
            "active_pages": active_pages,
            "watermark": watermark,
        }
        if admit_wait_ms:
            # queue waits (ms) of the requests this step admitted,
            # traced or not; absent when it admitted none
            rec["admit_wait_ms"] = admit_wait_ms
        prev = self._prev
        for field, attr in _DELTA_FIELDS:
            cur = getattr(metrics, attr, 0)
            d = cur - prev.get(attr, 0)
            prev[attr] = cur
            if isinstance(d, float):
                d = round(d, 3)
            if d:
                rec[field] = d
        self._seq += 1
        with self._lock:
            self._ring.append(rec)
        return rec

    def snapshot(self, n: Optional[int] = None) -> list[dict]:
        """Most recent `n` records, oldest first (all when n is None)."""
        with self._lock:
            out = list(self._ring)
        return tail(out, n)

    def to_wire(self, n: int = WIRE_RECORDS) -> list[dict]:
        """The window that rides the metrics frame (json/msgpack-safe)."""
        return self.snapshot(n)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)
