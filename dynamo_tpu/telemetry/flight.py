"""Flight recorder: an always-on bounded ring of per-step scheduler/
engine decisions — the evidence plane for "why is this worker slow".

The aggregate counters (EngineMetrics) and the phase histograms say how
much time went where over the worker's life; neither can say what the
scheduler decided *around second 41 when request r17 stopped emitting*.
The flight recorder can: every engine step appends one small structured
record into a bounded deque. A record holds, as `record_step` writes it:

- `seq`, `ts` (wall clock when the step ended; consumers cut windows by
  it), `kind`, `step_ms`;
- the batch: `n_decode` / `b_decode` (real rows / bucket), `ctx_min` (the
  shortest sequence among the decode rows, tokens; absent without decode
  rows), `n_prefill`, `t_bucket`, `prefill_tokens`;
- the queues and the pool: `waiting`, `running`, `free_pages`,
  `active_pages`, `watermark`;
- `admit_wait_ms`: the queue waits of the requests this step admitted
  (absent when it admitted none);
- the per-step DELTA of every counter in `_DELTA_FIELDS`, left out where
  it is 0: the loop's phases on the host's clock (`disp_ms`, `sync_ms`,
  `host_ms`, `sched_ms`, `stage_ms`, `emit_ms`, `intake_ms`), the
  launch-ahead pipeline (`overlap_hits`, `overlap_rollbacks`), the
  recurrent-state plane (`state_resets`, `state_restores`,
  `prefix_refused_state`), a selecting walk (`walk_pages_named`,
  `walk_pages_live`) and its sparse prompt chunks (`chunk_pages_read`,
  `chunk_pages_named`; `moe_experts_touched` where it counts the held
  experts its rows chose, `moe_extra_passes` where a share's assignments
  took a layer more than one pass), speculation (`spec_drafted`,
  `spec_accepted`), `compiles` / `compile_ms`, `preempted`, `tokens`,
  and the dry clock's counters (`dry_ms`, `dry_slack_ms`, `dry_wait_ms`,
  `dry_<phase>_ms`, `dry_launches`, `launches`);
- the dispatch timeline (`DryClock`, below): `disp`, one entry per
  program the step launched, and `ready`, one per dispatch whose ids the
  step read. Both are left out where empty.

Cost is one dict build + deque append per step (~µs; bench.py
`flight_overhead` prices it <1% of token throughput) plus, per dispatch,
some fifteen boundaries and up to a poll per output posted (an
`is_ready()` of 0.3-0.4 us and a `perf_counter()` each on a TPU v5e, and
none once the device is known dry) and two small dicts (on the chip:
nothing end to end in `phi3-chat-closed`, about 2 % of the tokens/s of
`nano3-chat-churn`, not explained: PERF.md 6, PR 38);
the plane is host-side only: with `EngineConfig.flight_recorder=False`
the engine holds neither recorder nor clock and the token path is
bit-identical.

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
  (compile storm, preemption thrash, prefill-induced decode stall, ...);
- the benchmark reads whole windows of them (`chipbench/run.py`
  `FlightDrain`; `chipbench/timeline.py` reads the dispatch timeline).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

#: the loop phases whose enter and exit are the dry clock's boundaries
#: (`engine.<phase>` spans, engine/engine.py `phase`), in loop order;
#: `engine.compile` and `engine.rollback` are transparent: they count
#: for the phase around them, as in chipbench/hostspans.py
PHASES = ("intake", "schedule", "stage", "launch", "readback",
          "postprocess", "emit")

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
    # a model whose decode walk reads a chosen part of a row's pages
    ("walk_pages_named", "walk_pages_named"),
    ("walk_pages_live", "walk_pages_live"),
    # and whose sparse prompt chunks read a page once a tile of queries
    ("chunk_pages_read", "chunk_pages_read"),
    ("chunk_pages_named", "chunk_pages_named"),
    # and which counts the held experts its rows touched (a share's chip)
    ("moe_experts_touched", "moe_experts_touched"),
    # and the passes over the share's assignments beyond a layer's first
    ("moe_extra_passes", "moe_extra_passes"),
    # speculative decoding (ngram or draft model): drafted/accepted per
    # step — a record with tokens but no spec_drafted is a plain step
    ("spec_drafted", "spec_drafted"),
    ("spec_accepted", "spec_accepted"),
    ("compiles", "compiles"),
    ("compile_ms", "compile_ms"),
    ("preempted", "preemptions"),
    ("tokens", "generated_tokens"),
    # the dry clock (`DryClock`): host ms during which the device had
    # nothing queued, by the loop phase that ran meanwhile, the measure's
    # uncertainty, and the launches made with the device empty
    ("dry_ms", "dry_ms"),
    ("dry_slack_ms", "dry_slack_ms"),
    ("dry_wait_ms", "dry_wait_ms"),
    *((f"dry_{p}_ms", f"dry_{p}_ms") for p in PHASES),
    ("dry_launches", "dry_launches"),
    ("launches", "launches"),
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


#: a readback longer than this BLOCKED: the host was already waiting when
#: the device finished, so its return is the device's finish to within a
#: thread wake-up
BLOCKED_MS = 0.2

_DRY_FIELD = {**{p: f"dry_{p}_ms" for p in PHASES}, "wait": "dry_wait_ms"}


def _is_ready(out) -> bool:
    try:
        return out.is_ready()
    except RuntimeError:
        # donated to a later program (a page injection between steps):
        # that program is on the queue behind it, so the device is busy
        return False


class DryClock:
    """When the loop let the chip run dry, with no profiler: the engine
    thread asks the output of its NEWEST launch whether it is ready. The
    device queue is in order, so that output being ready means the queue
    is empty.

    It is asked once at every boundary: enter and exit of the
    `engine.<PHASES>` spans and of the wait for takers (`engine.wait`
    under `AsyncEngineRunner._await_takers`), and `poll` inside the
    phases that run for milliseconds (around the staging transfer, every
    16 rows of the stop scan, every output posted, between the naps
    of the wait, after planning the batch ahead), all on the engine
    thread (`engine.engine._Phase` calls
    `enter` / `exit`, the launch sites call `_Phase.launched`). From the
    first boundary that finds it ready until the next program call
    returns the device is DRY. Every stretch between two boundaries in
    that interval is added to `dry_ms` and to the `dry_<phase>_ms` of the
    phase it ran under (`dry_wait_ms` for the wait for takers; a stretch
    between two phases is in `dry_ms` alone, so `dry_ms` less the eight
    is the loop's glue). The stretch between the last boundary that
    found the output busy and the first that found it ready is the
    measure's uncertainty, `dry_slack_ms`: true dry time lies in
    [`dry_ms`, `dry_ms + dry_slack_ms`]. A readback of the newest launch
    that blocked leaves no slack: its return is the device's finish.

    An engine with nothing to run is PARKED (`park`: the end of a step
    that leaves no work, `drain_overlap`): nothing counts until the next
    `engine.schedule` opens, so neither the idle wait nor the intake that
    brought the work is dry time (nothing was held up). The host turn that
    ends a busy period, after its last dispatch landed, is counted by the
    counters and belongs to no dispatch. The draft pool's cover programs
    (`_spec_draft_cover`) run under no phase and are not seen.

    Per dispatch (`launched`) one timeline entry goes into `disp` of the
    flight record of the step that launched it:

        seq            counter of launches (also `engine.launch`'s arg)
        kind           decode | decode_multi | mixed | prefill | spec_*
        rows, n_rows   row bucket of the program, real rows
        k              fused decode steps
        ahead          1 = launched ahead of its batch (`_speculate`)
        t, b_pre, chunk_tokens   a chunk's T bucket, the program's piece
                       rows and the real prompt tokens (mixed, prefill)
        t_launch       time.perf_counter() when the program call returned
        dry_before_ms  dry time since the launch before it,
        dry_phase      the phase under which the device was found to have
                       finished ("idle": first launch after a park;
                       "none": between two phases), and
        slack_ms       the stretch before that boundary in which it
                       finished (its share of `dry_slack_ms`; a long one
                       names a call the loop cannot ask inside: the
                       staging transfer, a program call, a GC pause):
                       all three ONLY on a launch made dry (counted in
                       `dry_launches`)

    and when a dispatch's ids are read (`exit("readback", seq)`) one
    entry goes into `ready` of THAT step's record: `seq`, `kind`,
    `t_ready` (when `engine.readback` returned), `blocked_ms` (its
    length) and, where both ends are known to within 0.5 ms, `dev_ms`:
    the dispatch's time on the device, finish less start. A finish is
    the `t_ready` of a readback that blocked (> BLOCKED_MS). A start is
    the dispatch's own `t_launch` if it was launched dry, else the finish
    of the dispatch before it (`seq - 1`). So `dev_ms` is absent where
    the readback did not block (the dispatch had landed before the host
    asked: a late launch follows it), where the dispatch was queued
    behind one whose finish is not known (never read: rolled back, or a
    prefill that samples nothing; or read without blocking), and for a
    dispatch that is never read. It includes the token feed program in
    front of a dispatch launched ahead and the launch latency of a dry
    one.
    """

    def __init__(self, metrics, now=time.perf_counter, is_ready=_is_ready):
        self._m = metrics
        self._now = now
        self._is_ready = is_ready
        self._stack: list = []  # open phases: (name, t_enter)
        self._t = now()  # the last boundary
        self._parked = True
        self._dry = True
        self._dry_phase = "idle"
        self._held = None  # the newest launch's output while busy
        self._acc_ms = 0.0  # dry ms since the last launch
        self._slack_ms = 0.0  # the slack of the dry stretch now open
        self._seq = 0
        self._sent: dict = {}  # seq -> (t_launch, launched dry, kind)
        self._finish: dict = {}  # seq -> when the device finished it
        self._disp: list = []
        self._ready: list = []

    # -- boundaries --------------------------------------------------------

    def _boundary(self, t: float, under, landed=None) -> None:
        """The stretch since the last boundary ran under `under` (a
        phase, "wait", or None between phases). `landed`: the newest
        output is known ready without asking, with this much slack."""
        dt, self._t = t - self._t, t
        if self._parked:
            return
        if self._dry:
            ms = dt * 1e3
            m = self._m
            m.dry_ms += ms
            self._acc_ms += ms
            if under is not None:
                f = _DRY_FIELD[under]
                setattr(m, f, getattr(m, f) + ms)
        elif landed is not None or self._is_ready(self._held):
            self._dry, self._held = True, None
            self._dry_phase = under or "none"
            self._slack_ms = (dt if landed is None else landed) * 1e3
            self._m.dry_slack_ms += self._slack_ms

    def _under(self):
        return self._stack[-1][0] if self._stack else None

    def enter(self, name: str) -> None:
        t = self._now()
        if self._parked and name == "schedule":
            self._unpark(t)
        self._boundary(t, self._under())
        self._stack.append((name, t))

    def exit(self, name: str, seq=None) -> None:
        t = self._now()
        _name, t0 = self._stack.pop()
        landed = None
        if seq is not None and name == "readback":
            landed = self._read(seq, t0, t)
        self._boundary(t, name, landed)

    def poll(self) -> None:
        """A boundary inside a phase, for the phases long enough to hide
        when the device finished (the wait for takers between its naps,
        staging, the stop scan, posting outputs). Only the first
        boundary that finds the output ready matters, so it asks nothing
        while the device is known dry."""
        if not (self._dry or self._parked):
            self._boundary(self._now(), self._under())

    def park(self) -> None:
        self._parked, self._dry, self._held = True, True, None
        self._acc_ms = 0.0

    def _unpark(self, t: float) -> None:
        self._parked, self._dry, self._dry_phase = False, True, "idle"
        self._t, self._slack_ms = t, 0.0

    # -- dispatches --------------------------------------------------------

    def launched(self, out, args: dict) -> int:
        """The program call of a step kind just returned `out` (one
        device array of its outputs); `args` are `engine.launch`'s."""
        t = self._now()
        if self._parked:
            self._unpark(t)
        self._boundary(t, self._under())
        m = self._m
        seq, self._seq = self._seq, self._seq + 1
        m.launches += 1
        entry = {
            "seq": seq, "kind": args.get("kind"),
            "rows": args.get("rows", 0), "n_rows": args.get("n_rows", 0),
            "k": args.get("k", 1), "ahead": args.get("speculative", 0),
            "t_launch": round(t, 6),
        }
        for key in ("t", "b_pre", "chunk_tokens"):
            if key in args:
                entry[key] = args[key]
        dry = self._dry
        if dry:
            m.dry_launches += 1
            entry["dry_before_ms"] = round(self._acc_ms, 3)
            entry["dry_phase"] = self._dry_phase
            entry["slack_ms"] = round(self._slack_ms, 3)
        self._acc_ms = 0.0
        self._dry, self._held = False, out
        self._sent[seq] = (t, dry, entry["kind"])
        self._sent.pop(seq - 16, None)
        self._finish.pop(seq - 16, None)
        self._disp.append(entry)
        return seq

    def _read(self, seq: int, t0: float, t: float):
        """`engine.readback` of dispatch `seq` ran from t0 to t."""
        blocked_ms = (t - t0) * 1e3
        blocked = blocked_ms > BLOCKED_MS
        sent = self._sent.get(seq)
        entry = {"seq": seq, "t_ready": round(t, 6),
                 "blocked_ms": round(blocked_ms, 3)}
        if sent is not None:
            entry["kind"] = sent[2]
        if blocked:
            self._finish[seq] = t
            if sent is not None:
                start = sent[0] if sent[1] else self._finish.get(seq - 1)
                if start is not None:
                    entry["dev_ms"] = round((t - start) * 1e3, 3)
        self._ready.append(entry)
        if seq == self._seq - 1 and not self._dry:
            # the newest launch itself: the queue is empty as of now
            return 0.0 if blocked else t - self._t
        return None

    def take(self) -> dict:
        """The entries since the last record: {"disp": [...], "ready":
        [...]}, each left out where empty."""
        out = {}
        if self._disp:
            out["disp"], self._disp = self._disp, []
        if self._ready:
            out["ready"], self._ready = self._ready, []
        return out


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
        ctx_min: int = 0,
        admit_wait_ms: Optional[list] = None,
        timeline: Optional[dict] = None,
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
        if n_decode:
            # the shortest sequence among the step's decode rows, tokens
            rec["ctx_min"] = ctx_min
        if admit_wait_ms:
            # queue waits (ms) of the requests this step admitted,
            # traced or not; absent when it admitted none
            rec["admit_wait_ms"] = admit_wait_ms
        if timeline:
            # DryClock.take(): the dispatches this step launched (`disp`)
            # and those whose ids it read (`ready`)
            rec.update(timeline)
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
