"""JaxEngine: the TPU-native inference engine.

Owns the model params, the device page pool, the host-side allocator and
continuous-batching scheduler, and a small cache of jitted step programs
(one per (kind, bucket) shape). This is the first-class engine the reference
lacks natively (it shells out to vLLM/SGLang/TRT-LLM — SURVEY.md L4);
tokens-in/tokens-out, KV events and worker metrics out.

Execution model per `step()`:
  scheduler -> ScheduledBatch -> pad to bucket -> jitted forward+sample ->
  host sync of sampled ids -> append/finish bookkeeping + page registration.

Overlapped decode (config.overlap_decode, docs/engine.md "The decode
loop"): after dispatching decode step N, the engine speculatively
dispatches step N+1 — same batch, +1 round, sampled ids fed back as a
device array — starts an async host copy of step N's ids, and only then
postprocesses step N. The device therefore computes N+1 while the host
scans N for stops and the next `step()` reads back a one-step-lagged,
already-copied result. The speculation is validated against the next
scheduled batch and rolled back (overshoot discarded, exactly like
decode_multi's post-stop tokens) when a finish, preemption, abort, or a
newly admitted prefill changes the batch.

Multi-chip: pass a MeshConfig; params/KV are device_put with tp/dp
PartitionSpecs and the same jitted programs run SPMD over the mesh.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import KvEvent, PageAllocator
from dynamo_tpu.engine.request import (
    FinishReason,
    Request,
    RequestState,
    SamplingParams,
    StepOutput,
)
from dynamo_tpu.engine.sampling import sample, sample_greedy
from dynamo_tpu.engine.scheduler import ScheduledBatch, Scheduler
from dynamo_tpu.models.registry import ModelAdapter, get_model
from dynamo_tpu.parallel.logical import default_rules
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
from dynamo_tpu.parallel.shardings import batch_spec, shardings_for
from dynamo_tpu.telemetry import phases
from dynamo_tpu.telemetry.flight import PHASES as _LOOP_PHASES, DryClock
from dynamo_tpu.tokens import TokenBlockSequence

logger = logging.getLogger(__name__)


def _canonical_gather(kv, ids, dk: int, dv: int):
    """Pool layout [L, P, S, Hkv, Dpad] -> canonical wire layout
    [L, Hkv, n, S, D] (padding stripped). THE one definition of the
    extract layout — single-process async extract and the multi-host
    sharded extract both trace this, so they can never diverge.

    Quantized pools pack each row's f32 scale into 4 trailing int8
    lanes: wire width becomes D+4 and the array stays ONE narrow-dtype
    tensor, so every downstream plane (KVBM host/disk tiers, disagg
    shm/bulk-TCP/device transfer, G4 serve/adopt) ships quantized bytes
    + scales at half the fp traffic without knowing about quantization
    — byte accounting (np.nbytes) is automatically honest."""
    k = jnp.take(kv.k, ids, axis=1).transpose(0, 3, 1, 2, 4)[..., :dk]
    v = jnp.take(kv.v, ids, axis=1).transpose(0, 3, 1, 2, 4)[..., :dv]
    if kv.k_scale is not None:
        bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.int8)
        # planes [L, P, Hkv, S'] -> [L, Hkv, n, S] (pad slots stripped)
        s = kv.k.shape[2]
        ks = jnp.take(kv.k_scale, ids, axis=1)[..., :s].transpose(0, 2, 1, 3)
        vs = jnp.take(kv.v_scale, ids, axis=1)[..., :s].transpose(0, 2, 1, 3)
        # fp8 payloads bitcast to int8 so payload+scale share one dtype
        k = jnp.concatenate([bits(k), bits(ks)], axis=-1)
        v = jnp.concatenate([bits(v), bits(vs)], axis=-1)
    return k, v


def _wire_unpack(arr, d_true: int, pool_dtype):
    """Canonical QUANTIZED wire array [..., D+4] int8 ->
    (payload [..., D] pool dtype, scale [...] f32): inverse of
    _canonical_gather's scale packing."""
    payload = jax.lax.bitcast_convert_type(arr[..., :d_true], pool_dtype)
    scale = jax.lax.bitcast_convert_type(
        arr[..., d_true : d_true + 4], jnp.float32
    )
    return payload, scale


#: `ModelAdapter.walk_pages`' running count, in order, as EngineMetrics
#: names its numbers
_WALK_COUNTS = ("walk_pages_named", "walk_pages_live", "chunk_pages_read",
                "chunk_pages_named", "moe_experts_touched",
                "moe_extra_passes")


@dataclass
class EngineMetrics:
    """Worker load snapshot published to routers/planner (parity with the
    reference's ForwardPassMetrics — kv_router/protocols.rs:43-69)."""

    num_waiting: int = 0
    num_running: int = 0
    kv_active_pages: int = 0
    kv_free_pages: int = 0
    kv_total_pages: int = 0
    kv_usage: float = 0.0
    #: device bytes the KV pool actually occupies (quantized pages +
    #: scale planes) vs the model-dtype equivalent — their ratio is the
    #: effective cache-capacity multiplier kv_quantize buys
    kv_pool_bytes: int = 0
    kv_pool_bytes_dense_equiv: int = 0
    prefix_hit_rate: float = 0.0
    steps: int = 0
    generated_tokens: int = 0
    #: monotonically increasing arrivals (planner derives request_rate)
    requests_received: int = 0
    #: speculative decoding (prompt lookup) — parity with the reference's
    #: SpecDecodeStats (kv_router/protocols.rs:96)
    spec_drafted: int = 0
    spec_accepted: int = 0
    #: why speculation DIDN'T run, by decode dispatch (observability:
    #: "ineligible" = a sampling/logprob/penalty request in the batch
    #: disables speculation batch-wide; "cooldown" = acceptance fell
    #: below spec_min_accept_rate and the engine is backing off)
    spec_skipped_ineligible: int = 0
    spec_skipped_cooldown: int = 0
    #: live draft-acceptance rate over a ~60 s window of spec steps
    #: (accepted/drafted; 0.0 when speculation is idle) — the lever the
    #: effective tok/s multiplier (1 + rate*S) rides on, exported as the
    #: dynamo_tpu_*_spec_accept_rate gauge on both Prometheus surfaces
    spec_accept_rate: float = 0.0
    #: drafts inside that same window — the rate's denominator/weight,
    #: shipped so aggregators can (a) tell an actively-FAILING draft
    #: (rate 0, window_drafted > 0) from an idle one and (b) compute the
    #: true windowed fleet ratio as a drafted-weighted mean instead of a
    #: lifetime ratio that never moves again
    spec_window_drafted: int = 0
    #: step-phase wall time, cumulative ms (host-loop observability:
    #: time_*_ms − the profiler's pure program time = host overhead,
    #: see scripts/tpu_decode_profile.py / docs/PERF.md). schedule
    #: covers admission + batch packing; prefill/decode cover host
    #: array build + dispatch + device sync + postprocess.
    time_schedule_ms: float = 0.0
    time_prefill_ms: float = 0.0
    time_decode_ms: float = 0.0
    #: mixed prefill+decode steps (config.mixed_steps): wall time and
    #: dispatch count of steps that carried BOTH a prefill chunk and the
    #: decode batch — the stall-free path; decode rows emitted a token
    #: on every one of these instead of waiting out the prefill
    time_mixed_ms: float = 0.0
    #: decode's phase split: dispatch = host array build + program
    #: launch (incl. any speculative next-step launch), sync = blocking
    #: on the sampled ids' device→host copy, host = the stop/finish
    #: scan + page registration. The dispatch column follows the DECODE
    #: ROWS wherever they run: pure decode steps and the decode half of
    #: mixed steps (whose step wall time lands in time_mixed_ms
    #: instead); sync and host are the counters of the `engine.readback`
    #: and `engine.postprocess` spans and so also take a prefill
    #: dispatch's readback and first-token postprocess. Under
    #: overlap_decode the sync column collapses (the copy was started a
    #: step earlier) — the overlap's visibility in bench.py extras.
    time_decode_dispatch_ms: float = 0.0
    time_decode_sync_ms: float = 0.0
    time_decode_host_ms: float = 0.0
    #: the rest of the loop, same clock and unit (cumulative host ms):
    #: stage = numpy array build + host→device transfer of a dispatch's
    #: inputs (the part of time_decode_dispatch_ms that is not the
    #: program launch); intake = the runner's inbox drain (admissions,
    #: aborts, deadlines) and emit = posting a step's outputs to the
    #: request queues — both outside step(). Each is the counter of the
    #: `engine.<phase>` span of the same name (`phase`, below).
    time_stage_ms: float = 0.0
    time_intake_ms: float = 0.0
    time_emit_ms: float = 0.0
    #: admission wait of EVERY admitted request (traced or not), summed
    #: where the wait ends (scheduler._admit), and how many admissions
    #: it is summed over: total / admissions = mean queue wait
    queue_wait_ms_total: float = 0.0
    admissions: int = 0
    #: program-launch counters. A mixed step normally launches ONE fused
    #: program (mixed_dispatches); its overlap split path launches the
    #: pure prefill program beside the consumed speculation, which also
    #: counts here as a prefill dispatch.
    prefill_dispatches: int = 0
    decode_dispatches: int = 0
    mixed_dispatches: int = 0
    #: decode rows that rode a prompt chunk's pass over the weights in a
    #: fused mixed program, summed where the dispatch is read: over
    #: mixed_dispatches, the rows an admission saved a weight stream of
    #: their own (0 on the split path, whose halves are two programs;
    #: models/moe.py counts them too but still passes twice:
    #: `registry._two_pass_mixed`)
    mixed_shared_rows: int = 0
    #: overlapped decode pipeline: speculative next-step dispatches
    #: issued / consumed as the real step / rolled back (overshoot
    #: discarded because the batch changed underneath them)
    overlap_dispatches: int = 0
    overlap_hits: int = 0
    overlap_rollbacks: int = 0
    #: engine-internals plane (fleet telemetry, docs/observability.md):
    #: jit-cache misses (one full XLA compile each) and their cumulative
    #: wall cost — climbing in steady state means the program family is
    #: churning (the compile hazard the 3-axis mixed family introduced)
    compiles: int = 0
    compile_ms: float = 0.0
    #: compile_ms by part (`_FirstCall`: jax's own events inside each
    #: first call): tracing, lowering, and XLA's compile or the
    #: persistent cache's read; what compile_ms holds beyond them is the
    #: programs' first runs. cache_requests counts the compiles whose
    #: result the persistent cache holds afterwards (answered from it, or
    #: written to it; one too quick for jax to keep is in neither),
    #: cache_hits those it answered: equal in a warm start, hits 0 in a
    #: cold one, between them a cache that lost entries
    compile_trace_ms: float = 0.0
    compile_lower_ms: float = 0.0
    compile_backend_ms: float = 0.0
    compile_cache_requests: int = 0
    compile_cache_hits: int = 0
    #: the boot, set once by the constructor (ms on the host's clock):
    #: boot_before_ms from the process's start, as the OS has it, to the
    #: constructor's entry (the interpreter, the imports, the backend's
    #: start where the caller made it, the tokenizer); boot_ms the whole
    #: constructor (span `engine.boot`), of it boot_weights_ms the
    #: parameters loaded or drawn, quantized and placed
    #: (`engine.boot.weights`) and boot_pools_ms the KV pool, the state
    #: slots and a draft model's (`engine.boot.pools`), each closed by
    #: block_until_ready; boot_end_perf_s is `time.perf_counter()` at the
    #: constructor's exit, for a caller that lays its own clock against
    #: the boot
    boot_before_ms: float = 0.0
    boot_ms: float = 0.0
    boot_weights_ms: float = 0.0
    boot_pools_ms: float = 0.0
    boot_end_perf_s: float = 0.0
    #: page-pool pressure: the high-watermark of active pages since boot
    #: and the scheduler's preemption-by-recompute count — preemptions
    #: climbing while the watermark pins at capacity is the "pool too
    #: small for this workload" signal
    kv_pages_watermark: int = 0
    preemptions: int = 0
    #: token throughput over a sliding window (~10 s)
    tokens_per_s: float = 0.0
    #: overload-protection plane (docs/operations.md "Overload &
    #: draining"): requests refused at admission because the bounded
    #: waiting queue (EngineConfig.max_waiting) was full — climbing
    #: means this worker is shedding (raise capacity), while a deep
    #: num_waiting with ZERO rejects means the queue is unbounded
    overload_rejects: int = 0
    #: requests error-finished because their end-to-end deadline passed
    #: (pre-admission drops + mid-decode expiries)
    deadline_expired: int = 0
    #: HBM accounting plane (GET /v1/debug/memory — docs/observability.md
    #: "Reading the perf plane"): byte rollups summed over this process's
    #: addressable devices. weights = the param trees' shard bytes,
    #: kv_pool = the paged KV pool (mirrors kv_pool_bytes but lives in
    #: the hbm_* family the plane exposes), free/peak from jax device
    #: memory_stats on TPU with the accounted CPU fallback. Refreshed by
    #: refresh_memory_metrics() on the publish cadence — the token path
    #: never touches them.
    hbm_weights_bytes: int = 0
    hbm_kv_pool_bytes: int = 0
    hbm_free_bytes: int = 0
    hbm_peak_bytes: int = 0
    #: mesh introspection plane (GET /v1/debug/mesh): this replica's
    #: process index under multi-host SPMD (0 single-host) and the
    #: recent-window decode dispatch p95 — the per-host straggler gauge
    #: the doctor's host-skew rule compares across hosts
    host: int = 0
    dispatch_p95_ms: float = 0.0
    #: recurrent-state plane (a model with state-space layers; all 0
    #: otherwise; docs/observability.md): slots of the state pool and the
    #: high watermark of slots held, the pool's device bytes (both
    #: generations), admissions that took a slot (each starts its sequence
    #: from zeros), rollbacks of a dispatch launched ahead that had
    #: advanced the state of rows still alive (their slots read as the last
    #: dispatch taken left them: `JaxEngine._discard_inflight`), and
    #: prefix-cache hits refused because pages without the state at their
    #: boundary cannot be used
    state_slots: int = 0
    state_slots_live: int = 0
    state_pool_bytes: int = 0
    state_resets: int = 0
    state_restores: int = 0
    prefix_hits_refused_state: int = 0
    #: a model whose decode walk reads a CHOSEN part of a row's pages
    #: (`ModelAdapter.walk_pages`; 0 for every other): pages the lists
    #: handed to the walks named and pages those rows held, a KV head and
    #: a layer each, COUNTED ON THE DEVICE by the step programs and read
    #: back beside each decode-carrying dispatch's ids (`_count_walk`)
    walk_pages_named: int = 0
    walk_pages_live: int = 0
    #: the same count's other half, of such a model's PROMPT CHUNKS past
    #: the switch to its sparse rule: pages the query tiles' lists named
    #: (what the chunk kernel read, a page once a tile) and pages their
    #: queries' selections named (what a walk a query would have read)
    chunk_pages_read: int = 0
    chunk_pages_named: int = 0
    #: where that model holds a share of its experts and counts a fifth
    #: number (models/dots3.py): the held experts some row of a step chose,
    #: an expert layer each: the matrices its grouped matmuls read
    moe_experts_touched: int = 0
    #: a sixth (models/mla.py `_routed_experts`): passes over a share's
    #: assignments beyond an expert layer's first, each of which reads the
    #: held experts' matrices again; 0 while a step's count stays inside
    #: `mla.share_rows`
    moe_extra_passes: int = 0
    #: the dry clock (telemetry/flight.py `DryClock`; all 0 with
    #: `flight_recorder=False`): cumulative host ms during which the
    #: device had NOTHING queued while the engine had work, from the first
    #: loop-phase boundary that found the newest launch's output ready
    #: until the next program call returned. dry_ms is all of it; the
    #: seven dry_<phase>_ms (twins of the time_*_ms counters, one per
    #: `engine.<phase>` span) and dry_wait_ms (the wait for takers of
    #: free slots, `AsyncEngineRunner._await_takers`) say under which
    #: phase it passed, and what dry_ms holds beyond them passed between
    #: two phases. dry_slack_ms is the measure's uncertainty (the stretch
    #: between the last boundary that found the device busy and the first
    #: that found it ready): true dry time lies in [dry_ms, dry_ms +
    #: dry_slack_ms]. launches counts every program call of a step kind
    #: (pure prefill programs too), dry_launches those made with the
    #: device empty: high beside a high overlap_hits means launches ahead
    #: of the batch but behind the device.
    dry_ms: float = 0.0
    dry_slack_ms: float = 0.0
    dry_wait_ms: float = 0.0
    dry_intake_ms: float = 0.0
    dry_schedule_ms: float = 0.0
    dry_stage_ms: float = 0.0
    dry_launch_ms: float = 0.0
    dry_readback_ms: float = 0.0
    dry_postprocess_ms: float = 0.0
    dry_emit_ms: float = 0.0
    dry_launches: int = 0
    launches: int = 0

    #: the engine's `DryClock`, or None (not a field: `phase` finds the
    #: clock where it finds the counters, and `to_dict` leaves it out)
    dry_clock = None

    #: the timing plane's field names — the one list consumers (perf
    #: harness, dashboards) should iterate instead of restating
    TIMING_FIELDS = (
        "time_schedule_ms", "time_prefill_ms", "time_decode_ms",
        "time_mixed_ms",
        "time_decode_dispatch_ms", "time_decode_sync_ms",
        "time_decode_host_ms",
        "time_stage_ms", "time_intake_ms", "time_emit_ms",
        "prefill_dispatches", "decode_dispatches", "mixed_dispatches",
        "mixed_shared_rows",
        "overlap_dispatches", "overlap_hits", "overlap_rollbacks",
    )

    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d.pop("dry_clock", None)
        return d


#: spans whose enter and exit are boundaries of the dry clock, by the
#: name the clock knows each under
_CLOCKED = {f"engine.{p}": p for p in (*_LOOP_PHASES, "wait")}


class _Phase:
    """One phase of the engine loop, measured once and shown twice: a
    `jax.profiler.TraceAnnotation` span on the profiler's clock (a no-op
    in C++ unless a capture is running — the engine's own `POST
    /v1/debug/profile` or anybody's `jax.profiler.start_trace`), and the
    elapsed host time added to the named cumulative-ms counters. Where
    the metrics carry a dry clock (`EngineMetrics.dry_clock`, with the
    flight recorder) the enter and exit of a loop phase are also its
    boundaries, and a launch (`launched`) its dispatch: every program
    call of a step kind is counted there (`launches`), pure prefill
    programs included."""

    __slots__ = ("_metrics", "_fields", "_span", "_t0", "_clock", "_name",
                 "_args")

    def __init__(self, metrics, name: str, fields: tuple, args: dict):
        self._metrics = metrics
        self._fields = fields
        self._span = jax.profiler.TraceAnnotation(name, **args)
        clock = getattr(metrics, "dry_clock", None)
        self._name = _CLOCKED.get(name)
        self._clock = clock if self._name is not None else None
        self._args = args

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        # the clock's boundaries lie INSIDE the span and the counter, so
        # the two still time the same stretch (tests/test_engine_spans.py)
        if self._clock is not None:
            self._clock.enter(self._name)
        return self

    def note(self, **args) -> None:
        """Span args known only once the phase has run."""
        self._span.set_metadata(**args)

    def elapsed_ms(self) -> float:
        """Host ms since the phase was entered."""
        return (time.perf_counter() - self._t0) * 1000.0

    def launched(self, out) -> Optional[int]:
        """Inside `engine.launch`, right after the program call returned
        `out` (one device array of its outputs): the dispatch's `seq` on
        the flight recorder's timeline and in the span. None, and
        nothing else, without a dry clock."""
        clock = self._clock
        if clock is None:
            return None
        seq = clock.launched(out, self._args)
        self._span.set_metadata(seq=seq)
        return seq

    def __exit__(self, *exc) -> bool:
        clock = self._clock
        if clock is not None:
            clock.exit(self._name, self._args.get("seq"))
            if self._name == "launch":
                # the span's exit on the host's monotonic clock: one span
                # gives the offset between that clock and the profiler's
                self._span.set_metadata(t_host_ns=time.perf_counter_ns())
        dt_ms = self.elapsed_ms()
        self._span.__exit__(*exc)
        m = self._metrics
        if m is not None:
            for f in self._fields:
                setattr(m, f, getattr(m, f, 0.0) + dt_ms)
        return False


def phase(metrics, name: str, *fields: str, **args) -> _Phase:
    """`with phase(metrics, "engine.stage", "time_stage_ms", kind=...)`:
    THE way a loop phase is timed (docs/observability.md lists the spans
    and their counters). `metrics` is an EngineMetrics, or None for an
    engine double that keeps none."""
    return _Phase(metrics, name, fields, args)


def _seq_arg(seq: Optional[int]) -> dict:
    """`engine.readback`'s `seq` arg: the dispatch it reads (no arg
    without a dry clock)."""
    return {} if seq is None else {"seq": seq}


#: jax's own events (jax.monitoring, 0.9.0) that split a first call
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: fired where jax WRITES an entry: a compile the cache did not hold and
#: now does (one too quick to be kept, under
#: `jax_persistent_cache_min_compile_time_secs`, fires neither)
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: the parts whose sums the compile table's rollup by kind carries
_FIRST_CALL_PARTS = ("trace_ms", "lower_ms", "backend_ms")

#: `.open` is the calling thread's first call in progress, if any
_first_calls = threading.local()
_listening = False
_listening_lock = threading.Lock()


class _FirstCall:
    """What jax says of itself during ONE first call of a step program
    (`JaxEngine._cache_jit`), on the thread that makes the call: ms of
    tracing, of lowering and of XLA's compile or the persistent cache's
    read, and whether the cache answered or was written to. Trace and
    lowering events NEST (an inner `jax.jit` traced for the first time
    inside the program fires its own, and the outermost, fired last,
    holds them all; a lowering rule that traces a helper fires a trace
    event inside the lowering's), so each is taken as the events' maximum
    up to the compile that follows them, not their sum."""

    __slots__ = ("trace_ms", "lower_ms", "backend_ms", "hits", "misses",
                 "_trace", "_lower")

    def __init__(self):
        self.trace_ms = self.lower_ms = self.backend_ms = 0.0
        self.hits = self.misses = 0
        self._trace = self._lower = 0.0

    def __enter__(self) -> "_FirstCall":
        _first_calls.open = self  # a first call never holds another
        return self

    def __exit__(self, *exc) -> bool:
        _first_calls.open = None
        self._fold()
        return False

    def _fold(self) -> None:
        self.trace_ms += self._trace
        self.lower_ms += self._lower
        self._trace = self._lower = 0.0

    def duration(self, event: str, ms: float) -> None:
        if event == _TRACE_EVENT:
            self._trace = max(self._trace, ms)
        elif event == _LOWER_EVENT:
            self._lower = max(self._lower, ms)
        elif event == _BACKEND_EVENT:
            self._fold()
            self.backend_ms += ms

    @property
    def cache(self) -> str:
        """"hit": the persistent cache answered; "miss": XLA compiled
        and the cache keeps the result, so a later start hits; "off": the
        cache was asked for nothing, or the compile was too quick for jax
        to keep (such a program never hits)."""
        if self.misses:
            return "miss"
        return "hit" if self.hits else "off"

    def parts(self, whole_ms: float) -> dict:
        """The first call by part; `run_ms` is the remainder of
        `whole_ms`: the program's first run, its inputs' transfer and the
        dispatch."""
        known = self.trace_ms + self.lower_ms + self.backend_ms
        return {
            "trace_ms": round(self.trace_ms, 3),
            "lower_ms": round(self.lower_ms, 3),
            "backend_ms": round(self.backend_ms, 3),
            "run_ms": round(whole_ms - known, 3),
            "cache": self.cache,
        }


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    call = getattr(_first_calls, "open", None)
    if call is not None:
        call.duration(event, duration_secs * 1000.0)


def _on_jax_event(event: str, **_kw) -> None:
    call = getattr(_first_calls, "open", None)
    if call is None:
        return
    if event == _CACHE_HIT_EVENT:
        call.hits += 1
    elif event == _CACHE_MISS_EVENT:
        call.misses += 1


def _listen_to_jax() -> None:
    """Register the process's ONE pair of jax.monitoring listeners (the
    first engine built does): each returns at its first test unless the
    calling thread is inside a first call."""
    global _listening
    with _listening_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration)
            jax.monitoring.register_event_listener(_on_jax_event)
            _listening = True


def _pages_only(method):
    """A method of the page-movement surface (disagg transfer planes,
    handover, KV tiers): refused for a model whose sequences also keep a
    recurrent state (`JaxEngine._refuse_state_transfer`)."""

    @functools.wraps(method)
    def guarded(self, *args, **kwargs):
        self._refuse_state_transfer(method.__name__)
        return method(self, *args, **kwargs)

    return guarded


@dataclass
class _Launched:
    """One decode-carrying dispatch on the device queue whose sampled ids
    the host has not read: a pure decode dispatch of `k_steps` fused
    steps, or a fused mixed step (`pieces` beside the decode rows).

    Launched for the batch in hand it is read back in the same step.
    Launched AHEAD of its batch (`JaxEngine._speculate`; `expected` set,
    the async host copy of its ids already started) it becomes the real
    step iff the next scheduled batch is the one it was built for: the
    same requests in the same rows, each advanced exactly the tokens the
    dispatch before it added, beside the same pieces. Otherwise it is
    rolled back (the ids are overshoot, and the KV it wrote sits past
    every live sequence's length, in pages of a prompt that the real
    dispatch writes again, or in freed pages that later writers fully
    overwrite before any read)."""

    reqs: tuple  # decode rows: row i of the ids
    b_bucket: int
    k_steps: int
    token_ids: object  # device array: [B], [K, B], or mixed [b_dec(+b_pre)]
    lp_data: Optional[tuple]  # device (chosen, top_ids, top_lps) or None
    #: mixed step: the fused prefill pieces; where one completes its
    #: prompt (`psamp`) piece j was sampled at row b_bucket + j
    pieces: tuple = ()
    psamp: bool = False
    #: launched ahead: per decode row the (num_tokens, len(output_tokens))
    #: the batch must show when this dispatch is consumed
    expected: Optional[tuple] = None
    #: its number on the flight recorder's dispatch timeline (None
    #: without the recorder)
    seq: Optional[int] = None
    #: device int32 [4] or None: the cache's running count of the pages
    #: its walks and chunk tiles read, as this dispatch leaves it
    #: (`_count_walk`)
    walk: object = None


@dataclass
class _InflightSpec:
    """One speculatively chained spec-fused dispatch (draft-model
    speculation composing with the overlap pipeline): its catch-up
    window is the PREVIOUS spec dispatch's on-device outputs — out_ids
    masked by n_acc feed the next draft+verify program with no host
    round-trip between spec steps. It becomes the real step iff the
    host's acceptance scan of the previous step agreed with the
    device's n_acc on every row (no finish/stop truncation — the device
    cannot see those) and the decode batch is unchanged; otherwise it
    rolls back exactly like a _Launched dispatch."""

    reqs: tuple
    b_bucket: int
    out_ids: object  # device [B, S+1]
    draft_ids: object  # device [B, S]
    n_acc: object  # device [B] i32
    counters_v0: object  # [B] verify-start draw counters (device or host)
    greedy: bool = False
    bias: bool = False
    #: filled by the previous step's postprocess once the host confirms
    #: the device acceptance; None means "not yet validated" and the
    #: speculation can never be consumed
    expected_num_tokens: Optional[tuple] = None
    expected_out_len: Optional[tuple] = None
    seq: Optional[int] = None  # as _Launched.seq


class JaxEngine:
    def __init__(
        self,
        config: EngineConfig,
        params=None,
        mesh_config: Optional[MeshConfig] = None,
        on_kv_event: Optional[Callable[[KvEvent], None]] = None,
        checkpoint_path: Optional[str] = None,
        on_tier_event=None,
    ):
        from dynamo_tpu.platform import process_age_s

        # the boot, timed from inside (docs/observability.md "Reading a
        # slow start"): what the process spent before this line, the whole
        # constructor, and inside it the weights and the pools
        self.metrics = EngineMetrics(boot_before_ms=process_age_s() * 1e3)
        with phase(self.metrics, "engine.boot", "boot_ms"):
            self._boot(
                config, params, mesh_config, on_kv_event, checkpoint_path,
                on_tier_event,
            )
        #: on `time.perf_counter()`, the clock a caller stamps its own
        #: start of serving with
        self.metrics.boot_end_perf_s = time.perf_counter()

    def _boot(
        self, config, params, mesh_config, on_kv_event, checkpoint_path,
        on_tier_event,
    ) -> None:
        """The constructor's body, under the `engine.boot` span."""
        from dynamo_tpu.platform import (
            enable_persistent_compile_cache,
            require_platform,
        )

        # an engine that was not told JAX_PLATFORMS=cpu serves on a TPU or
        # does not start: a chip held by another process must not turn
        # into a CPU engine (jax's own fallback)
        require_platform()
        enable_persistent_compile_cache()
        _listen_to_jax()
        self.config = config
        mc = mesh_config or MeshConfig(
            dp=config.dp, tp=config.tp, sp=config.sp, ep=config.ep
        )
        impl = config.attention_impl
        if impl not in ("auto", "xla", "pallas", "hybrid"):
            raise ValueError(
                f"unknown attention_impl {impl!r}; use auto|xla|pallas|hybrid"
            )
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        self.mesh = make_mesh(mc) if mc.num_devices > 1 else None
        #: mesh spans >1 process: multi-controller lockstep mode. Host
        #: batch arrays become global arrays assembled per-host from the
        #: (identical) replicated numpy copies; small jit outputs are
        #: replicated so every host reads every sampled token
        #: (engine/spmd.py keeps the hosts' schedulers in lockstep).
        self._multiproc = self.mesh is not None and (
            len({d.process_index for d in self.mesh.devices.flat}) > 1
            or config.force_multihost
        )
        if self._multiproc:
            from jax.sharding import NamedSharding, PartitionSpec

            self._rep_sharding = NamedSharding(self.mesh, PartitionSpec())
        else:
            self._rep_sharding = None
        # Under a mesh the Pallas kernels run shard_mapped over tp (heads
        # are embarrassingly parallel); the model needs the mesh object.
        self.adapter: ModelAdapter = get_model(
            config.model, dtype=config.dtype, attention_impl=impl,
            mesh=self.mesh,
        )
        acfg = self.adapter.config
        if not hasattr(acfg, "num_heads"):
            acfg = acfg.base
        #: a model with state-space layers keeps a recurrent state a
        #: sequence in a slot pool beside the pages (models/nemotron_h.py);
        #: slots for every running sequence plus an eighth more, so that a
        #: successor admitted ahead of a row certain to end (`next_batch`)
        #: finds one while the leaver still holds its own
        self._stateful = self.adapter.state_layers > 0
        self._state_slots = (
            config.max_seqs + max(1, config.max_seqs // 8)
            if self._stateful else 0
        )
        #: a slot that holds KV written by position (a window layer's ring,
        #: models/dots3.py) keeps one generation: `_row_tables`
        self._state_in_place = self.adapter.state_in_place
        if self._stateful and not self._state_in_place:
            self._refuse_for_state(config)
        self._refuse_for_adapter(config)
        # the device's running count of what its walks and chunk tiles
        # read: a copy taken behind each dispatch (the cache itself is
        # donated to the next one), read where that dispatch's ids are
        self._walk_peek = None
        if self.adapter.walk_pages is not None:
            copy = jax.jit(lambda count: count + 0)
            self._walk_peek = lambda kv: copy(self.adapter.walk_pages(kv))
        self._walk_seen = np.zeros(len(_WALK_COUNTS), np.int64)
        if mc.tp > 1:
            # MLA's shared-latent cache replicates over tp (the q heads
            # still shard) — only head-sharded caches need kv divisibility.
            kv_ok = (
                getattr(acfg, "mqa_latent_cache", False)
                or acfg.num_kv_heads % mc.tp == 0
            )
            if acfg.num_heads % mc.tp or not kv_ok:
                raise ValueError(
                    f"tp={mc.tp} must divide num_heads ({acfg.num_heads}) "
                    f"and num_kv_heads ({acfg.num_kv_heads}) for "
                    "head-sharded attention"
                )
        if (
            config.kv_quantize
            and getattr(acfg, "attention_impl", "xla") in ("pallas", "hybrid")
            and jax.default_backend() == "tpu"
        ):
            local_kv = acfg.num_kv_heads // mc.tp
            if local_kv % 4:
                # Mosaic packs four 8-bit rows into one 32-bit sublane, so
                # a page's [S, Hkv, D] DMA needs Hkv per shard in fours
                raise ValueError(
                    f"kv_quantize={config.kv_quantize!r} with the Pallas "
                    f"kernels needs kv heads per shard in multiples of 4; "
                    f"{acfg.num_kv_heads} kv heads over tp={mc.tp} leaves "
                    f"{local_kv} — lower tp or use attention_impl='xla'"
                )
        if config.host_kv_cache_bytes > 0 or config.disk_kv_cache_bytes > 0:
            from dynamo_tpu.kvbm import TieredPageAllocator

            # Cross-host meshes tier PER-HOST SHARDS: every replica runs
            # the same (lockstep-deterministic) tier decisions, extract
            # hands each host its own Hkv slice, inject reassembles the
            # global array from the local slices — so G2/G3 capacity
            # scales with hosts and no host ever addresses a remote
            # shard. The async double-buffered extract stays
            # single-process (its staged arrays materialize via
            # np.asarray, which a multi-host global array refuses).
            disk_dir = config.disk_kv_cache_dir
            if self._multiproc and disk_dir:
                # disk entries are keyed by seq_hash alone; co-located
                # processes sharing one dir would overwrite each other's
                # per-host slices (same shapes, silently wrong heads)
                disk_dir = os.path.join(
                    disk_dir, f"host{jax.process_index()}"
                )
            self.allocator: PageAllocator = TieredPageAllocator(
                config.num_pages,
                config.page_size,
                extract_fn=self.extract_pages,
                extract_async_fn=(
                    None if self._multiproc else self.extract_pages_async
                ),
                inject_fn=self.inject_pages,
                host_bytes=config.host_kv_cache_bytes,
                disk_bytes=config.disk_kv_cache_bytes,
                disk_dir=disk_dir,
                on_event=on_kv_event,
                on_tier_event=on_tier_event,
            )
        else:
            self.allocator = PageAllocator(
                config.num_pages, config.page_size, on_event=on_kv_event,
                state_slots=self._state_slots,
            )
        self.scheduler = Scheduler(config, self.allocator)
        self.metrics.kv_total_pages = config.num_pages - 1
        self.metrics.state_slots = self._state_slots
        #: mid-decode deadline expiries, bumped by the runner (its abort
        #: path) — folded with the scheduler's pre-admission drops into
        #: metrics.deadline_expired
        self._runner_deadline_expired = 0
        self._jit_cache: dict[tuple, Callable] = {}
        #: compile counter by program kind (prefill/decode/mixed/...) —
        #: published in the worker's fleet frame as per-kind labels
        self.compiles_by_kind: dict[str, int] = {}
        #: the compile table (docs/observability.md "Debugging a slow or
        #: stuck worker"): cache_key -> {kind, key, compile_ms}, one entry
        #: a program's first call; programs_report() rolls it up by kind
        #: (GET /v1/debug/programs)
        self.programs: dict[tuple, dict] = {}
        #: flight recorder (config.flight_recorder): bounded ring of
        #: per-step records appended at deque cost from step(); None
        #: when disabled — the token path is bit-identical either way
        if config.flight_recorder:
            from dynamo_tpu.telemetry.flight import FlightRecorder

            self.flight: Optional["FlightRecorder"] = FlightRecorder(
                config.flight_ring
            )
            # the recorder's dry clock rides the metrics: `phase` finds
            # it where it finds the counters (runner phases too)
            self.metrics.dry_clock = DryClock(self.metrics)
        else:
            self.flight = None
        #: armed jax.profiler capture (request_profile): {steps_left,
        #: dir, started}; consumed by _profile_tick on the engine thread
        self._profile: Optional[dict] = None
        self._profile_lock = threading.Lock()
        #: set (weakly) by AsyncEngineRunner when a stall watchdog is
        #: attached, so the in-process debug surface can list diagnoses
        self._watchdog_ref = None
        # in-process debug surface (GET /v1/debug/*): weak registration,
        # a GC'd engine drops out
        from dynamo_tpu.telemetry import debug as _debug

        self.debug_name = _debug.register_engine(self)
        #: fleet telemetry plane (config.fleet_telemetry; mutable so the
        #: bench A/B can toggle one warm engine): SLO sketches + the
        #: throughput window. All host-side — the token path never reads
        #: them.
        self._fleet_telemetry = config.fleet_telemetry
        if self._fleet_telemetry:
            from dynamo_tpu.telemetry.slo import SloTracker

            self.slo: Optional["SloTracker"] = SloTracker()
        else:
            self.slo = None
        #: per-request SLO marks: rid -> [ttft_ms|None, itl_sum_ms,
        #: itl_samples, last_emit_perf_t]
        self._slo_marks: dict[str, list] = {}
        #: (perf_t, tokens_computed) per recent step, for the windowed
        #: tokens/s gauge
        from collections import deque

        self._thru_window: deque = deque()
        self._thru_window_s = 10.0
        #: running sum of the window's token counts (kept in step with
        #: append/popleft so _refresh_metrics stays O(evicted), not
        #: O(window) — the window holds thousands of entries at speed)
        self._thru_tokens = 0
        #: adaptive speculation: steps left on the fused path after a
        #: low-acceptance spec dispatch
        self._spec_cooldown = 0
        #: draft-model speculation (config.spec_draft_model): a second
        #: adapter + param tree + its own page pool. The pool shares the
        #: TARGET allocator's page ids/accounting — request page tables
        #: address both pools, so no second allocator exists.
        self._spec_draft = config.spec_draft_model is not None
        self.draft_adapter: Optional[ModelAdapter] = None
        self.draft_params = None
        self.draft_kv = None
        #: chained spec dispatch in flight (overlap pipeline for the
        #: draft path; None when idle or chaining is off)
        self._inflight_spec: Optional[_InflightSpec] = None
        #: (perf_t, drafted, accepted) per spec step + running sums, for
        #: the windowed live acceptance-rate gauge
        self._spec_window: deque = deque()  # deque imported above
        self._spec_window_s = 60.0
        self._spec_win_drafted = 0
        self._spec_win_accepted = 0
        #: overlapped decode: the one speculative in-flight dispatch (or
        #: None). Carried ACROSS hosts since the logical-axis refactor:
        #: chained dispatch feeds tokens on-device (replicated outputs),
        #: so the lagged readback (_finish_decode) is the only per-window
        #: host sync and it is identical on every lockstep replica. Off
        #: under prompt-lookup speculation (drafts need host tokens).
        self._inflight: Optional[_Launched] = None
        #: wall seconds of a pure decode dispatch: the longest of the
        #: recent ones, fading a tenth a dispatch (`takers_wait_s`)
        self._decode_wall_s = 0.0
        self._overlap_enabled = (
            config.overlap_decode and config.spec_ngram <= 0
        )
        #: stall-free mixed prefill+decode steps: off under prompt-lookup
        #: speculation (the verify program owns the decode batch). The
        #: scheduler only emits `mixed` when this holds. Multi-host runs
        #: keep it: batch assembly is event-log deterministic, the fused
        #: program's sampled ids come back replicated.
        self._mixed_enabled = (
            config.mixed_steps and config.spec_ngram <= 0
        )
        self.scheduler.mixed_enabled = self._mixed_enabled
        #: per-request last token-emission mark for the decode-stall
        #: histogram: request_id -> (perf_counter at emission, prefill+
        #: mixed dispatch count at emission). A later emission whose
        #: dispatch count advanced observes the gap as
        #: dynamo_tpu_phase_decode_stall_ms — prefill-attributed stalls
        #: only, which is exactly what mixed steps collapse.
        self._last_emit: dict[str, tuple[float, int]] = {}

        with phase(self.metrics, "engine.boot.weights", "boot_weights_ms"):
            params = self._boot_params(config, params, checkpoint_path)
            if self.mesh is not None:
                specs = self.adapter.param_specs(
                    quantized=bool(config.quantize))
                params = self._put_global(
                    params, shardings_for(self.mesh, specs))
            # the phase's time is its own, not the next one's
            jax.block_until_ready(params)
        with phase(self.metrics, "engine.boot.pools", "boot_pools_ms"):
            kv = self.adapter.init_kv(
                config.num_pages, config.page_size,
                kv_quantize=config.kv_quantize,
                **({"state_slots": self._state_slots}
                   if self._stateful else {}),
            )
            if self.mesh is not None:
                kv = self._put_global(
                    kv,
                    shardings_for(
                        self.mesh,
                        self.adapter.kv_spec(kv_quantize=config.kv_quantize),
                    ),
                )
            self.params = params
            self.kv = kv
            if self._spec_draft:
                self._init_draft_model(config, impl)
            jax.block_until_ready((kv, self.draft_kv))
        # KV-pool byte gauges: actual device bytes (quantized pages +
        # scale planes) vs what the same pool costs at the model dtype —
        # the ~2x effective-capacity claim, measured not asserted.
        m = self.metrics
        m.state_pool_bytes = int(
            sum(x.nbytes for x in self._state_pools(kv))
        )
        m.kv_pool_bytes = int(
            sum(x.nbytes for x in jax.tree.leaves(kv))
            + sum(x.nbytes for x in jax.tree.leaves(self.draft_kv))
        ) - m.state_pool_bytes
        model_itemsize = jnp.dtype(
            getattr(self.adapter.config, "dtype", None)
            or self.adapter.config.base.dtype
        ).itemsize
        m.kv_pool_bytes_dense_equiv = int(
            (kv.k.size + kv.v.size) * model_itemsize
        )
        m.kv_free_pages = self.allocator.num_free
        # HBM accounting plane (GET /v1/debug/memory): the param trees
        # never change after construction, so their per-device shard
        # bytes and per-sharding-spec grouping are computed once here;
        # memory_report() joins them with the live KV pool / program
        # scratch / device memory_stats on every call.
        self._weights_by_device = self._per_device_bytes(
            (self.params, self.draft_params)
        )
        self._param_groups = self._param_group_specs()
        self.refresh_memory_metrics()
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            # ndim 3 covers mm_embeds [B, T, H]
            self._batch_shardings = {
                nd: NamedSharding(self.mesh, batch_spec(nd))
                for nd in (1, 2, 3)
            }
        else:
            self._batch_shardings = None

    def _boot_params(self, config: EngineConfig, params, checkpoint_path):
        """The model's parameter tree as the engine serves it: the
        caller's, a checkpoint's, or random ones; quantized where the
        configuration says so."""
        pre_quantized = False
        if params is None:
            checkpoint_path = checkpoint_path or self.adapter.default_checkpoint
            if checkpoint_path is not None and self.adapter.load_params:
                params = self.adapter.load_params(checkpoint_path)
            elif (
                config.quantize == "int8"
                and self.adapter.init_params_quantized is not None
            ):
                # straight into int8 layout: init+quantize would peak at
                # full-dtype model size (16GB for 8B — over v5e HBM)
                logger.info(
                    "initializing random int8 params for %s", config.model
                )
                params = self.adapter.init_params_quantized(jax.random.key(0))
                pre_quantized = True
            else:
                logger.info("initializing random params for %s", config.model)
                params = self.adapter.init_params(jax.random.key(0))
        if config.quantize and not pre_quantized:
            if config.quantize != "int8":
                raise ValueError(
                    f"unsupported quantize={config.quantize!r}; use int8"
                )
            if self.adapter.quantize_params is None:
                raise ValueError(
                    f"--quantize int8: the {config.model!r} adapter has no "
                    "quantized layout (Llama-family models support it)"
                )
            params = self.adapter.quantize_params(params)
        return params

    @staticmethod
    def _state_pools(kv) -> tuple:
        """The cache's slot pools of recurrent state (none for a family
        whose only per-sequence state is pages)."""
        return tuple(
            x for x in (getattr(kv, name, None)
                        for name in ("conv", "ssm", "ring", "ring_pe",
                                     "ring_v"))
            if x is not None
        )

    def _refuse_for_state(self, config: EngineConfig) -> None:
        """A sequence of a model with state-space layers is its pages AND
        its recurrent state. What moves, shares, narrows or rewinds pages
        alone would serve half a sequence: refused at start-up."""
        name = config.model

        def no(what: str, instead: str):
            raise ValueError(
                f"{name} has state-space layers (a recurrent state a "
                f"sequence beside its KV pages): {what} is not supported "
                f"for it; {instead}"
            )

        if config.kv_quantize:
            no(f"kv_quantize={config.kv_quantize!r}",
               "run with kv_quantize=None")
        if config.host_kv_cache_bytes > 0 or config.disk_kv_cache_bytes > 0:
            no("KVBM offload (host_kv_cache_bytes / disk_kv_cache_bytes)",
               "pages in a lower tier would come back without the state "
               "at their boundary; run without the tiers")
        if config.spec_ngram > 0 or config.spec_draft_model is not None:
            no("speculative decoding (spec_ngram / spec_draft_*)",
               "a rejected draft token has already advanced the state; "
               "run without speculation")

    def _refuse_for_adapter(self, config: EngineConfig) -> None:
        """What the model's adapter says it cannot serve
        (`ModelAdapter.refuses`): refused at start-up, with its reason."""
        asked = {
            "kv_tiers": (config.host_kv_cache_bytes > 0
                         or config.disk_kv_cache_bytes > 0),
            "speculation": (config.spec_ngram > 0
                            or config.spec_draft_model is not None),
        }
        for what, why in self.adapter.refuses:
            if asked.get(what):
                raise ValueError(
                    f"{config.model}: {what} is not supported for it ({why})"
                )

    def _refuse_state_transfer(self, what: str) -> None:
        """Guard of the page-movement surface (disagg transfer planes,
        handover, tier promotion): pages of a stateful model never travel
        without their state, nor a page without all its residents."""
        for refused, why in self.adapter.refuses:
            if refused == "page_transfer":
                raise ValueError(
                    f"{self.config.model}: {what} is not supported for it "
                    f"({why})"
                )
        if self._stateful:
            raise ValueError(
                f"{self.config.model} has state-space layers: {what} would "
                "move a sequence's KV pages without its recurrent state; "
                "disaggregated prefill, handover and KV tiers are not "
                "supported for it (ROADMAP R8)"
            )

    def _row_tables(self, pt: np.ndarray, reqs):
        """What a step function takes as `pt`: the page tables [B, MP]
        and, for a model with state-space layers, beside them the rows'
        state entries [B, 2] (read, write): a row reads its state where
        the last dispatch TAKEN left it (generation `state_gen` of its
        slot) and writes the other generation, which becomes the state
        only when this dispatch is taken (`_commit_state`). Padding rows
        keep (0, 0), the null slot. A slot benign in place
        (`ModelAdapter.state_in_place`) has one generation: both entries
        are the row's slot."""
        if not self._stateful:
            return pt
        stride = 0 if self._state_in_place else self._state_slots + 1
        rows = np.zeros((pt.shape[0], 2), np.int32)
        for i, req in enumerate(reqs):
            rows[i, 0] = req.state_gen * stride + req.state_slot
            rows[i, 1] = (1 - req.state_gen) * stride + req.state_slot
        return (pt, rows)

    def _commit_state(self, reqs) -> None:
        """The dispatch that carried `reqs` is the real step: what it
        wrote IS each row's state from now on (nothing to do where the
        slot is benign in place)."""
        if self._stateful and not self._state_in_place:
            for req in reqs:
                req.state_gen ^= 1

    def _put_global(self, tree, shardings):
        """Place a host pytree onto the mesh. Single-process: device_put.
        Multi-process: every host holds the identical full copy, so each
        assembles its addressable shards via make_array_from_callback
        (device_put cannot target non-addressable devices)."""
        if not self._multiproc:
            return jax.device_put(tree, shardings)

        def put(x, sh):
            h = np.asarray(x)
            return jax.make_array_from_callback(
                h.shape, sh, lambda idx: h[idx]
            )

        return jax.tree.map(put, tree, shardings)

    def _dev(self, arr: np.ndarray):
        """Host batch array -> device, dp-sharded along dim 0 on a mesh.

        Single-process: batches not divisible by dp (B=1 prefill, small
        decode buckets) are left for jit to reshard — an explicit
        device_put would raise. Multi-process: every input must be an
        explicit global array (replicated when not dp-divisible); the
        host copies are identical by the lockstep contract."""
        if self._multiproc:
            arr = np.asarray(arr)
            dp = self.mesh.shape.get("dp", 1)
            if dp > 1 and arr.shape[0] % dp == 0:
                sh = self._batch_shardings[arr.ndim]
            else:
                sh = self._rep_sharding
            return jax.make_array_from_callback(
                arr.shape, sh, lambda idx: arr[idx]
            )
        x = jnp.asarray(arr)
        if self._batch_shardings is not None:
            dp = self.mesh.shape.get("dp", 1)
            if dp > 1 and arr.shape[0] % dp == 0:
                x = jax.device_put(x, self._batch_shardings[arr.ndim])
        return x

    def _dev_tree(self, tree):
        """All host inputs of one dispatch -> device in a SINGLE batched
        transfer. A dispatch ships ~4-14 small arrays (tokens/positions/
        valid/page-table + sampling/penalty/bias planes). On the plain
        single-chip path jax.device_put of the whole pytree lands
        everything in one batched_device_put; sharded/multi-process paths
        keep the per-leaf placement rules of _dev."""
        if self._multiproc or self._batch_shardings is not None:
            return jax.tree.map(self._dev, tree)
        return jax.device_put(tree)

    def _init_draft_model(self, config: EngineConfig, impl: str) -> None:
        """Load the speculation draft model (config.spec_draft_model): a
        second adapter + param tree and a second KV pool addressed by the
        SAME page ids as the target pool — request page tables index both,
        so the PageAllocator's accounting covers draft pages for free.

        Self-draft (draft name == target model, no draft checkpoint)
        shares the target's param tree instead of loading a copy: zero
        extra HBM, acceptance ~1 under greedy — the pipeline-validation /
        upper-bound harness bench.py's spec_ab uses."""
        if self._multiproc:
            raise ValueError(
                "spec_draft_model is not supported on multi-process SPMD "
                "meshes yet (the chained dispatch feedback is per-process)"
            )
        self.draft_adapter = get_model(
            config.spec_draft_model, dtype=config.dtype,
            attention_impl=impl, mesh=self.mesh,
        )
        if self.draft_adapter.vocab_size != self.adapter.vocab_size:
            raise ValueError(
                f"draft model {config.spec_draft_model!r} has vocab "
                f"{self.draft_adapter.vocab_size} but target "
                f"{config.model!r} has {self.adapter.vocab_size} — "
                "speculation requires a shared tokenizer/vocabulary"
            )
        ckpt = (
            config.spec_draft_checkpoint
            or self.draft_adapter.default_checkpoint
        )
        if (
            config.spec_draft_model == config.model
            and config.spec_draft_checkpoint is None
        ):
            # self-draft: share the tree. Checked BEFORE the checkpoint
            # branch — when the model name IS a checkpoint dir/GGUF the
            # adapter carries a default_checkpoint, and loading it again
            # would duplicate the full target weights in HBM
            dparams = self.params
        elif ckpt is not None and self.draft_adapter.load_params:
            dparams = self.draft_adapter.load_params(ckpt)
        else:
            logger.info(
                "initializing random draft params for %s (acceptance "
                "will sit at chance until real weights are loaded)",
                config.spec_draft_model,
            )
            dparams = self.draft_adapter.init_params(jax.random.key(0))
        dkv = self.draft_adapter.init_kv(config.num_pages, config.page_size)
        if self.mesh is not None:
            if dparams is not self.params:
                dparams = self._put_global(
                    dparams,
                    shardings_for(
                        self.mesh, self.draft_adapter.param_specs()
                    ),
                )
            dkv = self._put_global(
                dkv, shardings_for(self.mesh, self.draft_adapter.kv_spec())
            )
        self.draft_params = dparams
        self.draft_kv = dkv

    # -- public API --------------------------------------------------------

    def add_request(
        self,
        request_id: str,
        prompt_tokens: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        mm_embeds: Optional[np.ndarray] = None,
        mm_positions: Sequence[int] = (),
        deadline: Optional[float] = None,
    ) -> Request:
        self._validate_bias(sampling)
        if mm_embeds is not None:
            mm_embeds = np.asarray(mm_embeds, np.float32)
            if len(mm_positions) != len(mm_embeds):
                raise ValueError(
                    f"{len(mm_embeds)} multimodal embeddings but "
                    f"{len(mm_positions)} placeholder positions"
                )
            hdim = self._hidden_size
            if mm_embeds.ndim != 2 or mm_embeds.shape[-1] != hdim:
                # Reject here, where the runner returns the error to THIS
                # client — a bad shape surfacing inside step() would wedge
                # the whole batch loop instead.
                raise ValueError(
                    f"multimodal embeddings must be [n, {hdim}] for this "
                    f"model; got {mm_embeds.shape}"
                )
            if any(
                not 0 <= p < len(prompt_tokens) for p in mm_positions
            ):
                raise ValueError(
                    "mm_positions out of range for the prompt"
                )
        req = Request(
            request_id=request_id,
            prompt_tokens=list(prompt_tokens),
            sampling=sampling or SamplingParams(),
            arrival_time=time.time(),
            deadline=deadline,
            mm_embeds=mm_embeds,
            mm_positions=tuple(mm_positions),
        )
        self.scheduler.add_request(req)
        self.metrics.requests_received += 1
        return req

    def abort_request(self, request_id: str) -> bool:
        self._last_emit.pop(request_id, None)
        self._slo_marks.pop(request_id, None)
        return self.scheduler.abort_request(request_id) is not None

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def step(self) -> list[StepOutput]:
        if self._profile is not None:
            self._profile_start()  # armed capture opens BEFORE this step
        # the loop's phases (`phase`) are this span's children, so a trace
        # viewer groups them by step
        with jax.profiler.StepTraceAnnotation(
            "engine.step", step_num=self.metrics.steps
        ):
            outputs, dispatched = self._step()
        if self._profile is not None and dispatched:
            # one dispatched step captured; counted after the step's span
            # closed, or a capture that stops here would lose it
            self._profile_count()
        return outputs

    def _step(self) -> tuple[list[StepOutput], bool]:
        """One engine step: (outputs, whether a batch was dispatched)."""
        with phase(
            self.metrics, "engine.schedule", "time_schedule_ms"
        ) as ph:
            batch = self.scheduler.schedule()
            outputs = self._drain_doomed()
            ph.note(
                kind=batch.kind if batch is not None else "none",
                waiting=self.scheduler.num_waiting(),
                running=self.scheduler.num_running(),
            )
        if batch is None or batch.kind not in ("decode", "mixed"):
            # A speculated decode step can only be the next decode step
            # or the decode half of a mixed step; a pure prefill (or a
            # drained queue) invalidates it.
            why = "no batch" if batch is None else "prefill scheduled"
            if self._inflight is not None:
                self._discard_inflight(why)
            if self._inflight_spec is not None:
                self._discard_inflight_spec(why)
        if batch is not None:
            t2 = time.perf_counter()  # after the drain: phase time is
            # dispatch+sync+postprocess only, as the field docs promise
            gen0 = self.metrics.generated_tokens
            # Dispatch counters increment BEFORE the run so emissions
            # inside it record the post-step mark (the decode-stall
            # histogram compares marks across emissions).
            # exemplar for this dispatch's bucket: any traced request in
            # the batch (None when tracing is off — zero extra work)
            batch_tid = self._batch_trace_id(batch)
            if batch.kind == "prefill":
                self.metrics.prefill_dispatches += 1
                outputs += self._run_prefill(batch)
                dt_ms = (time.perf_counter() - t2) * 1000.0
                self.metrics.time_prefill_ms += dt_ms
                phases.observe("prefill_ms", dt_ms, trace_id=batch_tid)
            elif batch.kind == "mixed":
                self.metrics.mixed_dispatches += 1
                outputs += self._run_mixed(batch)
                dt_ms = (time.perf_counter() - t2) * 1000.0
                self.metrics.time_mixed_ms += dt_ms
                phases.observe("mixed_step_ms", dt_ms, trace_id=batch_tid)
            else:
                self.metrics.decode_dispatches += 1
                outputs += self._run_decode(batch)
                dt_ms = (time.perf_counter() - t2) * 1000.0
                self.metrics.time_decode_ms += dt_ms
                # a dispatch read late (it landed during a stall) must
                # not read as a short one: keep the longest, fading
                self._decode_wall_s = max(
                    dt_ms / 1000.0, 0.9 * self._decode_wall_s
                )
                phases.observe("decode_step_ms", dt_ms, trace_id=batch_tid)
            self.metrics.steps += 1
            if self._fleet_telemetry:
                # tokens this step pushed through the model (prefill
                # chunk tokens + emitted decode tokens)
                step_toks = sum(p.length for p in batch.prefill) + (
                    self.metrics.generated_tokens - gen0
                )
                self._thru_window.append((time.perf_counter(), step_toks))
                self._thru_tokens += step_toks
            # taken every step, recorder or not, so the list stays short
            admit_waits = self.scheduler.take_admit_waits()
            if self.flight is not None:
                self.flight.record_step(
                    self.metrics,
                    kind=batch.kind,
                    step_ms=dt_ms,
                    n_decode=len(batch.decode),
                    b_decode=(
                        self.config.decode_bucket_for(len(batch.decode))
                        if batch.decode
                        else 0
                    ),
                    n_prefill=len(batch.prefill),
                    t_bucket=(
                        max(self._bucket_t(p.length) for p in batch.prefill)
                        if batch.prefill
                        else 0
                    ),
                    prefill_tokens=sum(p.length for p in batch.prefill),
                    waiting=self.scheduler.num_waiting(),
                    running=self.scheduler.num_running(),
                    free_pages=self.allocator.num_free,
                    active_pages=self.allocator.num_active,
                    watermark=max(
                        getattr(self.allocator, "watermark", 0),
                        self.metrics.kv_pages_watermark,
                    ),
                    ctx_min=min(
                        (r.num_tokens for r in batch.decode), default=0
                    ),
                    admit_wait_ms=admit_waits,
                    timeline=self.metrics.dry_clock.take(),
                )
        if not self.scheduler.has_work:
            # the wave ended on a sampled stop the speculation couldn't
            # predict: drop any dangling dispatch so device arrays free
            if self._inflight is not None:
                self._discard_inflight("idle")
            if self._inflight_spec is not None:
                self._discard_inflight_spec("idle")
            self._park_clock()
        self._refresh_metrics()
        return outputs, batch is not None

    def _drain_doomed(self) -> list[StepOutput]:
        """Finish requests the scheduler proved can never progress (or
        whose deadline expired pre-admission — those finish as ERROR)."""
        outputs = []
        for req, why, reason in self.scheduler.doomed:
            logger.error("request %s cannot progress: %s", req.request_id, why)
            self._last_emit.pop(req.request_id, None)
            self._slo_marks.pop(req.request_id, None)
            req.state = RequestState.FINISHED
            req.finish_reason = reason
            outputs.append(
                StepOutput(
                    request_id=req.request_id,
                    new_token_ids=(),
                    finish_reason=reason,
                )
            )
        self.scheduler.doomed.clear()
        return outputs

    def run_to_completion(self) -> dict[str, list[int]]:
        """Drain all queued work; returns request_id -> generated tokens."""
        done: dict[str, list[int]] = {}
        while self.has_work:
            for out in self.step():
                done.setdefault(out.request_id, []).extend(out.new_token_ids)
        return done

    # -- prefill -----------------------------------------------------------

    def _bucket_t(self, n: int) -> int:
        cap = max(self.config.prefill_chunk, 32)
        if n > cap:
            # The cap used to silently round DOWN, which would have
            # truncated the valid mask of an oversized piece. The
            # scheduler chunks at prefill_chunk, so this can only fire on
            # a scheduler bug — fail loudly instead of corrupting KV.
            raise ValueError(
                f"prefill piece of {n} tokens exceeds the T-bucket cap "
                f"{cap} (pieces must be chunked at prefill_chunk)"
            )
        if self.config.prefill_buckets:
            return next(t for t in self.config.prefill_buckets if t >= n)
        t = 32
        while t < n:
            t *= 2
        return min(t, cap)

    @staticmethod
    def _bucket_b(n: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return b

    def _run_prefill(
        self, batch: ScheduledBatch, mixed: bool = False
    ) -> list[StepOutput]:
        """Pieces grouped by T bucket run as one batched [B, T] program —
        many prompts prefill per dispatch instead of serial B=1 launches.
        `mixed` marks outputs emitted as part of a mixed step (the
        overlap split path runs the prefill half through here)."""
        outputs: list[StepOutput] = []
        if self._spec_draft:
            # the draft pool prefills alongside the target pool — this
            # also covers the prefix-cached region the target skipped
            # (cached pages hold target KV only; the draft must compute
            # its own), so spec_draft_pos reaches each piece's end
            self._spec_draft_cover(
                [
                    (p.request, p.start + p.length)
                    for p in batch.prefill
                    if p.request.mm_embeds is None
                ]
            )
        groups: dict[int, list] = {}
        for piece in batch.prefill:
            groups.setdefault(self._bucket_t(piece.length), []).append(piece)
        for t_bucket, pieces in sorted(groups.items()):
            outputs += self._run_prefill_group(t_bucket, pieces, mixed)
        return outputs

    def _run_prefill_group(
        self, t_bucket: int, pieces: list, mixed: bool
    ) -> list[StepOutput]:
        """One [B, T] prefill dispatch: the pieces of one T bucket."""
        m = self.metrics
        mp = self.config.max_pages_per_seq
        with phase(m, "engine.stage", "time_stage_ms"):
            b_bucket = self._bucket_b(len(pieces))
            tokens = np.zeros((b_bucket, t_bucket), np.int32)
            positions = np.zeros((b_bucket, t_bucket), np.int32)
            valid = np.zeros((b_bucket, t_bucket), bool)
            pt = np.zeros((b_bucket, mp), np.int32)
            last_idx = np.zeros(b_bucket, np.int32)
            any_last = False
            any_mm = any(p.request.mm_embeds is not None for p in pieces)
            mm_embeds = mm_mask = None
            if any_mm:
                mm_embeds = np.zeros(
                    (b_bucket, t_bucket, self._hidden_size), np.float32
                )
                mm_mask = np.zeros((b_bucket, t_bucket), bool)
            for i, piece in enumerate(pieces):
                req = piece.request
                chunk = req.all_tokens[piece.start : piece.start + piece.length]
                tokens[i, : piece.length] = chunk
                positions[i] = np.arange(t_bucket, dtype=np.int32) + piece.start
                valid[i, : piece.length] = True
                pt[i, : len(req.pages)] = req.pages
                last_idx[i] = piece.length - 1
                if piece.start + piece.length >= len(req.prompt_tokens):
                    any_last = True
                if req.mm_embeds is not None:
                    for j, pos in enumerate(req.mm_positions):
                        off = pos - piece.start
                        if 0 <= off < piece.length:
                            mm_embeds[i, off] = req.mm_embeds[j]
                            mm_mask[i, off] = True
            # a model that keeps no non-sampling twin samples every chunk
            # (a token of a piece that is not its prompt's last is unread)
            any_last = any_last or not self.adapter.step_twins

            host = {"base": (
                tokens, positions, valid,
                self._row_tables(pt, [p.request for p in pieces]),
            )}
            if any_mm:
                host["mm"] = (mm_embeds, mm_mask)
            # Every piece starting at 0 (un-chunked prompts, no prefix
            # hits — the common case) compiles a history-free program:
            # attention over the in-register chunk only, no page gather.
            first_chunk = all(p.start == 0 for p in pieces)
            lp = -1
            if any_last:
                reqs = [p.request for p in pieces]
                samp, all_greedy = self._sampling_arrays(reqs, pad_to=b_bucket)
                lp = self._batch_logprobs(reqs)
                # Penalties at prefill-sample time only matter when a
                # penalized request already HAS generated history — i.e. a
                # preempted request resuming via recompute.
                pen = self._batch_penalty_bucket(reqs)
                if pen and not any(self._penalty_history(r) for r in reqs):
                    pen = 0
                host.update(
                    samp=samp, last=last_idx,
                    pen=self._penalty_arrays(reqs, b_bucket, pen)
                    if pen else (),
                )
                bias = self._batch_bias(reqs)
                if bias:
                    host["bias"] = self._bias_arrays(reqs, b_bucket)
                dev = self._dev_tree(host)
                fn = self._get_step_fn(
                    "prefill", b_bucket, t_bucket, greedy=all_greedy,
                    mm=any_mm, first_chunk=first_chunk, lp=lp, pen=pen,
                    bias=bias,
                )
                tail = (dev["last"], *dev["samp"], *dev["pen"])
                # mm/bias ride as keywords: the positional tail of the
                # shared step signature belongs to the penalty args.
                kwargs = dict(dev.get("bias", {}))
                if any_mm:
                    kwargs.update(
                        mm_embeds=dev["mm"][0], mm_mask=dev["mm"][1]
                    )
            else:
                # No piece finishes its prompt: KV writes only — skip the
                # vocab-sized logits + sampling entirely.
                dev = self._dev_tree(host)
                fn = self._get_step_fn(
                    "prefill_nosample", b_bucket, t_bucket, mm=any_mm,
                    first_chunk=first_chunk,
                )
                tail, kwargs = dev.get("mm", ()), {}
            args = (self.params, *dev["base"][:3], self.kv, dev["base"][3])
        with phase(
            m, "engine.launch", kind="prefill", rows=b_bucket, t=t_bucket,
            speculative=0, n_rows=len(pieces), b_pre=b_bucket,
            chunk_tokens=sum(p.length for p in pieces),
        ) as ph:
            out = fn(*args, *tail, **kwargs)
            # a chunk that samples nothing returns the cache alone
            seq = ph.launched(
                out[0] if any_last else jax.tree_util.tree_leaves(out)[0]
            )
        self._commit_state([p.request for p in pieces])
        ids = lp_data = None
        if not any_last:
            self.kv = out
        else:
            if lp >= 0:
                token_ids, lp_raw, self.kv = out
            else:
                token_ids, self.kv = out
            with phase(
                m, "engine.readback", "time_decode_sync_ms", lagged=0,
                **_seq_arg(seq),
            ):
                if lp >= 0:
                    lp_data = tuple(np.asarray(x) for x in lp_raw)
                ids = np.asarray(token_ids)
        outputs: list[StepOutput] = []
        with phase(m, "engine.postprocess", "time_decode_host_ms") as ph:
            self._prefill_postprocess(
                pieces, ids, lp_data, 0, outputs, mixed
            )
            ph.note(tokens=len(outputs), finished=self._n_finished(outputs))
        return outputs

    @staticmethod
    def _n_finished(outputs: list[StepOutput]) -> int:
        return sum(1 for o in outputs if o.finish_reason is not None)

    def _prefill_postprocess(
        self, pieces: list, ids, lp_data, row0: int,
        outputs: list[StepOutput], mixed: bool,
    ) -> None:
        """Host half of a prefill dispatch: count the chunk computed,
        register its filled pages and, where a piece completed its
        prompt, accept the first token (row `row0 + i` of the sampled
        ids / logprob arrays, the latter shaped [rows(, N)])."""
        for i, piece in enumerate(pieces):
            req = piece.request
            req.num_computed_tokens += piece.length
            self._register_pages(req)
            if req.prefill_done:
                req.state = RequestState.DECODE
                lps = tops = None
                row = row0 + i
                if lp_data is not None and req.sampling.logprobs >= 0:
                    lps = (float(lp_data[0][row]),)
                    nk = req.sampling.logprobs
                    if nk > 0:
                        tops = (
                            tuple(
                                (
                                    int(lp_data[1][row, j]),
                                    float(lp_data[2][row, j]),
                                )
                                for j in range(
                                    min(nk, lp_data[1].shape[-1])
                                )
                            ),
                        )
                outputs.extend(
                    self._accept_token(
                        req, int(ids[row]), first=True, lps=lps,
                        tops=tops, mixed=mixed,
                    )
                )

    # -- decode ------------------------------------------------------------

    @staticmethod
    def _pow2_floor(k: int) -> int:
        """Largest power of two <= k (k >= 1). Fused-step counts snap to
        powers of two so the decode_multi program family stays
        log-sized — every distinct k is a full-model compile."""
        p = 1
        while p * 2 <= k:
            p *= 2
        return p

    def _pick_decode_steps(
        self, reqs: list[Request], ahead: Optional[list[int]] = None
    ) -> int:
        """Fused steps for this dispatch: capped by config, by remaining
        context room, and dropped to 1 when admission is pending (so new
        arrivals don't wait K steps) or when the pool can't pre-grow every
        sequence's page table K tokens ahead. `ahead[i]` are the tokens a
        dispatch still on the device adds to row i first (a dispatch
        launched ahead of its batch, `_speculate`)."""
        k = self.config.decode_steps
        if k <= 1:
            return 1
        ahead = ahead or [0] * len(reqs)
        # Admission pending AND actually possible this step: stay responsive.
        # (A backlog that can't admit anyway must not forfeit fusion.)
        if self.scheduler.num_waiting() > 0 and self.scheduler.can_admit_head():
            return 1
        cap_tokens = self.config.max_pages_per_seq * self.config.page_size
        for req, a in zip(reqs, ahead):
            k = min(k, self.config.max_context - req.num_tokens - a + 1)
            k = min(k, cap_tokens - req.num_tokens - a + 1)
        # Cover the longest remaining completion rounded UP to a power of
        # two (the decode_multi program family stays small — every distinct
        # k is a full-model compile). Requests finishing mid-scan discard
        # their overshoot in the accept loop, so the tail of a wave runs as
        # ONE dispatch instead of a halving ladder of dispatches, each a
        # full host sync (the sync, not the compute, is what costs).
        rem_max = 0
        for req, a in zip(reqs, ahead):
            s = req.sampling
            rem_max = max(
                rem_max,
                s.max_tokens - len(req.output_tokens) - req.num_emitted - a,
            )
        p = 1
        while p < max(1, rem_max):
            p *= 2
        k = min(k, p)
        # The context/page caps above can leave an arbitrary k: snap DOWN
        # so cap-bound sequences don't each compile a fresh decode_multi
        # program (k=37, 35, 33, ... would).
        k = self._pow2_floor(k)
        if k <= 1:
            return 1
        if not self._grow_pages_for(reqs, [a + k - 1 for a in ahead]):
            return 1  # single-step path handles pressure via preemption
        return k

    def _grow_pages_for(self, reqs: list[Request], ahead: list[int]) -> bool:
        """Grow every request's page table to cover num_tokens + its
        `ahead`, with an aggregate need-vs-free pre-check so pool pressure
        never half-grows the batch. False => nothing was allocated."""
        ps = self.config.page_size
        need = 0
        per_req = []
        for req, a in zip(reqs, ahead):
            extra = -(-(req.num_tokens + a) // ps) - len(req.pages)
            per_req.append(max(0, extra))
            need += max(0, extra)
        if need > self.allocator.num_free:
            return False
        for req, extra in zip(reqs, per_req):
            if extra:
                got = self.allocator.allocate(extra)
                if got is None:
                    return False  # unreachable given the pre-check
                req.pages.extend(got)
        return True

    # -- speculative decode (prompt lookup / n-gram) ------------------------

    def _spec_eligible(self, reqs: list[Request]) -> bool:
        """Draft-model speculation verifies any sampling configuration
        the on-device accept scan threads — temperature/top-p/top-k
        (exact rejection sampling), penalties and logit_bias/min_tokens
        ride the same row-space plumbing as the plain programs. Only
        logprob reporting (per-position logprob state isn't threaded)
        and multimodal requests (the draft has no mm path) fall back to
        plain decode. Draft-free prompt lookup keeps its greedy-only
        restriction: its verify program has no sampling plane at all."""
        if self._spec_draft:
            return not any(
                r.sampling.logprobs >= 0 or r.mm_embeds is not None
                for r in reqs
            )
        if self.config.spec_ngram <= 0:
            return False
        for r in reqs:
            s = r.sampling
            if (
                s.temperature > 0.0
                or s.logprobs >= 0
                or s.frequency_penalty
                or s.presence_penalty
                or s.repetition_penalty != 1.0
                or s.logit_bias
                or s.min_tokens
            ):
                return False
        return True

    def _spec_active(self, reqs: list[Request]) -> bool:
        """Whether THIS step's decode batch runs through a speculative
        verify program. One bookkeeping point for eligibility + the
        acceptance cooldown, shared by _run_decode and _run_mixed (which
        asks before splitting the spec verify out as its decode leg) —
        call it at most once per engine step."""
        if not (self._spec_draft or self.config.spec_ngram > 0):
            return False
        if self._spec_eligible(reqs):
            if self._spec_cooldown <= 0:
                return True
            self._spec_cooldown -= 1
            self.metrics.spec_skipped_cooldown += 1
        else:
            self.metrics.spec_skipped_ineligible += 1
        return False

    def _propose_drafts(self, req: Request, s: int) -> list[int]:
        """Prompt-lookup proposal: the s tokens that followed the LAST
        earlier occurrence of the sequence's trailing n-gram. No match =>
        zero-pads (they simply fail verification; one token still lands).

        The n-gram index is maintained incrementally on the request —
        each position is indexed exactly once over the request's lifetime
        (amortized O(1) per decode step instead of an O(L) rescan)."""
        n = self.config.spec_ngram_match
        if req.num_tokens <= n:
            return [0] * s
        if req.spec_index is not None and (
            req.num_tokens - len(req.spec_ctx) > len(req.output_tokens)
        ):
            # Preemption folded outputs into the prompt while spec state
            # was stale — the delta can no longer be read off
            # output_tokens. Rebuild rather than desync the index.
            req.spec_index = None
        if req.spec_index is None:
            req.spec_index = {}
            req.spec_ctx = req.all_tokens  # one full copy, then appended
            req.spec_indexed_upto = 0
        elif len(req.spec_ctx) < req.num_tokens:
            delta = req.num_tokens - len(req.spec_ctx)
            req.spec_ctx.extend(req.output_tokens[-delta:])
        ctx = req.spec_ctx
        # index every n-gram start except the trailing one (a tail must
        # match an EARLIER occurrence)
        for j in range(req.spec_indexed_upto, len(ctx) - n):
            req.spec_index[tuple(ctx[j : j + n])] = j
        req.spec_indexed_upto = max(req.spec_indexed_upto, len(ctx) - n)
        j = req.spec_index.get(tuple(ctx[-n:]))
        if j is None:
            return [0] * s
        cont = ctx[j + n : j + n + s]
        return cont + [0] * (s - len(cont))

    def _run_decode_spec(self, reqs: list[Request]) -> list[StepOutput]:
        """One verify dispatch: [last_token, draft_0..draft_{S-1}] runs
        through the model like a prefill chunk (causal over the window,
        paged KV behind it); target tokens are the argmax at every
        position. Accept matched drafts + the model's token at the first
        mismatch — per request, 1..S+1 tokens per step. Stale KV written
        for rejected positions is overwritten when the real tokens reach
        those positions; attention never reads past a sequence's length."""
        s = self.config.spec_ngram
        b_bucket = self.config.decode_bucket_for(len(reqs))
        mp = self.config.max_pages_per_seq
        t = s + 1
        cap_tokens = mp * self.config.page_size
        # Pre-grow pages to cover the verify window; pressure => no spec
        # (the aggregate pre-check in _grow_pages_for means a refusal
        # claims nothing).
        for req in reqs:
            if req.num_tokens + s > min(cap_tokens, self.config.max_context):
                return self._run_decode_plain(reqs)
        if not self._grow_pages_for(reqs, [s] * len(reqs)):
            return self._run_decode_plain(reqs)

        m = self.metrics
        # the same phases as _run_decode_plain (flight-recorder deltas
        # and the dispatch/sync/host split must not go blind under
        # speculation): array build + launch = dispatch, the blocking
        # device→host read = sync, the accept scan = host
        with phase(
            m, "engine.stage", "time_stage_ms", "time_decode_dispatch_ms"
        ):
            tokens = np.zeros((b_bucket, t), np.int32)
            positions = np.zeros((b_bucket, t), np.int32)
            valid = np.zeros((b_bucket, t), bool)
            pt = np.zeros((b_bucket, mp), np.int32)
            drafts = np.zeros((b_bucket, s), np.int32)
            for i, req in enumerate(reqs):
                d = self._propose_drafts(req, s)
                drafts[i] = d
                tokens[i, 0] = req.all_tokens[-1]
                tokens[i, 1:] = d
                positions[i] = (
                    np.arange(t, dtype=np.int32) + req.num_tokens - 1
                )
                valid[i] = True
                pt[i, : len(req.pages)] = req.pages

            fn = self._get_step_fn("spec_verify", b_bucket, t)
            d_tokens, d_positions, d_valid, d_pt = self._dev_tree(
                (tokens, positions, valid, pt)
            )
        with phase(
            m, "engine.launch", "time_decode_dispatch_ms",
            kind="spec_verify", rows=b_bucket, t=t, speculative=0,
            n_rows=len(reqs),
        ) as ph:
            target_ids, self.kv = fn(
                self.params, d_tokens, d_positions, d_valid, self.kv, d_pt,
            )
            seq = ph.launched(target_ids)
        with phase(
            m, "engine.readback", "time_decode_sync_ms", lagged=0,
            **_seq_arg(seq),
        ):
            target = np.asarray(target_ids)  # [B, t]
        outputs: list[StepOutput] = []
        step_drafted = step_accepted = 0
        with phase(m, "engine.postprocess", "time_decode_host_ms") as ph:
            for i, req in enumerate(reqs):
                accepted: list[int] = []
                finish: Optional[FinishReason] = None
                for j in range(t):
                    tok = int(target[i, j])
                    accepted.append(tok)
                    finish = self._finish_reason_for(req, tok, len(accepted))
                    if finish is not None:
                        break
                    if j < s and int(drafts[i, j]) != tok:
                        break  # draft diverged: the model's token lands
                step_drafted += s
                step_accepted += len(accepted) - 1
                req.num_computed_tokens += len(accepted)
                outputs.extend(
                    self._accept_tokens(req, accepted, finish, spec=True)
                )
                self._register_pages(req)
            self._note_spec_step(step_drafted, step_accepted)
            if (
                step_drafted
                and step_accepted / step_drafted
                < self.config.spec_min_accept_rate
            ):
                # Lookup is missing on this workload: revert to fused
                # multi-step decode for a while, then probe speculation
                # again.
                self._spec_cooldown = self.config.spec_cooldown_steps
            ph.note(
                tokens=step_accepted + len(reqs),
                finished=self._n_finished(outputs),
            )
        return outputs

    # -- speculative decode (draft model, fused on-device acceptance) ------

    def _note_spec_step(self, drafted: int, accepted: int) -> None:
        """Counters + the sliding window behind the live acceptance-rate
        gauge (shared by the prompt-lookup and draft-model paths)."""
        self.metrics.spec_drafted += drafted
        self.metrics.spec_accepted += accepted
        self._spec_window.append((time.perf_counter(), drafted, accepted))
        self._spec_win_drafted += drafted
        self._spec_win_accepted += accepted

    def _spec_draft_cover(self, spans) -> None:
        """Bring the DRAFT pool's KV up to date over `spans` = [(req,
        upto)]: chunked draft-model forward (KV writes only) over
        [req.spec_draft_pos, upto). The target prefill path calls this
        per piece — so the draft rides every prefill step, including the
        prefix-cached region the target skipped — and the spec decode
        path calls it when a request arrives in decode with a stale
        draft pool (disagg add_prefilled, fused-mixed prefills during an
        acceptance cooldown). Chunk r of every span runs before chunk
        r+1 of any (a mid-sequence chunk's attention reads the previous
        chunk's KV); within a round chunks batch by T bucket exactly
        like _run_prefill."""
        chunk = self.config.prefill_chunk
        mp = self.config.max_pages_per_seq
        rounds: list[list[tuple]] = []
        for req, upto in spans:
            start = req.spec_draft_pos
            r = 0
            while start < upto:
                take = min(chunk, upto - start)
                if r >= len(rounds):
                    rounds.append([])
                rounds[r].append((req, start, take))
                start += take
                r += 1
            req.spec_draft_pos = max(req.spec_draft_pos, upto)
        for round_items in rounds:
            groups: dict[int, list] = {}
            for item in round_items:
                groups.setdefault(self._bucket_t(item[2]), []).append(item)
            for t_bucket, items in sorted(groups.items()):
                b_bucket = self._bucket_b(len(items))
                tokens = np.zeros((b_bucket, t_bucket), np.int32)
                positions = np.zeros((b_bucket, t_bucket), np.int32)
                valid = np.zeros((b_bucket, t_bucket), bool)
                pt = np.zeros((b_bucket, mp), np.int32)
                for i, (req, start, length) in enumerate(items):
                    tokens[i, :length] = req.all_tokens[start : start + length]
                    positions[i] = (
                        np.arange(t_bucket, dtype=np.int32) + start
                    )
                    valid[i, :length] = True
                    pt[i, : len(req.pages)] = req.pages
                first_chunk = all(it[1] == 0 for it in items)
                fn = self._get_step_fn(
                    "spec_draft_prefill", b_bucket, t_bucket,
                    first_chunk=first_chunk,
                )
                d_tokens, d_positions, d_valid, d_pt = self._dev_tree(
                    (tokens, positions, valid, pt)
                )
                self.draft_kv = fn(
                    self.draft_params, d_tokens, d_positions, d_valid,
                    self.draft_kv, d_pt,
                )

    def _run_decode_spec_draft(
        self, reqs: list[Request], mixed: bool = False
    ) -> list[StepOutput]:
        """One draft-model spec step: a single fused program runs draft
        catch-up (the tokens accepted since the draft's last committed
        position) + S greedy draft proposals + the target verify forward
        + ON-DEVICE acceptance (bit-exact argmax for greedy rows, exact
        rejection sampling otherwise — sampling.spec_accept_step). Per
        request 1..S+1 tokens land per step. Composes with the overlap
        pipeline: when the batch is stable the NEXT spec dispatch chains
        off this one's device outputs (accepted window = out_ids masked
        by n_acc) before this one's ids reach the host."""
        s = self.config.spec_draft_tokens
        w = s + 1
        mp = self.config.max_pages_per_seq
        cap_tokens = mp * self.config.page_size
        for req in reqs:
            if req.num_tokens + s > min(cap_tokens, self.config.max_context):
                self._discard_inflight_spec("window over context cap")
                return self._run_decode_plain(reqs, mixed=mixed)
        if not self._grow_pages_for(reqs, [s] * len(reqs)):
            self._discard_inflight_spec("page pressure")
            return self._run_decode_plain(reqs, mixed=mixed)
        if self._inflight is not None:
            # a plain speculative dispatch (primed during a cooldown)
            # cannot serve the verify path
            self._discard_inflight("spec verify owns the decode batch")
        spans = [
            (req, req.num_tokens - 1)
            for req in reqs
            if req.num_tokens - req.spec_draft_pos > w
        ]
        if spans:
            self._spec_draft_cover(spans)
        b_bucket = self.config.decode_bucket_for(len(reqs))
        inflight, self._inflight_spec = self._inflight_spec, None
        if inflight is not None:
            if self._spec_inflight_matches(inflight, reqs):
                # the chained dispatch IS this step: chain the next one
                # (device never drains), then materialize the lagged ids
                self.metrics.overlap_hits += 1
                self._maybe_chain_spec(
                    reqs, b_bucket, inflight.out_ids, inflight.n_acc,
                    inflight.counters_v0, greedy=inflight.greedy,
                    bias=inflight.bias,
                )
                with phase(
                    self.metrics, "engine.readback", "time_decode_sync_ms",
                    lagged=1, **_seq_arg(inflight.seq),
                ):
                    out = np.asarray(inflight.out_ids)
                    drafts = np.asarray(inflight.draft_ids)
                    n_acc = np.asarray(inflight.n_acc)
                return self._spec_postprocess(
                    reqs, out, drafts, n_acc, mixed=mixed
                )
            self._inflight_spec = inflight
            self._discard_inflight_spec("decode batch changed")
        m = self.metrics
        with phase(
            m, "engine.stage", "time_stage_ms", "time_decode_dispatch_ms"
        ):
            win_tokens = np.zeros((b_bucket, w), np.int32)
            win_len = np.zeros(b_bucket, np.int32)
            pos0 = np.zeros(b_bucket, np.int32)
            pt = np.zeros((b_bucket, mp), np.int32)
            for i, req in enumerate(reqs):
                toks = req.all_tokens[req.spec_draft_pos :]
                win_tokens[i, : len(toks)] = toks
                win_len[i] = len(toks)
                pos0[i] = req.spec_draft_pos
                pt[i, : len(req.pages)] = req.pages
            samp, all_greedy = self._sampling_arrays(reqs, pad_to=b_bucket)
            pen = self._batch_penalty_bucket(reqs)
            pen_args = (
                self._penalty_arrays(reqs, b_bucket, pen) if pen else ()
            )
            bias = self._batch_bias(reqs)
            bias_kwargs = self._bias_arrays(reqs, b_bucket) if bias else {}
            host = {
                "base": (win_tokens, win_len, pos0, pt),
                "samp": samp, "pen": pen_args, "bias": bias_kwargs,
            }
            dev = self._dev_tree(host)
            d_tokens, d_len, d_pos0, d_pt = dev["base"]
            fn = self._get_step_fn(
                "spec_fused", b_bucket, w, greedy=all_greedy, pen=pen,
                bias=bias,
            )
        with phase(
            m, "engine.launch", "time_decode_dispatch_ms",
            kind="spec_fused", rows=b_bucket, t=w, speculative=0,
            n_rows=len(reqs),
        ) as ph:
            out_ids, draft_ids, n_acc, self.kv, self.draft_kv = fn(
                self.params, self.draft_params, d_tokens, d_len, d_pos0,
                self.kv, self.draft_kv, d_pt, *dev["samp"], *dev["pen"],
                **dev["bias"],
            )
            seq = ph.launched(out_ids)
        # keep the device busy past this step BEFORE blocking on its
        # result (same discipline as _run_decode_plain)
        self._maybe_chain_spec(
            reqs, b_bucket, out_ids, n_acc, samp[4],
            greedy=all_greedy, bias=bias,
        )
        with phase(
            m, "engine.readback", "time_decode_sync_ms", lagged=0,
            **_seq_arg(seq),
        ):
            out = np.asarray(out_ids)
            drafts = np.asarray(draft_ids)
            n_acc_h = np.asarray(n_acc)
        return self._spec_postprocess(reqs, out, drafts, n_acc_h, mixed=mixed)

    def _spec_postprocess(
        self, reqs: list[Request], out: np.ndarray, drafts: np.ndarray,
        n_acc: np.ndarray, mixed: bool = False,
    ) -> list[StepOutput]:
        """Host half of a draft-spec step: the same accept loop as the
        prompt-lookup path (accept matched drafts + the device's token at
        the first mismatch — the on-device scan already made out[i, j]
        the canonical token at each position), plus chain validation: a
        finish/stop truncation the device could not see invalidates the
        chained next dispatch."""
        s = self.config.spec_draft_tokens
        outputs: list[StepOutput] = []
        step_drafted = step_accepted = 0
        with phase(
            self.metrics, "engine.postprocess", "time_decode_host_ms"
        ) as ph:
            chain = self._inflight_spec  # chained for the NEXT step
            chain_ok = chain is not None
            for i, req in enumerate(reqs):
                accepted: list[int] = []
                finish: Optional[FinishReason] = None
                for j in range(s + 1):
                    tok = int(out[i, j])
                    accepted.append(tok)
                    finish = self._finish_reason_for(
                        req, tok, len(accepted)
                    )
                    if finish is not None:
                        break
                    if j < s and int(drafts[i, j]) != tok:
                        break
                step_drafted += s
                step_accepted += len(accepted) - 1
                # catch-up committed through the old last token; the
                # accepted tokens are the next step's window
                req.spec_draft_pos = req.num_tokens
                req.num_computed_tokens += len(accepted)
                if finish is not None or len(accepted) != int(n_acc[i]):
                    chain_ok = False
                outputs.extend(
                    self._accept_tokens(
                        req, accepted, finish, mixed=mixed, spec=True
                    )
                )
                self._register_pages(req)
            self._note_spec_step(step_drafted, step_accepted)
            if chain is not None:
                if chain_ok:
                    chain.expected_num_tokens = tuple(
                        r.num_tokens for r in reqs
                    )
                    chain.expected_out_len = tuple(
                        len(r.output_tokens) for r in reqs
                    )
                else:
                    self._discard_inflight_spec(
                        "acceptance diverged or finish"
                    )
            if (
                step_drafted
                and step_accepted / step_drafted
                < self.config.spec_min_accept_rate
            ):
                # the draft is missing on this workload: fall back to
                # the plain (overlapped/fused) path for a while, then
                # probe again
                self._spec_cooldown = self.config.spec_cooldown_steps
                self._discard_inflight_spec("acceptance cooldown")
            ph.note(
                tokens=step_accepted + len(reqs),
                finished=self._n_finished(outputs),
            )
        return outputs

    def _maybe_chain_spec(
        self, reqs: list[Request], b_bucket: int, out_ids, n_acc,
        counters_v0, greedy: bool, bias: bool,
    ) -> None:
        """Dispatch the NEXT spec step before the pending one's ids reach
        the host: its catch-up window is the pending step's accepted
        tokens, derived ON DEVICE from (out_ids, n_acc) — the same
        token-feedback trick the plain overlap loop uses, generalized to
        a data-dependent window length. Only when the decode rows that
        come next are these (`Scheduler.next_batch`; mixed steps count:
        the chained dispatch lands as the decode leg of the next mixed
        step), no
        request can finish inside the pending window's worst case, pages
        can pre-grow to cover both windows, and no penalty history (host
        state) is in play."""
        if not self._overlap_enabled:
            return
        # the decode rows that come next must be these (how many tokens
        # the pending step accepts is not known, so none is counted;
        # the worst case is excluded below)
        nxt = self.scheduler.next_batch(reqs, 0)
        if (
            nxt is None
            or len(nxt.decode) != len(reqs)
            or any(a is not b for a, b in zip(nxt.decode, reqs))
        ):
            return
        if self._batch_penalty_bucket(reqs):
            return
        s = self.config.spec_draft_tokens
        w = s + 1
        cap = min(
            self.config.max_context,
            self.config.max_pages_per_seq * self.config.page_size,
        )
        for req in reqs:
            sp = req.sampling
            if (
                len(req.output_tokens) + req.num_emitted + w
                >= sp.max_tokens
            ):
                return  # the pending step may finish it
            if req.num_tokens + w + s > cap:
                return
        if not self._grow_pages_for(reqs, [2 * s + 1] * len(reqs)):
            return
        m = self.metrics
        with phase(
            m, "engine.stage", "time_stage_ms", "time_decode_dispatch_ms"
        ):
            mp = self.config.max_pages_per_seq
            pos0 = np.zeros(b_bucket, np.int32)
            pt = np.zeros((b_bucket, mp), np.int32)
            for i, req in enumerate(reqs):
                pos0[i] = req.num_tokens  # accepted tokens land at n, n+1, …
                pt[i, : len(req.pages)] = req.pages
            samp, _ = self._sampling_arrays(reqs, pad_to=b_bucket)
            bias_kwargs = self._bias_arrays(reqs, b_bucket) if bias else {}
            host = {
                "base": (pos0, pt), "samp": samp[:4], "bias": bias_kwargs,
            }
            dev = self._dev_tree(host)
            d_pos0, d_pt = dev["base"]
            fn = self._get_step_fn(
                "spec_fused", b_bucket, w, greedy=greedy, pen=0, bias=bias,
            )
        with phase(
            m, "engine.launch", "time_decode_dispatch_ms",
            kind="spec_fused", rows=b_bucket, t=w, speculative=1,
            n_rows=len(reqs),
        ) as ph:
            # verify-start counters advance by the pending acceptance —
            # a device add, no host round-trip
            cv0 = jnp.asarray(counters_v0) + n_acc
            out2, drafts2, nacc2, self.kv, self.draft_kv = fn(
                self.params, self.draft_params, out_ids, n_acc, d_pos0,
                self.kv, self.draft_kv, d_pt, *dev["samp"], cv0,
                **dev["bias"],
            )
            seq = ph.launched(out2)
            for arr in (out2, drafts2, nacc2):
                arr.copy_to_host_async()
        self.metrics.overlap_dispatches += 1
        self._inflight_spec = _InflightSpec(
            reqs=tuple(reqs),
            b_bucket=b_bucket,
            out_ids=out2,
            draft_ids=drafts2,
            n_acc=nacc2,
            counters_v0=cv0,
            greedy=greedy,
            bias=bias,
            seq=seq,
        )

    def _spec_inflight_matches(
        self, inflight: _InflightSpec, reqs: list[Request]
    ) -> bool:
        """A chained spec dispatch is this step iff the previous step's
        postprocess validated it (host acceptance == device n_acc, no
        finish) and the batch is the same requests, each advanced
        exactly as validated."""
        if inflight.expected_num_tokens is None:
            return False
        if len(reqs) != len(inflight.reqs):
            return False
        for r, spec_r, exp_nt, exp_out in zip(
            reqs, inflight.reqs, inflight.expected_num_tokens,
            inflight.expected_out_len,
        ):
            if (
                r is not spec_r
                or r.num_tokens != exp_nt
                or len(r.output_tokens) != exp_out
            ):
                return False
        return True

    def _discard_inflight_spec(self, why: str) -> None:
        """Roll back a chained spec dispatch. Like _discard_inflight, the
        sampled ids are overshoot and its KV writes (target AND draft
        pool) are benign: surviving requests' true tokens overwrite
        those positions before any read, and freed pages' next owners
        fully overwrite them."""
        inflight, self._inflight_spec = self._inflight_spec, None
        if inflight is None:
            return
        self._note_rollback(why)
        logger.debug("spec chain rollback: %s", why)

    def _run_decode(self, batch: ScheduledBatch) -> list[StepOutput]:
        reqs = list(batch.decode)
        if self._spec_active(reqs):
            if self._spec_draft:
                return self._run_decode_spec_draft(reqs)
            return self._run_decode_spec(reqs)
        if self._inflight_spec is not None:
            # cooldown or an ineligible batch routes to the plain path:
            # a chained spec dispatch can never land there
            self._discard_inflight_spec("speculation inactive")
        return self._run_decode_plain(reqs)

    def _run_decode_plain(
        self, reqs: list[Request], mixed: bool = False
    ) -> list[StepOutput]:
        st = self._take_inflight(reqs, (), "decode batch changed")
        if st is None:
            st = self._launch_decode(reqs)
        # Keep the device busy past this step BEFORE blocking on its
        # result: the dispatch launched ahead computes while the host
        # scans this step's ids for stops below.
        self._speculate(st)
        return self._finish_decode(st, mixed)

    def _stage_decode_rows(
        self, reqs: list[Request], b_bucket: int,
        ahead: Optional[list[int]],
    ):
        """Host arrays of the decode rows of one dispatch: ((tokens,
        positions, valid, page table), sampling arrays, all_greedy).
        `ahead[i]` tokens are still to come to row i from the dispatch on
        the device (None: a dispatch for the batch in hand): positions
        and draw counters advance by them, and the row's input token is
        that dispatch's, fed on the device (`_feed`)."""
        n = len(reqs)
        mp = self.config.max_pages_per_seq
        tokens = np.zeros((b_bucket, 1), np.int32)
        positions = np.zeros((b_bucket, 1), np.int32)
        valid = np.zeros((b_bucket, 1), bool)
        pt = np.zeros((b_bucket, mp), np.int32)
        for i, req in enumerate(reqs):
            if ahead is None or not ahead[i]:
                tokens[i, 0] = req.all_tokens[-1]
            positions[i, 0] = req.num_tokens - 1
            valid[i, 0] = True
            pt[i, : len(req.pages)] = req.pages
        samp, all_greedy = self._sampling_arrays(reqs, pad_to=b_bucket)
        if ahead is not None:
            positions[:n, 0] += ahead
            # the dispatch on the device advances every draw counter by
            # the tokens it samples
            samp[4][:n] += np.asarray(ahead, np.int32)
        return (
            (tokens, positions, valid, self._row_tables(pt, reqs)),
            samp, all_greedy,
        )

    def _decode_rows_bucket(
        self, n: int, kind: str, k_steps: int, lp: int, pen: int,
        bias: bool, greedy: bool,
    ) -> int:
        """The row bucket of a pure decode dispatch over `n` rows: the
        smallest of `decode_buckets` that holds them, unless that
        program has never run and the same program over more rows has.
        A first call costs seconds (5-16 for a hybrid model's step),
        padding rows cost microseconds: a batch that thins out (a lull,
        streams cut at once and aborted one by one) keeps the program it
        has instead of walking down through every smaller bucket, one
        first call each."""
        exact = self.config.decode_bucket_for(n)
        for b in self.config.decode_buckets:
            if b >= exact and (
                kind, b, k_steps, greedy, False, False, lp, pen, bias, 0,
                False,
            ) in self._jit_cache:
                return b
        return exact

    def _feed(self, feed, tokens):
        """The `[B, 1]` token input of a dispatch launched ahead of its
        batch: row i takes `ids.reshape(-1)[src[i]]` of the dispatch
        still on the device where `src[i] >= 0` (no host round-trip),
        else the host's `tokens[i]`. One small program per (ids shape,
        rows)."""
        ids, src = feed
        key = ("feed", tuple(ids.shape), tokens.shape[0])
        fn = self._jit_cache.get(key)
        if fn is None:

            def feed_fn(ids, src, tokens):
                picked = ids.reshape(-1)[jnp.maximum(src, 0)]
                return jnp.where(
                    src[:, None] >= 0, picked[:, None].astype(jnp.int32),
                    tokens,
                )

            fn = self._cache_jit("feed", key, jax.jit(feed_fn))
        return fn(ids, src, tokens)

    def _launch_decode(
        self, reqs: list[Request], ahead: Optional[list[int]] = None,
        feed=None,
    ) -> Optional[_Launched]:
        """Stage and launch one pure decode dispatch over `reqs`: one
        step, or the fused scan. With `ahead`/`feed` it is launched ahead
        of its batch (`_speculate`); None then means the pool cannot
        pre-grow the rows' pages."""
        m = self.metrics
        speculative = ahead is not None
        with phase(
            m, "engine.stage", "time_stage_ms", "time_decode_dispatch_ms"
        ):
            k_steps = self._pick_decode_steps(reqs, ahead)
            if speculative and not self._grow_pages_for(
                reqs, [a + k_steps - 1 for a in ahead]
            ):
                return None
            kind = "decode" if k_steps == 1 else "decode_multi"
            lp = self._batch_logprobs(reqs)
            # penalty history needs the pending tokens host-side: no
            # dispatch is launched ahead with one (_speculate)
            pen = 0 if speculative else self._batch_penalty_bucket(reqs)
            bias = self._batch_bias(reqs)
            b_bucket = self._decode_rows_bucket(
                len(reqs), kind, k_steps, lp, pen, bias,
                all(r.sampling.temperature <= 0.0 for r in reqs),
            )
            base, samp, all_greedy = self._stage_decode_rows(
                reqs, b_bucket, ahead
            )
            pen_args = (
                self._penalty_arrays(reqs, b_bucket, pen) if pen else ()
            )
            bias_kwargs = self._bias_arrays(reqs, b_bucket) if bias else {}
            host = {
                "base": base, "samp": samp, "pen": pen_args,
                "bias": bias_kwargs,
            }
            if k_steps == 1:
                host["last"] = np.zeros(b_bucket, np.int32)
            if speculative:
                host["src"] = np.full(b_bucket, -1, np.int32)
                host["src"][: len(reqs)] = feed[1]
            self._poll_clock()
            dev = self._dev_tree(host)
            self._poll_clock()
            fn = self._get_step_fn(
                kind, b_bucket, k_steps, greedy=all_greedy, lp=lp, pen=pen,
                bias=bias,
            )
            head = (dev["last"],) if kind == "decode" else ()
        with phase(
            m, "engine.launch", "time_decode_dispatch_ms", kind=kind,
            rows=b_bucket, k=k_steps, speculative=int(speculative),
            n_rows=len(reqs),
        ) as ph:
            d_tokens, d_positions, d_valid, d_pt = dev["base"]
            if speculative:
                d_tokens = self._feed((feed[0], dev["src"]), d_tokens)
                self._poll_clock()
            out = fn(
                self.params, d_tokens, d_positions, d_valid, self.kv, d_pt,
                *head, *dev["samp"], *dev["pen"], **dev["bias"],
            )
            seq = ph.launched(out[0])
        lp_data = None
        if lp >= 0:
            token_ids, lp_data, self.kv = out
        else:
            token_ids, self.kv = out  # [B], or [K, B] when fused
        if not speculative:
            self._commit_state(reqs)
        return _Launched(
            reqs=tuple(reqs), b_bucket=b_bucket, k_steps=k_steps,
            token_ids=token_ids, lp_data=lp_data, seq=seq,
            walk=self._walk_peek and self._walk_peek(self.kv),
        )

    def _finish_decode(
        self, st: _Launched, mixed: bool = False
    ) -> list[StepOutput]:
        """Read a pure decode dispatch's ids and postprocess them. For a
        dispatch launched ahead the async copy started a step ago, so
        this sync is (near) free."""
        reqs = list(st.reqs)
        with phase(
            self.metrics, "engine.readback", "time_decode_sync_ms",
            lagged=int(st.expected is not None), **_seq_arg(st.seq),
        ):
            ids = np.asarray(st.token_ids).reshape(st.k_steps, st.b_bucket)
            lp_arrays = self._materialize_lp(
                st.lp_data, st.k_steps, st.b_bucket
            )
            self._count_walk(st)
        return self._decode_postprocess(
            reqs, st.k_steps, ids, lp_arrays, mixed=mixed
        )

    def _count_walk(self, st: _Launched) -> None:
        """Add what the device counted since the last dispatch read (a
        dispatch rolled back in between walked its pages too)."""
        if st.walk is None:
            return
        now = np.asarray(st.walk).astype(np.int64)  # four numbers to six
        seen = self._walk_seen[:now.size]
        for name, n in zip(_WALK_COUNTS, (now - seen) % (1 << 32)):
            setattr(self.metrics, name, getattr(self.metrics, name) + int(n))
        seen[:] = now

    @staticmethod
    def _materialize_lp(lp_data, k_steps: int, b_bucket: int):
        """Device logprob outputs -> host (chosen, top_ids, top_lps),
        reshaped to [K, B(, N)]; None passes through."""
        if lp_data is None:
            return None
        return (
            np.asarray(lp_data[0]).reshape(k_steps, b_bucket),
            np.asarray(lp_data[1]).reshape(k_steps, b_bucket, -1),
            np.asarray(lp_data[2]).reshape(k_steps, b_bucket, -1),
        )

    def _decode_postprocess(
        self, reqs: list[Request], k_steps: int, ids: np.ndarray, lp_arrays,
        mixed: bool = False,
    ) -> list[StepOutput]:
        """Host half of a decode step: scan sampled ids for finish
        conditions (dropping overshoot past a stop), append accepted
        tokens, and register newly filled pages. Under overlap_decode
        this runs while the device computes the NEXT step."""
        outputs: list[StepOutput] = []
        n_tokens = n_finished = 0
        with phase(
            self.metrics, "engine.postprocess", "time_decode_host_ms"
        ) as ph:
            for i, req in enumerate(reqs):
                if not i & 15:
                    self._poll_clock()
                accepted: list[int] = []
                finish: Optional[FinishReason] = None
                for kk in range(k_steps):
                    accepted.append(int(ids[kk, i]))
                    finish = self._finish_reason_for(
                        req, int(ids[kk, i]), len(accepted)
                    )
                    if finish is not None:
                        n_finished += 1
                        break
                n_tokens += len(accepted)
                req.num_computed_tokens += len(accepted)
                lps = tops = None
                if lp_arrays is not None and req.sampling.logprobs >= 0:
                    chosen_lp, top_ids, top_lps = lp_arrays
                    n = len(accepted)
                    lps = tuple(float(chosen_lp[kk, i]) for kk in range(n))
                    nk = req.sampling.logprobs
                    if nk > 0:
                        tops = tuple(
                            tuple(
                                (
                                    int(top_ids[kk, i, j]),
                                    float(top_lps[kk, i, j]),
                                )
                                for j in range(min(nk, top_ids.shape[-1]))
                            )
                            for kk in range(n)
                        )
                outputs.extend(
                    self._accept_tokens(
                        req, accepted, finish, lps=lps, tops=tops,
                        mixed=mixed,
                    )
                )
                self._register_pages(req)
            ph.note(tokens=n_tokens, finished=n_finished)
        return outputs

    # -- mixed prefill+decode steps ----------------------------------------

    def _run_mixed(self, batch: ScheduledBatch) -> list[StepOutput]:
        """One stall-free step: a bounded prefill chunk AND the decode
        batch fused into a single XLA program — one `_dev_tree` transfer,
        one readback, one pass over the weights. The program runs every
        norm, projection and the FFN on the prompt rows and the decode
        rows together (`ModelAdapter.forward_hidden_mixed`); attention,
        rope and the cache write run per group, decode rows through the
        same [B, 1] page walk as a pure decode step and prefill pieces
        through the same [B, T] chunk path as a pure prefill step (pages
        are per-request disjoint, so the groups cannot read each other's
        writes). The mathematics of a row is that of the XOR scheduler's
        two programs; its floats agree with theirs to rounding, not bit
        for bit: a compiler may order a row's matmul sums by how many rows
        share the matmul (XLA:CPU does; a logit's last bits move, so an
        argmax or top-k tie could fall the other way). What
        tests/test_engine_mixed.py pins: token streams equal to the XOR
        scheduler's, reported logprobs bitwise where the row counts leave
        the matmuls as they were and to 1e-5 elsewhere; on the chip every
        run's `correct` compares with the plain reference.
        `mixed_shared_rows` counts the decode rows that shared a chunk's
        weight pass.

        Two cases run the halves as separate dispatches instead (same
        semantics, same streams): a decode dispatch launched ahead that
        matches the decode rows (a prompt arrived that nobody foresaw) —
        it lands as the decode half and the prefill chunk dispatches
        beside it — and pieces the fused program has no variant for
        (multimodal)."""
        reqs_d = list(batch.decode)
        pieces = list(batch.prefill)
        if self._spec_draft and self._spec_active(reqs_d):
            # Speculation composes with mixed steps: the fused
            # draft+verify program runs as the DECODE LEG beside the
            # prefill chunk (two dispatches, same stall-free semantics —
            # decode rows emit 1..S+1 tokens while the backlog drains;
            # the chained spec dispatch consumes/primes exactly as in
            # pure decode). The prefill half rides _run_prefill, which
            # also keeps the draft pool covered for the pieces.
            outputs = self._prefill_beside(batch)
            outputs += self._run_decode_spec_draft(reqs_d, mixed=True)
            return outputs
        if self._inflight_spec is not None:
            self._discard_inflight_spec("speculation inactive")
        if self._inflight is not None and self._inflight.pieces:
            # a whole mixed step launched ahead: this one, or nothing
            st = self._take_inflight(
                reqs_d, pieces, "mixed composition changed"
            )
            if st is not None:
                self._speculate(st)
                return self._finish_mixed(st)
        inflight = self._inflight
        use_inflight = inflight is not None and self._inflight_matches(
            inflight, reqs_d, ()
        )
        if use_inflight or not self._fusable(pieces):
            outputs = self._prefill_beside(batch)
            # consumes (or rolls back) the inflight itself and launches
            # the next dispatch ahead, the rows that just joined in it
            outputs += self._run_decode_plain(reqs_d, mixed=True)
            return outputs
        if inflight is not None:
            self._discard_inflight("mixed composition changed")

        # Pieces must run under EXACTLY the (T bucket, first_chunk)
        # program variants the XOR scheduler would pick — that variant
        # match is what makes the bit-exactness guarantee structural
        # rather than a numerics claim about padded masking. Group like
        # _run_prefill does, fuse the largest-T group (the bulk of the
        # work) with the decode batch, and dispatch any remaining groups
        # through the plain prefill path beside it.
        groups: dict[int, list] = {}
        for piece in pieces:
            groups.setdefault(self._bucket_t(piece.length), []).append(piece)
        fuse_pieces = groups.pop(max(groups))
        rest = [p for g in groups.values() for p in g]
        outputs: list[StepOutput] = []
        if rest:
            self.metrics.prefill_dispatches += 1
            outputs = self._run_prefill(
                ScheduledBatch(kind="prefill", prefill=tuple(rest)),
                mixed=True,
            )
        st = self._launch_mixed(reqs_d, fuse_pieces)
        self._speculate(st)
        return outputs + self._finish_mixed(st)

    def _prefill_beside(self, batch: ScheduledBatch) -> list[StepOutput]:
        """A mixed batch's pieces as prefill dispatches of their own,
        read back at once, beside a separate decode half."""
        self.metrics.prefill_dispatches += 1
        return self._run_prefill(
            ScheduledBatch(kind="prefill", prefill=batch.prefill),
            mixed=True,
        )

    @staticmethod
    def _fusable(pieces: list) -> bool:
        """Whether a mixed batch runs as the one fused program: no
        multimodal piece (the fused program has no mm variant)."""
        return all(p.request.mm_embeds is None for p in pieces)

    def _launch_mixed(
        self, reqs_d: list[Request], pieces: list,
        ahead: Optional[list[int]] = None, feed=None,
    ) -> Optional[_Launched]:
        """Stage and launch the fused mixed program: decode rows
        `reqs_d` beside `pieces`, which share one T bucket. With
        `ahead`/`feed` it is launched ahead of its batch (`_speculate`);
        None then means the pool cannot pre-grow the decode rows."""
        m = self.metrics
        speculative = ahead is not None
        with phase(
            m, "engine.stage", "time_stage_ms", "time_decode_dispatch_ms"
        ):
            if speculative and not self._grow_pages_for(reqs_d, ahead):
                return None
            b_dec = self.config.decode_bucket_for(len(reqs_d))
            mp = self.config.max_pages_per_seq
            # decode half: identical arrays to a k=1 decode step
            based, samp_d, greedy_d = self._stage_decode_rows(
                reqs_d, b_dec, ahead
            )
            # prefill half: one T-bucket group per fused program keeps the
            # compile family at (b_decode_bucket, t_prefill_bucket,
            # b_prefill_bucket)
            t_bucket = max(self._bucket_t(p.length) for p in pieces)
            b_pre = self._bucket_b(len(pieces))
            p_tokens = np.zeros((b_pre, t_bucket), np.int32)
            p_positions = np.zeros((b_pre, t_bucket), np.int32)
            p_valid = np.zeros((b_pre, t_bucket), bool)
            p_pt = np.zeros((b_pre, mp), np.int32)
            last_idx = np.zeros(b_pre, np.int32)
            any_last = False
            for i, piece in enumerate(pieces):
                req = piece.request
                chunk = req.all_tokens[piece.start : piece.start + piece.length]
                p_tokens[i, : piece.length] = chunk
                p_positions[i] = np.arange(t_bucket, dtype=np.int32) + piece.start
                p_valid[i, : piece.length] = True
                p_pt[i, : len(req.pages)] = req.pages
                last_idx[i] = piece.length - 1
                if piece.start + piece.length >= len(req.prompt_tokens):
                    any_last = True
            any_last = any_last or not self.adapter.step_twins
            first_chunk = all(p.start == 0 for p in pieces)
            self._poll_clock()
            # sampled row space: decode rows [0, b_dec); when a piece
            # completes its prompt, prefill rows join at [b_dec, b_dec+b_pre)
            pre_reqs = [p.request for p in pieces]
            if any_last:
                samp_p, greedy_p = self._sampling_arrays(pre_reqs, pad_to=b_pre)
                samp = tuple(
                    np.concatenate([a, b]) for a, b in zip(samp_d, samp_p)
                )
                all_greedy = greedy_d and greedy_p
                row_reqs = reqs_d + pre_reqs
            else:
                samp, all_greedy, row_reqs = samp_d, greedy_d, reqs_d
            lp = self._batch_logprobs(row_reqs)
            # no dispatch is launched ahead with a penalty (_speculate)
            pen = 0 if speculative else self._batch_penalty_bucket(row_reqs)
            if pen:
                pen_d = self._penalty_arrays(reqs_d, b_dec, pen)
                if any_last:
                    pen_p = self._penalty_arrays(pre_reqs, b_pre, pen)
                    pen_args = tuple(
                        np.concatenate([a, b]) for a, b in zip(pen_d, pen_p)
                    )
                else:
                    pen_args = pen_d
            else:
                pen_args = ()
            bias = self._batch_bias(row_reqs)
            if bias:
                bias_d = self._bias_arrays(reqs_d, b_dec)
                if any_last:
                    bias_p = self._bias_arrays(pre_reqs, b_pre)
                    bias_kwargs = {
                        k: np.concatenate([bias_d[k], bias_p[k]]) for k in bias_d
                    }
                else:
                    bias_kwargs = bias_d
            else:
                bias_kwargs = {}

            host = {
                "based": based,
                "basep": (
                    p_tokens, p_positions, p_valid,
                    self._row_tables(p_pt, [p.request for p in pieces]),
                ),
                "last": last_idx, "samp": samp, "pen": pen_args,
                "bias": bias_kwargs,
            }
            if speculative:
                host["src"] = np.full(b_dec, -1, np.int32)
                host["src"][: len(reqs_d)] = feed[1]
            self._poll_clock()
            dev = self._dev_tree(host)
            self._poll_clock()
            fn = self._get_step_fn(
                "mixed", b_dec, t_bucket, greedy=all_greedy,
                first_chunk=first_chunk, lp=lp, pen=pen, bias=bias,
                b_pre=b_pre, psamp=any_last,
            )
        with phase(
            m, "engine.launch", "time_decode_dispatch_ms", kind="mixed",
            rows=b_dec, t=t_bucket, k=1, speculative=int(speculative),
            n_rows=len(reqs_d), b_pre=b_pre,
            chunk_tokens=sum(p.length for p in pieces),
        ) as ph:
            d_tokens, d_positions, d_valid, d_pt = dev["based"]
            if speculative:
                d_tokens = self._feed((feed[0], dev["src"]), d_tokens)
                self._poll_clock()
            out = fn(
                self.params, d_tokens, d_positions, d_valid, self.kv, d_pt,
                *dev["basep"], dev["last"],
                *dev["samp"], *dev["pen"], **dev["bias"],
            )
            seq = ph.launched(out[0])
        lp_data = None
        if lp >= 0:
            token_ids, lp_data, self.kv = out
        else:
            token_ids, self.kv = out  # [b_dec] or [b_dec + b_pre]
        if not speculative:
            self._commit_state([*reqs_d, *(p.request for p in pieces)])
        return _Launched(
            reqs=tuple(reqs_d), b_bucket=b_dec, k_steps=1,
            token_ids=token_ids, lp_data=lp_data, pieces=tuple(pieces),
            psamp=any_last, seq=seq,
            walk=self._walk_peek and self._walk_peek(self.kv),
        )

    def _finish_mixed(self, st: _Launched) -> list[StepOutput]:
        """Read a fused mixed dispatch's ids and postprocess both halves,
        decode rows first."""
        m = self.metrics
        b_dec = st.b_bucket
        m.mixed_shared_rows += len(st.reqs)
        with phase(
            m, "engine.readback", "time_decode_sync_ms",
            lagged=int(st.expected is not None), **_seq_arg(st.seq),
        ):
            ids = np.asarray(st.token_ids)  # [b_dec] or [b_dec + b_pre]
            lp_arrays = self._materialize_lp(st.lp_data, 1, ids.shape[0])
            self._count_walk(st)
        d_lp = p_lp = None
        if lp_arrays is not None:
            d_lp = tuple(a[:, :b_dec] for a in lp_arrays)
            p_lp = tuple(a[0] for a in lp_arrays)
        outputs = self._decode_postprocess(
            list(st.reqs), 1, ids[None, :b_dec], d_lp, mixed=True
        )
        with phase(m, "engine.postprocess", "time_decode_host_ms") as ph:
            n0 = len(outputs)
            self._prefill_postprocess(
                list(st.pieces), ids, p_lp, b_dec, outputs, mixed=True
            )
            ph.note(
                tokens=len(outputs) - n0,
                finished=self._n_finished(outputs[n0:]),
            )
        return outputs

    # -- overlapped decode (one dispatch ahead, one-step-lagged readback) --

    def _speculate(self, pending: _Launched) -> None:
        """Launch the NEXT decode-carrying dispatch before `pending`'s
        ids reach the host: the batch `schedule()` will return after it
        (`Scheduler.next_batch`: rows certain to end in `pending` gone,
        their successors admitted, a prompt's last piece joined), each
        row's input token taken from `pending`'s ids ON DEVICE (`_feed`),
        positions and draw counters advanced by what `pending` adds. A
        mixed batch is launched as the fused program where it would run
        as one (`_fusable`, one T bucket), else its decode rows alone
        (they land as the decode half, `_run_mixed`). Nothing is
        launched where that batch cannot be known, carries no decode
        row, a penalty is in play (its history needs the pending tokens
        host-side) or the rows' pages cannot pre-grow."""
        if not self._overlap_enabled:
            return
        nxt = self.scheduler.next_batch(
            pending.reqs, pending.k_steps, pending.pieces
        )
        self._poll_clock()  # planning the batch ahead runs under no phase
        if nxt is None or not nxt.decode:
            return
        rows, pieces = list(nxt.decode), list(nxt.prefill)
        fuse = (
            nxt.kind == "mixed"
            and self._fusable(pieces)
            and len({self._bucket_t(p.length) for p in pieces}) == 1
        )
        if self._batch_penalty_bucket(
            rows + [p.request for p in pieces if fuse]
        ):
            return
        # where in pending's ids each row's input token is, and how many
        # tokens pending adds to the row first; a row it does not carry
        # (joined through a dispatch already read) feeds from the host
        k, b = pending.k_steps, pending.b_bucket
        comes = {
            id(r): ((k - 1) * b + i, k) for i, r in enumerate(pending.reqs)
        }
        if pending.psamp:
            for j, p in enumerate(pending.pieces):
                if p.start + p.length >= len(p.request.prompt_tokens):
                    comes[id(p.request)] = (b + j, 1)
        src = [comes.get(id(r), (-1, 0))[0] for r in rows]
        ahead = [comes.get(id(r), (-1, 0))[1] for r in rows]
        feed = (pending.token_ids, src)
        if fuse:
            st = self._launch_mixed(rows, pieces, ahead, feed)
        else:
            st = self._launch_decode(rows, ahead, feed)
        if st is None:
            return
        # one-step-lagged readback: start the device→host copy now so
        # the next step's sync finds the bytes already landed
        for arr in (st.token_ids, *(st.lp_data or ())):
            arr.copy_to_host_async()
        st.expected = tuple(
            (r.num_tokens + a, len(r.output_tokens) + a)
            for r, a in zip(rows, ahead)
        )
        self.metrics.overlap_dispatches += 1
        self._inflight = st

    @staticmethod
    def _inflight_matches(
        inflight: _Launched, reqs: list[Request], pieces
    ) -> bool:
        """The dispatch launched ahead is this step iff the scheduled
        batch is the SAME requests (identity — an aborted+resubmitted id
        is a new object) in the same rows, each advanced exactly the
        tokens expected of it (a preemption/recompute resets
        output_tokens and fails here even though num_tokens survives the
        fold), beside the same prompt pieces."""
        if len(reqs) != len(inflight.reqs) or len(pieces) != len(
            inflight.pieces
        ):
            return False
        for r, spec_r, (exp_nt, exp_out) in zip(
            reqs, inflight.reqs, inflight.expected
        ):
            if (
                r is not spec_r
                or r.num_tokens != exp_nt
                or len(r.output_tokens) != exp_out
            ):
                return False
        return all(
            p.request is q.request
            and p.start == q.start
            and p.length == q.length
            for p, q in zip(pieces, inflight.pieces)
        )

    def _take_inflight(
        self, reqs: list[Request], pieces, why: str
    ) -> Optional[_Launched]:
        """The dispatch launched ahead, if it IS this step (all of it or
        nothing); else it is rolled back (`why`) and None returned."""
        inflight = self._inflight
        if inflight is None:
            return None
        if not self._inflight_matches(inflight, reqs, pieces):
            self._discard_inflight(why)
            return None
        self._inflight = None
        self.metrics.overlap_hits += 1
        self._commit_state(
            [*inflight.reqs, *(p.request for p in inflight.pieces)]
        )
        return inflight

    def _discard_inflight(self, why: str) -> None:
        """Roll back a dispatch launched ahead. The sampled ids are
        overshoot — dropped exactly like decode_multi's post-stop tokens.
        Its KV writes are benign: for surviving requests they used the
        true tokens at the true positions (a prompt piece's included; the
        real dispatch overwrites them before any read, and no page is
        registered for reuse before it); for finished, aborted or
        preempted requests they sit in released pages whose next owner's
        writes are stream-ordered after them. Pages grown for the window,
        and a request admitted early for it, stay as they are: the next
        `schedule()` finds the state it would have made itself.

        Its writes of RECURRENT state (a model with state-space layers)
        would not be benign in place: a state is not written by position,
        so the real dispatch would advance every surviving row a second
        time. They went to the other generation of each row's slot
        (`_row_tables`), which only `_take_inflight` makes the row's
        state; here nothing is committed, so every surviving row still
        reads what the last dispatch taken left it, and the next dispatch
        overwrites what this one wrote. `state_rows` of the span counts
        the rows that were put back that way."""
        inflight, self._inflight = self._inflight, None
        if inflight is None:
            return
        state_rows = 0
        if self._stateful and not self._state_in_place:
            state_rows = sum(
                1 for r in (
                    *inflight.reqs, *(p.request for p in inflight.pieces)
                ) if r.state_slot
            )
            self.metrics.state_restores += int(state_rows > 0)
        self._note_rollback(why, state_rows)
        logger.debug("overlap rollback: %s", why)

    def _note_rollback(self, why: str, state_rows: int = 0) -> None:
        """Count a rolled-back speculative dispatch, and mark the moment
        in a running capture: a zero-length `engine.rollback` span."""
        self.metrics.overlap_rollbacks += 1
        with phase(None, "engine.rollback", why=why, state_rows=state_rows):
            pass

    def takers_wait_s(self, queued: int = 0) -> float:
        """How long the loop's next step can be held back for the takers
        of free decode slots, `queued` of whom are in the runner's inbox
        (`AsyncEngineRunner._await_takers`): while a fused decode scan
        launched ahead still runs on the device, a slot is free and
        nobody waits for it, three quarters of the wall of a recent
        decode dispatch, so the next launch (a host turn of a few
        milliseconds) still lands behind a busy device. An
        arrival's place in the dispatches then does not turn on a
        millisecond. 0 where nothing is launched ahead, it is a
        one-token dispatch or it has landed (the device would idle), or
        every free slot has its taker."""
        self._poll_clock()  # the wait naps: a boundary each time it asks
        st = self._inflight
        if st is None or st.k_steps < 2 or st.token_ids.is_ready():
            return 0.0
        s = self.scheduler
        room = self.config.max_seqs - s.num_running() - s.num_waiting()
        return 0.75 * self._decode_wall_s if room > queued else 0.0

    def drain_overlap(self) -> None:
        """Public: discard any speculative in-flight decode dispatch
        (idle/stop paths; also pins the sync/overlap boundary in tests)."""
        self._discard_inflight("drained")
        self._discard_inflight_spec("drained")
        if not self.scheduler.has_work:
            self._park_clock()

    def _poll_clock(self) -> None:
        """A boundary of the dry clock INSIDE a phase, where a phase is
        long enough to hide when the device finished (staging, the
        transfer, the stop scan): free while the device is known dry."""
        clock = self.metrics.dry_clock
        if clock is not None:
            clock.poll()

    def _park_clock(self) -> None:
        """Nothing to run: what passes until the next step is not dry
        time (`DryClock.park`)."""
        clock = self.metrics.dry_clock
        if clock is not None:
            clock.park()

    # -- shared ------------------------------------------------------------

    @staticmethod
    def _batch_logprobs(reqs: list[Request]) -> int:
        """Program-variant selector: -1 when no request wants logprobs,
        else the largest top-N requested (the program computes one top-k;
        per-request N slices it host-side). Snapped to the small OpenAI
        range {0,1,..,20} so the compile family stays bounded."""
        lp = -1
        for r in reqs:
            lp = max(lp, min(r.sampling.logprobs, 20))
        return lp

    @staticmethod
    def _penalty_history(req: Request) -> list[int]:
        """Every token this request has GENERATED — the history the OpenAI
        penalties run over. Preemption-by-recompute folds generated tokens
        into prompt_tokens (scheduler._preempt_youngest); num_emitted counts
        them, so the folded tail stays part of the history."""
        hist = req.output_tokens
        if req.num_emitted:
            hist = req.prompt_tokens[-req.num_emitted :] + hist
        return hist

    def _batch_penalty_bucket(self, reqs: list[Request]) -> int:
        """0 when no request carries a frequency/presence penalty; else the
        generated-history bucket O (power of two) the penalty programs
        index. The bucket, not the batch, keys the program variant — the
        family grows log2(max_tokens) deep."""
        if not any(
            r.sampling.frequency_penalty
            or r.sampling.presence_penalty
            or r.sampling.repetition_penalty != 1.0
            for r in reqs
        ):
            return 0
        longest = max(len(self._penalty_history(r)) for r in reqs)
        o = 1
        while o < max(1, longest):
            o *= 2
        return o

    def _penalty_arrays(self, reqs: list[Request], pad_to: int, o_bucket: int):
        """(freq [B], pres [B], rep [B], out_tokens [B, O], out_valid
        [B, O]) — the generated-token history the penalties are computed
        over. Padding rows carry rep=1 (multiplicative no-op)."""
        freq = np.zeros(pad_to, np.float32)
        pres = np.zeros(pad_to, np.float32)
        rep = np.ones(pad_to, np.float32)
        out_toks = np.zeros((pad_to, o_bucket), np.int32)
        out_valid = np.zeros((pad_to, o_bucket), bool)
        for i, r in enumerate(reqs):
            freq[i] = r.sampling.frequency_penalty
            pres[i] = r.sampling.presence_penalty
            rep[i] = r.sampling.repetition_penalty or 1.0
            hist = self._penalty_history(r)
            n = min(len(hist), o_bucket)
            if n:
                out_toks[i, :n] = hist[-n:]
                out_valid[i, :n] = True
        return (freq, pres, rep, out_toks, out_valid)

    def _validate_bias(self, sampling: Optional[SamplingParams]) -> None:
        """Reject over-limit / out-of-vocab logit_bias at admission, where
        the runner returns the error to THIS client (a failure inside
        step() would wedge the whole batch loop)."""
        if sampling is None or not (sampling.logit_bias or sampling.min_tokens):
            return
        from dynamo_tpu.engine.sampling import BIAS_SLOTS

        need = len(sampling.logit_bias or ())
        if sampling.min_tokens > 0:
            ban = set(sampling.stop_token_ids)
            if not sampling.ignore_eos:
                ban |= set(self.config.eos_token_ids)
            need += len(ban)
        if need > BIAS_SLOTS:
            raise ValueError(
                f"logit_bias entries + min_tokens eos/stop bans need "
                f"{need} slots; at most {BIAS_SLOTS} supported"
            )
        v = self.adapter.vocab_size
        for tid, _ in sampling.logit_bias or ():
            if not 0 <= tid < v:
                raise ValueError(
                    f"logit_bias token id {tid} outside vocab [0,{v})"
                )

    @staticmethod
    def _batch_bias(reqs: list[Request]) -> bool:
        """Program-variant selector for the sparse logit-bias/min_tokens
        path (sampling.apply_logit_bias)."""
        return any(
            r.sampling.logit_bias or r.sampling.min_tokens for r in reqs
        )

    def _bias_row(self, req: Request):
        """Per-request packed bias slots, computed once and cached on the
        request — the rows are invariant for its lifetime (only the
        counters vary per step, and those ride the sampling arrays)."""
        row = getattr(req, "_bias_row", None)
        if row is not None:
            return row
        from dynamo_tpu.engine.sampling import BIAS_SLOTS

        ids = np.zeros(BIAS_SLOTS, np.int32)
        vals = np.zeros(BIAS_SLOTS, np.float32)
        gated = np.zeros(BIAS_SLOTS, bool)
        s = req.sampling
        slot = 0
        for tid, bv in s.logit_bias or ():
            ids[slot] = tid
            vals[slot] = bv
            slot += 1
        if s.min_tokens > 0:
            ban = set(s.stop_token_ids)
            if not s.ignore_eos:
                ban |= set(self.config.eos_token_ids)
            for tid in sorted(ban):
                if slot >= BIAS_SLOTS:
                    break  # bounded at admission; belt and braces
                ids[slot] = tid
                vals[slot] = -1e30
                gated[slot] = True
                slot += 1
        row = (ids, vals, gated, s.min_tokens)
        req._bias_row = row
        return row

    def _bias_arrays(self, reqs: list[Request], pad_to: int) -> dict:
        """kwargs for the bias program variants: user logit_bias entries
        plus min_tokens' gated eos/stop bans packed into BIAS_SLOTS."""
        from dynamo_tpu.engine.sampling import BIAS_SLOTS

        ids = np.zeros((pad_to, BIAS_SLOTS), np.int32)
        vals = np.zeros((pad_to, BIAS_SLOTS), np.float32)
        gated = np.zeros((pad_to, BIAS_SLOTS), bool)
        mins = np.zeros(pad_to, np.int32)
        for i, r in enumerate(reqs):
            row_ids, row_vals, row_gated, row_min = self._bias_row(r)
            ids[i] = row_ids
            vals[i] = row_vals
            gated[i] = row_gated
            mins[i] = row_min
        return {
            "bias_ids": ids,
            "bias_vals": vals,
            "bias_gated": gated,
            "min_toks": mins,
        }

    def _sampling_arrays(self, reqs: list[Request], pad_to: Optional[int] = None):
        """Returns ((temps, top_ps, top_ks, seeds, counters), all_greedy).
        all_greedy selects the argmax-only program variant — temperature-0
        batches never pay for top-k/gumbel."""
        n = pad_to or len(reqs)
        temps = np.zeros(n, np.float32)
        top_ps = np.ones(n, np.float32)
        top_ks = np.zeros(n, np.int32)
        seeds = np.zeros(n, np.uint32)
        counters = np.zeros(n, np.int32)
        all_greedy = True
        for i, r in enumerate(reqs):
            temps[i] = r.sampling.temperature
            top_ps[i] = r.sampling.top_p
            top_ks[i] = r.sampling.top_k
            seeds[i] = self._request_seed(r)
            # num_emitted keeps the draw counter monotonic across preemption
            counters[i] = r.num_emitted + len(r.output_tokens)
            if r.sampling.temperature > 0.0:
                all_greedy = False
        return ((temps, top_ps, top_ks, seeds, counters), all_greedy)

    def _request_seed(self, req: Request) -> int:
        if req.sampling.seed is not None:
            return req.sampling.seed & 0xFFFFFFFF
        import xxhash

        return (
            xxhash.xxh32_intdigest(req.request_id.encode(), seed=self.config.seed)
            & 0xFFFFFFFF
        )

    def _cache_jit(self, kind: str, cache_key, jitted: Callable) -> Callable:
        """Install a jitted program into the cache wrapped so its FIRST
        invocation — where jax traces and lowers it and XLA compiles —
        is counted, timed (dynamo_tpu_phase_compile_ms; wall time of
        that plus the first run), split into its parts by jax's own
        events (`_FirstCall`) and listed in self.programs for GET
        /v1/debug/programs. The wrapper replaces itself with the bare
        jitted fn after that one call, so the steady-state dispatch path
        pays nothing."""

        def first_call(*args, **kwargs):
            m = self.metrics
            ms0 = m.compile_ms
            with phase(
                m, "engine.compile", "compile_ms", key=str(cache_key),
            ) as span:
                with _FirstCall() as call:
                    out = jitted(*args, **kwargs)
                parts = call.parts(span.elapsed_ms())
                span.note(**parts)
            dt_ms = m.compile_ms - ms0
            m.compiles += 1
            m.compile_trace_ms += call.trace_ms
            m.compile_lower_ms += call.lower_ms
            m.compile_backend_ms += call.backend_ms
            m.compile_cache_requests += call.hits + call.misses
            m.compile_cache_hits += call.hits
            self.compiles_by_kind[kind] = (
                self.compiles_by_kind.get(kind, 0) + 1
            )
            phases.observe("compile_ms", dt_ms)
            self._jit_cache[cache_key] = jitted
            self.programs[cache_key] = {
                "kind": kind,
                "key": str(cache_key),
                "compile_ms": round(dt_ms, 3),
                **parts,
            }
            return out

        self._jit_cache[cache_key] = first_call
        return first_call

    def _get_step_fn(
        self, kind: str, b: int, t: int, greedy: bool = False,
        mm: bool = False, first_chunk: bool = False, lp: int = -1,
        pen: int = 0, bias: bool = False, b_pre: int = 0,
        psamp: bool = False,
    ) -> Callable:
        # a model that keeps no history-free twin runs every chunk as a
        # later one (`ModelAdapter.step_twins`)
        first_chunk = first_chunk and self.adapter.step_twins
        cache_key = (
            kind, b, t, greedy, mm, first_chunk, lp, pen, bias, b_pre,
            psamp,
        )
        fn = self._jit_cache.get(cache_key)
        if fn is not None:
            return fn
        adapter = self.adapter
        rep_sh = self._rep_sharding

        def rep(x):
            """Replicate a small output across the whole mesh so every
            host of a multi-process mesh can read it (sampled ids drive
            the replicated schedulers); no-op single-process."""
            if rep_sh is None or x is None:
                return x
            return jax.tree.map(
                lambda y: jax.lax.with_sharding_constraint(y, rep_sh), x
            )

        def maybe_logprobs(logits, ids):
            """(chosen_lp, top_ids, top_lps) when this variant reports
            logprobs, else None (OpenAI semantics — unscaled, unpenalized
            model distribution)."""
            if lp < 0:
                return None
            from dynamo_tpu.engine.sampling import token_logprobs

            return token_logprobs(logits, ids, lp)

        def pick(logits, samp_args, counts=None, freq=None, pres=None,
                 rep_p=None, bias_args=None):
            """Sample ids [B] from (possibly penalty/bias-adjusted)
            logits; logprob reporting reads the raw logits separately.
            bias_args = (bias_ids, bias_vals, bias_gated, min_toks); the
            min-token gating reads the CURRENT counters from samp_args,
            so fused-scan steps gate correctly as the count advances."""
            with jax.named_scope("sample"):
                return _pick(
                    logits, samp_args, counts, freq, pres, rep_p, bias_args
                )

        def _pick(logits, samp_args, counts, freq, pres, rep_p, bias_args):
            eff = logits
            if counts is not None:
                from dynamo_tpu.engine.sampling import apply_penalties

                eff = apply_penalties(logits, counts, freq, pres, rep_p)
            if bias_args is not None:
                from dynamo_tpu.engine.sampling import apply_logit_bias

                b_ids, b_vals, b_gated, b_min = bias_args
                eff = apply_logit_bias(
                    eff, b_ids, b_vals, b_gated, samp_args[4], b_min
                )
            if greedy:
                ids = sample_greedy(eff)
            else:
                ids = sample(eff, *samp_args)
            return ids

        if kind == "embed":

            def embed_fn(params, tokens, positions, valid, kv, pt):
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt
                )
                # masked sum over the chunk; the host accumulates across
                # chunks and divides by the true token count
                pooled = jnp.sum(
                    hidden.astype(jnp.float32) * valid[..., None], axis=1
                )
                return rep(pooled), kv

            jitted = jax.jit(embed_fn, donate_argnums=(4,))
            logger.info("compiled %s program B=%d T=%d", kind, b, t)
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "decode_multi":
            k_steps = t  # the (b, t) slot carries (bucket, fused steps)
            stateful = self._stateful

            def multi_fn(params, tokens, positions, valid, kv, pt,
                         temps, top_ps, top_ks, seeds, counters,
                         freq=None, pres=None, rep_p=None,
                         out_toks=None, out_valid=None,
                         bias_ids=None, bias_vals=None, bias_gated=None,
                         min_toks=None):
                if pen:
                    from dynamo_tpu.engine.sampling import build_output_counts

                    counts0 = build_output_counts(
                        out_toks, out_valid, adapter.vocab_size
                    )
                else:
                    counts0 = jnp.zeros((), jnp.float32)  # unused carry

                def body(carry, _):
                    tokens, positions, kv, counters, counts, *state = carry
                    # a recurrent state is read where `pt` says in the
                    # first fused step, then where the step before wrote it
                    pt_k = (pt[0], state[0]) if stateful else pt
                    hidden, kv = adapter.forward_hidden(
                        params, tokens, positions, valid, kv, pt_k
                    )
                    if stateful:
                        state = [jnp.broadcast_to(
                            state[0][:, 1:], state[0].shape
                        )]
                    logits = adapter.compute_logits(params, hidden[:, -1])
                    ids = pick(
                        logits, (temps, top_ps, top_ks, seeds, counters),
                        counts=counts if pen else None, freq=freq, pres=pres,
                        rep_p=rep_p,
                        bias_args=(
                            (bias_ids, bias_vals, bias_gated, min_toks)
                            if bias
                            else None
                        ),
                    )
                    if pen:
                        # Each fused step extends the history it penalizes.
                        rows = jnp.arange(ids.shape[0])
                        counts = counts.at[rows, ids].add(1.0)
                    out = (ids, maybe_logprobs(logits, ids))
                    with jax.named_scope("feedback"):
                        carry = (
                            ids[:, None], positions + 1, kv, counters + 1,
                            counts, *state,
                        )
                    return carry, out

                (_, _, kv, *_), (all_ids, all_lp) = jax.lax.scan(
                    body,
                    (tokens, positions, kv, counters, counts0,
                     *((pt[1],) if stateful else ())),
                    None, length=k_steps,
                )
                if lp >= 0:
                    return rep(all_ids), rep(all_lp), kv  # [K, B] (+ lp)
                return rep(all_ids), kv  # [K, B]

            jitted = jax.jit(multi_fn, donate_argnums=(4,))
            logger.info(
                "compiled decode_multi program B=%d K=%d greedy=%s",
                b, k_steps, greedy,
            )
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "mixed":
            # One fused program per (b=decode bucket, t=prefill T bucket,
            # b_pre=prefill row bucket): prefill chunk KV+decode token in
            # a single dispatch and ONE pass over the weights
            # (`adapter.forward_hidden_mixed`). Shared by the two groups
            # of rows: every norm, projection and the whole FFN, which run
            # on the prompt rows and the decode rows concatenated. Per
            # group: rope, attention and the cache write, the code the
            # pure programs run (decode [B, 1] page walk, prefill [B, T]
            # chunk). Per-row results are the XOR scheduler's two programs'
            # to rounding (a row's matmul sums may be ordered by the row
            # count of the matmul it shares: `_run_mixed`); the tests pin
            # equal token streams.
            # psamp selects whether prefill rows sample (some piece
            # completes its prompt); without it only decode rows pay the
            # lm_head.

            def mixed_fn(params, d_tokens, d_positions, d_valid, kv, d_pt,
                         p_tokens, p_positions, p_valid, p_pt, last_idx,
                         temps, top_ps, top_ks, seeds, counters,
                         freq=None, pres=None, rep_p=None,
                         out_toks=None, out_valid=None,
                         bias_ids=None, bias_vals=None, bias_gated=None,
                         min_toks=None):
                # prompt rows first (the XOR policy's order); page
                # tables are per-request disjoint, so neither group can
                # read the other's writes
                hidden_p, hidden_d, kv = adapter.forward_hidden_mixed(
                    params, (p_tokens, p_positions, p_valid, p_pt),
                    (d_tokens, d_positions, d_valid, d_pt), kv,
                    first_chunk=first_chunk,
                )
                last_h = hidden_d[:, -1]  # [B_dec, H] (T=1)
                if psamp:
                    rows_p = jnp.arange(hidden_p.shape[0])
                    last_h = jnp.concatenate(
                        [last_h, hidden_p[rows_p, last_idx]], axis=0
                    )
                logits = adapter.compute_logits(params, last_h)
                counts = None
                if pen:
                    from dynamo_tpu.engine.sampling import (
                        build_output_counts,
                    )

                    counts = build_output_counts(
                        out_toks, out_valid, adapter.vocab_size
                    )
                ids = pick(
                    logits, (temps, top_ps, top_ks, seeds, counters),
                    counts=counts, freq=freq, pres=pres, rep_p=rep_p,
                    bias_args=(
                        (bias_ids, bias_vals, bias_gated, min_toks)
                        if bias
                        else None
                    ),
                )
                if lp >= 0:
                    return rep(ids), rep(maybe_logprobs(logits, ids)), kv
                return rep(ids), kv

            jitted = jax.jit(mixed_fn, donate_argnums=(4,))
            logger.info(
                "compiled mixed program Bdec=%d T=%d Bpre=%d psamp=%s",
                b, t, b_pre, psamp,
            )
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "spec_verify":

            def verify_fn(params, tokens, positions, valid, kv, pt):
                hidden, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt
                )
                bsz, tlen, h = hidden.shape
                logits = adapter.compute_logits(
                    params, hidden.reshape(bsz * tlen, h)
                )
                ids = jnp.argmax(logits, axis=-1).reshape(bsz, tlen)
                return rep(ids.astype(jnp.int32)), kv

            jitted = jax.jit(verify_fn, donate_argnums=(4,))
            logger.info("compiled %s program B=%d T=%d", kind, b, t)
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "spec_draft_prefill":
            draft_adapter = self.draft_adapter

            def draft_pre_fn(draft_params, tokens, positions, valid,
                             draft_kv, pt):
                _, draft_kv = draft_adapter.forward_hidden(
                    draft_params, tokens, positions, valid, draft_kv, pt,
                    first_chunk=first_chunk,
                )
                return draft_kv

            jitted = jax.jit(draft_pre_fn, donate_argnums=(4,))
            logger.info("compiled %s program B=%d T=%d", kind, b, t)
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "spec_fused":
            # One program per spec step (docs/engine.md "Speculative
            # decoding"): draft catch-up over the accepted window + S
            # greedy draft proposals (on-device feedback, own KV pool) +
            # the target verify forward over [last, d_0..d_{S-1}] + the
            # sequential acceptance scan. The (b, t) slot carries
            # (decode bucket, S+1). Inputs are window tokens + per-row
            # lengths so the HOST-fed first dispatch and the DEVICE-fed
            # chained dispatch (win_tokens=prev out_ids, win_len=prev
            # n_acc) share one compiled program.
            draft_adapter = self.draft_adapter
            s_steps = self.config.spec_draft_tokens
            vocab = adapter.vocab_size
            b_static = b

            def spec_fn(params, draft_params, win_tokens, win_len, pos0,
                        kv, draft_kv, pt,
                        temps, top_ps, top_ks, seeds, counters_v0,
                        freq=None, pres=None, rep_p=None,
                        out_toks=None, out_valid=None,
                        bias_ids=None, bias_vals=None, bias_gated=None,
                        min_toks=None):
                from dynamo_tpu.engine.sampling import spec_accept_step

                rows = jnp.arange(b_static)
                w = s_steps + 1
                w_positions = (
                    pos0[:, None] + jnp.arange(w, dtype=jnp.int32)[None]
                )
                w_valid = (
                    jnp.arange(w, dtype=jnp.int32)[None]
                    < win_len[:, None]
                )
                live = win_len > 0  # padding rows never write KV
                last_idx = jnp.maximum(win_len - 1, 0)
                # draft catch-up: commits the window tokens' draft KV and
                # yields the hidden state the first proposal reads
                hid_d, draft_kv = draft_adapter.forward_hidden(
                    draft_params, win_tokens, w_positions, w_valid,
                    draft_kv, pt,
                )
                h = hid_d[rows, last_idx]
                pos_last = pos0 + last_idx  # [B] = num_tokens - 1
                d0 = jnp.argmax(
                    draft_adapter.compute_logits(draft_params, h), axis=-1
                ).astype(jnp.int32)
                if s_steps > 1:

                    def propose(carry, j):
                        tok, dkv = carry
                        hj, dkv = draft_adapter.forward_hidden(
                            draft_params, tok[:, None],
                            (pos_last + 1 + j)[:, None], live[:, None],
                            dkv, pt,
                        )
                        nxt = jnp.argmax(
                            draft_adapter.compute_logits(
                                draft_params, hj[:, -1]
                            ),
                            axis=-1,
                        ).astype(jnp.int32)
                        return (nxt, dkv), nxt

                    (_, draft_kv), rest = jax.lax.scan(
                        propose, (d0, draft_kv),
                        jnp.arange(s_steps - 1, dtype=jnp.int32),
                    )
                    draft_ids = jnp.concatenate(
                        [d0[:, None], rest.T], axis=1
                    )  # [B, S]
                else:
                    draft_ids = d0[:, None]
                # target verify over [last accepted, d_0 .. d_{S-1}]
                last_tok = win_tokens[rows, last_idx]
                v_tokens = jnp.concatenate(
                    [last_tok[:, None], draft_ids], axis=1
                )
                v_positions = (
                    pos_last[:, None]
                    + jnp.arange(w, dtype=jnp.int32)[None]
                )
                v_valid = jnp.broadcast_to(live[:, None], (b_static, w))
                hid_t, kv = adapter.forward_hidden(
                    params, v_tokens, v_positions, v_valid, kv, pt
                )
                bsz, tlen, hdim = hid_t.shape
                logits = adapter.compute_logits(
                    params, hid_t.reshape(bsz * tlen, hdim)
                ).reshape(bsz, tlen, -1)
                # sequential acceptance: position j emits iff every
                # earlier draft was accepted; penalties extend their
                # history per emitted token exactly like decode_multi
                if pen:
                    from dynamo_tpu.engine.sampling import (
                        build_output_counts,
                    )

                    counts = build_output_counts(out_toks, out_valid, vocab)
                else:
                    counts = None
                alive = live
                n_acc = jnp.zeros(b_static, jnp.int32)
                outs = []
                for j in range(w):
                    eff = logits[:, j]
                    if pen:
                        from dynamo_tpu.engine.sampling import (
                            apply_penalties,
                        )

                        eff = apply_penalties(
                            eff, counts, freq, pres, rep_p
                        )
                    if bias:
                        from dynamo_tpu.engine.sampling import (
                            apply_logit_bias,
                        )

                        eff = apply_logit_bias(
                            eff, bias_ids, bias_vals, bias_gated,
                            counters_v0 + j, min_toks,
                        )
                    draft_j = (
                        draft_ids[:, j]
                        if j < s_steps
                        else jnp.zeros(b_static, jnp.int32)
                    )
                    if greedy:
                        chosen = jnp.argmax(eff, axis=-1).astype(jnp.int32)
                        acc = (
                            chosen == draft_j
                            if j < s_steps
                            else jnp.ones(b_static, bool)
                        )
                    else:
                        chosen, acc = spec_accept_step(
                            eff, draft_j, j < s_steps, temps, top_ps,
                            top_ks, seeds, counters_v0 + j,
                        )
                    outs.append(chosen)
                    n_acc = n_acc + alive.astype(jnp.int32)
                    if pen:
                        counts = counts.at[rows, chosen].add(
                            alive.astype(jnp.float32)
                        )
                    alive = alive & acc
                out_ids = jnp.stack(outs, axis=1)  # [B, S+1]
                return (
                    rep(out_ids), rep(draft_ids), rep(n_acc), kv, draft_kv
                )

            jitted = jax.jit(spec_fn, donate_argnums=(5, 6))
            logger.info(
                "compiled spec_fused program B=%d S=%d greedy=%s pen=%s "
                "bias=%s", b, s_steps, greedy, pen, bias,
            )
            return self._cache_jit(kind, cache_key, jitted)

        if kind == "prefill_nosample":

            def nosample_fn(params, tokens, positions, valid, kv, pt,
                            mm_embeds=None, mm_mask=None):
                _, kv = adapter.forward_hidden(
                    params, tokens, positions, valid, kv, pt,
                    mm_embeds=mm_embeds, mm_mask=mm_mask,
                    first_chunk=first_chunk,
                )
                return kv

            jitted = jax.jit(nosample_fn, donate_argnums=(4,))
            logger.info("compiled %s program B=%d T=%d", kind, b, t)
            return self._cache_jit(kind, cache_key, jitted)

        def step_fn(params, tokens, positions, valid, kv, pt, last_idx,
                    temps, top_ps, top_ks, seeds, counters,
                    freq=None, pres=None, rep_p=None,
                    out_toks=None, out_valid=None,
                    bias_ids=None, bias_vals=None, bias_gated=None,
                    min_toks=None, mm_embeds=None, mm_mask=None):
            hidden, kv = adapter.forward_hidden(
                params, tokens, positions, valid, kv, pt,
                mm_embeds=mm_embeds, mm_mask=mm_mask,
                first_chunk=first_chunk,
            )
            rows = jnp.arange(hidden.shape[0])
            last_hidden = hidden[rows, last_idx]  # [B, H] — lm_head only here
            logits = adapter.compute_logits(params, last_hidden)
            counts = None
            if pen:
                from dynamo_tpu.engine.sampling import build_output_counts

                counts = build_output_counts(
                    out_toks, out_valid, adapter.vocab_size
                )
            ids = pick(
                logits, (temps, top_ps, top_ks, seeds, counters),
                counts=counts, freq=freq, pres=pres, rep_p=rep_p,
                bias_args=(
                    (bias_ids, bias_vals, bias_gated, min_toks)
                    if bias
                    else None
                ),
            )
            if lp >= 0:
                return rep(ids), rep(maybe_logprobs(logits, ids)), kv
            return rep(ids), kv

        # one body, a name for each kind: a profiler's trace shows the
        # program as jit_<name>, and prompt processing must not read as
        # one-step decode
        if kind == "decode":

            def decode_fn(*args, **kwargs):
                return step_fn(*args, **kwargs)

            jitted = jax.jit(decode_fn, donate_argnums=(4,))
        else:

            def prefill_fn(*args, **kwargs):
                return step_fn(*args, **kwargs)

            jitted = jax.jit(prefill_fn, donate_argnums=(4,))
        logger.info("compiled %s program B=%d T=%d", kind, b, t)
        return self._cache_jit(kind, cache_key, jitted)

    def _finish_reason_for(
        self, req: Request, token: int, n_new: int
    ) -> Optional[FinishReason]:
        """Finish check for the n_new'th newly-sampled token of this
        dispatch (token not yet appended to the request)."""
        s = req.sampling
        if not s.ignore_eos and (
            token in self.config.eos_token_ids or token in s.stop_token_ids
        ):
            return FinishReason.STOP
        if len(req.output_tokens) + n_new + req.num_emitted >= s.max_tokens:
            return FinishReason.LENGTH
        if req.num_tokens + n_new >= self.config.max_context:
            return FinishReason.LENGTH
        return None

    @staticmethod
    def _batch_trace_id(batch) -> Optional[str]:
        """Any traced request's trace id in this dispatch — the phase
        histogram's exemplar for the bucket the step lands in. Always
        None when tracing is off (no Request carries a trace_id then),
        so the disabled path pays one short loop over the batch."""
        for req in batch.decode:
            if req.trace_id is not None:
                return req.trace_id
        for piece in batch.prefill:
            if piece.request.trace_id is not None:
                return piece.request.trace_id
        return None

    def _observe_emission(self, req: Request, finished: bool) -> None:
        """Decode-stall histogram bookkeeping: observe the gap since this
        request's previous token emission whenever a prefill-carrying
        dispatch (pure prefill or mixed) ran in between — the prefill-
        attributed stall one running request experienced. Under the XOR
        scheduler these gaps are whole backlog drains; under mixed steps
        they collapse to one step."""
        now = time.perf_counter()
        mark = self.metrics.prefill_dispatches + self.metrics.mixed_dispatches
        prev = self._last_emit.get(req.request_id)
        if prev is not None and mark > prev[1]:
            stall_ms = (now - prev[0]) * 1000.0
            if req.trace_id is not None:
                # traced request: accumulate so the final StepOutput can
                # carry the request's TOTAL prefill-induced stall onto
                # its engine.generate span (timeline breakdown)
                req.stall_accum_ms += stall_ms
            phases.observe(
                "decode_stall_ms", stall_ms, trace_id=req.trace_id
            )
        if finished:
            self._last_emit.pop(req.request_id, None)
        else:
            self._last_emit[req.request_id] = (now, mark)

    def _observe_slo(self, req: Request, n_tokens: int, finished: bool) -> None:
        """Feed the worker-side SLO sketches (config.fleet_telemetry):
        TTFT on the first emission, per-token ITL on later ones (a fused
        dispatch's delivery spreads its gap over its tokens), e2e + the
        SLA/goodput judgement at finish. arrival_time is 0.0 for
        directly-constructed Requests (unit tests, tools) — those skip
        the wall-clock metrics rather than record epoch-sized garbage."""
        now = time.perf_counter()
        mark = self._slo_marks.get(req.request_id)
        if mark is None:
            ttft_ms = None
            if req.arrival_time:
                ttft_ms = max(0.0, (time.time() - req.arrival_time) * 1000.0)
                self.slo.observe("ttft_ms", ttft_ms)
            mark = self._slo_marks[req.request_id] = [ttft_ms, 0.0, 0, now]
        else:
            gap_ms = (now - mark[3]) * 1000.0 / max(1, n_tokens)
            self.slo.observe("itl_ms", gap_ms)
            mark[1] += gap_ms
            mark[2] += 1
            mark[3] = now
        if finished:
            self._slo_marks.pop(req.request_id, None)
            e2e_ms = None
            if req.arrival_time:
                e2e_ms = max(0.0, (time.time() - req.arrival_time) * 1000.0)
                self.slo.observe("e2e_ms", e2e_ms)
            self.slo.finish_request(
                ttft_ms=mark[0],
                itl_ms=mark[1] / mark[2] if mark[2] else None,
                e2e_ms=e2e_ms,
                tokens=len(req.output_tokens) + req.num_emitted,
            )

    def _accept_tokens(
        self,
        req: Request,
        tokens: Sequence[int],
        finish: Optional[FinishReason],
        first: bool = False,
        lps: Optional[tuple[float, ...]] = None,
        tops: Optional[tuple] = None,
        mixed: bool = False,
        spec: bool = False,
    ) -> list[StepOutput]:
        chain = self.scheduler.chains.get(req.request_id)
        for tok in tokens:
            req.output_tokens.append(tok)
            if chain is not None:
                chain.append(tok)
        self.metrics.generated_tokens += len(tokens)
        if tokens:
            self._observe_emission(req, finished=finish is not None)
            if self.slo is not None:
                self._observe_slo(req, len(tokens), finish is not None)
        if finish is not None:
            self.scheduler.finish(req)
            req.finish_reason = finish
        return [
            StepOutput(
                request_id=req.request_id,
                new_token_ids=tuple(tokens),
                finish_reason=finish,
                is_first=first,
                logprobs=lps,
                top_logprobs=tops,
                # prefix-cache accounting rides the first output (OpenAI
                # usage.prompt_tokens_details.cached_tokens)
                cached_tokens=req.num_cached_prompt_tokens if first else None,
                mixed=mixed,
                spec=spec,
                # tracing enrichment (traced requests only; None — and
                # absent from the wire — otherwise): queue wait on the
                # first output, accumulated decode stall on the last
                queue_wait_ms=(
                    req.queue_wait_ms
                    if first and req.trace_id is not None
                    else None
                ),
                stall_ms=(
                    round(req.stall_accum_ms, 3)
                    if finish is not None
                    and req.trace_id is not None
                    and req.stall_accum_ms > 0.0
                    else None
                ),
            )
        ]

    def _accept_token(
        self, req: Request, token: int, first: bool = False,
        lps: Optional[tuple[float, ...]] = None, tops: Optional[tuple] = None,
        mixed: bool = False,
    ) -> list[StepOutput]:
        finish = self._finish_reason_for(req, token, 1)
        return self._accept_tokens(
            req, [token], finish, first=first, lps=lps, tops=tops,
            mixed=mixed,
        )

    # -- embeddings --------------------------------------------------------

    def embed(
        self, prompts: Sequence[Sequence[int]], normalize: bool = True
    ) -> np.ndarray:
        """Mean-pooled (optionally L2-normalized) last-layer hidden states,
        one vector per prompt (the /v1/embeddings engine path — the
        reference delegates this to its engines; here it shares the prefill
        programs' chunked execution and page pool). Pages are scratch:
        allocated for attention across chunks, freed before returning."""
        if self._stateful:
            raise ValueError(
                f"{self.config.model} keeps a state slot a sequence beside "
                "its pages: /v1/embeddings is not supported for it (its "
                "scratch pages would need a scratch state slot)"
            )
        out: list[np.ndarray] = []
        ps = self.config.page_size
        mp = self.config.max_pages_per_seq
        for toks in prompts:
            toks = list(toks)
            if not toks:
                raise ValueError("cannot embed an empty token sequence")
            need = -(-len(toks) // ps)
            if need > mp:
                raise ValueError(
                    f"prompt of {len(toks)} tokens needs {need} KV pages; "
                    f"max_pages_per_seq is {mp}"
                )
            pages = self.allocator.allocate(need)
            if pages is None:
                raise RuntimeError("no KV pages free for embedding")
            try:
                acc: Optional[np.ndarray] = None
                for start in range(0, len(toks), self.config.prefill_chunk):
                    chunk = toks[start : start + self.config.prefill_chunk]
                    t_bucket = self._bucket_t(len(chunk))
                    tokens = np.zeros((1, t_bucket), np.int32)
                    tokens[0, : len(chunk)] = chunk
                    positions = (
                        np.arange(t_bucket, dtype=np.int32)[None] + start
                    )
                    valid = np.zeros((1, t_bucket), bool)
                    valid[0, : len(chunk)] = True
                    pt = np.zeros((1, mp), np.int32)
                    pt[0, : len(pages)] = pages
                    fn = self._get_step_fn("embed", 1, t_bucket)
                    d_tokens, d_positions, d_valid, d_pt = self._dev_tree(
                        (tokens, positions, valid, pt)
                    )
                    pooled, self.kv = fn(
                        self.params, d_tokens, d_positions, d_valid,
                        self.kv, d_pt,
                    )
                    vec = np.asarray(pooled, np.float32)[0]
                    acc = vec if acc is None else acc + vec
                mean = acc / len(toks)
            finally:
                self.allocator.free(pages)
            if normalize:
                norm = float(np.linalg.norm(mean))
                if norm > 0:
                    mean = mean / norm
            out.append(mean)
        return np.stack(out)

    # -- disaggregated prefill/decode hooks -------------------------------
    # (decode side pre-allocates pages; a prefill worker computes the KV,
    #  extracts it from its own pool, and the transfer service injects it
    #  here — the reference's NIXL RDMA write path, dynamo_flow.md:36-38,
    #  re-done as explicit page movement through host/DCN for TPU.)

    @property
    def _hidden_size(self) -> int:
        cfg = self.adapter.config
        return (
            cfg.hidden_size
            if hasattr(cfg, "hidden_size")
            else cfg.base.hidden_size
        )

    @property
    def _canonical_head_dims(self) -> tuple:
        """The true last-dim widths of (k, v) — the wire/host format for
        extracted pages. The device cache may be lane-padded
        (cfg.kv_head_dim) when the Pallas kernel is active; extract strips
        the padding and inject restores it, so disagg peers and KVBM tiers
        with different attention impls interoperate (and host/disk tiers
        don't store zero lanes). MLA caches are ASYMMETRIC (k = latent,
        v = rope key, lane-padded under the kernels like any head)."""
        cfg = self.adapter.config
        if hasattr(cfg, "kv_lora_rank"):  # MLA: asymmetric
            return (cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        d = cfg.head_dim if hasattr(cfg, "head_dim") else cfg.base.head_dim
        return (d, d)

    @_pages_only
    def extract_pages(self, page_ids: Sequence[int]):
        """Pull KV pages to host in the canonical wire format:
        (k, v) as [L, Hkv, n, page_size, D] — layout- and padding-agnostic
        so disagg peers and KVBM tiers interoperate across engine configs.
        (Device cache is [L, P, S, Hkv, Dpad].)

        Cross-host meshes return the PROCESS-LOCAL Hkv slice: each host
        tiers its own shard and `inject_pages` reassembles the global
        array from the per-host slices (reference KVBM has no
        single-process restriction either, block_manager.rs:69-78)."""
        if not self._multiproc:
            k, v = self.extract_pages_async(page_ids)
            return np.asarray(k), np.asarray(v)
        n = len(page_ids)
        fn = self._jit_cache.get(("extract_mp", n))
        if fn is None:
            dk, dv = self._canonical_head_dims
            fn = jax.jit(
                lambda kv, ids: _canonical_gather(kv, ids, dk, dv),
                out_shardings=(
                    self._canonical_kv_sharding(self.kv.k),
                    self._canonical_kv_sharding(self.kv.v),
                ),
            )
            fn = self._cache_jit("extract", ("extract_mp", n), fn)
        k, v = fn(self.kv, jnp.asarray(np.asarray(page_ids, np.int32)))
        return self._process_local_np(k), self._process_local_np(v)

    def _canonical_kv_sharding(self, pool):
        """Sharding of the canonical [L, Hkv, n, S, D] layout matching
        `pool`'s [L, P, S, Hkv, Dpad] placement: the Hkv axis keeps the
        pool's mesh axis (tp for head-sharded caches, replicated for
        MLA's shared latent), everything else replicates."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = getattr(pool.sharding, "spec", None)
        head_axis = spec[3] if spec is not None and len(spec) > 3 else None
        return NamedSharding(self.mesh, P(None, head_axis, None, None, None))

    @staticmethod
    def _process_local_np(arr) -> np.ndarray:
        """This process's slice of a canonical global array as numpy:
        dedupe the addressable shards by their Hkv offset (dp replicas
        carry identical bytes) and concatenate the distinct slices."""
        by_start: dict = {}
        for s in arr.addressable_shards:
            sl = s.index[1]
            start = sl.start or 0
            if start not in by_start:
                by_start[start] = np.asarray(s.data)
        starts = sorted(by_start)
        parts = [by_start[i] for i in starts]
        # make_array_from_process_local_data needs one contiguous local
        # block per process — standard mesh construction guarantees it
        for a, b, p in zip(starts, starts[1:], parts):
            assert a + p.shape[1] == b, (
                "non-contiguous local KV shards; mesh device order is "
                "not process-contiguous"
            )
        return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=1)

    @_pages_only
    def extract_pages_async(self, page_ids: Sequence[int]):
        """Async variant: the page gather + canonical transpose run on
        device and the device→host copy is started without blocking; the
        returned jax arrays materialize on first np.asarray. The gather is
        enqueued on the device stream BEFORE any later dispatch can
        overwrite the pages, so content is captured even though the pool
        may hand the page ids out immediately (KVBM's double-buffered
        offload rides this — the reference overlaps offload DMA the same
        way, block_manager/offload.rs)."""
        ids = jnp.asarray(np.asarray(page_ids, np.int32))
        dk, dv = self._canonical_head_dims
        k, v = _canonical_gather(self.kv, ids, dk, dv)
        k.copy_to_host_async()
        v.copy_to_host_async()
        return k, v

    @_pages_only
    def inject_pages(self, page_ids: Sequence[int], k: np.ndarray, v: np.ndarray) -> None:
        """Write transferred KV pages (canonical [L, Hkv, n, S, D]) into
        this engine's pool in place. Host arrays become uncommitted device
        arrays, so the jitted scatter reshards them onto whatever mesh the
        pool lives on. Cross-host meshes take the PROCESS-LOCAL Hkv slice
        (what `extract_pages` returned on this host) and assemble the
        global array from every host's slice."""
        if self._multiproc:
            ksh = self._canonical_kv_sharding(self.kv.k)
            vsh = self._canonical_kv_sharding(self.kv.v)
            hkv = self.kv.k.shape[3]
            gk = jax.make_array_from_process_local_data(
                ksh, np.ascontiguousarray(k),
                (k.shape[0], hkv, *k.shape[2:]),
            )
            gv = jax.make_array_from_process_local_data(
                vsh, np.ascontiguousarray(v),
                (v.shape[0], hkv, *v.shape[2:]),
            )
            self.inject_pages_device(page_ids, gk, gv)
            return
        self.inject_pages_device(page_ids, jnp.asarray(k), jnp.asarray(v))

    @_pages_only
    def inject_pages_device(self, page_ids: Sequence[int], k, v) -> None:
        """Device-path inject: k/v are jax arrays (canonical
        [L, Hkv, n, S, D] — D+4 int8 with trailing packed scales on
        quantized pools); the unpack, transpose, head-dim pad, and
        scatter all run in one jitted program — no host round-trip on the
        single-chip path (the point of the ICI transfer plane)."""
        pool_sharding = getattr(self.kv.k, "sharding", None)
        if (
            pool_sharding is not None
            and len(pool_sharding.device_set) > 1
            and getattr(k, "sharding", None) is not None
            and k.sharding.device_set != pool_sharding.device_set
        ):
            # Pulled arrays are committed to one device; a jit over a
            # multi-device pool would reject the conflicting placement.
            # Stage through host (per-shard ICI pulls are the future
            # optimization) — jnp.asarray(np) yields uncommitted arrays
            # the scatter can reshard freely.
            k = jnp.asarray(np.asarray(k))
            v = jnp.asarray(np.asarray(v))
        n = len(page_ids)
        quantized = self.kv.k_scale is not None
        scale_lanes = 4 if quantized else 0
        dpad_k = self.kv.k.shape[-1] - (k.shape[-1] - scale_lanes)
        dpad_v = self.kv.v.shape[-1] - (v.shape[-1] - scale_lanes)
        fn = self._jit_cache.get(("inject_dev", n, dpad_k, dpad_v))
        if fn is None:
            def inject_fn(kv, ids, kk, vv):
                kks = vvs = None
                if quantized:
                    kk, kks = _wire_unpack(
                        kk, kv.k.shape[-1] - dpad_k, kv.k.dtype
                    )
                    vv, vvs = _wire_unpack(
                        vv, kv.v.shape[-1] - dpad_v, kv.v.dtype
                    )
                kk = kk.transpose(0, 2, 3, 1, 4)
                vv = vv.transpose(0, 2, 3, 1, 4)
                if dpad_k:
                    kk = jnp.pad(
                        kk, [(0, 0)] * (kk.ndim - 1) + [(0, dpad_k)]
                    )
                if dpad_v:
                    vv = jnp.pad(
                        vv, [(0, 0)] * (vv.ndim - 1) + [(0, dpad_v)]
                    )
                out = kv._replace(
                    k=kv.k.at[:, ids].set(kk.astype(kv.k.dtype)),
                    v=kv.v.at[:, ids].set(vv.astype(kv.v.dtype)),
                )
                if quantized:
                    # wire [L, Hkv, n, S] -> planes' [L, n, Hkv, :S]
                    s = kv.k.shape[2]
                    out = out._replace(
                        k_scale=kv.k_scale.at[:, ids, :, :s].set(
                            kks.transpose(0, 2, 1, 3)
                        ),
                        v_scale=kv.v_scale.at[:, ids, :, :s].set(
                            vvs.transpose(0, 2, 1, 3)
                        ),
                    )
                return out
            fn = self._cache_jit(
                "inject", ("inject_dev", n, dpad_k, dpad_v),
                jax.jit(inject_fn, donate_argnums=(0,)),
            )
        self.kv = fn(
            self.kv, jnp.asarray(np.asarray(page_ids, np.int32)), k, v
        )
        # The transfer server acks the sender the moment its write_fn
        # returns, and the sender then reuses its staging buffer (the shm
        # plane reuses the very mmap our jnp.asarray views may alias on
        # the CPU backend, or an async H2D copy may still be reading on
        # TPU). Commit the scatter before returning so the ack really
        # means "bytes landed" — once per transfer, not per token. On the
        # worker path this blocks the ENGINE thread (runner.submit), not
        # the event loop, and the next decode step would queue behind the
        # same device stream anyway.
        jax.block_until_ready(tuple(x for x in self.kv if x is not None))

    # -- G4 remote tier: serve/adopt blocks across workers -----------------
    # (reference: KvBlockManager::export_local_blockset / onboard_blocks —
    # block_manager.rs:121,169)

    @_pages_only
    def serve_blocks(self, seq_hashes: Sequence[int]):
        """Export the longest locally-resident chain of `seq_hashes` for a
        peer: (metas, k, v) with metas=[(seq_hash, parent, tokens)...] and
        k/v canonical FULL-Hkv [L, Hkv, n, S, D] host arrays; None when
        the first hash isn't here. Device pages are ref-held during
        extraction; the lower tiers are read without promotion.

        Cross-host meshes refuse: extraction (and the tiers) hold only
        this process's Hkv slice, and shipping a partial-head array to a
        peer expecting the full canonical layout would install silently
        wrong KV. (The Worker already bars kv_remote on SPMD groups —
        this guard keeps the contract honest for direct callers.)"""
        if self._multiproc:
            return None
        alloc = self.allocator
        pages = PageAllocator.lookup(alloc, seq_hashes)  # never onboards
        metas: list[tuple] = []
        parts_k: list[np.ndarray] = []
        parts_v: list[np.ndarray] = []
        try:
            if pages:
                k, v = self.extract_pages(pages)
                parts_k.append(k)
                parts_v.append(v)
                metas = [alloc._page_meta[p] for p in pages]
        finally:
            if pages:
                alloc.free(pages)
        tier_get = getattr(alloc, "_tier_get", None)
        if tier_get is not None:
            entries = []
            for h in seq_hashes[len(pages):]:
                e = tier_get(h)
                if e is None:
                    break
                entries.append(e)
            if entries:
                parts_k.append(np.stack([e.k for e in entries], axis=2))
                parts_v.append(np.stack([e.v for e in entries], axis=2))
                metas.extend(
                    (e.seq_hash, e.parent_hash, e.tokens) for e in entries
                )
        if not metas:
            return None
        k = parts_k[0] if len(parts_k) == 1 else np.concatenate(parts_k, axis=2)
        v = parts_v[0] if len(parts_v) == 1 else np.concatenate(parts_v, axis=2)
        return metas, k, v

    @_pages_only
    def adopt_blocks(self, metas: Sequence[tuple], k, v) -> int:
        """Land a peer-served chain into this engine's prefix cache:
        allocate fresh pages, inject the bytes, register the hashes (which
        also publishes 'stored' events so routers learn the new holder).
        Returns blocks adopted; skips blocks already resident and refuses
        chains whose parent isn't resident (nothing would ever match
        them)."""
        alloc = self.allocator
        tier_contains = getattr(alloc, "tier_contains", lambda h: False)
        start = 0
        while start < len(metas) and alloc.match_length([metas[start][0]]):
            start += 1
        todo = list(metas[start:])
        if not todo:
            return 0
        parent = todo[0][1]
        if (
            parent is not None
            and not alloc.match_length([parent])
            and not tier_contains(parent)
        ):
            return 0
        pages = alloc.allocate(len(todo))
        if pages is None:
            return 0  # pool pressure — skip this time
        self.inject_pages(pages, k[:, :, start:], v[:, :, start:])
        for page, (h, ph, toks) in zip(pages, todo):
            alloc.register_promoted(page, h, ph, tuple(toks))
        # Adopted blocks are cache content, not request-held: release so
        # they stay registered but reclaimable.
        alloc.free(pages)
        return len(todo)

    # -- worker handover: bulk export / adopt of the registered block set
    # (docs/operations.md "Rolling upgrades & worker handover"). The
    # byte movement itself rides the disagg transfer planes via the
    # normal page-addressed write path — these helpers only deal in the
    # allocator's content addressing on either side. ---------------------

    def handover_metas(self) -> list:
        """Topo-ordered (seq_hash, parent_hash, tokens) for every
        device-registered block — the retiring worker's migratable hot
        set, parents before children so any batch prefix is adoptable.
        Cross-host meshes export nothing (same partial-Hkv refusal as
        serve_blocks)."""
        if self._multiproc:
            return []
        from dynamo_tpu.handover import topo_order_metas

        return topo_order_metas(list(self.allocator._page_meta.values()))

    @_pages_only
    def export_blocks_by_hash(self, seq_hashes: Sequence[int]):
        """Extract the subset of `seq_hashes` still device-registered as
        (metas, k, v) in the canonical wire format — the handover batch
        export. Unlike serve_blocks this addresses blocks individually
        (a topo batch may span branches), holds a reference on each page
        across the extraction, and never touches the lower tiers. None
        when nothing in the batch is still resident (eviction between
        the meta listing and this call is legal — the batch shrinks)."""
        if self._multiproc:
            return None
        alloc = self.allocator
        pages: list[int] = []
        metas: list[tuple] = []
        try:
            for h in seq_hashes:
                got = PageAllocator.lookup(alloc, [h])  # base: no onboard
                if not got:
                    continue
                pages.append(got[0])
                metas.append(alloc._page_meta[got[0]])
            if not pages:
                return None
            k, v = self.extract_pages(pages)
        finally:
            if pages:
                alloc.free(pages)
        return metas, np.asarray(k), np.asarray(v)

    @_pages_only
    def prepare_handover_adopt(self, metas: Sequence[tuple]):
        """Successor-side reservation: allocate fresh pages for the
        not-yet-resident blocks of `metas`. Returns (pages, kept_metas,
        want_idx) — the transfer write lands bytes into `pages`, then
        commit_handover_adopt registers them (or abort_ frees them).
        Trims to what the pool can take right now: a handover must never
        preempt live work on the successor."""
        alloc = self.allocator
        tier_contains = getattr(alloc, "tier_contains", lambda h: False)
        kept: list[tuple] = []
        want_idx: list[int] = []
        for i, (h, p, toks) in enumerate(metas):
            if alloc.match_length([h]) or tier_contains(h):
                continue
            kept.append((h, p, toks))
            want_idx.append(i)
        n_fit = min(len(kept), alloc.num_free)
        kept, want_idx = kept[:n_fit], want_idx[:n_fit]
        if not kept:
            return None
        pages = alloc.allocate(len(kept))
        if pages is None:
            return None
        return pages, kept, want_idx

    def commit_handover_adopt(self, pages, metas) -> int:
        """The batch's bytes landed (transfer ack fired): content-address
        the reserved pages and release them into the reclaimable cache —
        registration publishes 'stored' events, so routers immediately
        score this worker for the migrated prefixes."""
        for page, (h, p, toks) in zip(pages, metas):
            self.allocator.register_promoted(page, h, p, tuple(toks))
        self.allocator.free(pages)
        return len(pages)

    def abort_handover_adopt(self, pages) -> None:
        """The bytes never landed: the unregistered reservation goes
        straight back to the free list — no leak, no half-adopted KV."""
        self.allocator.free(pages)

    @_pages_only
    def allocate_for_remote_prefill(
        self,
        request_id: str,
        prompt_tokens: Sequence[int],
        sampling: Optional[SamplingParams] = None,
    ) -> Optional[Request]:
        """Decode-side page reservation: allocate the prompt's pages (plus
        one-token headroom) now so a prefill worker can write into them.
        Returns None when the pool can't take it (caller falls back local)."""
        self._validate_bias(sampling)
        ps = self.config.page_size
        need = -(-(len(prompt_tokens) + 1) // ps)
        pages = self.allocator.allocate(need)
        if pages is None:
            return None
        req = Request(
            request_id=request_id,
            prompt_tokens=list(prompt_tokens),
            sampling=sampling or SamplingParams(),
            arrival_time=time.time(),
        )
        req.pages = pages
        return req

    @_pages_only
    def add_prefilled(self, req: Request, first_token: int) -> list[StepOutput]:
        """Admit a remote-prefilled request into decode: its pages hold the
        prompt KV; accept the prefill worker's first sampled token and let
        the normal decode loop continue."""
        chain = TokenBlockSequence(
            req.prompt_tokens, block_size=self.config.page_size,
            salt=self.config.model,
        )
        self.scheduler.add_prefilled(req, chain)
        outputs = self._accept_token(req, first_token, first=True)
        self._register_pages(req)
        self._refresh_metrics()
        return outputs

    def cancel_remote_prefill(self, req: Request) -> None:
        """Transfer failed or timed out: give the reservation back."""
        if req.pages:
            self.allocator.free(req.pages)
            req.pages = []

    def _register_pages(self, req: Request) -> None:
        """Content-address any newly *filled* pages (enables prefix sharing
        and emits 'stored' KV events for routers). Called for every row of
        every dispatch, so it resumes at `req.registered_blocks` and does
        not walk the request's pages from block 0: a 64-row batch of
        4k-token contexts was ~4,100 allocator calls a dispatch, each a
        ctypes call that hands the interpreter lock to the thread
        delivering tokens."""
        if not self.config.enable_prefix_caching or req.mm_embeds is not None:
            return
        chain = self.scheduler.chains.get(req.request_id)
        if chain is None:
            return
        full_computed = min(
            min(req.num_computed_tokens, len(chain)) // self.config.page_size,
            len(req.pages),
        )
        done = req.registered_blocks
        for bi in range(done, full_computed):
            block = chain.blocks[bi]
            if self.allocator.register(
                req.pages[bi],
                block.sequence_hash,
                block.parent_sequence_hash,
                block.tokens,
            ) and bi == done:
                # a block refused as a duplicate is offered again next
                # time (the page it duplicates may have been evicted)
                done += 1
        req.registered_blocks = done

    def _refresh_metrics(self) -> None:
        # Complete async KVBM offloads started last step (double buffer:
        # the device→host copies overlapped this step's compute).
        self.allocator.flush_offloads()
        m = self.metrics
        m.num_waiting = self.scheduler.num_waiting()
        m.num_running = self.scheduler.num_running()
        m.kv_active_pages = self.allocator.num_active
        m.kv_free_pages = self.allocator.num_free
        m.kv_usage = self.allocator.usage()
        m.prefix_hit_rate = self.allocator.stats.hit_rate
        m.kv_pages_watermark = max(
            getattr(self.allocator, "watermark", 0), m.kv_active_pages,
            m.kv_pages_watermark,
        )
        m.preemptions = self.scheduler.preemptions
        if self._stateful:
            m.state_slots_live = self.allocator.slots_watermark
            m.state_resets = self.allocator.slots_taken
            m.prefix_hits_refused_state = (
                self.allocator.prefix_hits_refused_state
            )
        m.queue_wait_ms_total = self.scheduler.queue_wait_ms_total
        m.admissions = self.scheduler.admissions
        if self._spec_draft or self.config.spec_ngram > 0:
            # live acceptance-rate gauge over the spec-step window
            now_s = time.perf_counter()
            sw = self._spec_window
            while sw and now_s - sw[0][0] > self._spec_window_s:
                _, d, a = sw.popleft()
                self._spec_win_drafted -= d
                self._spec_win_accepted -= a
            m.spec_accept_rate = (
                round(self._spec_win_accepted / self._spec_win_drafted, 4)
                if self._spec_win_drafted
                else 0.0
            )
            m.spec_window_drafted = self._spec_win_drafted
        # pre-admission deadline drops land here; the runner adds its own
        # mid-decode expiries on top (they never reach the scheduler)
        m.deadline_expired = (
            self.scheduler.deadline_drops + self._runner_deadline_expired
        )
        if self._fleet_telemetry:
            # windowed throughput
            now = time.perf_counter()
            w = self._thru_window
            while w and now - w[0][0] > self._thru_window_s:
                self._thru_tokens -= w.popleft()[1]
            if len(w) >= 2:
                span = now - w[0][0]
                toks = self._thru_tokens
                if span > 1e-3 and toks:
                    m.tokens_per_s = round(toks / span, 2)
            else:
                # window drained: an idle worker must report zero, not
                # its last busy throughput forever
                m.tokens_per_s = 0.0

    # -- debug plane: the compile table + on-demand profiling -------------
    # (docs/observability.md "Debugging a slow or stuck worker")

    def programs_report(self) -> dict:
        """GET /v1/debug/programs: every program this engine has loaded
        (kind, key, first-call ms) plus a per-kind rollup: how many
        programs, how many compiles, their summed first-call ms. A kind
        whose `compiles` climbs in steady state is the program family
        churning (doctor's compile-storm points here)."""
        # list() first: the engine thread inserts on steady-state
        # recompiles (the compile-storm case this report diagnoses)
        # while the publish loop / debug endpoints iterate here
        programs = [dict(p) for p in list(self.programs.values())]
        kinds: dict[str, dict] = {}
        for p in programs:
            k = kinds.setdefault(
                p["kind"],
                {"programs": 0, "compile_ms": 0.0,
                 **dict.fromkeys(_FIRST_CALL_PARTS, 0.0), "cache_hits": 0,
                 "compiles": self.compiles_by_kind.get(p["kind"], 0)},
            )
            k["programs"] += 1
            for part in ("compile_ms", *_FIRST_CALL_PARTS):
                k[part] = round(k[part] + p[part], 3)
            k["cache_hits"] += p["cache"] == "hit"
        boot = {
            part: round(getattr(self.metrics, f"boot_{part}"), 3)
            for part in ("before_ms", "weights_ms", "pools_ms", "ms")
        }
        return {"programs": programs, "kinds": kinds, "boot": boot}

    def programs_wire(self) -> dict:
        """The compact per-kind rollup that rides the metrics frame."""
        return self.programs_report()["kinds"]

    # -- HBM accounting & mesh introspection (GET /v1/debug/{memory,
    # mesh} — docs/observability.md "Reading the perf plane"). All
    # host-side, publish-cadence work: the token path never runs any of
    # it, and with collection enabled the emitted tokens are
    # bit-identical (pinned in tests/test_perf_plane.py). ---------------

    @staticmethod
    def _device_key(dev) -> str:
        """Stable per-device label: the jax device id (the `device`
        label of the dynamo_tpu_hbm_* families)."""
        return str(getattr(dev, "id", 0))

    def _per_device_bytes(self, tree) -> dict[str, int]:
        """Bytes each addressable device holds of `tree`: sharded
        jax.Arrays contribute their LOCAL shard bytes to the device each
        shard lives on (so a tp=4 weight counts a quarter per chip);
        host-resident leaves (numpy, before any device_put) are
        attributed to device 0, where the first dispatch places them."""
        out: dict[str, int] = {}
        default = self._device_key(jax.devices()[0])
        for x in jax.tree.leaves(tree):
            shards = getattr(x, "addressable_shards", None)
            if shards:
                for s in shards:
                    k = self._device_key(s.device)
                    out[k] = out.get(k, 0) + int(s.data.nbytes)
            else:
                out[default] = (
                    out.get(default, 0) + int(getattr(x, "nbytes", 0))
                )
        return out

    def _param_group_specs(self) -> dict:
        """Per-sharding-spec param grouping for /v1/debug/mesh:
        spec-string -> {params, bytes, logical}. `logical` lists the
        model-declared logical axis names (models/*_logical_axes
        leaves, e.g. "(layers, None, heads)") that resolved into this
        placement through the rule table — the provenance half of the
        logical-axis system. Meshless engines group everything under
        "replicated"."""
        leaves = jax.tree.leaves(self.params)
        logical: list = [None] * len(leaves)
        if getattr(self.adapter, "logical_axes", None) is not None:
            try:
                from jax.sharding import PartitionSpec as P

                from dynamo_tpu.parallel.logical import AxisNames

                ax = jax.tree.leaves(
                    self.adapter.logical_axes(
                        quantized=bool(self.config.quantize)
                    ),
                    is_leaf=lambda x: isinstance(x, (AxisNames, P)),
                )
                if len(ax) == len(leaves):
                    logical = ax
            except Exception:  # noqa: BLE001 — provenance is advisory;
                # the byte accounting must never fail over it
                logger.exception("logical-axis provenance unavailable")
        groups: dict[str, dict] = {}
        for x, names in zip(leaves, logical):
            spec = getattr(getattr(x, "sharding", None), "spec", None)
            key = str(spec) if spec is not None else "replicated"
            g = groups.setdefault(
                key, {"params": 0, "bytes": 0, "logical": []}
            )
            g["params"] += 1
            g["bytes"] += int(getattr(x, "nbytes", 0))
            if names is not None:
                lbl = "(" + ", ".join(str(n) for n in names) + ")"
                if lbl not in g["logical"]:
                    g["logical"].append(lbl)
        return groups

    def memory_report(self) -> dict:
        """GET /v1/debug/memory: per-device HBM byte breakdown.

        Accounted components: `weights` (param-tree shard bytes, cached
        at construction — they never change), `kv_pool` (paged KV +
        draft KV incl. quantization scale planes) and `state_pool`.
        live/free/peak come from jax device `memory_stats()` where the
        backend provides them (TPU); the documented CPU fallback is
        pure accounting — live = weights + pools, free =
        platform.device_hbm_bytes() − live (the per-generation table),
        peak = live. `source` names which path produced the live
        numbers."""
        from dynamo_tpu.platform import device_hbm_bytes

        state_by_dev = self._per_device_bytes(self._state_pools(self.kv))
        kv_by_dev = {
            key: n - state_by_dev.get(key, 0)
            for key, n in self._per_device_bytes(
                (self.kv, self.draft_kv)
            ).items()
        }
        weights = self._weights_by_device
        limit_nominal = int(device_hbm_bytes())
        devices: dict[str, dict] = {}
        source = "accounted"
        for d in jax.local_devices():
            key = self._device_key(d)
            w = int(weights.get(key, 0))
            kvb = int(kv_by_dev.get(key, 0))
            row = {
                "kind": str(getattr(d, "device_kind", "cpu")),
                "weights_bytes": w,
                "kv_pool_bytes": kvb,
                "state_pool_bytes": int(state_by_dev.get(key, 0)),
            }
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats and stats.get("bytes_in_use") is not None:
                source = "memory_stats"
                live = int(stats.get("bytes_in_use") or 0)
                limit = int(stats.get("bytes_limit") or limit_nominal)
                row["live_bytes"] = live
                row["limit_bytes"] = limit
                row["free_bytes"] = max(0, limit - live)
                row["peak_bytes"] = int(
                    stats.get("peak_bytes_in_use") or live
                )
            else:
                live = w + kvb + row["state_pool_bytes"]
                row["live_bytes"] = live
                row["limit_bytes"] = limit_nominal
                row["free_bytes"] = max(0, limit_nominal - live)
                row["peak_bytes"] = live
            devices[key] = row
        totals = {
            f: sum(r[f] for r in devices.values())
            for f in (
                "weights_bytes", "kv_pool_bytes", "state_pool_bytes",
                "live_bytes", "free_bytes", "peak_bytes",
            )
        }
        return {"source": source, "devices": devices, "totals": totals}

    def refresh_memory_metrics(self) -> dict:
        """Fold memory_report totals into the EngineMetrics hbm_*
        gauges plus the host/dispatch straggler fields (the worker's
        publish loop calls this once per frame). Returns the full
        report so a caller wanting both doesn't pay twice."""
        rep = self.memory_report()
        t = rep["totals"]
        m = self.metrics
        m.hbm_weights_bytes = t["weights_bytes"]
        m.hbm_kv_pool_bytes = t["kv_pool_bytes"]
        m.hbm_free_bytes = t["free_bytes"]
        m.hbm_peak_bytes = t["peak_bytes"]
        try:
            m.host = int(jax.process_index())
        except Exception:
            m.host = 0
        m.dispatch_p95_ms = float(
            self.dispatch_stats().get("p95_ms") or 0.0
        )
        return rep

    #: flight-record kinds whose step wall time counts as a decode
    #: dispatch for the straggler gauge
    _DISPATCH_KINDS = ("decode", "decode_multi", "mixed")

    def dispatch_stats(self) -> dict:
        """Recent-window decode dispatch wall-time stats (the per-host
        half of the host-skew gauge, /v1/debug/mesh): p50/p95/mean over
        the flight ring's decode-ish records. With the recorder off,
        the lifetime mean from the cumulative counters stands in for
        every quantile — no window exists to rank."""
        if self.flight is not None:
            vals = sorted(
                float(r.get("step_ms") or 0.0)
                for r in self.flight.snapshot(None)
                if r.get("kind") in self._DISPATCH_KINDS
            )
            if vals:
                def q(p: float) -> float:
                    return round(
                        vals[min(len(vals) - 1, int(p * len(vals)))], 3
                    )

                return {
                    "n": len(vals),
                    "p50_ms": q(0.50),
                    "p95_ms": q(0.95),
                    "mean_ms": round(sum(vals) / len(vals), 3),
                }
        m = self.metrics
        disp = m.decode_dispatches + m.mixed_dispatches
        total = m.time_decode_ms + m.time_mixed_ms
        mean = round(total / disp, 3) if disp else None
        return {"n": disp, "p50_ms": mean, "p95_ms": mean, "mean_ms": mean}

    def mesh_report(self) -> dict:
        """GET /v1/debug/mesh: what the SPMD layer actually built —
        mesh shape + axis names, the per-sharding-spec param grouping
        (with each group's logical-axis names), the rule table that
        resolved those names to mesh axes, the KV pool's sharding, this
        replica's process seat, and the recent decode dispatch window
        (the metrics service compares the latter ACROSS hosts into the
        fleet's host-skew view)."""
        mesh_doc = None
        if self.mesh is not None:
            mesh_doc = {
                "axis_names": [str(a) for a in self.mesh.axis_names],
                "shape": {
                    str(k): int(v) for k, v in self.mesh.shape.items()
                },
                "devices": int(self.mesh.devices.size),
            }
        try:
            pi, pc = int(jax.process_index()), int(jax.process_count())
        except Exception:
            pi, pc = 0, 1
        kv_spec = getattr(
            getattr(getattr(self.kv, "k", None), "sharding", None),
            "spec", None,
        )
        return {
            "mesh": mesh_doc,
            "multiprocess": bool(self._multiproc),
            "process_index": pi,
            "process_count": pc,
            "param_groups": self._param_groups,
            "logical_axis_rules": [
                list(r) for r in default_rules().doc()
            ],
            "kv_sharding": (
                str(kv_spec) if kv_spec is not None else "replicated"
            ),
            "dispatch": self.dispatch_stats(),
        }

    def request_profile(self, steps: int, outdir: Optional[str] = None) -> dict:
        """Arm a jax.profiler capture for `steps` engine steps (POST
        /v1/debug/profile). The engine thread starts the trace at the
        end of its next step() and stops it after `steps` dispatched
        steps, so the capture brackets whole dispatches. Thread-safe;
        refuses while a capture is already armed. An idle engine starts
        capturing at its next piece of traffic."""
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if outdir is None:
            outdir = os.path.join(
                "artifacts", "profile",
                f"{self.config.model.replace('/', '_')}-{int(time.time())}",
            )
        with self._profile_lock:
            if self._profile is not None:
                raise RuntimeError(
                    "a profile capture is already armed/running"
                )
            self._profile = {
                "steps_left": int(steps), "dir": outdir, "started": False,
            }
        return {"dir": outdir, "steps": int(steps)}

    def _profile_start(self) -> None:
        """Engine-thread half of request_profile (1/2): open the trace
        before the first step after arming. Behind a plain None check in
        step() — zero cost unarmed."""
        with self._profile_lock:
            p = self._profile
            if p is None or p["started"]:
                return
            try:
                os.makedirs(p["dir"], exist_ok=True)
                jax.profiler.start_trace(p["dir"])
            except Exception:
                logger.exception("jax.profiler capture failed to start")
                self._profile = None
                return
            p["started"] = True
            logger.info(
                "profiling %d steps into %s", p["steps_left"], p["dir"]
            )

    def _profile_count(self) -> None:
        """Engine-thread half of request_profile (2/2): one dispatched
        step captured; stop after the armed count."""
        with self._profile_lock:
            p = self._profile
            if p is None or not p["started"]:
                return
            p["steps_left"] -= 1
            if p["steps_left"] <= 0:
                try:
                    jax.profiler.stop_trace()
                    logger.info("profile capture done: %s", p["dir"])
                except Exception:
                    logger.exception("jax.profiler stop failed")
                self._profile = None
