"""Standalone metrics service: fleet observability -> Prometheus.

Capability parity with the reference's metrics component
(/root/reference components/metrics/src/main.rs: scrape endpoint stats,
aggregate LLMWorkerLoadCapacityConfig, serve Prometheus, subscribe
KVHitRateEvent on `kv-hit-rate`). Here the worker metrics plane is
push-based (worker.py _publish_loop), so the service subscribes instead of
scraping, converts the latest per-worker snapshots plus cumulative
router hit-rate counters into Prometheus text format, and serves
/metrics + /health over HTTP.

Run: `dynamo-tpu metrics --fabric host:port --port 9091`.
"""

from __future__ import annotations

import asyncio
import logging
from typing import Optional

from aiohttp import web

from dynamo_tpu.kv_router.metrics_aggregator import MetricsAggregator
from dynamo_tpu.subjects import (
    FLEET_EVENTS_SUBJECT,
    KV_HIT_RATE_SUBJECT,
    KV_INDEX_SUBJECT,
    PLANNER_SUBJECT,
    TRACE_SPANS_SUBJECT,
)
from dynamo_tpu.telemetry.events import EventRing
from dynamo_tpu.telemetry.traceplane import TailSampler, TraceAssembler

logger = logging.getLogger(__name__)

PREFIX = "dynamo_tpu"

#: worker snapshot fields -> (prometheus suffix, type). Counters whose
#: field name lacks the `_total` suffix gain it in the EXPOSED name
#: (Prometheus naming convention, enforced by telemetry/promlint.py in
#: tests) — e.g. snapshot field `steps` serves as
#: dynamo_tpu_worker_steps_total. See docs/migrating.md.
_WORKER_FIELDS = (
    ("kv_usage", "gauge"),
    ("kv_active_pages", "gauge"),
    ("kv_free_pages", "gauge"),
    ("kv_total_pages", "gauge"),
    # KV-pool byte gauges (EngineConfig.kv_quantize): actual device bytes
    # (quantized pages + scale planes) vs the model-dtype equivalent —
    # their ratio is the effective cache-capacity multiplier
    ("kv_pool_bytes", "gauge"),
    ("kv_pool_bytes_dense_equiv", "gauge"),
    ("num_waiting", "gauge"),
    ("num_running", "gauge"),
    ("prefix_hit_rate", "gauge"),
    ("steps", "counter"),
    ("generated_tokens", "counter"),
    ("requests_received", "counter"),
    # disagg KV transfer planes (absent on non-disagg workers)
    ("kv_transfer_device_total", "counter"),
    ("kv_transfer_shm_total", "counter"),
    ("kv_transfer_bulk_total", "counter"),
    ("kv_transfer_host_total", "counter"),
    ("remote_prefills_total", "counter"),
    # step-phase wall time (EngineMetrics.time_*_ms — host-loop
    # observability; ratios against dispatch counters give ms/dispatch)
    ("time_schedule_ms", "counter"),
    ("time_prefill_ms", "counter"),
    ("time_decode_ms", "counter"),
    # mixed prefill+decode steps (EngineConfig.mixed_steps): one fused
    # dispatch carrying a prefill chunk AND the decode batch — the
    # stall-free path (docs/engine.md "Mixed steps")
    ("time_mixed_ms", "counter"),
    # decode's phase split (dispatch/sync/postprocess) + the overlapped-
    # decode pipeline counters — sync collapsing toward zero is the
    # overlap working (docs/engine.md "The decode loop")
    ("time_decode_dispatch_ms", "counter"),
    ("time_decode_sync_ms", "counter"),
    ("time_decode_host_ms", "counter"),
    # the rest of the loop (the `engine.stage` / `engine.intake` /
    # `engine.emit` spans' counters) and every admission's queue wait:
    # queue_wait_ms_total / admissions = mean wait for a slot
    ("time_stage_ms", "counter"),
    ("time_intake_ms", "counter"),
    ("time_emit_ms", "counter"),
    ("queue_wait_ms_total", "counter"),
    ("admissions", "counter"),
    ("prefill_dispatches", "counter"),
    ("decode_dispatches", "counter"),
    ("mixed_dispatches", "counter"),
    ("mixed_shared_rows", "counter"),
    ("overlap_dispatches", "counter"),
    ("overlap_hits", "counter"),
    ("overlap_rollbacks", "counter"),
    # the dry clock (telemetry/flight.py DryClock; 0 with the flight
    # recorder off): host ms the device had nothing queued while the
    # engine had work, every program call of a step kind, and those made
    # with the device empty. dry_launches high beside overlap_hits high =
    # launches ahead of the batch but behind the device
    ("dry_ms", "counter"),
    ("dry_launches", "counter"),
    ("launches", "counter"),
    # recurrent-state plane (a model with state-space layers, 0 for every
    # other; docs/observability.md): slots of the state pool, the high
    # watermark of slots held, the pool's bytes, admissions that took a
    # slot, rollbacks that had surviving rows' state to leave untouched,
    # prefix hits refused for want of the state at the pages' boundary
    ("state_slots", "gauge"),
    ("state_slots_live", "gauge"),
    ("state_pool_bytes", "gauge"),
    ("state_resets", "counter"),
    ("state_restores", "counter"),
    ("prefix_hits_refused_state", "counter"),
    # a decode walk that reads a chosen part of a row's pages: pages named
    # by the selected lists and pages the rows hold (0 for other models)
    ("walk_pages_named", "counter"),
    ("walk_pages_live", "counter"),
    # its sparse prompt chunks: pages the query tiles read and pages their
    # queries' selections named (named / read: reads saved by the tile)
    ("chunk_pages_read", "counter"),
    ("chunk_pages_named", "counter"),
    # a chip that holds a share of the experts: the held experts a step's
    # rows chose, an expert layer each (models/dots3.py; 0 for the others)
    ("moe_experts_touched", "counter"),
    # and the passes over the share's assignments beyond a layer's first
    # (models/mla.py `share_rows`: each reads the held matrices again)
    ("moe_extra_passes", "counter"),
    # speculative decoding (spec_ngram / spec_draft_model): drafts
    # proposed vs accepted — their ratio times S is the extra tokens per
    # verify dispatch; the skip counters say WHY speculation sat out
    # (ineligible batch vs acceptance cooldown). spec_accept_rate is the
    # engine's live ~60 s window, not the lifetime ratio.
    ("spec_drafted", "counter"),
    ("spec_accepted", "counter"),
    ("spec_skipped_ineligible", "counter"),
    ("spec_skipped_cooldown", "counter"),
    ("spec_accept_rate", "gauge"),
    ("spec_window_drafted", "gauge"),
    # subprocess external-engine harness (absent on native workers):
    # supervisor lifecycle for foreign engines (docs/external_engines.md
    # "Level 2") — restarts climbing or ready=0 is a crash-looping child
    ("ext_ready", "gauge"),
    ("ext_broken", "gauge"),
    ("ext_restarts_total", "counter"),
    ("ext_consecutive_failures", "gauge"),
    # engine-internals plane (fleet telemetry): jit-cache misses + their
    # cumulative wall cost, page-pool pressure (high-watermark +
    # preemption-by-recompute), and the live throughput gauge
    ("compiles", "counter"),
    ("compile_ms", "counter"),
    ("kv_pages_watermark", "gauge"),
    ("preemptions", "counter"),
    ("tokens_per_s", "gauge"),
    # stall watchdog (telemetry/watchdog.py): stalls diagnosed on this
    # worker — climbing means streams are wedging (the per-cause split
    # is in the worker's own dynamo_tpu_stalls_total{cause} and in the
    # /v1/fleet snapshot's stalls_by_cause)
    ("stalls_total", "counter"),
    # overload plane (docs/operations.md "Overload & draining"): bounded-
    # admission rejects (EngineConfig.max_waiting) and deadline-expired
    # error finishes — climbing rejects = shedding (raise capacity);
    # deep num_waiting with zero rejects = queue unbounded (enable caps)
    ("overload_rejects", "counter"),
    ("deadline_expired", "counter"),
    # role flips this worker performed (closed-loop planner actuation —
    # docs/operations.md "Closed-loop autoscaling & role flips")
    ("flips_total", "counter"),
    # worker handover (docs/operations.md "Rolling upgrades & worker
    # handover"): completed handovers vs drain fallbacks on the retiring
    # side, KV bytes/blocks migrated out, blocks adopted as a successor,
    # and transfer frames the codec checksum rejected (wire corruption
    # never lands)
    ("handovers_total", "counter"),
    ("handover_fallbacks_total", "counter"),
    ("handover_bytes_total", "counter"),
    ("handover_blocks_total", "counter"),
    ("handovers_adopted_total", "counter"),
    ("kv_transfer_corrupt_total", "counter"),
    # control-plane HA (docs/operations.md "Control-plane HA"): the
    # worker's broker-connection view — degraded is live only while the
    # worker can still publish (partial partitions); the counters carry
    # the post-recovery accounting of full outages
    ("degraded", "gauge"),
    ("degraded_entries_total", "counter"),
    ("kv_events_dropped_total", "counter"),
    ("kv_events_pending", "gauge"),
    # KV economy (docs/operations.md "The KV economy"): source-side
    # per-prefix migration counters + KVBM tier residency/traffic — the
    # Grafana "KV economy" row and the doctor's migration-storm /
    # tier-pressure rules read these
    ("kv_migrations_total", "counter"),
    ("kv_migration_fallbacks_total", "counter"),
    ("kv_migration_bytes_total", "counter"),
    ("kv_migration_blocks_total", "counter"),
    ("kvbm_host_blocks", "gauge"),
    ("kvbm_disk_blocks", "gauge"),
    ("kvbm_demotions_total", "counter"),
    ("kvbm_promotions_total", "counter"),
    ("kvbm_host_hits_total", "counter"),
    ("kvbm_disk_hits_total", "counter"),
    # HBM accounting plane (docs/observability.md "Reading the perf
    # plane"): per-worker byte totals summed over the worker's local
    # devices — weights (param-tree shards), KV pool, free and peak.
    # On CPU the engine falls back to accounted sums
    # (source="accounted" in the /v1/debug/memory doc); the per-device
    # split rides the frames' "memory" report
    ("hbm_weights_bytes", "gauge"),
    ("hbm_kv_pool_bytes", "gauge"),
    ("hbm_free_bytes", "gauge"),
    ("hbm_peak_bytes", "gauge"),
    # multi-host SPMD introspection: jax.process_index() of the worker
    # plus its flight-window dispatch p95 — the fleet host-skew family
    # (dynamo_tpu_fleet_host_dispatch_p95_ms{host}) and the doctor's
    # host-skew rule are derived from these two
    ("host", "gauge"),
    ("dispatch_p95_ms", "gauge"),
)

#: numeric per-worker fields copied verbatim into the /v1/fleet snapshot
_FLEET_WORKER_FIELDS = (
    "kv_usage", "kv_free_pages", "kv_active_pages", "kv_total_pages",
    "kv_pages_watermark", "preemptions", "num_running", "num_waiting",
    "steps", "generated_tokens", "requests_received", "compiles",
    "compile_ms", "tokens_per_s", "prefix_hit_rate",
    "stalls_total", "overload_rejects", "deadline_expired", "flips_total",
    "spec_drafted", "spec_accepted", "spec_skipped_ineligible",
    "spec_skipped_cooldown", "spec_accept_rate", "spec_window_drafted",
    "handovers_total", "handover_fallbacks_total", "handover_bytes_total",
    "handover_blocks_total", "handovers_adopted_total",
    "kv_transfer_corrupt_total",
    "degraded", "degraded_entries_total", "kv_events_dropped_total",
    "kv_events_pending",
    "kv_migrations_total", "kv_migration_fallbacks_total",
    "kv_migration_bytes_total", "kv_migration_blocks_total",
    "kvbm_host_blocks", "kvbm_disk_blocks", "kvbm_demotions_total",
    "kvbm_promotions_total", "kvbm_host_hits_total",
    "kvbm_disk_hits_total",
    "hbm_weights_bytes", "hbm_kv_pool_bytes", "hbm_free_bytes",
    "hbm_peak_bytes", "host", "dispatch_p95_ms",
)


class MetricsService:
    def __init__(
        self,
        fabric,
        component: str = "backend",
        host: str = "127.0.0.1",
        port: int = 9091,
        fabric_stats_interval: float = 2.0,
        extra_components: tuple = ("prefill",),
        trace_sample_rate: Optional[int] = None,
        trace_window_s: float = 2.0,
        trace_keep: int = 512,
        trace_sample_seed: int = 0,
    ):
        self.fabric = fabric
        self.component = component
        self.host = host
        self.port = port
        self.aggregator = MetricsAggregator(fabric, component)
        #: fleet trace plane (docs/observability.md "Fleet traces &
        #: event timeline"): assemble every process's shipped spans into
        #: cross-process traces behind the tail sampler. "Slow" tracks
        #: the LIVE fleet SLO p95s via _slo_p95s (cached ~5 s).
        import os as _os

        rate = (
            trace_sample_rate
            if trace_sample_rate is not None
            else int(_os.environ.get("DYNTPU_TRACE_SAMPLE_RATE", "10") or 10)
        )
        self.trace_sampler = TailSampler(
            healthy_rate=rate,
            seed=trace_sample_seed,
            slo_p95s=self._slo_p95s,
        )
        self.traces = TraceAssembler(
            sampler=self.trace_sampler,
            window_s=trace_window_s,
            keep=trace_keep,
        )
        self._slo_p95_cache: tuple[float, dict] = (0.0, {})
        #: fleet event timeline: bounded ring of control-plane events
        #: (flips, handovers, shed episodes, planner decisions, replays,
        #: resyncs, worker losses) served at GET /v1/fleet/events and
        #: exposed for the Grafana annotation layer
        self.events = EventRing()
        #: fleet view spans every serving role: one aggregator per
        #: component's subject space (decode pool + disagg prefill pool
        #: by default). The primary keeps its name for back-compat.
        self.aggregators = [self.aggregator] + [
            MetricsAggregator(fabric, c)
            for c in extra_components
            if c and c != component
        ]
        #: per-instance (requests_received, generated_tokens, monotonic)
        #: baselines for the fleet snapshot's req/s + tok/s rates
        self._rate_state: dict[str, tuple[int, int, float]] = {}
        #: counter-churn bookkeeping for the `dynamo_tpu_fleet_*_total`
        #: families: last-seen counter contributions per live worker, and
        #: per-role monotonic bases holding the contributions of departed
        #: or restarted workers (see _fold_departed)
        self._live_contrib: dict[str, tuple[str, dict]] = {}
        self._retired_counters: dict[str, dict] = {}
        #: contributions folded for AGED-OUT workers, kept so a worker
        #: that returns with its counters intact (a transient publish
        #: gap — partition, fabric outage — not a restart) can be
        #: UN-folded instead of double-counted (see _fold_departed)
        self._ghost_contrib: dict[str, tuple[str, dict]] = {}
        #: last advertised state per worker (serving/draining/handover)
        #: — a departure that ANNOUNCED itself (drain, handover: it
        #: already put its own event on the timeline) must not also
        #: fire a worker_lost warning when its frames age out
        self._last_state: dict[str, str] = {}
        # cumulative router-decision counters (KVHitRateEvent stream)
        self.hit_events = 0
        self.isl_tokens_total = 0
        self.overlap_tokens_total = 0
        #: latest broker self-metrics snapshot (fabric `stats` op) —
        #: empty when the fabric backend doesn't expose stats
        self.fabric_stats: dict = {}
        self.fabric_stats_interval = fabric_stats_interval
        #: latest closed-loop planner status frame (ControlRunner.status
        #: over PLANNER_SUBJECT) + when it arrived — serves the
        #: dynamo_tpu_planner_* families and the /v1/fleet `planner`
        #: section doctor's planner rules read
        self.planner_status: Optional[dict] = None
        self.planner_status_age: float = 0.0
        #: latest KV index-health frame per (component, router id)
        #: (KvRouter publishes its indexer's stats over
        #: KV_INDEX_SUBJECT) — serves the
        #: dynamo_tpu_router_kv_index_*{component,router} families and
        #: the /v1/fleet `kv_index` section doctor's kv-index-drift
        #: rule reads
        self.kv_index_status: dict[str, dict] = {}
        self.kv_index_status_age: dict[str, float] = {}
        self._sub = None
        self._planner_sub = None
        self._kv_index_sub = None
        self._trace_sub = None
        self._events_sub = None
        self._task: Optional[asyncio.Task] = None
        self._kv_index_task: Optional[asyncio.Task] = None
        self._planner_task: Optional[asyncio.Task] = None
        self._stats_task: Optional[asyncio.Task] = None
        self._trace_task: Optional[asyncio.Task] = None
        self._events_task: Optional[asyncio.Task] = None
        self._sweep_task: Optional[asyncio.Task] = None
        self._runner: Optional[web.AppRunner] = None

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        for agg in self.aggregators:
            await agg.start()
        self._sub = await self.fabric.subscribe(KV_HIT_RATE_SUBJECT)
        self._task = asyncio.get_running_loop().create_task(self._pump())
        self._planner_sub = await self.fabric.subscribe(PLANNER_SUBJECT)
        self._planner_task = asyncio.get_running_loop().create_task(
            self._planner_pump()
        )
        self._kv_index_sub = await self.fabric.subscribe(KV_INDEX_SUBJECT)
        self._kv_index_task = asyncio.get_running_loop().create_task(
            self._kv_index_pump()
        )
        self._trace_sub = await self.fabric.subscribe(TRACE_SPANS_SUBJECT)
        self._trace_task = asyncio.get_running_loop().create_task(
            self._trace_pump()
        )
        self._events_sub = await self.fabric.subscribe(FLEET_EVENTS_SUBJECT)
        self._events_task = asyncio.get_running_loop().create_task(
            self._events_pump()
        )
        self._sweep_task = asyncio.get_running_loop().create_task(
            self._trace_sweep_loop()
        )
        if hasattr(self.fabric, "stats"):
            self._stats_task = asyncio.get_running_loop().create_task(
                self._poll_fabric_stats()
            )
        app = web.Application()
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/health", self._health)
        app.router.add_get("/v1/fleet", self._fleet)
        app.router.add_get("/v1/fleet/events", self._fleet_events)
        app.router.add_get("/v1/traces", self._traces)
        app.router.add_get("/v1/traces/{trace_id}", self._trace)
        app.router.add_get("/v1/debug/flight", self._debug_flight)
        app.router.add_get("/v1/debug/programs", self._debug_programs)
        app.router.add_get("/v1/debug/memory", self._debug_memory)
        app.router.add_get("/v1/debug/mesh", self._debug_mesh)
        app.router.add_post("/v1/debug/profile", self._debug_profile)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = self._runner.addresses[0][1]
        logger.info("metrics service on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._sub is not None:
            self._sub.close()
        if self._task is not None:
            self._task.cancel()
        if self._planner_sub is not None:
            self._planner_sub.close()
        if self._planner_task is not None:
            self._planner_task.cancel()
        if self._kv_index_sub is not None:
            self._kv_index_sub.close()
        if self._kv_index_task is not None:
            self._kv_index_task.cancel()
        if self._trace_sub is not None:
            self._trace_sub.close()
        if self._trace_task is not None:
            self._trace_task.cancel()
        if self._events_sub is not None:
            self._events_sub.close()
        if self._events_task is not None:
            self._events_task.cancel()
        if self._sweep_task is not None:
            self._sweep_task.cancel()
        if self._stats_task is not None:
            self._stats_task.cancel()
        for agg in self.aggregators:
            await agg.stop()
        if self._runner is not None:
            await self._runner.cleanup()

    async def _pump(self) -> None:
        while True:
            msg = await self._sub.next()
            if msg is None:
                return
            try:
                h = msg.header or {}
                isl = int(h.get("isl_tokens", 0))
                overlap = int(h.get("overlap_tokens", 0))
            except (TypeError, ValueError, AttributeError):
                # One malformed publish must not kill the consumer task and
                # freeze the counters for every later legitimate event.
                logger.warning("malformed kv-hit-rate event: %r", msg.header)
                continue
            self.hit_events += 1
            self.isl_tokens_total += isl
            self.overlap_tokens_total += overlap

    async def _trace_pump(self) -> None:
        """Consume shipped span batches into the assembler. A malformed
        batch is logged and skipped — one garbage publisher must not
        sever the whole trace plane."""
        import msgpack

        while True:
            msg = await self._trace_sub.next()
            if msg is None:
                return
            try:
                spans = msgpack.unpackb(msg.payload, raw=False)
                if not isinstance(spans, list):
                    raise TypeError(f"span batch is {type(spans).__name__}")
            except Exception:
                logger.warning("malformed trace.spans batch", exc_info=True)
                continue
            try:
                self.traces.add_spans(spans)
            except Exception:
                logger.warning("trace assembly failed", exc_info=True)

    async def _events_pump(self) -> None:
        """Consume fleet-event batch frames into the bounded ring
        (garbage batches/frames are dropped — by the unpack guard and
        EventRing.add respectively — and never kill the pump)."""
        import msgpack

        while True:
            msg = await self._events_sub.next()
            if msg is None:
                return
            try:
                batch = msgpack.unpackb(msg.payload, raw=False)
                if not isinstance(batch, list):
                    raise TypeError(
                        f"event batch is {type(batch).__name__}"
                    )
            except Exception:
                logger.warning(
                    "malformed fleet.events batch", exc_info=True
                )
                continue
            for ev in batch:
                self.events.add(ev)

    async def _trace_sweep_loop(self) -> None:
        """Finalize trace assemblies that went quiet past the window
        (the tail-sampling decision point)."""
        interval = max(0.25, self.traces.window_s / 4.0)
        while True:
            await asyncio.sleep(interval)
            try:
                self.traces.sweep()
            except Exception:
                logger.warning("trace sweep failed", exc_info=True)

    def _slo_p95s(self) -> dict:
        """Live fleet TTFT/e2e p95s for the tail sampler's "slow"
        thresholds, merged from the workers' SLO wires and cached ~5 s
        (the sampler calls this per finalized trace). Sketches with too
        few observations return nothing — a cold fleet must not flag
        every trace slow off three data points."""
        import time as _time

        from dynamo_tpu.telemetry import slo as slo_mod

        now = _time.monotonic()
        cached_at, cached = self._slo_p95_cache
        if now - cached_at < 5.0:
            return cached
        wires = []
        for iid, (m, age, comp) in self._snapshot_all().items():
            wire = m.get("slo")
            if isinstance(wire, dict):
                wires.append(wire)
        out: dict = {}
        try:
            merged = slo_mod.merge_trackers(wires)
            for metric in ("ttft_ms", "e2e_ms"):
                sk = merged.sketches.get(metric)
                if sk is not None and sk.count >= 50:
                    q = sk.quantile(0.95)
                    if q is not None:
                        out[metric] = float(q)
        except Exception:
            logger.warning("slo p95 merge failed", exc_info=True)
            out = {}
        self._slo_p95_cache = (now, out)
        return out

    async def _planner_pump(self) -> None:
        """Latest-wins consumer of the planner's status frames. A
        malformed frame is logged and skipped — the planner section
        degrades to its previous value, never kills the pump."""
        import time as _time

        while True:
            msg = await self._planner_sub.next()
            if msg is None:
                return
            frame = getattr(msg, "header", None)
            if not isinstance(frame, dict):
                logger.warning("malformed planner frame: %r", frame)
                continue
            self.planner_status = frame
            self.planner_status_age = _time.monotonic()

    async def _kv_index_pump(self) -> None:
        """Latest-wins consumer of router index-health frames, keyed by
        (component, router id) — two routers serving the SAME component
        (e.g. two frontends) must not overwrite each other into a
        counter sawtooth; the exposition emits per-key samples and the
        fleet doc sums them. A malformed frame is logged and skipped,
        never kills the pump. Frames from dead routers age out."""
        import time as _time

        while True:
            msg = await self._kv_index_sub.next()
            if msg is None:
                return
            frame = getattr(msg, "header", None)
            if not isinstance(frame, dict):
                logger.warning("malformed kv_index frame: %r", frame)
                continue
            comp = str(frame.get("component") or "backend")
            key = f"{comp}|{frame.get('router') or ''}"
            now = _time.monotonic()
            self.kv_index_status[key] = frame
            self.kv_index_status_age[key] = now
            # a restarted router gets a fresh router id: prune entries
            # that stopped refreshing so its old counters don't linger
            for k in list(self.kv_index_status):
                if now - self.kv_index_status_age.get(k, now) > 15.0:
                    del self.kv_index_status[k]
                    self.kv_index_status_age.pop(k, None)

    def _kv_index_doc(self) -> Optional[dict]:
        """The /v1/fleet `kv_index` section: SUMMED counters across
        every live router frame at the top level (one stale subtree
        anywhere must surface there) plus the per-(component, router)
        frames underneath."""
        import time as _time

        if not self.kv_index_status:
            return None
        now = _time.monotonic()
        doc: dict = {"components": {}}
        totals = {
            k: 0
            for k in (
                "gaps_total", "resyncs_total", "resync_failures_total",
                "drift_blocks_total", "digest_mismatches_total",
                "stale_workers",
            )
        }
        for key, frame in sorted(self.kv_index_status.items()):
            doc["components"][key] = {
                **frame,
                "last_seen_s": round(
                    now - self.kv_index_status_age.get(key, now), 3
                ),
            }
            for k in totals:
                try:
                    totals[k] += int(frame.get(k) or 0)
                except (TypeError, ValueError):
                    pass
        doc.update(totals)
        return doc

    def _kv_index_lines(self) -> list[str]:
        """`dynamo_tpu_router_kv_index_*{component,router}` — the
        fleet-side view of router-published index health, one sample
        per live router frame (dashboards sum over them; the routers'
        own processes expose the unlabeled dynamo_tpu_kv_index_*
        families via debug.kv_index_lines)."""
        if not self.kv_index_status:
            return []
        lines: list[str] = []
        fields = (
            ("gaps_total", "counter"),
            ("resyncs_total", "counter"),
            ("resync_failures_total", "counter"),
            ("drift_blocks_total", "counter"),
            ("digest_mismatches_total", "counter"),
            ("stale_workers", "gauge"),
        )
        for fieldname, ptype in fields:
            samples = []
            for key, frame in sorted(self.kv_index_status.items()):
                v = frame.get(fieldname)
                if isinstance(v, (int, float)):
                    comp = str(frame.get("component") or "backend")
                    router = str(frame.get("router") or "")
                    samples.append((comp, router, v))
            if not samples:
                continue
            name = f"{PREFIX}_router_kv_index_{fieldname}"
            lines.append(f"# TYPE {name} {ptype}")
            for comp, router, v in samples:
                lines.append(
                    f'{name}{{component="{comp}",router="{router}"}} {v}'
                )
        return lines

    def _planner_doc(self) -> Optional[dict]:
        import time as _time

        if self.planner_status is None:
            return None
        return {
            **self.planner_status,
            "last_seen_s": round(
                _time.monotonic() - self.planner_status_age, 3
            ),
        }

    def _planner_lines(self) -> list[str]:
        """`dynamo_tpu_planner_*`: the closed-loop autoscaler's own
        exposition — pool targets vs observed, decision counters, flip
        count, SLO signals vs setpoint (the Grafana "Planner" row)."""
        p = self.planner_status
        if not isinstance(p, dict):
            return []
        lines: list[str] = []

        def fam(name: str, ptype: str, samples: list) -> None:
            samples = [(lab, v) for lab, v in samples if v is not None]
            if not samples:
                return
            lines.append(f"# TYPE {PREFIX}_planner_{name} {ptype}")
            for lab, v in samples:
                label = f"{{{lab}}}" if lab else ""
                lines.append(f"{PREFIX}_planner_{name}{label} {v}")

        targets = p.get("targets") or {}
        observed = p.get("observed") or {}
        fam("pool_target", "gauge", [
            (f'role="{r}"', targets.get(r)) for r in sorted(targets)
        ])
        fam("pool_observed", "gauge", [
            (f'role="{r}"', observed.get(r)) for r in sorted(observed)
        ])
        decisions = p.get("decisions_total") or {}
        fam("decisions_total", "counter", [
            (f'action="{a}"', decisions.get(a)) for a in sorted(decisions)
        ])
        fam("flips_total", "counter", [("", p.get("flips_total", 0))])
        fam("actions_clamped_total", "counter",
            [("", p.get("actions_clamped_total", 0))])
        fam("cooldown_holds_total", "counter",
            [("", p.get("cooldown_holds_total", 0))])
        signals = p.get("signals") or {}
        setpoint = p.get("setpoint") or {}
        fam("sla_attainment", "gauge",
            [("", signals.get("sla_attainment"))])
        fam("burn_rate", "gauge", [("", signals.get("burn_rate"))])
        fam("attainment_setpoint", "gauge",
            [("", setpoint.get("attainment"))])
        fam("burn_high_ticks", "gauge", [("", p.get("burn_high_ticks"))])
        fam("at_max", "gauge", [("", int(bool(p.get("at_max"))))])
        return lines

    def _control_plane_doc(self) -> dict:
        """The /v1/fleet `control_plane` section doctor's
        control-plane-degraded and replication-lag rules read: this
        process's own broker-connection state plus the latest broker
        self-metrics (replication lag, fence, orphaned leases)."""
        fab = self.fabric
        doc = {
            "degraded": bool(getattr(fab, "degraded", False)),
            "disconnected_s": round(
                float(getattr(fab, "disconnected_s", 0.0) or 0.0), 2
            ),
            "degraded_total": int(getattr(fab, "degraded_total", 0) or 0),
            "failovers_total": int(
                getattr(fab, "failovers_total", 0) or 0
            ),
            "addresses": list(getattr(fab, "addresses", []) or []),
        }
        st = self.fabric_stats
        if st:
            doc["broker"] = {
                k: st[k]
                for k in (
                    "is_primary", "fence", "repl_subscribers",
                    "repl_lag_records", "promotions_total",
                    "demotions_total", "orphaned_leases", "active_leases",
                )
                if k in st
            }
        return doc

    async def _poll_fabric_stats(self) -> None:
        """Broker self-metrics: poll the fabric's `stats` op (RemoteFabric
        issues the wire request; LocalFabric answers in-process). A
        broker outage blanks the snapshot instead of serving stale
        numbers."""
        while True:
            try:
                res = self.fabric.stats()
                if asyncio.iscoroutine(res):
                    res = await res
                self.fabric_stats = res or {}
            except asyncio.CancelledError:
                raise
            except Exception:
                self.fabric_stats = {}
            await asyncio.sleep(self.fabric_stats_interval)

    # -- exposition --------------------------------------------------------

    def _fabric_lines(self) -> list[str]:
        lines = []
        for key, val in sorted(self.fabric_stats.items()):
            if key == "queues":
                name = f"{PREFIX}_fabric_queue_depth"
                lines.append(f"# TYPE {name} gauge")
                for qname, depth in sorted(val.items()):
                    lines.append(f'{name}{{queue="{qname}"}} {depth}')
                continue
            if not isinstance(val, (int, float)):
                continue
            ptype = "counter" if key.endswith("_total") else "gauge"
            name = f"{PREFIX}_fabric_{key}"
            lines.append(f"# TYPE {name} {ptype}")
            lines.append(f"{name} {val}")
        return lines

    def _snapshot_all(self) -> dict[str, tuple[dict, float, str]]:
        """instance_id → (frame, age_s, component) across every
        aggregated component (decode + prefill pools)."""
        out: dict[str, tuple[dict, float, str]] = {}
        for agg in self.aggregators:
            for iid, (m, age) in agg.snapshot_with_age().items():
                comp = m.get("component") or agg.component
                out[iid] = (m, age, str(comp))
        return out

    # -- fleet view (docs/observability.md "Fleet view & SLO accounting") --

    def _assemble_fleet(self, snap=None):
        """One pass over the live frames -> (snapshot doc, per-role
        MergedSlo). A worker publishing garbage is logged and skipped —
        the fleet view degrades by one worker, never dies (and never
        kills the serving pump; see tests/test_fleet_telemetry.py)."""
        import time as _time

        from dynamo_tpu.telemetry import slo as slo_mod

        if snap is None:
            snap = self._snapshot_all()
        now = _time.monotonic()
        workers: dict[str, dict] = {}
        wires_by_role: dict[str, list[dict]] = {}
        role_stats: dict[str, dict] = {}
        contribs: dict[str, tuple[str, dict]] = {}
        for iid, (m, age, comp) in sorted(snap.items()):
            try:
                role = str(
                    m.get("role")
                    or ("prefill" if "prefill" in comp else "decode")
                )
                w: dict = {
                    "role": role,
                    "component": comp,
                    "model": m.get("model"),
                    "last_seen_s": round(age, 3),
                }
                state = m.get("state")
                if isinstance(state, str):
                    # serving | draining | handover — doctor's draining-
                    # worker / handover-stuck rules and fleet_top key
                    # off this
                    w["state"] = state
                    self._last_state[iid] = state
                phase = m.get("handover_phase")
                if isinstance(phase, str):
                    w["handover_phase"] = phase
                for f in _FLEET_WORKER_FIELDS:
                    v = m.get(f)
                    if isinstance(v, (int, float)):
                        w[f] = v
                # req/s + tok/s from per-instance counter deltas (>=1 s
                # between baselines so rapid /v1/fleet polls don't alias)
                rr = int(m.get("requests_received", 0) or 0)
                gt = int(m.get("generated_tokens", 0) or 0)
                prev = self._rate_state.get(iid)
                if prev is not None and now - prev[2] >= 1.0:
                    dt = now - prev[2]
                    prev = (
                        rr, gt, now,
                        round(max(0, rr - prev[0]) / dt, 3),
                        round(max(0, gt - prev[1]) / dt, 2),
                    )
                    self._rate_state[iid] = prev
                elif prev is None:
                    prev = (rr, gt, now, 0.0, 0.0)
                    self._rate_state[iid] = prev
                w["req_s"], w["tok_s"] = prev[3], prev[4]
                cbk = m.get("compiles_by_kind")
                if isinstance(cbk, dict):
                    w["compiles_by_kind"] = {
                        str(k): int(v)
                        for k, v in cbk.items()
                        if isinstance(v, int)
                    }
                sbc = m.get("stalls_by_cause")
                if isinstance(sbc, dict):
                    w["stalls_by_cause"] = {
                        str(k): int(v)
                        for k, v in sbc.items()
                        if isinstance(v, int)
                    }
                st = role_stats.setdefault(
                    role,
                    {"workers": 0, "kv_usage": [],
                     "tokens_per_s": 0.0, "preemptions": 0,
                     "spec_drafted": 0, "spec_accepted": 0,
                     "spec_rate_num": 0.0, "spec_rate_den": 0,
                     "compiles_by_kind": {}},
                )
                st["workers"] += 1
                if "kv_usage" in w:
                    st["kv_usage"].append(float(w["kv_usage"]))
                st["tokens_per_s"] += float(w.get("tokens_per_s", 0.0))
                st["preemptions"] += int(w.get("preemptions", 0))
                st["spec_drafted"] += int(w.get("spec_drafted", 0))
                st["spec_accepted"] += int(w.get("spec_accepted", 0))
                # the LIVE per-role rate is the drafted-weighted mean of
                # the workers' ~60 s windowed rates (== the true windowed
                # accepted/drafted ratio), NOT the lifetime ratio — a
                # draft that degrades must move this gauge within the
                # window, and an actively-failing draft (rate 0, window
                # drafted > 0) must weigh it down rather than vanish
                wd = int(w.get("spec_window_drafted", 0) or 0)
                if wd > 0:
                    st["spec_rate_num"] += (
                        float(w.get("spec_accept_rate", 0.0) or 0.0) * wd
                    )
                    st["spec_rate_den"] += wd
                for k, v in w.get("compiles_by_kind", {}).items():
                    st["compiles_by_kind"][k] = (
                        st["compiles_by_kind"].get(k, 0) + v
                    )
                # None marks a family ABSENT from this frame (the worker
                # drops a key it failed to build, a garbage wire merges
                # to zero sources) — _fold_departed must tell that apart
                # from a genuine counter reset, or the fold+restore cycle
                # double-counts the monotonic fleet families
                slo_counts = None
                wire = m.get("slo")
                if isinstance(wire, dict):
                    one = slo_mod.merge_trackers([wire])
                    if one.sources:
                        w["slo"] = one.to_snapshot()
                        wires_by_role.setdefault(role, []).append(wire)
                        slo_counts = (
                            one.requests_total, one.within_sla_total,
                            one.tokens_total, one.goodput_tokens_total,
                        )
                contribs[iid] = (
                    role,
                    {
                        "preemptions": (
                            None if m.get("preemptions") is None
                            else int(w.get("preemptions", 0) or 0)
                        ),
                        "spec": (
                            None if m.get("spec_drafted") is None
                            else (
                                int(w.get("spec_drafted", 0) or 0),
                                int(w.get("spec_accepted", 0) or 0),
                            )
                        ),
                        "compiles": (
                            dict(w["compiles_by_kind"])
                            if isinstance(w.get("compiles_by_kind"), dict)
                            else None
                        ),
                        "slo": slo_counts,
                    },
                )
                workers[iid] = w
            except Exception:
                logger.warning(
                    "skipping malformed worker frame from %s", iid,
                    exc_info=True,
                )
        self._fold_departed(snap, contribs)
        role_merged = {
            role: slo_mod.merge_trackers(wires)
            for role, wires in wires_by_role.items()
        }
        all_wires = [w for ws in wires_by_role.values() for w in ws]
        roles: dict[str, dict] = {}
        for role, st in sorted(role_stats.items()):
            roles[role] = {
                "workers": st["workers"],
                "kv_usage": (
                    round(sum(st["kv_usage"]) / len(st["kv_usage"]), 4)
                    if st["kv_usage"]
                    else None
                ),
                "tokens_per_s": round(st["tokens_per_s"], 2),
                "preemptions": st["preemptions"],
                "spec_drafted": st["spec_drafted"],
                "spec_accepted": st["spec_accepted"],
                "spec_accept_rate": (
                    round(st["spec_rate_num"] / st["spec_rate_den"], 4)
                    if st["spec_rate_den"]
                    else 0.0
                ),
                "compiles_by_kind": st["compiles_by_kind"],
            }
            merged = role_merged.get(role)
            if merged is not None and merged.sources:
                roles[role]["slo"] = merged.to_snapshot()
        fleet = slo_mod.merge_trackers(all_wires)
        doc = {
            "workers": workers,
            "roles": roles,
            "fleet": {
                "workers": len(workers),
                **(
                    {"slo": fleet.to_snapshot()} if fleet.sources else {}
                ),
            },
        }
        doc["control_plane"] = self._control_plane_doc()
        planner = self._planner_doc()
        if planner is not None:
            doc["planner"] = planner
        kv_index = self._kv_index_doc()
        if kv_index is not None:
            doc["kv_index"] = kv_index
        return doc, role_merged, role_stats

    def _fold_departed(self, snap: dict, contribs: dict) -> None:
        """Counter-churn bookkeeping for the fleet exposition. The
        `dynamo_tpu_fleet_*_total` families are sums over live worker
        frames — a worker aging out (or restarting with fresh counters)
        would make them DROP, which Prometheus rate()/increase() reads
        as a counter reset and turns into a phantom spike equal to the
        whole new sum. So: when a worker departs or its counters
        regress, its last-seen contribution moves into a per-role
        monotonic base that _fleet_lines adds back. Also prunes the
        req/s-tok/s rate baselines of departed workers (unbounded
        growth under churn otherwise)."""
        for iid in list(self._rate_state):
            if iid not in snap:
                del self._rate_state[iid]
        # a worker RETURNING after aging out: if its counters carried on
        # from where the fold left them (>= the folded contribution in
        # every present family), the gap was a transient publish outage,
        # not a restart — un-fold the ghost so the monotonic fleet
        # families don't count its history twice. A genuinely regressed
        # family means a restart: the fold stays (the new counters are a
        # fresh life).
        for iid in list(self._ghost_contrib):
            cur = contribs.get(iid)
            if cur is None:
                continue
            role, ghost = self._ghost_contrib.pop(iid)
            c = cur[1]
            unfold = {
                "preemptions": 0, "spec": None, "compiles": {}, "slo": None,
            }
            if ghost.get("preemptions") is not None and (
                c.get("preemptions") or 0
            ) >= ghost["preemptions"]:
                unfold["preemptions"] = ghost["preemptions"]
            if ghost.get("spec") is not None and all(
                x >= p
                for x, p in zip(c.get("spec") or (0, 0), ghost["spec"])
            ):
                unfold["spec"] = ghost["spec"]
            if ghost.get("compiles") is not None and all(
                (c.get("compiles") or {}).get(k, 0) >= v
                for k, v in ghost["compiles"].items()
            ):
                unfold["compiles"] = ghost["compiles"]
            if ghost.get("slo") is not None and all(
                x >= p
                for x, p in zip(c.get("slo") or (0, 0, 0, 0), ghost["slo"])
            ):
                unfold["slo"] = ghost["slo"]
            self._unfold_retired(role, unfold)
        for iid, (role, prev) in list(self._live_contrib.items()):
            cur = contribs.get(iid)
            if cur is None:
                # malformed-this-pass frames (iid still in snap) keep
                # their old contribution until they truly age out
                if iid not in snap:
                    self._fold_retired(role, prev)
                    # fleet event timeline: an UNANNOUNCED disappearance
                    # is exactly what an incident reconstruction looks
                    # for. A worker whose last frame said draining/
                    # handover already put its own event on the timeline
                    # — a planned wind-down must not cry worker_lost.
                    last_state = self._last_state.pop(iid, "serving")
                    if last_state not in ("draining", "handover"):
                        self.events.add({
                            "type": "worker_lost", "severity": "warning",
                            "source": iid, "attrs": {"role": role},
                        })
                    self._ghost_contrib[iid] = (role, prev)
                    while len(self._ghost_contrib) > 1024:
                        self._ghost_contrib.pop(
                            next(iter(self._ghost_contrib))
                        )
                    del self._live_contrib[iid]
                continue
            c = cur[1]
            # a family ABSENT from this frame (None) keeps its previous
            # contribution — absence is a dropped key on the worker or a
            # garbage wire, not a counter reset; treating it as zero
            # would fold prev now and re-add it from the next healthy
            # frame, permanently double-counting the monotonic families
            for fam in ("preemptions", "spec", "compiles", "slo"):
                if c.get(fam) is None:
                    c[fam] = prev.get(fam)
            # fold ONLY the families that actually regressed (reset on a
            # worker restart) — a regression in one never implies the
            # others reset too
            folded = {
                "preemptions": 0, "spec": None, "compiles": {},
                "slo": None,
            }
            any_folded = False
            if (
                prev["preemptions"] is not None
                and (c["preemptions"] or 0) < prev["preemptions"]
            ):
                folded["preemptions"] = prev["preemptions"]
                any_folded = True
            if prev.get("spec") is not None and any(
                x < p
                for x, p in zip(c.get("spec") or (0, 0), prev["spec"])
            ):
                folded["spec"] = prev["spec"]
                any_folded = True
            if prev["compiles"] is not None and any(
                (c["compiles"] or {}).get(k, 0) < v
                for k, v in prev["compiles"].items()
            ):
                folded["compiles"] = prev["compiles"]
                any_folded = True
            if prev["slo"] is not None and any(
                x < p for x, p in zip(c["slo"] or (0, 0, 0, 0), prev["slo"])
            ):
                folded["slo"] = prev["slo"]
                any_folded = True
            if any_folded:
                self._fold_retired(role, folded)
        self._live_contrib.update(contribs)

    def _unfold_retired(self, role: str, contrib: dict) -> None:
        """Subtract a returned worker's folded contribution back out of
        the per-role monotonic base (floored at 0: the base must never
        make a fleet counter regress)."""
        base = self._retired_counters.get(role)
        if base is None:
            return
        base["preemptions"] = max(
            0, base["preemptions"] - (contrib.get("preemptions") or 0)
        )
        base["spec"] = [
            max(0, a - b)
            for a, b in zip(
                base.get("spec", [0, 0]), contrib.get("spec") or (0, 0)
            )
        ]
        for k, v in (contrib.get("compiles") or {}).items():
            if k in base["compiles"]:
                base["compiles"][k] = max(0, base["compiles"][k] - v)
        base["slo"] = [
            max(0, a - b)
            for a, b in zip(base["slo"], contrib.get("slo") or (0, 0, 0, 0))
        ]

    def _fold_retired(self, role: str, contrib: dict) -> None:
        base = self._retired_counters.setdefault(
            role,
            {"preemptions": 0, "spec": [0, 0], "compiles": {},
             "slo": [0, 0, 0, 0]},
        )
        base["preemptions"] += contrib["preemptions"] or 0
        base["spec"] = [
            a + b
            for a, b in zip(
                base.get("spec", [0, 0]), contrib.get("spec") or (0, 0)
            )
        ]
        for k, v in (contrib["compiles"] or {}).items():
            base["compiles"][k] = base["compiles"].get(k, 0) + v
        base["slo"] = [
            a + b
            for a, b in zip(base["slo"], contrib["slo"] or (0, 0, 0, 0))
        ]

    def fleet_snapshot(self) -> dict:
        return self._assemble_fleet()[0]

    def _fleet_lines(self, assembled=None) -> list[str]:
        """`dynamo_tpu_fleet_*{role=...}` exposition: per-role worker
        counts, merged SLO percentiles / attainment / burn rates /
        goodput, mean utilization, and folded engine-internals counters.
        Counter families include the retired-worker bases so they stay
        monotonic across worker churn (the /v1/fleet JSON deliberately
        does not — it describes the live fleet at this instant)."""
        import dataclasses

        from dynamo_tpu.telemetry import slo as slo_mod

        _, role_merged, role_stats = assembled or self._assemble_fleet()
        retired = self._retired_counters
        lines: list[str] = []
        if role_stats:
            lines.append(f"# TYPE {PREFIX}_fleet_workers gauge")
            for role, st in sorted(role_stats.items()):
                lines.append(
                    f'{PREFIX}_fleet_workers{{role="{role}"}} '
                    f'{st["workers"]}'
                )
            for field, ptype, pick in (
                ("kv_usage", "gauge",
                 lambda role, st: (
                     sum(st["kv_usage"]) / len(st["kv_usage"])
                     if st["kv_usage"] else None
                 )),
                ("tokens_per_s", "gauge",
                 lambda role, st: st["tokens_per_s"]),
                ("preemptions_total", "counter",
                 lambda role, st: (
                     st["preemptions"]
                     + retired.get(role, {}).get("preemptions", 0)
                 )),
                # speculation: drafted/accepted counters stay monotonic
                # across worker churn like preemptions; the rate gauge
                # is the LIVE fleet ratio (live workers only)
                ("spec_drafted_total", "counter",
                 lambda role, st: (
                     st.get("spec_drafted", 0)
                     + retired.get(role, {}).get("spec", [0, 0])[0]
                 )),
                ("spec_accepted_total", "counter",
                 lambda role, st: (
                     st.get("spec_accepted", 0)
                     + retired.get(role, {}).get("spec", [0, 0])[1]
                 )),
                # windowed drafted-weighted mean, NOT the lifetime ratio
                # (which would stop moving after hours of serving)
                ("spec_accept_rate", "gauge",
                 lambda role, st: (
                     st["spec_rate_num"] / st["spec_rate_den"]
                     if st.get("spec_rate_den")
                     else 0.0
                 )),
            ):
                vals = [
                    (role, pick(role, st))
                    for role, st in sorted(role_stats.items())
                ]
                vals = [(r, v) for r, v in vals if v is not None]
                if not vals:
                    continue
                lines.append(f"# TYPE {PREFIX}_fleet_{field} {ptype}")
                for role, v in vals:
                    lines.append(
                        f'{PREFIX}_fleet_{field}{{role="{role}"}} '
                        f"{round(v, 6)}"
                    )
            kind_totals: dict[str, dict] = {}
            for role, st in role_stats.items():
                kt = dict(st["compiles_by_kind"])
                for k, v in retired.get(role, {}).get("compiles", {}).items():
                    kt[k] = kt.get(k, 0) + v
                kind_totals[role] = kt
            kind_samples = [
                (role, k, v)
                for role in sorted(role_stats)
                for k, v in sorted(kind_totals[role].items())
            ]
            if kind_samples:
                lines.append(f"# TYPE {PREFIX}_fleet_compile_total counter")
                for role, k, v in kind_samples:
                    lines.append(
                        f'{PREFIX}_fleet_compile_total{{role="{role}",'
                        f'kind="{k}"}} {v}'
                    )
        scopes = []
        for role, merged in sorted(role_merged.items()):
            b = retired.get(role, {}).get("slo")
            if b and any(b):
                merged = dataclasses.replace(
                    merged,
                    requests_total=merged.requests_total + b[0],
                    within_sla_total=merged.within_sla_total + b[1],
                    tokens_total=merged.tokens_total + b[2],
                    goodput_tokens_total=merged.goodput_tokens_total + b[3],
                )
            scopes.append((f'role="{role}"', merged))
        lines += slo_mod.expose_lines(f"{PREFIX}_fleet", scopes)
        return lines

    def expose(self, openmetrics: bool = False) -> str:
        """Classic Prometheus text by default; `openmetrics=True` is
        the negotiated rendering (OpenMetrics counter naming, `# EOF`,
        phase-histogram exemplars — classic parsers reject exemplar
        syntax, so it never rides the text/plain surface)."""
        snap3 = self._snapshot_all()
        assembled = self._assemble_fleet(snap3)
        counts: dict[str, int] = {self.component: 0}
        for _, (_, _, comp) in snap3.items():
            counts[comp] = counts.get(comp, 0) + 1
        lines = [f"# TYPE {PREFIX}_live_workers gauge"]
        for comp, n in sorted(counts.items()):
            lines.append(
                f'{PREFIX}_live_workers{{component="{comp}"}} {n}'
            )
        for field, ptype in _WORKER_FIELDS:
            name = f"{PREFIX}_worker_{field}"
            if ptype == "counter" and not field.endswith("_total"):
                name += "_total"
            lines.append(f"# TYPE {name} {ptype}")
            for iid, (m, _, comp) in sorted(snap3.items()):
                if field in m and isinstance(m[field], (int, float)):
                    lines.append(
                        f'{name}{{component="{comp}",'
                        f'instance="{iid}"}} {m[field]}'
                    )
        lines += [
            f"# TYPE {PREFIX}_kv_hit_rate_events_total counter",
            f"{PREFIX}_kv_hit_rate_events_total {self.hit_events}",
            f"# TYPE {PREFIX}_kv_hit_rate_isl_tokens_total counter",
            f"{PREFIX}_kv_hit_rate_isl_tokens_total {self.isl_tokens_total}",
            f"# TYPE {PREFIX}_kv_hit_rate_overlap_tokens_total counter",
            f"{PREFIX}_kv_hit_rate_overlap_tokens_total {self.overlap_tokens_total}",
            f"# TYPE {PREFIX}_kv_hit_rate gauge",
            f"{PREFIX}_kv_hit_rate "
            f"{self.overlap_tokens_total / self.isl_tokens_total if self.isl_tokens_total else 0.0}",
        ]
        lines += self._fabric_lines()
        lines += self._fleet_lines(assembled)
        lines += self._planner_lines()
        lines += self._kv_index_lines()
        # fleet trace plane: assembly/sampling counters + the event-
        # timeline counter family the Grafana annotation layer queries
        lines += self.traces.expose_lines(PREFIX)
        lines += self.events.expose_lines(PREFIX)
        # process-global speculation counters (in-process engines; the
        # per-worker fleet view is dynamo_tpu_worker_spec_* above) —
        # the same families FrontendMetrics exposes, both surfaces
        from dynamo_tpu.telemetry import debug as _debug

        lines += _debug.spec_lines(PREFIX)
        # data-integrity rejections (disk-tier checksum misses, corrupt
        # transfer frames) — same both-surfaces contract as spec_lines
        lines += _debug.integrity_lines(PREFIX)
        # control-plane HA: this process's broker-connection state
        # (degraded gauge, outage counters, client-observed failovers)
        # — docs/operations.md "Control-plane HA"
        lines += _debug.control_plane_lines(PREFIX)
        # process-global KV index health (zeros here — this process hosts
        # no router; the per-component fleet view is
        # dynamo_tpu_router_kv_index_* above) — both-surfaces contract
        lines += _debug.kv_index_lines(PREFIX)
        # process-global HBM accounting (zeros here — no engine in this
        # process; the per-worker fleet view is the
        # dynamo_tpu_worker_hbm_* families above) — both-surfaces
        # contract
        lines += _debug.hbm_lines(PREFIX)
        # host-skew straggler gauge: per-host max of the workers'
        # flight-window dispatch p95, grouped by the frames' `host`
        # (jax.process_index()). Under lockstep SPMD one slow host drags
        # every dispatch — this family makes WHICH host visible. The
        # zeroed {host="0"} default keeps the family present for the
        # Grafana panel-vs-emitted-names gate
        skew: dict[str, float] = {}
        for _, (m, _, _) in sorted(snap3.items()):
            p95 = m.get("dispatch_p95_ms")
            if not isinstance(p95, (int, float)):
                continue
            h = str(int(m.get("host", 0) or 0))
            skew[h] = max(skew.get(h, 0.0), float(p95))
        lines.append(f"# TYPE {PREFIX}_fleet_host_dispatch_p95_ms gauge")
        for h, v in sorted(skew.items()) or [("0", 0.0)]:
            lines.append(
                f'{PREFIX}_fleet_host_dispatch_p95_ms{{host="{h}"}} {v}'
            )
        # per-phase latency histograms (telemetry plane, process-global)
        from dynamo_tpu.telemetry import phases

        lines += phases.expose_lines(exemplars=openmetrics)
        # stall-watchdog counters (process-global, usually empty here —
        # the per-worker view is dynamo_tpu_worker_stalls_total above)
        from dynamo_tpu.telemetry.watchdog import stall_counters

        lines += stall_counters.expose_lines()
        text = "\n".join(lines) + "\n"
        if openmetrics:
            from dynamo_tpu.telemetry.openmetrics import to_openmetrics

            return to_openmetrics(text)
        return text

    async def _metrics(self, request: web.Request) -> web.Response:
        from dynamo_tpu.telemetry import openmetrics

        if openmetrics.negotiate(request.headers.get("Accept")):
            return web.Response(
                text=self.expose(openmetrics=True),
                content_type=openmetrics.CONTENT_TYPE,
                charset="utf-8",
            )
        return web.Response(
            text=self.expose(), content_type="text/plain", charset="utf-8"
        )

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"status": "ok", "workers": len(self.aggregator.snapshot())}
        )

    async def _fleet(self, request: web.Request) -> web.Response:
        """The queryable fleet snapshot: per-worker role / rates /
        engine internals / SLO percentiles + per-role and fleet-wide
        merged views (scripts/fleet_top.py renders this)."""
        return web.json_response(self.fleet_snapshot())

    async def _traces(self, request: web.Request) -> web.Response:
        """GET /v1/traces — the fleet trace SEARCH API over assembled,
        tail-sampled traces: ?min_ms= &status= &worker= &endpoint=
        &since= &sort=recent|duration &limit=N. (The per-process rings
        still serve the same path on each frontend/worker; this surface
        is the cross-process one.)"""
        q = request.query
        try:
            kwargs = {
                "min_ms": float(q["min_ms"]) if "min_ms" in q else None,
                "status": q.get("status"),
                "worker": q.get("worker"),
                "endpoint": q.get("endpoint"),
                "since": float(q["since"]) if "since" in q else None,
                "sort": q.get("sort", "recent"),
                "limit": int(q.get("limit", "50")),
            }
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": f"bad query parameter: {e}"}, status=400
            )
        if kwargs["sort"] not in ("recent", "duration"):
            return web.json_response(
                {"error": "sort must be recent|duration"}, status=400
            )
        return web.json_response(
            {
                "traces": self.traces.search(**kwargs),
                "stats": self.traces.stats(),
                "sample_rate": self.trace_sampler.healthy_rate,
            }
        )

    async def _trace(self, request: web.Request) -> web.Response:
        """GET /v1/traces/{id}[?format=chrome] — one ASSEMBLED trace:
        spans from every process, the timeline breakdown, and the fleet
        events that overlapped its window."""
        tid = request.match_info["trace_id"]
        doc = self.traces.get(tid)
        if doc is None:
            return web.json_response(
                {"error": f"trace {tid!r} not found"}, status=404
            )
        if request.query.get("format") == "chrome":
            from dynamo_tpu.telemetry.chrome_export import to_chrome_trace

            return web.json_response(to_chrome_trace(doc["spans"]))
        summary = doc["summary"]
        t0 = float(summary.get("start_ts") or 0.0)
        dur_ms = float(summary.get("duration_ms") or 0.0)
        doc["events"] = self.events.overlapping(t0, t0 + dur_ms / 1000.0)
        doc["breakdown"] = (summary or {}).get("breakdown")
        return web.json_response(doc)

    async def _fleet_events(self, request: web.Request) -> web.Response:
        """GET /v1/fleet/events — the fleet event timeline:
        ?since=<id> &since_ts=<epoch> &type= &severity= &source=
        &limit=N (newest last; `id` is monotonic, tail with since=)."""
        q = request.query
        try:
            kwargs = {
                "since_id": int(q["since"]) if "since" in q else None,
                "since_ts": (
                    float(q["since_ts"]) if "since_ts" in q else None
                ),
                "etype": q.get("type"),
                "severity": q.get("severity"),
                "source": q.get("source"),
                "limit": int(q.get("limit", "200")),
            }
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": f"bad query parameter: {e}"}, status=400
            )
        return web.json_response({"events": self.events.query(**kwargs)})

    # -- debug plane: fleet-wide flight windows + program cost tables ------
    # (the per-worker data rides the metrics frames; docs/observability.md
    # "Debugging a slow or stuck worker")

    async def _debug_flight(self, request: web.Request) -> web.Response:
        from dynamo_tpu.telemetry.debug import parse_window
        from dynamo_tpu.telemetry.flight import tail

        n, err = parse_window(request.query.get("n"))
        if err is not None:
            return web.json_response(err, status=400)

        workers = {}
        for iid, (m, age, comp) in sorted(self._snapshot_all().items()):
            fl = m.get("flight")
            if not isinstance(fl, list):
                continue
            fl = tail(fl, n)
            workers[iid] = {
                "component": comp,
                "last_seen_s": round(age, 3),
                "records": fl,
            }
        return web.json_response({"workers": workers})

    async def _debug_programs(self, request: web.Request) -> web.Response:
        workers = {}
        for iid, (m, age, comp) in sorted(self._snapshot_all().items()):
            pk = m.get("programs_by_kind")
            if not isinstance(pk, dict):
                continue
            workers[iid] = {
                "component": comp,
                "last_seen_s": round(age, 3),
                "kinds": pk,
            }
        return web.json_response({"workers": workers})

    async def _debug_memory(self, request: web.Request) -> web.Response:
        """Fleet-wide HBM accounting: each worker's per-device
        weights/kv_pool/free/peak byte breakdown, as published
        in its frames (engine.memory_report())."""
        workers = {}
        for iid, (m, age, comp) in sorted(self._snapshot_all().items()):
            mem = m.get("memory")
            if not isinstance(mem, dict):
                continue
            workers[iid] = {
                "component": comp,
                "last_seen_s": round(age, 3),
                **mem,
            }
        return web.json_response({"workers": workers})

    async def _debug_mesh(self, request: web.Request) -> web.Response:
        """Fleet-wide mesh/sharding introspection: each worker's mesh
        shape, per-param-group sharding specs, process_index and
        dispatch timing, as published in its frames
        (engine.mesh_report())."""
        workers = {}
        for iid, (m, age, comp) in sorted(self._snapshot_all().items()):
            mesh = m.get("mesh")
            if not isinstance(mesh, dict):
                continue
            workers[iid] = {
                "component": comp,
                "last_seen_s": round(age, 3),
                **mesh,
            }
        return web.json_response({"workers": workers})

    async def _debug_profile(self, request: web.Request) -> web.Response:
        # the metrics service hosts no engine; the payload layer answers
        # the honest 501 (profile captures must be triggered on the
        # process that owns the device)
        from dynamo_tpu.telemetry.debug import profile_payload

        try:
            body = await request.json()
        except Exception:
            body = {}
        payload, status = profile_payload(body)
        return web.json_response(payload, status=status)
