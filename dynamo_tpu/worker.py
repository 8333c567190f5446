"""Engine worker process: engine + ingress + registration + publishers.

One worker = one JaxEngine serving one model over the fabric. It:
1. starts the engine thread (AsyncEngineRunner),
2. serves `generate` (and `flush`) on its ingress,
3. registers its endpoint instance under the process lease,
4. publishes the model card + entry (register_llm),
5. publishes KV events (subject kv_events.{instance_id}) and worker load
   metrics (subject metrics.{component}) for routers/planner.

Equivalent of the reference's engine-subprocess workers joining the
runtime (launch/dynamo-run/src/subprocess/vllm_inc.py + endpoint.rs).
"""

from __future__ import annotations

import asyncio
import logging
import os
from typing import Optional

import msgpack

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.async_engine import (
    AsyncEngineRunner,
    EchoEngine,
    SpmdEngineRunner,
)
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.page_table import KvEvent
from dynamo_tpu.model_card import ModelDeploymentCard, register_llm
from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
from dynamo_tpu.runtime import DistributedRuntime, IngressServer
from dynamo_tpu.subjects import (
    KV_EVENT_SUBJECT,
    KVBM_TIER_SUBJECT,
    METRICS_SUBJECT,
)
from dynamo_tpu import telemetry

logger = logging.getLogger(__name__)


class Worker:
    def __init__(
        self,
        runtime: DistributedRuntime,
        card: ModelDeploymentCard,
        engine_config: Optional[EngineConfig] = None,
        engine_kind: str = "jax",
        namespace: str = "dynamo",
        component: str = "backend",
        endpoint: str = "generate",
        checkpoint_path: Optional[str] = None,
        metrics_interval: float = 1.0,
        router_mode: str = "round_robin",
        enable_disagg: bool = False,
        disagg_config=None,
        prefill_queue_name: str = "prefill_queue",
        advertise_host: str = "127.0.0.1",
        kv_remote: bool = False,
        kv_remote_min_blocks: int = 2,
        kv_remote_timeout_s: float = 5.0,
        echo_delay: float = 0.0,
        mock_args=None,
        engine=None,
        drain_budget_s: float = 30.0,
        kv_sequencing: bool = True,
        kv_economy: bool = False,
    ):
        self.runtime = runtime
        self.card = card
        self.engine_config = engine_config
        self.engine_kind = engine_kind
        self.namespace = namespace
        self.component = component
        self.endpoint_name = endpoint
        self.checkpoint_path = checkpoint_path
        self.metrics_interval = metrics_interval
        self.router_mode = router_mode
        self.mock = None
        self.enable_disagg = enable_disagg
        self.disagg_config = disagg_config
        self.prefill_queue_name = prefill_queue_name
        #: host other processes (frontends, prefill workers) reach us at —
        #: must be a routable address in multi-host deployments
        self.advertise_host = advertise_host
        self.transfer_server = None
        self.disagg_router = None
        self.prefill_queue = None
        self.remote_prefills = 0
        #: G4 remote tier (cross-worker onboarding over the transfer plane)
        self.kv_remote = kv_remote
        self.kv_remote_min_blocks = kv_remote_min_blocks
        self.kv_remote_timeout_s = kv_remote_timeout_s
        self.kv_directory = None
        self.remote_onboards = 0
        self._fetch_client = None
        self._peer_source = None
        self._tier_event_buffer: list[tuple[int, Optional[int], str]] = []
        self.ingress = IngressServer()
        self.runner: Optional[AsyncEngineRunner] = None
        self.echo: Optional[EchoEngine] = None
        self.registration = None
        self.instance_id: str = ""
        self.echo_delay = echo_delay
        self.mock_args = mock_args
        #: engine_kind="external": a caller-supplied AsyncEngine — any
        #: object with `generate(context, PreprocessedRequest) -> async
        #: iterator of {token_ids, finish_reason}` joins as a first-class
        #: worker (the reference's engine-subprocess shims,
        #: launch/dynamo-run/src/subprocess/vllm_v1_inc.py). See
        #: docs/external_engines.md.
        if engine is not None and engine_kind != "external":
            # silently routing generate() to `engine` while start() builds
            # the native one would serve tokens from one engine and
            # metrics from another
            raise ValueError(
                f"engine= requires engine_kind='external' (got "
                f"{engine_kind!r})"
            )
        self.external = engine
        self._kv_event_buffer: list[KvEvent] = []
        #: KV event sequencing + rolling block-set digest (docs/
        #: operations.md "KV index consistency"): every published event
        #: carries a per-worker monotonic `seq`, and the metrics frames
        #: carry (seq, xxh3-fold, count) of the registered block set —
        #: indexers detect lost events (sequence gaps) and silent drift
        #: (digest mismatch) and resync from the `kv.snapshot` ingress
        #: op. Off = the exact pre-sequencing wire (no seq keys, no
        #: digest frame, no snapshot state), pinned by tests.
        self.kv_sequencing = kv_sequencing
        self._kv_seq = 0
        from dynamo_tpu.kv_router.digest import SetDigest

        self._kv_digest = SetDigest()
        #: designed degraded mode (docs/operations.md "Control-plane
        #: HA"): while no broker answers, KV events buffer UNSTAMPED in
        #: this bounded queue — a short outage loses nothing; overflow
        #: is stamped-and-dropped so the burned seqs surface as a
        #: detectable gap (indexers resync on reconnect) instead of
        #: silent divergence or unbounded memory
        self._kv_pending: list[dict] = []
        self.kv_pending_cap = int(
            os.environ.get("DYNTPU_KV_EVENT_BUFFER", "4096")
        )
        self.kv_events_dropped = 0
        self._tasks: list[asyncio.Task] = []
        #: graceful drain (docs/operations.md "Overload & draining"):
        #: SIGTERM or the `drain` ingress op flips this — the worker
        #: deregisters, refuses new ingress (router retries a survivor),
        #: finishes in-flight work within drain_budget_s, then `drained`
        #: fires so the CLI process can exit 0
        self.draining = False
        self.drain_budget_s = drain_budget_s
        self.drained = asyncio.Event()
        #: live role (closed-loop planner flips this between decode and
        #: prefill via the `flip` ingress op — docs/operations.md
        #: "Closed-loop autoscaling & role flips"). The engine, its KV
        #: pool, and the instance id survive a flip: hot pages stay
        #: registered (and G4-serveable), so prefix routing stays warm.
        self.role = "prefill" if "prefill" in component else "decode"
        #: where a flip to decode registers (a worker STARTED in the
        #: prefill role has component="prefill", which is not a decode
        #: pool — flips land it in the default decode pool)
        self.decode_component = (
            component if "prefill" not in component else "backend"
        )
        self.decode_endpoint = (
            endpoint if "prefill" not in component else "generate"
        )
        self.flips = 0
        self._prefill_embedded = None
        self._flip_lock = asyncio.Lock()
        #: worker handover (docs/operations.md "Rolling upgrades & worker
        #: handover"): live KV migration to a successor before this
        #: process exits — the planner's zero-downtime alternative to
        #: kill+spawn, and the drain path's warm-KV upgrade
        self.handing_over = False
        self._handover_phase: Optional[str] = None
        self.handovers = 0          # completed as the retiring side
        self.handover_fallbacks = 0  # degraded to plain drain
        self.handover_bytes = 0      # KV bytes shipped to successors
        self.handover_blocks = 0     # blocks accepted by successors
        self.handovers_adopted = 0   # blocks adopted as a successor
        self._handover_tasks: set[asyncio.Task] = set()
        #: KV economy (docs/operations.md "The KV economy"): per-prefix
        #: migration — a KV-economy router asks THIS worker (the holder
        #: of a hot prefix) to push just that chain to the worker it
        #: chose, through the same offer/transfer plane handover uses.
        #: The flag additionally drives the TierPolicy demotion loop on
        #: the publish cadence when the engine's allocator is tiered.
        self.kv_economy = kv_economy
        self._tier_policy = None
        self.migrations = 0           # completed as the source side
        self.migration_fallbacks = 0  # failed/degraded to cold prefill
        self.migration_bytes = 0      # KV bytes pushed to destinations
        self.migration_blocks = 0     # blocks accepted by destinations

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        if self.engine_kind == "external":
            if self.external is None:
                raise ValueError(
                    "engine_kind='external' needs an `engine` object "
                    "implementing AsyncEngine.generate"
                )
            # foreign engines publish KV events (prefix routing) by
            # calling this sink — duck-typed so a shim can opt out
            if hasattr(self.external, "on_kv_event"):
                self.external.on_kv_event = self._kv_event_buffer.append
        elif self.engine_kind == "echo":
            self.echo = EchoEngine(delay=self.echo_delay)
        elif self.engine_kind == "mock":
            from dynamo_tpu.mocker import MockEngine, MockEngineArgs

            args = self.mock_args or MockEngineArgs(
                page_size=self.card.kv_page_size, salt=self.card.name
            )
            if (
                args.page_size != self.card.kv_page_size
                or args.salt != self.card.name
            ):
                # Routers hash blocks with (card page size, card name) —
                # a mismatched mock would emit events no router can match.
                raise ValueError(
                    f"mock_args page_size/salt ({args.page_size}, "
                    f"{args.salt!r}) must match the card "
                    f"({self.card.kv_page_size}, {self.card.name!r})"
                )
            self.mock = MockEngine(
                args,
                on_kv_event=lambda e: self._kv_event_buffer.append(e),
            )
        else:
            # Engine construction (param init, first compiles) blocks for
            # seconds — run it off-loop or the fabric lease keepalives
            # starve and the registration lease expires before it exists.
            engine = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: JaxEngine(
                    self.engine_config,
                    on_kv_event=lambda e: self._kv_event_buffer.append(e),
                    checkpoint_path=self.checkpoint_path,
                    on_tier_event=(
                        (lambda h, p, t: self._tier_event_buffer.append(
                            (h, p, t)
                        ))
                        if self.kv_remote or self.kv_economy
                        else None
                    ),
                ),
            )
            if engine._multiproc:
                # One replica of a cross-host lockstep group: this host
                # (the leader) owns the fabric endpoint; admissions ride
                # the SpmdDriver broadcast to the follower replicas
                # (engine/spmd.py). Disagg/G4 mutate engine state through
                # runner.submit and would desync the replicas.
                if self.enable_disagg or self.kv_remote:
                    raise ValueError(
                        "disagg / kv-remote are not supported on a "
                        "cross-host SPMD group yet"
                    )
                from dynamo_tpu.engine.spmd import SpmdDriver

                self.runner = SpmdEngineRunner(engine, SpmdDriver(engine))
            else:
                self.runner = AsyncEngineRunner(engine)
            self.runner.start()

        self.ingress.add_handler("generate", self._generate)
        self.ingress.add_handler("embed", self._embed)
        self.ingress.add_handler("flush", self._flush)
        self.ingress.add_handler("kv.snapshot", self._kv_snapshot_handler)
        self.ingress.add_handler("drain", self._drain_handler)
        self.ingress.add_handler("flip", self._flip_handler)
        self.ingress.add_handler("handover", self._handover_handler)
        self.ingress.add_handler("handover_offer", self._handover_offer_handler)
        self.ingress.add_handler("migrate_prefix", self._migrate_prefix_handler)
        await self.ingress.start()

        metadata = {"model": self.card.name}
        if self.runner is not None or self.mock is not None:
            # role-flip capable: has an ingress the planner can reach and
            # an engine whose KV pool survives the flip (external/echo
            # engines have no paged KV to keep warm — they stay put)
            metadata["flippable"] = True
        # The KV transfer plane serves every single-host engine worker,
        # not just disagg/kv-remote ones: worker handover ships the
        # retiring worker's registered pages through it, so any jax
        # worker must be able to RECEIVE pages (docs/operations.md
        # "Rolling upgrades & worker handover"). SPMD groups refuse —
        # extraction holds only the process-local Hkv slice.
        if self.runner is not None and not isinstance(
            self.runner, SpmdEngineRunner
        ):
            from dynamo_tpu.disagg import KvTransferServer, device_transfer

            # decode also serves G4 fetches / could stage in future
            # reversals; advertise a routable pull address in multi-host
            device_transfer.configure(self.advertise_host)

            runner = self.runner

            async def write_fn(page_ids, k, v):
                await runner.submit(
                    lambda eng: eng.inject_pages(page_ids, k, v)
                )

            async def device_write_fn(page_ids, k, v):
                await runner.submit(
                    lambda eng: eng.inject_pages_device(page_ids, k, v)
                )

            fetch_fn = None
            if self.kv_remote:
                async def fetch_fn(seq_hashes):
                    return await runner.submit(
                        lambda eng: eng.serve_blocks(seq_hashes)
                    )

            self.transfer_server = KvTransferServer(
                write_fn, device_write_fn=device_write_fn, fetch_fn=fetch_fn
            )
            await self.transfer_server.start()
            metadata["kv_transfer_port"] = self.transfer_server.port
        if self.enable_disagg and self.runner is not None:
            from dynamo_tpu.disagg import DisaggregatedRouter, PrefillQueue

            self.disagg_router = DisaggregatedRouter(
                self.runtime.fabric, self.disagg_config
            )
            await self.disagg_router.start()
            self.prefill_queue = PrefillQueue(
                self.runtime.fabric, self.prefill_queue_name
            )

        if (
            self.kv_economy
            and self.runner is not None
            and not isinstance(self.runner, SpmdEngineRunner)
        ):
            alloc = getattr(self.runner.engine, "allocator", None)
            if hasattr(alloc, "demote"):
                from dynamo_tpu.kv_economy import TierPolicy

                self._tier_policy = TierPolicy(alloc)
        ep = (
            self.runtime.namespace(self.namespace)
            .component(self.component)
            .endpoint(self.endpoint_name)
        )
        self.registration = await ep.register(
            self.advertise_host, self.ingress.port, metadata=metadata
        )
        self.instance_id = self.registration.instance.instance_id
        await register_llm(
            self.runtime.fabric, self.card, self.namespace, self.component,
            self.endpoint_name, lease_id=self.runtime.primary_lease,
            router_mode=self.router_mode,
        )
        if self.kv_remote and self.runner is not None:
            from dynamo_tpu.disagg.transfer import KvTransferClient
            from dynamo_tpu.kvbm.directory import BlockDirectory
            from dynamo_tpu.runtime.component import InstanceSource

            self.kv_directory = BlockDirectory(
                self.runtime.fabric, own_instance_id=self.instance_id
            )
            await self.kv_directory.start()
            self._fetch_client = KvTransferClient()
            self._peer_source = InstanceSource(
                self.runtime.fabric, self.namespace, self.component,
                self.endpoint_name,
            )
            await self._peer_source.start()
        # fleet trace plane: finished spans buffer for shipping on the
        # metrics-frame cadence (no-op while tracing is off); fleet
        # events (flips, handovers, drains) ride the same shipper
        from dynamo_tpu.telemetry import traceplane

        traceplane.ensure_shipping()
        loop = asyncio.get_running_loop()
        self._tasks.append(loop.create_task(self._publish_loop()))
        logger.info(
            "worker %s serving %s on :%d", self.instance_id, self.card.name,
            self.ingress.port,
        )

    def _busy(self) -> bool:
        # ingress inflight covers the whole request lifecycle —
        # runner._pending hand-off, disagg transfer waits, and the
        # final response frames — not just scheduler occupancy.
        if self.ingress.num_inflight > 0:
            return True
        return self.runner is not None and self.runner.engine.has_work

    async def _deregister(self) -> None:
        if self.registration is None:
            return
        try:
            await self.registration.deregister()
        except Exception:
            # Routers will keep sending until the lease expires — make
            # that window observable instead of silent.
            logger.warning(
                "deregister failed; relying on lease expiry", exc_info=True
            )
        self.registration = None

    async def drain(self, budget_s: Optional[float] = None) -> bool:
        """Graceful drain (docs/operations.md "Overload & draining"):
        deregister so routers stop choosing this worker, refuse new
        ingress (`_generate` raises RetryableHandlerError — the router
        retries a survivor), finish in-flight requests within the
        budget, then fire `drained` so the host process exits 0. KV
        stays serveable the whole time: --kv-remote peers can still
        onboard this worker's blocks over the transfer plane until the
        process exits (the serve/adopt hand-off path). Returns True if
        everything in flight finished inside the budget."""
        if self.draining:
            await self.drained.wait()
            return not self._busy()
        self.draining = True
        budget = self.drain_budget_s if budget_s is None else budget_s
        logger.info(
            "worker %s draining (budget %.1fs, %d in flight)",
            self.instance_id, budget, self.ingress.num_inflight,
        )
        telemetry.events.record(
            "drain", source=self.instance_id,
            inflight=self.ingress.num_inflight, budget_s=budget,
        )
        # ship NOW, not on the next publish tick — a quiet drain exits
        # before the tick and would take its own timeline entry with it
        from dynamo_tpu.telemetry import traceplane

        await traceplane.ship_once(self.runtime.fabric, self.instance_id)
        await self._deregister()
        clean = True
        deadline = asyncio.get_running_loop().time() + max(budget, 0.0)
        while self._busy() and asyncio.get_running_loop().time() < deadline:
            await asyncio.sleep(0.05)
        if self._busy():
            clean = False
            logger.warning(
                "drain budget exhausted: %d calls still in flight",
                self.ingress.num_inflight,
            )
        else:
            logger.info("worker %s drained", self.instance_id)
        self.drained.set()
        return clean

    async def _drain_handler(self, ctx, request):
        """`drain` ingress op (POST /v1/admin/drain at the frontend):
        acknowledge immediately, wind down in the background."""
        budget = None
        if isinstance(request, dict) and request.get("budget_s") is not None:
            budget = float(request["budget_s"])
        task = asyncio.get_running_loop().create_task(self.drain(budget))
        task.add_done_callback(
            lambda t: t.cancelled() or t.exception()  # observe, never raise
        )
        yield {
            "draining": True,
            "inflight": self.ingress.num_inflight,
            "budget_s": self.drain_budget_s if budget is None else budget,
        }

    # -- role flips (docs/operations.md "Closed-loop autoscaling & role
    # flips"): the planner's alternative to kill+spawn -------------------

    async def flip_role(
        self, role: str, budget_s: Optional[float] = None
    ) -> bool:
        """Flip this worker between decode and prefill roles in place.

        decode -> prefill: deregister from the decode endpoint (routers
        retry survivors), let in-flight decodes finish within the budget
        (they keep streaming even past it — the ingress stays up), start
        an embedded prefill-queue consumer on the SAME engine runner,
        and register the prefill endpoint under the SAME instance id.
        The KV pool is untouched: every page the worker computed stays
        registered, serveable to G4 peers over the transfer plane, and
        warm for the flip back.

        prefill -> decode: stop consuming the queue (in-flight prefills
        finish; borrowed runner keeps running) and re-register the
        decode endpoint, again under the same instance id — routers'
        prefix indexes for this id apply immediately, so the first
        request with a cached prefix hits warm pages."""
        if role not in ("decode", "prefill"):
            raise ValueError(f"unknown role {role!r}")
        if role == "prefill" and self.runner is None and self.mock is None:
            raise ValueError(
                f"engine kind {self.engine_kind!r} cannot serve the "
                "prefill role"
            )
        async with self._flip_lock:
            if role == self.role:
                return True
            loop = asyncio.get_running_loop()
            if role == "prefill":
                # quiesce decode: stop being chosen, finish what's here
                self.draining = True
                await self._deregister()
                budget = (
                    self.drain_budget_s if budget_s is None else budget_s
                )
                deadline = loop.time() + max(budget, 0.0)
                while self._busy() and loop.time() < deadline:
                    await asyncio.sleep(0.05)
                if self._busy():
                    logger.warning(
                        "flip budget exhausted with %d in flight; they "
                        "keep streaming while the worker serves prefill",
                        self.ingress.num_inflight,
                    )
                if self.runner is not None and self.engine_config is not None:
                    from dynamo_tpu.disagg.prefill_worker import PrefillWorker

                    self._prefill_embedded = PrefillWorker(
                        self.runtime,
                        self.engine_config,
                        namespace=self.namespace,
                        queue_name=self.prefill_queue_name,
                        runner=self.runner,
                        advertise_host=self.advertise_host,
                        register=False,
                    )
                    await self._prefill_embedded.start()
                ep = (
                    self.runtime.namespace(self.namespace)
                    .component("prefill")
                    .endpoint("prefill")
                )
                self.registration = await ep.register(
                    self.advertise_host,
                    self.ingress.port,
                    metadata={"model": self.card.name, "flippable": True},
                    instance_id=self.instance_id,
                )
                self.role = "prefill"
                self.draining = False
            else:
                await self._deregister()
                if self._prefill_embedded is not None:
                    await self._prefill_embedded.stop()
                    self._prefill_embedded = None
                metadata = {"model": self.card.name, "flippable": True}
                if self.transfer_server is not None:
                    metadata["kv_transfer_port"] = self.transfer_server.port
                ep = (
                    self.runtime.namespace(self.namespace)
                    .component(self.decode_component)
                    .endpoint(self.decode_endpoint)
                )
                self.registration = await ep.register(
                    self.advertise_host,
                    self.ingress.port,
                    metadata=metadata,
                    instance_id=self.instance_id,
                )
                self.role = "decode"
                self.draining = False
            self.flips += 1
            logger.info(
                "worker %s flipped to %s (flip #%d)",
                self.instance_id, self.role, self.flips,
            )
            telemetry.events.record(
                "role_flip", source=self.instance_id,
                dst=self.role,
                src="decode" if self.role == "prefill" else "prefill",
                flips=self.flips,
            )
            return True

    async def _flip_handler(self, ctx, request):
        """`flip` ingress op (the planner's FleetFlipper): validate,
        acknowledge immediately, flip in the background."""
        role = (request or {}).get("role") if isinstance(request, dict) else None
        if role not in ("decode", "prefill"):
            raise ValueError(f"flip needs role=decode|prefill, got {role!r}")
        if role == "prefill" and self.runner is None and self.mock is None:
            raise ValueError(
                f"engine kind {self.engine_kind!r} cannot serve the "
                "prefill role"
            )
        budget = None
        if request.get("budget_s") is not None:
            budget = float(request["budget_s"])
        task = asyncio.get_running_loop().create_task(
            self.flip_role(role, budget)
        )
        task.add_done_callback(
            lambda t: t.cancelled() or t.exception()  # observe, never raise
        )
        yield {
            "flipping": True,
            "to": role,
            "from": self.role,
            "inflight": self.ingress.num_inflight,
        }

    # -- worker handover (docs/operations.md "Rolling upgrades & worker
    # handover"): live KV migration to a successor, then exit 0 ----------

    def _handover_capable(self) -> bool:
        from dynamo_tpu.engine.async_engine import SpmdEngineRunner as _Spmd

        if self.mock is not None:
            return True
        return self.runner is not None and not isinstance(
            self.runner, _Spmd
        )

    async def handover(
        self,
        successor_id: Optional[str] = None,
        budget_s: Optional[float] = None,
    ) -> bool:
        """Retire this worker with its KV pages kept warm fleet-wide:

        1. **drain** — stop admissions (deregister; routers retry
           survivors), exactly the PR-8 drain machinery;
        2. **extract** — topo-order the device-registered block set and
           pull each batch to host in the canonical quantized wire
           format (engine.export_blocks_by_hash);
        3. **offer/transfer** — the successor reserves pages and arms a
           transfer waiter (handover_offer), then the bytes ride the
           normal `KvTransferClient.send` page write — device/shm/bulk/
           inline, checksummed end to end;
        4. **adopt** (successor side) — landed pages get registered,
           'stored' events publish, KV-aware routers score the successor
           immediately; this worker announces the bulk ownership move on
           its KV-event subject (`handed_over`);
        5. **finish** — in-flight streams get the remaining budget, then
           `drained` fires and the host process exits 0. Streams still
           open at that point continue on survivors via the PR-10 replay
           path — their prompt blocks are already warm on the successor,
           so the replayed prefill is a prefix hit, not a recompute.

        ANY failure mid-phase degrades to the plain drain+replay path:
        pages freed on both sides, zero hung streams. Returns True only
        when the migration completed."""
        if self.draining:
            await self.drained.wait()
            return False
        loop = asyncio.get_running_loop()
        self.handing_over = True
        self.draining = True
        self._handover_phase = "drain"
        logger.info(
            "worker %s handing over (%d in flight)",
            self.instance_id, self.ingress.num_inflight,
        )
        telemetry.events.record(
            "handover", source=self.instance_id, phase="start",
            successor=successor_id, inflight=self.ingress.num_inflight,
        )
        # ship immediately: the retiring process exits at the end of
        # this method — its timeline entries must not die with it
        from dynamo_tpu.telemetry import traceplane

        await traceplane.ship_once(self.runtime.fabric, self.instance_id)
        await self._deregister()
        ok = False
        try:
            ok = await self._handover_migrate(successor_id)
        except Exception:
            logger.exception(
                "handover migration failed; degrading to drain+replay"
            )
        if ok:
            self.handovers += 1
            logger.info("worker %s handover complete", self.instance_id)
            telemetry.events.record(
                "handover", source=self.instance_id, phase="complete",
                bytes=self.handover_bytes, blocks=self.handover_blocks,
            )
        else:
            self.handover_fallbacks += 1
            logger.warning(
                "worker %s handover fell back to plain drain (streams "
                "continue on survivors by replay-with-recompute)",
                self.instance_id,
            )
            telemetry.events.record(
                "handover", severity="warning", source=self.instance_id,
                phase="fallback",
            )
        self._handover_phase = "finish"
        budget = self.drain_budget_s if budget_s is None else budget_s
        deadline = loop.time() + max(budget, 0.0)
        while self._busy() and loop.time() < deadline:
            await asyncio.sleep(0.05)
        if self._busy():
            logger.info(
                "handover: %d stream(s) still in flight at exit; they "
                "continue on survivors via stream replay",
                self.ingress.num_inflight,
            )
        self._handover_phase = None
        self.handing_over = False
        self.drained.set()
        # flush the complete/fallback event (and any final spans)
        # before the host process exits
        await traceplane.ship_once(self.runtime.fabric, self.instance_id)
        return ok

    async def _pick_successor(self, successor_id: Optional[str]):
        """A live peer of this worker's CURRENT role to adopt the pages:
        the named instance when given, else every candidate sorted (the
        caller tries them in order). Returns a list of Instance."""
        from dynamo_tpu.runtime.component import InstanceSource

        if self.role == "decode":
            comp, ep = self.decode_component, self.decode_endpoint
        elif "prefill" in self.component:
            comp, ep = self.component, self.endpoint_name
        else:
            comp, ep = "prefill", "prefill"
        src = InstanceSource(self.runtime.fabric, self.namespace, comp, ep)
        await src.start()
        try:
            deadline = asyncio.get_running_loop().time() + 2.0
            while asyncio.get_running_loop().time() < deadline:
                peers = [
                    i
                    for i in src.list()
                    if i.instance_id != self.instance_id
                    and (
                        successor_id is None
                        or i.instance_id == successor_id
                    )
                ]
                if peers:
                    return peers
                await asyncio.sleep(0.05)
            return []
        finally:
            await src.stop()

    async def _handover_migrate(self, successor_id: Optional[str]) -> bool:
        from dynamo_tpu import handover as ho
        from dynamo_tpu.testing import faults

        if not self._handover_capable():
            return False
        runner, mock = self.runner, self.mock
        self._handover_phase = "extract"
        await faults.fire("handover.extract")
        if runner is not None:
            metas = await runner.submit(lambda eng: eng.handover_metas())
        else:
            metas = ho.topo_order_metas(
                list(mock.allocator._page_meta.values())
            )
        peers = await self._pick_successor(successor_id)
        if not peers:
            logger.warning("handover: no successor instance available")
            return False
        succ, last_err = None, None
        for cand in peers[:3]:
            try:
                done = await self._handover_to(cand, metas, runner, mock)
            except Exception as e:
                last_err = e
                logger.warning(
                    "handover to %s failed: %s", cand.instance_id, e
                )
                continue
            if done:
                succ = cand
                break
        if succ is None:
            if last_err is not None:
                logger.warning("handover: every candidate failed")
            return False
        # bulk ownership move: indexers reassign this worker's block
        # entries to the successor NOW instead of waiting for lease
        # expiry + stored-event propagation (kv_router/indexer.py
        # `handed_over`). Rides the SAME stamped path as store/remove
        # events — with any still-buffered events flushed ahead of it in
        # the batch — so the move keeps its place in the sequence stream
        # and this worker's advertised digest empties with it.
        pending = self._kv_event_buffer[: len(self._kv_event_buffer)]
        del self._kv_event_buffer[: len(pending)]
        held, self._kv_pending = self._kv_pending, []
        await self._publish_kv_events(
            held
            + [self._kv_event_wire(e) for e in pending]
            + [{
                "kind": "handed_over",
                "block_hashes": [],
                "successor": succ.instance_id,
            }]
        )
        return True

    async def _handover_to(self, succ, metas, runner, mock) -> bool:
        """Ship every batch to ONE candidate successor. True when all
        batches were offered (an empty want-list counts — the successor
        already holds those blocks)."""
        from dynamo_tpu import handover as ho
        from dynamo_tpu.testing import faults

        if not metas:
            # nothing registered to migrate — the handover is trivially
            # complete (the drain tail still runs)
            return True
        client = None
        try:
            for batch in ho.batches(metas):
                self._handover_phase = "offer"
                await faults.fire("handover.offer")
                if mock is not None:
                    reply = await ho.call_ingress(
                        succ.host, succ.port, "handover_offer",
                        {
                            "metas": ho.metas_to_wire(batch),
                            "source": self.instance_id,
                            "payload": False,
                        },
                    )
                    self.handover_blocks += int(reply.get("adopted") or 0)
                    continue
                exported = await runner.submit(
                    lambda eng, b=batch: eng.export_blocks_by_hash(
                        [h for h, _, _ in b]
                    )
                )
                if exported is None:
                    continue  # evicted since the listing — batch gone
                emetas, k, v = exported
                reply = await ho.call_ingress(
                    succ.host, succ.port, "handover_offer",
                    {
                        "metas": ho.metas_to_wire(emetas),
                        "source": self.instance_id,
                        "payload": True,
                    },
                )
                page_ids = reply.get("page_ids") or []
                if not page_ids:
                    continue  # successor already holds the whole batch
                want = list(reply.get("want_idx") or ())
                self._handover_phase = "transfer"
                await faults.fire("handover.transfer")
                if client is None:
                    from dynamo_tpu.disagg.transfer import KvTransferClient

                    client = KvTransferClient()
                if len(want) != k.shape[2]:
                    import numpy as np

                    k = np.ascontiguousarray(k[:, :, want])
                    v = np.ascontiguousarray(v[:, :, want])
                ok = await asyncio.wait_for(
                    client.send(
                        reply["host"], int(reply["port"]), reply["rid"],
                        page_ids, k, v, 0,
                    ),
                    timeout=ho.ADOPT_TIMEOUT_S,
                )
                if not ok:
                    return False
                self.handover_bytes += int(k.nbytes + v.nbytes)
                self.handover_blocks += len(page_ids)
                if ho.MAX_BYTES and self.handover_bytes >= ho.MAX_BYTES:
                    logger.info(
                        "handover: byte budget reached (%d); leaving the "
                        "colder tail behind", self.handover_bytes,
                    )
                    break
            return True
        finally:
            if client is not None:
                client.close()

    async def _handover_handler(self, ctx, request):
        """`handover` ingress op (POST /v1/admin/handover, planner
        FleetHandover): validate, acknowledge immediately, migrate in
        the background — mirrors the drain/flip handler shape."""
        req = request if isinstance(request, dict) else {}
        if not self._handover_capable():
            raise ValueError(
                f"engine kind {self.engine_kind!r} has no KV pool to hand "
                "over; use drain"
            )
        if self.draining:
            # refuse instead of ack: an ack here would make a planner
            # (whose instance watch hasn't seen the deregistration yet)
            # count the SAME victim as a second retirement and skip its
            # kill fallback — the caller must pick another worker
            raise ValueError(
                f"worker {self.instance_id} is already "
                f"{'handing over' if self.handing_over else 'draining'}"
            )
        successor = req.get("successor") or None
        budget = (
            float(req["budget_s"]) if req.get("budget_s") is not None else None
        )
        task = asyncio.get_running_loop().create_task(
            self.handover(successor, budget)
        )
        task.add_done_callback(
            lambda t: t.cancelled() or t.exception()  # observe, never raise
        )
        yield {
            "handing_over": True,
            "inflight": self.ingress.num_inflight,
            "successor": successor,
            "budget_s": self.drain_budget_s if budget is None else budget,
        }

    async def _handover_offer_handler(self, ctx, request):
        """Successor side: reserve pages for the offered block batch and
        arm a transfer waiter; the source then writes the bytes through
        the normal transfer plane addressed at those pages, and the
        watchdog task registers them on landing (or frees them on
        timeout/failure — a dead source can never leak our pages)."""
        import time as _time
        import uuid as _uuid

        from dynamo_tpu import handover as ho
        from dynamo_tpu.telemetry import phases
        from dynamo_tpu.testing import faults

        await faults.fire("handover.adopt")
        if self.draining:
            from dynamo_tpu.runtime.ingress import RetryableHandlerError

            raise RetryableHandlerError(
                f"worker {self.instance_id} is draining; cannot adopt"
            )
        req = request if isinstance(request, dict) else {}
        metas = ho.metas_from_wire(req.get("metas") or [])
        if not metas:
            yield {"adopted": 0, "page_ids": []}
            return
        if self.mock is not None:
            # mock fleets: metadata-only adopt — the mock's KV "content"
            # IS the hash chain, so registering the metas gives replayed
            # streams the same warm-prefix admission a real pool would
            alloc = self.mock.allocator
            n = 0
            for h, p, toks in metas:
                if alloc.match_length([h]):
                    continue
                pages = alloc.allocate(1)
                if pages is None:
                    break
                alloc.register_promoted(pages[0], h, p, tuple(toks))
                alloc.free(pages)
                n += 1
            self.handovers_adopted += n
            yield {"adopted": n, "page_ids": [], "payload": False}
            return
        if (
            self.runner is None
            or self.transfer_server is None
            or not self._handover_capable()
        ):
            raise ValueError(
                f"worker {self.instance_id} cannot adopt a handover"
            )
        if req.get("payload") is False:
            raise ValueError("metadata-only offer refused: this worker "
                             "holds real KV bytes")
        runner = self.runner
        prep = await runner.submit(
            lambda eng: eng.prepare_handover_adopt(metas)
        )
        if prep is None:
            yield {"adopted": 0, "page_ids": []}
            return
        pages, kept, want_idx = prep
        rid = f"ho-{self.instance_id}-{_uuid.uuid4().hex[:8]}"
        waiter = self.transfer_server.expect(rid)
        t0 = _time.perf_counter()

        async def _watch():
            try:
                await asyncio.wait_for(waiter, ho.ADOPT_TIMEOUT_S)
            except BaseException:
                self.transfer_server.forget(rid)
                await runner.submit(
                    lambda eng: eng.abort_handover_adopt(pages)
                )
                logger.warning(
                    "handover adopt %s never landed; %d reserved pages "
                    "freed", rid, len(pages),
                )
                return
            n = await runner.submit(
                lambda eng: eng.commit_handover_adopt(pages, kept)
            )
            self.handovers_adopted += n
            phases.observe(
                "handover_adopt_ms", (_time.perf_counter() - t0) * 1000.0
            )
            logger.info(
                "adopted %d handover block(s) from %s",
                n, req.get("source") or "?",
            )

        task = asyncio.get_running_loop().create_task(_watch())
        self._handover_tasks.add(task)
        task.add_done_callback(self._handover_tasks.discard)
        yield {
            "rid": rid,
            "page_ids": list(pages),
            "want_idx": list(want_idx),
            "host": self.advertise_host,
            "port": self.transfer_server.port,
        }

    async def _hot_prefix_hashes(self, max_blocks: int) -> list:
        """The deepest resident prefix chain, root-first, capped at
        `max_blocks` — the donor side of `migrate_prefix {auto: true}`.
        Depth is the proxy for heat: the longest registered chain is the
        prefix most requests have been extending."""

        def pick(metas):
            parent = {h: p for h, p, _t in metas}
            if not parent:
                return []
            depth: dict = {}

            def d(h):
                seen = []
                x = h
                while x is not None and x not in depth and x in parent:
                    seen.append(x)
                    x = parent.get(x)
                    if len(seen) > len(parent) + 1:
                        break  # corrupt-meta cycle guard
                base = depth.get(x, 0) if x is not None else 0
                for i, y in enumerate(reversed(seen)):
                    depth[y] = base + i + 1
                return depth.get(h, 0)

            tip = max(parent, key=lambda h: (d(h), h))
            chain = []
            x = tip
            while x is not None and x in parent:
                chain.append(x)
                x = parent.get(x)
            chain.reverse()
            return [int(h) for h in chain[:max_blocks]]

        if self.mock is not None:
            return pick(list(self.mock.allocator._page_meta.values()))
        if self.runner is None:
            return []
        return await self.runner.submit(
            lambda eng: pick(list(eng.allocator._page_meta.values()))
        )

    async def _migrate_prefix_handler(self, ctx, request):
        """`migrate_prefix` ingress op — the KV economy's unit of work
        (docs/operations.md "The KV economy"). A KV-economy router picked
        worker D for a request whose prefix THIS worker holds deeper;
        when the CostModel says the bytes are cheaper than D's cold
        prefill, the router asks us (the source) to PUSH just that chain
        to D through the unchanged handover offer/transfer plane:

        - mock fleets: metadata-only offer (the mock's KV "content" IS
          the hash chain) — D registers the metas and the request
          admits warm;
        - jax engines: export_blocks_by_hash in the canonical quantized
          wire format, offer, then the normal checksummed
          KvTransferClient page write.

        Blocks are COPIED, not moved — both workers then hold (and
        advertise) the prefix, which is exactly what a hot prefix
        wants. ANY failure degrades to D cold-prefilling: our export
        refs free in its finally, D's adopt watchdog frees reserved
        pages on transfer timeout, and the reply says migrated=False so
        the router stops waiting. Nothing leaks, nothing hangs."""
        import numpy as np

        from dynamo_tpu import handover as ho
        from dynamo_tpu.testing import faults

        req = request if isinstance(request, dict) else {}
        hashes = [int(h) for h in (req.get("hashes") or [])]
        dest = req.get("dest") or {}
        if not dest.get("host") or not dest.get("port"):
            yield {"migrated": False, "error": "bad request"}
            return
        if self.draining or not self._handover_capable():
            yield {"migrated": False, "error": "source unavailable"}
            return
        if not hashes and req.get("auto"):
            # planner pre-warm / victim-drain mode: no router in the
            # loop to name a chain, so WE pick our deepest resident
            # prefix (the hottest thing a cold newcomer can inherit)
            hashes = await self._hot_prefix_hashes(
                int(req.get("max_blocks") or 32)
            )
        if not hashes:
            yield {"migrated": False, "error": "nothing to migrate"}
            return
        try:
            await faults.fire("migrate.extract")
            if self.mock is not None:
                alloc = self.mock.allocator
                meta_by_hash = {
                    h: (h, p, toks)
                    for h, p, toks in alloc._page_meta.values()
                }
                metas = []
                for h in hashes:
                    meta = meta_by_hash.get(h)
                    if meta is None:
                        break  # evicted since the router's index view
                    metas.append(meta)
                if not metas:
                    yield {"migrated": False, "error": "prefix evicted"}
                    return
                await faults.fire("migrate.offer")
                await faults.fire("migrate.transfer")
                reply = await ho.call_ingress(
                    dest["host"], int(dest["port"]), "handover_offer",
                    {
                        "metas": ho.metas_to_wire(metas),
                        "source": self.instance_id,
                        "payload": False,
                    },
                )
                blocks = int(reply.get("adopted") or 0)
                self.migrations += 1
                self.migration_blocks += blocks
                telemetry.events.record(
                    "kv_migration", source=self.instance_id,
                    dest=dest.get("instance_id"), blocks=blocks,
                    coalesce_s=5.0,
                )
                yield {"migrated": True, "blocks": blocks, "bytes": 0}
                return
            runner = self.runner
            exported = await runner.submit(
                lambda eng: eng.export_blocks_by_hash(hashes)
            )
            if exported is None:
                yield {"migrated": False, "error": "prefix evicted"}
                return
            emetas, k, v = exported
            await faults.fire("migrate.offer")
            reply = await ho.call_ingress(
                dest["host"], int(dest["port"]), "handover_offer",
                {
                    "metas": ho.metas_to_wire(emetas),
                    "source": self.instance_id,
                    "payload": True,
                },
            )
            page_ids = reply.get("page_ids") or []
            if not page_ids:
                # destination already holds the whole chain — the
                # router's view lagged; count it migrated (the request
                # admits warm either way)
                self.migrations += 1
                yield {"migrated": True, "blocks": 0, "bytes": 0}
                return
            want = list(reply.get("want_idx") or ())
            await faults.fire("migrate.transfer")
            if len(want) != k.shape[2]:
                k = np.ascontiguousarray(k[:, :, want])
                v = np.ascontiguousarray(v[:, :, want])
            from dynamo_tpu.disagg.transfer import KvTransferClient

            client = KvTransferClient()
            try:
                ok = await asyncio.wait_for(
                    client.send(
                        reply["host"], int(reply["port"]), reply["rid"],
                        page_ids, k, v, 0,
                    ),
                    timeout=ho.ADOPT_TIMEOUT_S,
                )
            finally:
                client.close()
            if not ok:
                raise RuntimeError("transfer send failed")
            nbytes = int(k.nbytes + v.nbytes)
            self.migrations += 1
            self.migration_bytes += nbytes
            self.migration_blocks += len(page_ids)
            telemetry.events.record(
                "kv_migration", source=self.instance_id,
                dest=dest.get("instance_id"), blocks=len(page_ids),
                bytes=nbytes, coalesce_s=5.0,
            )
            yield {
                "migrated": True, "blocks": len(page_ids), "bytes": nbytes,
            }
        except Exception as e:
            self.migration_fallbacks += 1
            telemetry.events.record(
                "kv_migration", severity="warning",
                source=self.instance_id, dest=dest.get("instance_id"),
                phase="fallback",
            )
            logger.warning(
                "prefix migration to %s failed (request cold-prefills): "
                "%s", dest.get("instance_id") or "?", e,
            )
            yield {"migrated": False, "error": str(e)}

    async def stop(self, drain_timeout: float = 30.0) -> None:
        """Graceful shutdown (reference: the vLLM drain handlers,
        examples worker.py:156-170): deregister FIRST so routers stop
        sending here, let in-flight requests finish up to drain_timeout,
        then tear the planes down."""
        await self._deregister()
        if drain_timeout > 0 and not self.drained.is_set():
            deadline = asyncio.get_running_loop().time() + drain_timeout
            while self._busy() and asyncio.get_running_loop().time() < deadline:
                await asyncio.sleep(0.05)
            if self._busy():
                logger.warning(
                    "drain timeout: %d calls still in flight; closing",
                    self.ingress.num_inflight,
                )
        for t in self._tasks:
            t.cancel()
        for t in list(self._handover_tasks):
            # cancelling an adopt watchdog frees its page reservation
            # (the _watch except-path) before the runner goes away
            t.cancel()
        if self._handover_tasks:
            await asyncio.gather(
                *self._handover_tasks, return_exceptions=True
            )
        if self._prefill_embedded is not None:
            await self._prefill_embedded.stop()
            self._prefill_embedded = None
        await self.ingress.stop()
        if self.transfer_server is not None:
            await self.transfer_server.stop()
        if self.disagg_router is not None:
            await self.disagg_router.stop()
        if self.kv_directory is not None:
            await self.kv_directory.stop()
        if self._peer_source is not None:
            await self._peer_source.stop()
        if self._fetch_client is not None:
            self._fetch_client.close()
        if self.runner:
            self.runner.stop()

    # -- handlers ----------------------------------------------------------

    async def _generate(self, ctx, request: dict):
        if self.draining or self.role != "decode":
            # the router retries a survivor; this instance is already
            # deregistered (draining, or flipped to the prefill role —
            # a stale router list may still push here briefly) and only
            # finishing what it has
            from dynamo_tpu.runtime.ingress import RetryableHandlerError

            raise RetryableHandlerError(
                f"worker {self.instance_id} is "
                f"{'draining' if self.draining else 'serving prefill'}"
            )
        pre = PreprocessedRequest.from_dict(request)
        if self.kv_directory is not None and pre.mm_embeds is None:
            try:
                await self._maybe_remote_onboard(pre)
            except Exception:
                logger.exception("remote onboard failed; serving cold")
        if self.prefill_queue is not None and await self._want_remote(pre):
            handled = False
            async for event in self._generate_disagg(ctx, pre):
                handled = True
                yield event
            if handled:
                return
            # transfer fell through — run the normal local path below
        gen = (
            self.external or self.echo or self.mock or self.runner
        ).generate(ctx, pre)
        if pre.deadline and self.runner is None:
            # engines without the runner's built-in deadline enforcement
            # (external subprocess / echo / mock): the guard cancels the
            # context on expiry — the cancel frame reaches subprocess
            # children — and error-finishes the stream
            from dynamo_tpu.runtime.overload import deadline_guard

            gen = deadline_guard(ctx, pre.deadline, gen)
        async for event in gen:
            yield event

    async def _embed(self, ctx, request: dict):
        """Embedding RPC: {"prompts": [[token ids], ...]} -> one reply with
        the vectors (float lists; the frontend handles encoding_format)."""
        prompts = request["prompts"]
        if self.runner is not None:
            vecs = await self.runner.embed(prompts)
        elif self.external is not None and hasattr(self.external, "embed"):
            vecs = await self.external.embed(prompts)
        else:
            from dynamo_tpu.engine.async_engine import fake_embedding

            import numpy as np

            vecs = np.stack([fake_embedding(p) for p in prompts])
        yield {
            "embeddings": [[float(x) for x in v] for v in vecs],
            "prompt_tokens": sum(len(p) for p in prompts),
        }

    # -- G4 remote tier: cross-worker prefix onboarding --------------------

    def _peer_transfer_addr(self, worker_id: str):
        inst = self._peer_source.instances.get(worker_id)
        if inst is None:
            return None
        port = inst.metadata.get("kv_transfer_port")
        if not port:
            return None
        return inst.host, int(port)

    async def _maybe_remote_onboard(self, pre: PreprocessedRequest) -> None:
        """Before admission: if a live peer holds more of this prompt's
        block chain than we do, pull those blocks over the transfer plane
        and adopt them — the reference's onboard_blocks driven by
        directory knowledge (block_manager.rs:169). Failures only cost the
        fetch: the request prefills the cold blocks as usual."""
        runner = self.runner
        directory = self.kv_directory
        if not directory.has_entries():
            return  # nothing claimable anywhere — skip the engine round trip
        # Hashing needs only static config (page size / salt), so it runs
        # on the event loop, and the directory is consulted BEFORE the
        # engine runner: requests with no claimable chain anywhere must not
        # serialize with engine step dispatch just to learn that.
        from dynamo_tpu.tokens import hash_token_blocks

        cfg = self.engine_config
        hashes = hash_token_blocks(
            pre.token_ids, block_size=cfg.page_size, salt=cfg.model
        )
        if not directory.has_chain(hashes, self.kv_remote_min_blocks):
            return
        n_local = await runner.submit(
            lambda eng: eng.allocator.resident_match_length(hashes)
        )
        if n_local >= len(hashes):
            return
        best = directory.best_chain(hashes, n_local)
        if best is None or best[1] < self.kv_remote_min_blocks:
            return
        worker_id, depth = best
        want = hashes[n_local : n_local + depth]
        addr = self._peer_transfer_addr(worker_id)
        if addr is None:
            # Peer is gone, or live but serving no transfer port (not
            # --kv-remote): drop its claims so we don't re-select it for
            # this prefix forever, and prune dead workers wholesale.
            directory.drop(worker_id, want)
            directory.retain_workers(list(self._peer_source.instances))
            return
        try:
            served = await asyncio.wait_for(
                self._fetch_client.fetch(*addr, want),
                self.kv_remote_timeout_s,
            )
        except Exception:
            logger.warning("KV fetch from %s failed", worker_id, exc_info=True)
            served = None
        if not served:
            directory.drop(worker_id, want)  # self-heal the stale claim
            return
        metas, k, v = served
        n = await runner.submit(lambda eng: eng.adopt_blocks(metas, k, v))
        self.remote_onboards += n
        if n:
            logger.info(
                "onboarded %d blocks for %s from peer %s",
                n, pre.request_id, worker_id,
            )

    # -- disaggregated path ------------------------------------------------

    async def _want_remote(self, pre: PreprocessedRequest) -> bool:
        # Multimodal prompts prefill locally: the remote-prefill wire
        # carries token ids only, and placeholder ids don't identify the
        # image embeddings.
        if pre.mm_embeds is not None:
            return False
        # Logprob requests prefill locally: the transfer result carries the
        # first sampled token but not its logprob, and OpenAI logprob
        # arrays must align with the emitted tokens from the first one.
        if pre.logprobs >= 0:
            return False
        # logit_bias / min_tokens requests prefill locally: the remote
        # wire's sampling dict doesn't carry them, so the prefill worker
        # would sample the FIRST token unbiased (min_tokens could even
        # end the request on an un-banned eos).
        if getattr(pre, "logit_bias", None) or getattr(pre, "min_tokens", 0):
            return False
        # Cheap local short-circuit: uncached length can't exceed prompt
        # length, so short prompts never qualify — skip the engine-thread
        # and fabric round-trips entirely.
        if (
            len(pre.token_ids)
            <= self.disagg_router.config.max_local_prefill_length
        ):
            return False
        runner = self.runner

        def _hit(eng):
            from dynamo_tpu.tokens import hash_token_blocks

            hashes = hash_token_blocks(
                pre.token_ids, block_size=eng.config.page_size,
                salt=eng.config.model,
            )
            return eng.allocator.match_length(hashes) * eng.config.page_size

        prefix_hit = await runner.submit(_hit)
        depth = await self.prefill_queue.depth()
        return self.disagg_router.prefill_remote(
            len(pre.token_ids), prefix_hit, depth
        )

    async def _generate_disagg(self, ctx, pre: PreprocessedRequest):
        """Remote prefill: reserve pages, enqueue, wait for the KV landing,
        then decode locally. Yields nothing (falls back) on reservation
        failure or transfer timeout."""
        import time as _time

        from dynamo_tpu import telemetry
        from dynamo_tpu.disagg.protocol import RemotePrefillRequest
        from dynamo_tpu.disagg.transfer import RemotePrefillError
        from dynamo_tpu.engine.async_engine import _sampling_from
        from dynamo_tpu.telemetry import phases

        runner = self.runner
        rid = pre.request_id
        sampling = _sampling_from(pre)
        with telemetry.span(
            "disagg.remote_prefill", service="disagg",
            attrs={"request_id": rid, "isl_tokens": len(pre.token_ids)},
        ) as dspan:
            req = await runner.submit(
                lambda eng: eng.allocate_for_remote_prefill(
                    rid, pre.token_ids, sampling
                )
            )
            if req is None:
                logger.info(
                    "disagg: no pages free for %s; local fallback", rid
                )
                dspan.end(status="cancelled")
                return
            dspan.add_event("pages_reserved", pages=len(req.pages))
            # From here until add_prefilled succeeds, any failure must give
            # the page reservation and the transfer waiter back.
            waiter = self.transfer_server.expect(rid)
            t_push = _time.perf_counter()
            try:
                await self.prefill_queue.push(
                    RemotePrefillRequest(
                        request_id=rid,
                        token_ids=list(pre.token_ids),
                        page_ids=list(req.pages),
                        transfer_host=self.advertise_host,
                        transfer_port=self.transfer_server.port,
                        sampling={
                            "temperature": pre.temperature, "top_p": pre.top_p,
                            "top_k": pre.top_k, "seed": pre.seed,
                        },
                        model=self.card.name,
                        trace=telemetry.wire_context() or {},
                        deadline=pre.deadline,
                    )
                )
                timeout = self.disagg_router.config.transfer_timeout_s
                result = await asyncio.wait_for(waiter, timeout)
            except RemotePrefillError as e:
                # the prefill fleet dead-lettered this request: error-
                # finish (a local fallback would just poison again)
                self.transfer_server.forget(rid)
                await runner.submit(lambda eng: eng.cancel_remote_prefill(req))
                logger.error(
                    "disagg: remote prefill for %s dead-lettered: %s", rid, e
                )
                dspan.end(status="error")
                yield {"token_ids": [], "finish_reason": "error"}
                return
            except Exception:
                self.transfer_server.forget(rid)
                await runner.submit(lambda eng: eng.cancel_remote_prefill(req))
                logger.warning(
                    "disagg: remote prefill for %s failed/timed out; "
                    "local fallback",
                    rid,
                )
                dspan.end(status="error")
                return
            transfer_ms = (_time.perf_counter() - t_push) * 1000.0
            phases.observe("disagg_transfer_ms", transfer_ms)
            dspan.add_event("kv_landed", transfer_ms=round(transfer_ms, 3))
            self.remote_prefills += 1
        from dynamo_tpu.engine.async_engine import output_to_dict

        out_q = runner.watch_request(rid)
        try:
            if pre.deadline and _time.time() > pre.deadline:
                # the deadline lapsed while the transfer was in flight:
                # never admit (the reservation frees, no decode flops) —
                # tracking it BEFORE admission would let the runner
                # expire-and-forget it, then add_prefilled would admit a
                # request nothing ever aborts
                await runner.submit(lambda eng: eng.cancel_remote_prefill(req))
                yield {"token_ids": [], "finish_reason": "error"}
                return
            try:
                outputs = await runner.submit(
                    lambda eng: eng.add_prefilled(req, result.first_token)
                )
            except Exception:
                await runner.submit(lambda eng: eng.cancel_remote_prefill(req))
                raise
            if pre.deadline:
                # decode-side deadline enforcement for the out-of-band
                # admission path, armed only once the request is ADMITTED
                # (an expiry now aborts a live request and frees pages)
                runner.track_deadline(rid, pre.deadline)
            for out in outputs:
                yield output_to_dict(out)
                if out.finish_reason is not None:
                    return
            async for item in runner.drain(ctx, rid, out_q):
                yield item
        finally:
            runner.unwatch_request(rid)

    async def _flush(self, ctx, request):
        n = 0
        if isinstance(self.runner, SpmdEngineRunner):
            # replicated clear: every host's allocator must stay identical
            n = await self.runner.clear_kv()
        elif self.runner is not None:
            # The engine thread is the only thread allowed to touch the
            # allocator — route through it.
            n = await self.runner.submit(
                lambda eng: eng.allocator.clear_cache()
            )
        elif self.mock is not None:
            n = self.mock.allocator.clear_cache()
        yield {"cleared_pages": n}

    # -- KV event sequencing + snapshot (docs/operations.md "KV index
    # consistency"): the worker side of the convergent index protocol ---

    @staticmethod
    def _kv_event_wire(e: KvEvent) -> dict:
        return {
            "kind": e.kind,
            "block_hashes": list(e.block_hashes),
            "parent_hash": e.parent_hash,
            "token_blocks": [list(t) for t in e.token_blocks],
        }

    def _stamp_kv_events(self, wire_events: list[dict]) -> None:
        """Stamp each outgoing event with the next per-worker sequence
        number and fold it into the rolling digest. Runs ONLY on the
        event-loop publish path, so seq/digest state is loop-confined
        and the advertised digest is exactly the set as-of the last
        stamped seq."""
        dg = self._kv_digest
        for ev in wire_events:
            self._kv_seq += 1
            ev["seq"] = self._kv_seq
            kind = ev.get("kind")
            if kind == "stored":
                parent = ev.get("parent_hash")
                for h in ev.get("block_hashes", ()):
                    dg.store(h, parent)
            elif kind == "removed":
                for h in ev.get("block_hashes", ()):
                    dg.remove(h)
            elif kind == "handed_over":
                # ownership moved wholesale to the successor: this
                # worker's advertised set empties, matching the index's
                # post-move view of it
                dg.clear()

    async def _publish_kv_events(self, wire_events: list[dict]) -> None:
        """Stamp (when sequencing) and publish one event batch. A failed
        publish DROPS the batch — the stamped seqs are burned, so the
        indexer sees a sequence gap and repairs by resync; re-sending
        later would reorder the stream, which is worse than honest
        loss."""
        if self.kv_sequencing:
            self._stamp_kv_events(wire_events)
        try:
            await self.runtime.fabric.publish(
                f"{KV_EVENT_SUBJECT}.{self.instance_id}",
                {"instance_id": self.instance_id, "count": len(wire_events)},
                msgpack.packb(wire_events, use_bin_type=True),
            )
        except Exception:
            logger.warning(
                "KV event publish failed; %d event(s) dropped (indexers "
                "detect the sequence gap and resync)", len(wire_events),
                exc_info=True,
            )

    async def _kv_snapshot_handler(self, ctx, request):
        """`kv.snapshot` ingress op: the full registered hash forest +
        the digest, as of the last PUBLISHED event — indexers use it for
        cold-start bootstrap and targeted resync (events with seq >
        this snapshot's seq apply cleanly on top)."""
        if not self.kv_sequencing:
            yield {"sequencing": False}
            return
        dg = self._kv_digest
        yield {
            "sequencing": True,
            "seq": self._kv_seq,
            "fold": dg.fold,
            "count": dg.count,
            "blocks": [[h, p] for h, p in dg.blocks.items()],
        }

    # -- publishers --------------------------------------------------------

    async def _publish_loop(self) -> None:
        """Ship buffered KV events + a load-metrics snapshot periodically
        (reference: KvEventPublisher publisher.rs:99 + WorkerMetricsPublisher
        :463; events ride the bus, scrape-free)."""
        fabric = self.runtime.fabric
        while True:
            await asyncio.sleep(self.metrics_interval)
            try:
                await self._publish_once(fabric)
            except asyncio.CancelledError:
                raise
            except Exception:
                # a fabric outage (or any publish failure) must not kill
                # the loop: frames resume when the fabric does, and any
                # KV events lost in between surface as sequence gaps the
                # indexer repairs by resync
                logger.warning("publish tick failed", exc_info=True)

    def _broker_reachable(self, fabric) -> bool:
        # LocalFabric (and anything without connection state) is always
        # reachable; RemoteFabric reports its live connection
        return getattr(fabric, "connected", True) is not False

    async def _publish_once(self, fabric) -> None:
        # Drain WITHOUT rebinding: the engine thread appends through a
        # late-binding callback, but any captured reference must stay
        # valid — rebinding here once silently severed the event plane
        # (appends landed in the dead list forever after).
        events = self._kv_event_buffer[: len(self._kv_event_buffer)]
        del self._kv_event_buffer[: len(events)]
        wire = self._kv_pending + [self._kv_event_wire(e) for e in events]
        self._kv_pending = []
        if wire:
            if not self._broker_reachable(fabric):
                # degraded mode: hold UNSTAMPED events for the broker's
                # return (a short outage loses nothing); past the cap,
                # stamp-and-drop the oldest — their burned seqs are the
                # detectable gap that triggers resync on reconnect
                overflow = wire[: max(0, len(wire) - self.kv_pending_cap)]
                self._kv_pending = wire[len(overflow):]
                if overflow:
                    if self.kv_sequencing:
                        self._stamp_kv_events(overflow)
                    self.kv_events_dropped += len(overflow)
                    logger.warning(
                        "degraded: KV event buffer overflowed; %d "
                        "event(s) dropped with seqs burned (indexers "
                        "resync on reconnect)", len(overflow),
                    )
            else:
                await self._publish_kv_events(wire)
        tiered = self._tier_event_buffer[: len(self._tier_event_buffer)]
        del self._tier_event_buffer[: len(tiered)]
        if tiered and not self._broker_reachable(fabric):
            # lower-tier hints are advisory (peers re-learn them from
            # later events): bound the outage backlog instead of growing
            tiered = tiered[-self.kv_pending_cap:]
            self._tier_event_buffer[:0] = tiered
            tiered = []
        if tiered:
            # the `tier` field is additive: BlockDirectory ignores it
            # (servable is servable), the router's TierMap prices it
            payload = msgpack.packb(
                [
                    {
                        "kind": "stored",
                        "block_hashes": [h],
                        "parent_hash": p,
                        "tier": t,
                    }
                    for h, p, t in tiered
                ],
                use_bin_type=True,
            )
            await fabric.publish(
                f"{KVBM_TIER_SUBJECT}.{self.instance_id}",
                {"instance_id": self.instance_id, "count": len(tiered)},
                payload,
            )
        if self._tier_policy is not None and self.runner is not None:
            # watermark-driven demotion rides the publish cadence: one
            # bounded engine-thread tick per interval, and the demoted
            # blocks' tier hints ship on the NEXT tick's publish above
            policy = self._tier_policy
            try:
                n = await self.runner.submit(lambda eng: policy.run_once())
            except Exception:
                n = 0
                logger.warning("tier policy tick failed", exc_info=True)
            if n:
                telemetry.events.record(
                    "kv_demotion", source=self.instance_id, blocks=n,
                    coalesce_s=5.0,
                )
        m = None
        if self.runner is not None:
            m = self.runner.metrics.to_dict()
        elif self.external is not None and hasattr(
            self.external, "metrics_dict"
        ):
            m = dict(self.external.metrics_dict())
        elif self.mock is not None:
            alloc = self.mock.allocator
            m = {
                "num_waiting": self.mock.num_waiting,
                "num_running": self.mock.num_running,
                "kv_active_pages": alloc.num_active,
                "kv_total_pages": alloc.num_pages - 1,
                "kv_usage": alloc.usage(),
                "prefix_hit_rate": alloc.stats.hit_rate,
                "requests_received": self.mock.requests_received,
                "generated_tokens": self.mock.generated_tokens,
                "preemptions": self.mock.preemptions,
            }
            try:
                # mock fleets ride the real SLO plane (fleet sim)
                m["slo"] = self.mock.slo.to_wire()
            except Exception:
                logger.warning(
                    "mock SLO frame failed", exc_info=True
                )
        if m is not None:
            # fleet telemetry plane (docs/observability.md "Fleet
            # view & SLO accounting"): role for the per-role fleet
            # rollup, SLO sketches + per-kind compile counters when
            # the engine carries them. Defensive: a telemetry
            # serialization bug must not sever the load-metrics
            # plane routers/planner depend on.
            # a flipped worker reports (and routes its frames) under
            # its LIVE role so /v1/fleet and the planner see the
            # pool move the moment the flip lands
            if self.role == "prefill":
                # a worker CONFIGURED as prefill keeps its own
                # component subject; only a flipped decode worker
                # moves its frames into the default prefill space
                pub_component = (
                    self.component
                    if "prefill" in self.component
                    else "prefill"
                )
            else:
                pub_component = self.decode_component
            m["component"] = pub_component
            m["role"] = self.role
            m["flips_total"] = self.flips
            # drain visibility: /v1/fleet shows state=draining while
            # the worker winds down (doctor's draining-worker rule
            # keys off this instead of tripping dead/stalled rules);
            # state=handover while a live KV migration runs (doctor's
            # handover-stuck rule watches its age + phase)
            m["state"] = (
                "handover"
                if self.handing_over
                else "draining" if self.draining else "serving"
            )
            if self._handover_phase is not None:
                m["handover_phase"] = self._handover_phase
            m["handovers_total"] = self.handovers
            m["handover_fallbacks_total"] = self.handover_fallbacks
            m["handover_bytes_total"] = self.handover_bytes
            m["handover_blocks_total"] = self.handover_blocks
            m["handovers_adopted_total"] = self.handovers_adopted
            # KV economy: source-side migration counters + tier residency
            # (the Grafana "KV economy" row and the doctor's
            # migration-storm / tier-pressure rules read these)
            m["kv_migrations_total"] = self.migrations
            m["kv_migration_fallbacks_total"] = self.migration_fallbacks
            m["kv_migration_bytes_total"] = self.migration_bytes
            m["kv_migration_blocks_total"] = self.migration_blocks
            alloc = getattr(
                getattr(self.runner, "engine", None), "allocator", None
            )
            if alloc is None and self.mock is not None:
                alloc = self.mock.allocator
            if alloc is not None and hasattr(alloc, "tier_hits"):
                occ = alloc.tier_occupancy()
                m["kvbm_host_blocks"] = occ["host"]
                m["kvbm_disk_blocks"] = occ["disk"]
                m["kvbm_demotions_total"] = alloc.stats.offloaded_blocks
                m["kvbm_promotions_total"] = alloc.stats.onboarded_blocks
                m["kvbm_host_hits_total"] = alloc.tier_hits["host"]
                m["kvbm_disk_hits_total"] = alloc.tier_hits["disk"]
            eng = getattr(self.runner, "engine", None)
            if eng is not None and getattr(eng, "slo", None) is not None:
                try:
                    m["slo"] = eng.slo.to_wire()
                    m["compiles_by_kind"] = dict(eng.compiles_by_kind)
                except Exception:
                    logger.warning(
                        "fleet telemetry frame failed", exc_info=True
                    )
            # debug plane (docs/observability.md "Debugging a slow
            # or stuck worker"): the flight-recorder window + the
            # per-kind program cost rollup ride the frame so the
            # metrics service can serve GET /v1/debug/{flight,
            # programs} for the whole fleet; same defensive wrap.
            if eng is not None:
                try:
                    fl = getattr(eng, "flight", None)
                    if fl is not None:
                        m["flight"] = fl.to_wire()
                    if getattr(eng, "programs", None):
                        m["programs_by_kind"] = eng.programs_wire()
                except Exception:
                    logger.warning(
                        "debug-plane frame failed", exc_info=True
                    )
                # HBM accounting + mesh seat (docs/observability.md
                # "Reading the perf plane"): refresh the hbm_* / host /
                # dispatch gauges (m snapshotted metrics BEFORE the
                # refresh, so fold the fresh values in), and ship the
                # full per-device table + mesh doc so the metrics
                # service serves GET /v1/debug/{memory,mesh} fleet-wide.
                try:
                    if hasattr(eng, "refresh_memory_metrics"):
                        m["memory"] = eng.refresh_memory_metrics()
                        md = eng.metrics
                        for f in (
                            "hbm_weights_bytes", "hbm_kv_pool_bytes",
                            "hbm_free_bytes", "hbm_peak_bytes", "host",
                            "dispatch_p95_ms",
                        ):
                            m[f] = getattr(md, f)
                        m["mesh"] = eng.mesh_report()
                except Exception:
                    logger.warning(
                        "memory/mesh frame failed", exc_info=True
                    )
            wd = getattr(self.runner, "watchdog", None)
            if wd is not None:
                m["stalls_by_cause"] = wd.counters.snapshot()
                m["stalls_total"] = wd.counters.total
            if self.transfer_server is not None:
                # which KV plane transfers actually rode (device /
                # shm / bulk / inline host) — the ops signal for a
                # misconfigured fast path silently falling back
                for plane, n in self.transfer_server.transfers.items():
                    m[f"kv_transfer_{plane}_total"] = n
                m["remote_prefills_total"] = self.remote_prefills
                # frames the codec's checksum rejected (wire bit-rot
                # / chaos corrupt rules): corrupt pages never land
                m["kv_transfer_corrupt_total"] = (
                    self.transfer_server.corrupt_rejects
                )
            if self.kv_sequencing:
                # rolling block-set digest as of the last published KV
                # event: indexers run their anti-entropy sweep against
                # this (docs/operations.md "KV index consistency")
                m["kv_digest"] = {
                    "seq": self._kv_seq,
                    "fold": self._kv_digest.fold,
                    "count": self._kv_digest.count,
                }
            # control-plane health from THIS worker's seat (docs/
            # operations.md "Control-plane HA"): the live degraded flag
            # plus outage counters — during a full outage these frames
            # cannot ship, so what the fleet view mostly sees is the
            # post-recovery accounting (how long, how many drops)
            m["degraded"] = 1 if getattr(fabric, "degraded", False) else 0
            m["degraded_entries_total"] = int(
                getattr(fabric, "degraded_total", 0)
            )
            m["kv_events_dropped_total"] = self.kv_events_dropped
            m["kv_events_pending"] = len(self._kv_pending)
            m["instance_id"] = self.instance_id
            m["model"] = self.card.name
            if self._broker_reachable(fabric):
                await fabric.publish(
                    f"{METRICS_SUBJECT}.{pub_component}.{self.instance_id}",
                    m,
                )
        # fleet trace plane: ship buffered spans + fleet events on the
        # same cadence as the metrics frames (empty -> no publish)
        from dynamo_tpu.telemetry import traceplane

        await traceplane.ship_once(fabric, self.instance_id)
