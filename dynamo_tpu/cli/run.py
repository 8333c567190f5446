"""`dynamo-tpu run` — the one-command launcher.

  dynamo-tpu run in=http out=jax model=llama3-1b            # single process
  dynamo-tpu run in=text out=echo                           # REPL chat
  dynamo-tpu run in=batch:prompts.jsonl out=jax model=tiny  # batch file
  dynamo-tpu run in=dyn out=jax model=llama3-8b --fabric host:port
                                                            # join as worker
  dynamo-tpu run in=http out=dyn --fabric host:port         # frontend only
  dynamo-tpu run in=http 'out=ext:python -m my_engine_shim' # subprocess
                                                            # engine harness

`out=ext:<command...>` runs the command as a supervised subprocess
speaking the external-engine wire protocol (docs/external_engines.md
"Level 2") — the reference's `dynamo-run in=http out=vllm` shape
(launch/dynamo-run/src/subprocess/vllm_inc.py). Quote the whole
`out=ext:...` token when the engine command takes flags that collide
with dynamo-tpu's own (e.g. --model).

(reference: `dynamo run in=<http|text|stdin|batch:f|dyn://...>
out=<engine>` — launch/dynamo-run/src/lib.rs:44, opt.rs:7.)
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
from typing import Optional

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.logging_config import configure_logging

logger = logging.getLogger(__name__)


def _engine_config(args, eos_token_ids: tuple = ()) -> EngineConfig:
    return EngineConfig(
        model=args.model,
        num_pages=args.num_pages,
        page_size=args.page_size,
        max_pages_per_seq=args.max_context // args.page_size,
        prefill_chunk=args.prefill_chunk,
        prefill_buckets=getattr(args, "prefill_buckets", None),
        max_seqs=args.max_seqs,
        dtype=args.dtype,
        dp=args.dp,
        tp=args.tp,
        sp=getattr(args, "sp", 1),
        ep=getattr(args, "ep", 1),
        topology=getattr(args, "topology", "") or "",
        eos_token_ids=tuple(eos_token_ids) or (0,),
        host_kv_cache_bytes=getattr(args, "host_kv_bytes", 0),
        disk_kv_cache_bytes=getattr(args, "disk_kv_bytes", 0),
        disk_kv_cache_dir=getattr(args, "disk_kv_dir", None),
        spec_ngram=getattr(args, "spec_ngram", 0),
        spec_draft_model=getattr(args, "spec_draft", None),
        spec_draft_tokens=getattr(args, "spec_draft_tokens", 4),
        spec_draft_checkpoint=getattr(args, "spec_draft_checkpoint", None),
        max_waiting=getattr(args, "max_waiting", None),
        overlap_decode=getattr(args, "overlap_decode", True),
        mixed_steps=getattr(args, "mixed_steps", True),
        fleet_telemetry=getattr(args, "fleet_telemetry", True),
        flight_recorder=getattr(args, "flight_recorder", True),
        stall_watchdog=getattr(args, "stall_watchdog", True),
        stall_hard_deadline_s=getattr(args, "stall_hard_deadline", None),
        quantize=getattr(args, "quantize", None),
        kv_quantize=getattr(args, "kv_quantize", None),
        attention_impl=getattr(args, "attention_impl", "auto"),
        prefill_token_budget=getattr(args, "prefill_budget", None),
        prefill_budget_policy=getattr(args, "prefill_policy", "fixed"),
        prefill_budget_max=getattr(args, "prefill_budget_max", None),
        **(
            {"decode_steps": args.decode_steps}
            if getattr(args, "decode_steps", None) is not None
            else {}
        ),
    )


def _disagg_config(args):
    if not args.disagg:
        return None
    from dynamo_tpu.disagg import DisaggConfig

    return DisaggConfig(
        max_local_prefill_length=args.max_local_prefill,
        transfer_timeout_s=getattr(args, "transfer_timeout", 30.0),
    )


def _card(args):
    import os

    from dynamo_tpu.model_card import ModelDeploymentCard

    tokenizer = {"kind": "byte"}
    context_length = args.max_context
    eos: tuple[int, ...] = ()
    if args.tokenizer:
        tokenizer = {"kind": "hf", "path": args.tokenizer}
    elif args.model.endswith(".gguf") and os.path.isfile(args.model):
        # Serve the model's own embedded vocabulary + limits.
        from dynamo_tpu.gguf import read_gguf

        g = read_gguf(args.model)
        if g.tokenizer_vocab() is not None:
            tokenizer = {"kind": "gguf", "path": args.model}
            eos_id = g.tokenizer_vocab().get("eos_token_id")
            if eos_id is not None:
                eos = (int(eos_id),)
        context_length = min(context_length, g.context_length())
    elif os.path.isdir(args.model) and os.path.exists(
        os.path.join(args.model, "tokenizer_config.json")
    ):
        tokenizer = {"kind": "hf", "path": args.model}
    return ModelDeploymentCard(
        name=args.model,
        tokenizer=tokenizer,
        context_length=context_length,
        kv_page_size=args.page_size,
        **({"eos_token_ids": eos} if eos else {}),
    )


async def _make_local_pipeline(args):
    from dynamo_tpu.engine.async_engine import AsyncEngineRunner, EchoEngine
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.frontend.service import local_pipeline

    card = _card(args)
    if args.out == "echo":
        return local_pipeline(card, EchoEngine()), None
    if args.out == "mock":
        from dynamo_tpu.mocker import MockEngine

        return local_pipeline(card, MockEngine()), None
    if args.out.startswith("ext:"):
        from dynamo_tpu.external import SubprocessEngine

        engine = SubprocessEngine(args.ext_cmd, name="ext")
        await engine.start()
        return local_pipeline(card, engine), engine
    engine = JaxEngine(
        _engine_config(args, card.eos_token_ids),
        checkpoint_path=args.checkpoint,
    )
    runner = AsyncEngineRunner(engine)
    runner.start()
    return local_pipeline(card, runner), runner


async def _stop_engine(runner) -> None:
    """AsyncEngineRunner.stop() is sync; SubprocessEngine.stop() is a
    coroutine — stop either."""
    if runner is None:
        return
    res = runner.stop()
    if asyncio.iscoroutine(res):
        await res


async def _start_http(args):
    """Build and start the `run in=http` server: (HttpService, engine
    runner or None, model watcher or None — the caller keeps it alive).
    Split from _run_http so chip_smoke.py serves through the same
    construction the CLI uses."""
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.service import ModelWatcher

    manager = ModelManager()
    runner = None
    watcher = None
    if args.out == "dyn":
        from dynamo_tpu.runtime import DistributedRuntime

        rt = await DistributedRuntime.create(args.fabric)
        watcher = ModelWatcher(
            rt, manager,
            stream_replay=getattr(args, "stream_replay", False),
            kv_economy=getattr(args, "kv_economy", False),
        )
        await watcher.start()
    else:
        pipeline, runner = await _make_local_pipeline(args)
        manager.add(args.model, pipeline)
    svc = HttpService(
        manager, host=args.host, port=args.port,
        max_inflight=getattr(args, "max_inflight", None),
        shed_burn_threshold=getattr(args, "shed_burn_threshold", None),
        request_timeout_s=getattr(args, "request_timeout", None),
    )
    await svc.start()
    return svc, runner, watcher


async def _run_http(args) -> None:
    svc, runner, _watcher = await _start_http(args)
    print(f"listening on http://{args.host}:{svc.port}/v1", flush=True)
    try:
        await asyncio.Event().wait()
    finally:
        await svc.stop()
        await _stop_engine(runner)


async def _run_text(args) -> None:
    from dynamo_tpu.protocols.openai import ChatCompletionRequest, ChatMessage

    pipeline, runner = await _make_local_pipeline(args)
    print(f"chat with {args.model} (out={args.out}); /quit to exit", flush=True)
    history: list[ChatMessage] = []
    try:
        while True:
            try:
                line = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: input("> ")
                )
            except EOFError:
                break
            if line.strip() in ("/quit", "/exit"):
                break
            history.append(ChatMessage(role="user", content=line))
            req = ChatCompletionRequest(
                model=args.model, messages=history, stream=True,
                max_tokens=args.max_tokens,
            )
            text = []
            async for chunk in pipeline.chat_stream(req):
                for c in chunk.choices:
                    if c.delta.content:
                        text.append(c.delta.content)
                        print(c.delta.content, end="", flush=True)
            print()
            history.append(ChatMessage(role="assistant", content="".join(text)))
    finally:
        await _stop_engine(runner)


async def _run_batch(args, path: str) -> None:
    from dynamo_tpu.protocols.openai import ChatCompletionRequest, ChatMessage

    pipeline, runner = await _make_local_pipeline(args)
    try:
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip()]
        for i, item in enumerate(lines):
            prompt = item.get("prompt") or item.get("text") or ""
            req = ChatCompletionRequest(
                model=args.model,
                messages=[ChatMessage(role="user", content=prompt)],
                stream=True,
                max_tokens=item.get("max_tokens", args.max_tokens),
            )
            text = []
            async for chunk in pipeline.chat_stream(req):
                for c in chunk.choices:
                    if c.delta.content:
                        text.append(c.delta.content)
            print(json.dumps({"index": i, "prompt": prompt, "output": "".join(text)}), flush=True)
    finally:
        await _stop_engine(runner)


def _run_spmd_follower(args) -> None:
    """Follower host of a cross-host SPMD serving group: build the
    identical engine replica and block in the lockstep serve loop."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.spmd import SpmdDriver

    card = _card(args)
    engine = JaxEngine(
        _engine_config(args, card.eos_token_ids),
        checkpoint_path=args.checkpoint,
    )
    drv = SpmdDriver(engine)
    if drv.is_leader:  # pragma: no cover — arg-mismatch guard
        raise RuntimeError("follower entry reached on process 0")
    print(
        f"spmd follower {args.host_id} up (model={args.model})", flush=True
    )
    drv.serve()
    print(f"spmd follower {args.host_id} released", flush=True)


async def _run_worker(args) -> None:
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.worker import Worker

    rt = await DistributedRuntime.create(args.fabric)
    # progress line BEFORE engine construction: lets a supervisor
    # distinguish "loading/compiling" (slow but alive) from a process
    # that never got going (this line never appears)
    print(f"worker booting (model={args.model}, role={args.role})",
          flush=True)
    if args.role == "prefill":
        from dynamo_tpu.disagg.prefill_worker import PrefillWorker

        pw = PrefillWorker(
            rt, _engine_config(args), namespace=args.namespace,
            checkpoint_path=args.checkpoint,
            advertise_host=args.host,
        )
        await pw.start()
        print(f"prefill worker {pw.instance_id} up (model={args.model})", flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            await pw.stop()
        return
    external = None
    if args.out.startswith("ext:"):
        from dynamo_tpu.external import SubprocessEngine

        external = SubprocessEngine(args.ext_cmd, name="ext")
        await external.start()
    mock_args = None
    if args.out == "mock" and getattr(args, "mock_step", None):
        from dynamo_tpu.mocker import MockEngineArgs

        mock_args = MockEngineArgs(
            page_size=args.page_size,
            salt=args.model,
            decode_s_per_step=args.mock_step,
        )
    worker = Worker(
        rt,
        _card(args),
        engine_config=(
            _engine_config(args, _card(args).eos_token_ids)
            if args.out == "jax"
            else None
        ),
        engine_kind="external" if external is not None else args.out,
        engine=external,
        mock_args=mock_args,
        namespace=args.namespace,
        component=args.component,
        endpoint=args.endpoint,
        checkpoint_path=args.checkpoint,
        router_mode=args.router_mode,
        enable_disagg=args.disagg,
        disagg_config=_disagg_config(args),
        kv_remote=getattr(args, "kv_remote", False),
        echo_delay=getattr(args, "echo_delay", 0.0),
        advertise_host=args.host,
        drain_budget_s=getattr(args, "drain_budget", 30.0),
        kv_sequencing=getattr(args, "kv_sequencing", True),
        kv_economy=getattr(args, "kv_economy", False),
    )
    await worker.start()
    print(f"worker {worker.instance_id} up (model={args.model})", flush=True)
    # SIGTERM = graceful drain (docs/operations.md "Overload & draining"):
    # deregister, finish in-flight within --drain-budget, exit 0. SIGINT
    # keeps its fast KeyboardInterrupt teardown for interactive use.
    import signal as _signal

    term = asyncio.Event()
    loop = asyncio.get_running_loop()
    try:
        loop.add_signal_handler(_signal.SIGTERM, term.set)
    except (NotImplementedError, RuntimeError):  # non-main thread / win
        pass
    try:
        waits = [
            asyncio.ensure_future(term.wait()),
            asyncio.ensure_future(worker.drained.wait()),
        ]
        try:
            # wakes on SIGTERM or on an admin-triggered drain completing
            await asyncio.wait(waits, return_when=asyncio.FIRST_COMPLETED)
        finally:
            for w in waits:
                w.cancel()
        if not worker.drained.is_set():
            print(f"worker {worker.instance_id} draining", flush=True)
            await worker.drain()
        print(f"worker {worker.instance_id} drained; exiting", flush=True)
    finally:
        await worker.stop()
        if external is not None:
            await external.stop()


async def _run_ctl(args) -> None:
    """llmctl parity (reference launch/llmctl/src/main.rs:114-139): list,
    add, remove model registrations against the fabric store."""
    from dynamo_tpu.model_card import (
        ModelDeploymentCard,
        ModelEntry,
        model_key,
        register_llm,
    )
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.component import INSTANCE_ROOT, MODEL_ROOT, Instance

    rt = await DistributedRuntime.create(args.fabric)
    try:
        fabric = rt.fabric
        if args.ctl_cmd == "list":
            models = await fabric.get_prefix(MODEL_ROOT + "/")
            print(f"models ({len(models)}):")
            for key, raw in sorted(models.items()):
                try:
                    e = ModelEntry.unpack(raw)
                    print(
                        f"  {e.model}  ->  {e.namespace}/{e.component}/"
                        f"{e.endpoint}  (router={e.router_mode})  [{key}]"
                    )
                except Exception:
                    print(f"  {key}  (unreadable)")
            instances = await fabric.get_prefix(INSTANCE_ROOT + "/")
            print(f"instances ({len(instances)}):")
            for key, raw in sorted(instances.items()):
                try:
                    inst = Instance.unpack(raw)
                    print(
                        f"  {inst.instance_id}  {inst.namespace}/"
                        f"{inst.component}/{inst.endpoint}  at "
                        f"{inst.host}:{inst.port}"
                    )
                except Exception:
                    print(f"  {key}  (unreadable)")
        elif args.ctl_cmd == "add":
            from dynamo_tpu.model_card import CARD_OBJ_PREFIX

            card = ModelDeploymentCard(
                name=args.model, tokenizer={"kind": "byte"}, context_length=4096
            )
            # Never clobber a live model's real card with this placeholder.
            existing = await fabric.obj_get(CARD_OBJ_PREFIX + args.model)
            await register_llm(
                fabric, card, args.namespace, args.component, args.endpoint,
                router_mode=args.router_mode,
                publish_card=existing is None,
            )
            print(f"registered {args.model} -> "
                  f"{args.namespace}/{args.component}/{args.endpoint}"
                  + (" (kept existing card)" if existing is not None else ""))
        elif args.ctl_cmd == "remove":
            base = model_key(args.model)
            keys = await fabric.get_prefix(base)
            n = 0
            for key in keys:
                # Exact model only: 'llama3' must not remove 'llama3-70b'.
                if key == base or key.startswith(base + "/"):
                    if await fabric.delete(key):
                        n += 1
            print(f"removed {n} registration(s) for {args.model}")
    finally:
        await rt.close()


async def _run_serve(args) -> None:
    """Orchestrate a service graph: one OS process per replica (the
    reference's circus-arbiter local serving, sdk cli/serving.py:152)."""
    import subprocess

    from dynamo_tpu.sdk.config import load_config, replica_count
    from dynamo_tpu.sdk.decorators import service_meta
    from dynamo_tpu.sdk.graph import discover_graph
    from dynamo_tpu.sdk.serving import resolve_service

    root = resolve_service(args.graph)
    config = load_config(args.config) if args.config else {}

    fabric_server = None
    fabric_addr = args.fabric
    if fabric_addr is None:
        from dynamo_tpu.runtime.fabric import FabricServer

        fabric_server = FabricServer(port=args.fabric_port)
        await fabric_server.start()
        fabric_addr = fabric_server.address
        print(f"fabric on {fabric_addr}", flush=True)

    # SIGTERM/SIGINT must run the cleanup below, or every replica (and the
    # locally spawned fabric) outlives the orchestrator.
    import signal as _signal

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (_signal.SIGTERM, _signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)

    procs: list[tuple[str, "subprocess.Popen"]] = []
    child_died = False
    try:
        for cls in discover_graph(root):
            meta = service_meta(cls)
            svc_cfg = config.get(meta.name, {})
            replicas = replica_count(svc_cfg, meta.workers)
            spec = f"{cls.__module__}:{cls.__name__}"
            for _ in range(replicas):
                cmd = [
                    sys.executable, "-m", "dynamo_tpu.sdk.serving", spec,
                    "--fabric", fabric_addr,
                ]
                if args.config:
                    cmd += ["-f", args.config]
                print(f"spawning {meta.name}: {' '.join(cmd)}", flush=True)
                procs.append((meta.name, subprocess.Popen(cmd)))
        print(f"graph up: {len(procs)} service processes", flush=True)
        # Supervise: a dead child means a degraded graph — tear down and
        # exit nonzero so the outer supervisor (systemd/k8s) restarts us.
        while not stop.is_set():
            for name, p in procs:
                code = p.poll()
                if code is not None:
                    print(
                        f"service {name} (pid {p.pid}) exited with {code}; "
                        "stopping graph", file=sys.stderr, flush=True,
                    )
                    child_died = True
                    stop.set()
                    break
            try:
                await asyncio.wait_for(stop.wait(), timeout=1.0)
            except asyncio.TimeoutError:
                pass
    finally:
        for _, p in procs:
            if p.poll() is None:
                p.terminate()
        for _, p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if fabric_server is not None:
            await fabric_server.stop()
        if child_died:
            sys.exit(1)


async def _run_metrics(args) -> None:
    from dynamo_tpu.metrics_service import MetricsService
    from dynamo_tpu.runtime import DistributedRuntime

    rt = await DistributedRuntime.create(args.fabric)
    svc = MetricsService(
        rt.fabric, component=args.component, host=args.host, port=args.port,
        trace_sample_rate=getattr(args, "trace_sample_rate", None),
        trace_window_s=getattr(args, "trace_window", 2.0),
        trace_keep=getattr(args, "trace_keep", 512),
    )
    await svc.start()
    print(
        f"metrics service on {args.host}:{svc.port} "
        f"(/metrics, /v1/fleet, /v1/fleet/events, /v1/traces)",
        flush=True,
    )
    try:
        await asyncio.Event().wait()
    finally:
        await svc.stop()
        await rt.close()


async def _run_planner(args) -> None:
    import shlex

    from dynamo_tpu.planner import (
        ClosedLoopPlanner,
        ControlConfig,
        ControlRunner,
        LoadPlanner,
        LocalConnector,
        PerfInterpolator,
        PlannerConfig,
        SlaPlanner,
    )
    from dynamo_tpu.planner.planner import PlannerRunner, SlaTargets
    from dynamo_tpu.planner.service import (
        FleetFlipper,
        FleetHandover,
        FleetObserver,
        rolling_upgrade,
    )
    from dynamo_tpu.runtime import DistributedRuntime

    cfg = PlannerConfig(
        interval_s=args.interval,
        min_decode=args.min_decode,
        max_decode=args.max_decode,
        min_prefill=args.min_prefill,
        max_prefill=args.max_prefill,
    )
    if args.mode == "closed":
        planner = ClosedLoopPlanner(
            ControlConfig(
                interval_s=args.interval,
                min_decode=args.min_decode,
                max_decode=args.max_decode,
                min_prefill=args.min_prefill,
                max_prefill=args.max_prefill,
                ttft_target_ms=args.ttft_ms,
                itl_target_ms=args.itl_ms,
                cooldown_s=args.cooldown,
                flip_cooldown_s=args.flip_cooldown,
                max_actions_per_tick=args.max_actions,
                allow_flips=args.flip,
            )
        )
    elif args.mode == "sla":
        if not args.perf_table:
            print("--perf-table is required in SLA mode", file=sys.stderr)
            sys.exit(2)
        with open(args.perf_table) as f:
            table = json.load(f)
        if table.get("configs"):
            # Multi-(tp,dp) table from the profiler sweep: re-select
            # against the PLANNER's targets (which may differ from the
            # profile-time SLA) on per-chip SLA-feasible rate.
            from dynamo_tpu.planner.perf_model import select_parallel_config

            chosen = select_parallel_config(
                table["configs"], args.ttft_ms, args.itl_ms
            )
            table = dict(table, **{
                "ttft_vs_rate": chosen["ttft_vs_rate"],
                "itl_vs_rate": chosen["itl_vs_rate"],
            })
            print(
                f"planner: perf table selects tp={chosen['tp']} "
                f"dp={chosen['dp']} for ttft<={args.ttft_ms}ms "
                f"itl<={args.itl_ms}ms",
                flush=True,
            )
        planner = SlaPlanner(
            cfg,
            SlaTargets(ttft_ms=args.ttft_ms, itl_ms=args.itl_ms),
            ttft_vs_rate=PerfInterpolator(*zip(*table["ttft_vs_rate"])),
            itl_vs_rate=PerfInterpolator(*zip(*table["itl_vs_rate"])),
        )
    else:
        planner = LoadPlanner(cfg)

    extra = shlex.split(args.worker_args)

    def spawn_cmd(role: str) -> list[str]:
        cmd = [
            sys.executable, "-m", "dynamo_tpu.cli.run", "run",
            "in=dyn", "out=jax",
            "--fabric", args.fabric,
            "--role", role,
            "--namespace", args.namespace,
            "--component", args.component if role == "decode" else "prefill",
            "--model", args.model,
        ]
        if args.checkpoint:
            cmd += ["--checkpoint", args.checkpoint]
        return cmd + extra

    rt = await DistributedRuntime.create(args.fabric)
    observer = FleetObserver(
        rt, namespace=args.namespace, decode_component=args.component
    )
    await observer.start()
    if args.connector == "kube":
        from dynamo_tpu.operator.kube import InClusterKube
        from dynamo_tpu.planner.kube_connector import KubeConnector

        role_services = dict(kv.split("=", 1) for kv in args.role_service)
        connector = KubeConnector(
            InClusterKube(),
            cr_name=args.cr_name,
            namespace=args.k8s_namespace,
            role_services=role_services,
        )
    else:
        connector = LocalConnector(spawn_cmd)
    if getattr(args, "rolling_upgrade", False):
        # one sweep, then exit: replace every worker one at a time with
        # live KV handover (docs/operations.md "Rolling upgrades &
        # worker handover")
        print(
            f"rolling upgrade starting (cooldown="
            f"{args.upgrade_cooldown}s)",
            flush=True,
        )
        try:
            # give the instance watches a moment to prime
            await asyncio.sleep(0.5)
            summary = await rolling_upgrade(
                observer, connector, FleetHandover(observer),
                cooldown_s=args.upgrade_cooldown,
            )
            print(json.dumps({"rolling_upgrade": summary}), flush=True)
            failed = any(v["failed"] for v in summary.values())
            if failed:
                sys.exit(3)
        finally:
            await observer.stop()
            await rt.close()
        return
    if args.mode == "closed":
        from dynamo_tpu.subjects import PLANNER_SUBJECT
        from dynamo_tpu.telemetry.traceplane import TelemetryShipper

        async def status_fn(frame: dict) -> None:
            await rt.fabric.publish(PLANNER_SUBJECT, frame)

        # fleet event timeline: planner decisions buffered by the
        # ControlRunner ship to fleet.events on a 1 s cadence
        shipper = TelemetryShipper(rt.fabric, source="planner")
        shipper.start()
        economy = None
        if getattr(args, "kv_economy", False):
            from dynamo_tpu.kv_economy import cost_model_from_card
            from dynamo_tpu.planner.service import FleetKvEconomy

            # no card in the planner process — the 1B-class shape
            # defaults; only the flops/byte RATIO gates decisions
            economy = FleetKvEconomy(observer, cost_model_from_card(None))
        runner = ControlRunner(
            planner, connector, observer.observe,
            flipper=FleetFlipper(observer) if args.flip else None,
            handover=(
                FleetHandover(observer, economy=economy)
                if getattr(args, "handover", True)
                else None
            ),
            prewarm=economy.prewarm if economy is not None else None,
            status_fn=status_fn,
            # HOLD while the control plane is degraded (no broker):
            # signals are frozen and actuation would fly blind
            degraded_fn=lambda: bool(
                getattr(rt.fabric, "degraded", False)
            ),
        )
    else:
        shipper = None
        runner = PlannerRunner(planner, connector, observer.observe)
    print(
        f"planner up (mode={args.mode}, connector={args.connector}, "
        f"interval={args.interval}s)",
        flush=True,
    )
    try:
        await runner.run()
    finally:
        if hasattr(connector, "stop_all"):
            connector.stop_all()
        if shipper is not None:
            await shipper.stop()
        await observer.stop()
        await rt.close()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dynamo-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    runp = sub.add_parser("run", help="serve / chat / batch / worker")
    runp.add_argument("io", nargs="*", help="in=<http|text|batch:file|dyn> out=<jax|echo|mock|dyn>")
    runp.add_argument("--model", default="tiny")
    runp.add_argument("--checkpoint", default=None, help="local HF checkpoint dir")
    runp.add_argument("--tokenizer", default=None, help="local tokenizer dir")
    runp.add_argument(
        "--fabric", default=None,
        help="fabric broker address(es): host:port, or a comma list "
             "a:4222,b:4222 for an HA pair — the client rotates through "
             "them, follows NotPrimary redirects, and rides out a "
             "broker failover (docs/operations.md 'Control-plane HA')",
    )
    runp.add_argument("--host", default="127.0.0.1")
    runp.add_argument("--port", type=int, default=8080)
    runp.add_argument(
        "--router-mode", default="round_robin", dest="router_mode",
        choices=["round_robin", "random", "kv"],
        help="how frontends route to this worker's endpoint",
    )
    runp.add_argument(
        "--role", default="decode", choices=["decode", "prefill"],
        help="worker role when in=dyn (prefill = queue consumer)",
    )
    runp.add_argument(
        "--disagg", action="store_true",
        help="decode worker: send long prefills to the prefill fleet",
    )
    runp.add_argument(
        "--max-local-prefill", type=int, default=512, dest="max_local_prefill",
        help="uncached prefill tokens above which prefill goes remote",
    )
    runp.add_argument(
        "--echo-delay", type=float, default=0.0, dest="echo_delay",
        help="out=echo: seconds per emitted token (stream-timing tests)",
    )
    runp.add_argument(
        "--mock-step", type=float, default=None, dest="mock_step",
        help="out=mock (in=dyn): simulated engine step seconds — slows "
             "the mock's batched decode tick for stream-timing/chaos "
             "tests (default: MockEngineArgs.decode_s_per_step)",
    )
    runp.add_argument(
        "--max-waiting", type=int, default=None, dest="max_waiting",
        help="bounded admission: cap on the engine's waiting queue — a "
             "full queue answers 'overloaded' (HTTP 429 + Retry-After at "
             "the frontend) instead of queueing forever (default: "
             "unbounded; docs/operations.md 'Overload & draining')",
    )
    runp.add_argument(
        "--max-inflight", type=int, default=None, dest="max_inflight",
        help="frontend admission cap: reject with 429 + Retry-After once "
             "this many requests are in flight (default: unbounded)",
    )
    runp.add_argument(
        "--request-timeout", type=float, default=None,
        dest="request_timeout", metavar="SECONDS",
        help="server-default end-to-end deadline; per-request "
             "x-request-timeout overrides it. Expired requests are "
             "dropped before admission and error-finished mid-decode "
             "(default: none)",
    )
    runp.add_argument(
        "--shed-burn-threshold", type=float, default=None,
        dest="shed_burn_threshold", metavar="RATE",
        help="SLO-burn load shedder: when the endpoint's short-window "
             "burn rate exceeds this (1.0 = spending the error budget "
             "exactly), shed best-effort requests (x-priority < 1) with "
             "probability ramping to 100%% at 2x the threshold "
             "(default: off)",
    )
    runp.add_argument(
        "--stream-replay", action="store_true", dest="stream_replay",
        help="crash-replayed streams (frontend, in=http out=dyn): when "
             "a worker dies mid-stream, re-dispatch the request to a "
             "survivor as prompt + tokens-emitted-so-far — the client "
             "stream continues with no duplicate and no missing token "
             "(bit-identical for greedy; sampled streams resume under a "
             "derived seed). Default off; router behavior is identical "
             "to before when off",
    )
    runp.add_argument(
        "--drain-budget", type=float, default=30.0, dest="drain_budget",
        metavar="SECONDS",
        help="graceful drain budget: on SIGTERM (or POST /v1/admin/"
             "drain) the worker deregisters, finishes in-flight "
             "requests up to this long, then exits 0",
    )
    runp.add_argument(
        "--no-kv-sequencing", action="store_false", dest="kv_sequencing",
        default=True,
        help="disable KV event sequence stamping + the rolling block-set "
             "digest (docs/operations.md 'KV index consistency'): the "
             "event wire reverts to the pre-sequencing format and "
             "indexers lose gap/drift detection for this worker",
    )
    runp.add_argument(
        "--transfer-timeout", type=float, default=30.0,
        dest="transfer_timeout",
        help="seconds to wait for the remote-prefill KV landing before "
             "falling back to local prefill",
    )
    runp.add_argument(
        "--trace", action="store_true",
        help="enable distributed request tracing (in-memory ring served "
             "at /v1/traces; equivalently DYNTPU_TRACING=1 or "
             "DYNTPU_TRACE_RING=<n> — docs/observability.md)",
    )
    runp.add_argument(
        "--log-file", default=None, dest="log_file", metavar="NAME|PATH",
        help="also log (JSONL) to this file; a bare name lands in "
             "DYNTPU_LOG_DIR (default artifacts/log), never the CWD",
    )
    runp.add_argument("--namespace", default="dynamo")
    runp.add_argument("--component", default="backend")
    runp.add_argument("--endpoint", default="generate")
    runp.add_argument("--num-pages", type=int, default=512, dest="num_pages")
    runp.add_argument("--page-size", type=int, default=64, dest="page_size")
    runp.add_argument(
        "--decode-steps", type=int, default=None, dest="decode_steps",
        help="decode steps fused per dispatch (host sync per K tokens/seq)."
             " Default: engine default (8)",
    )
    runp.add_argument(
        "--host-kv-bytes", type=int, default=0, dest="host_kv_bytes",
        help="KVBM G2: host-DRAM KV tier byte budget (0 = off); evicted "
             "device pages offload here and onboard on prefix hit",
    )
    runp.add_argument(
        "--disk-kv-bytes", type=int, default=0, dest="disk_kv_bytes",
        help="KVBM G3: disk KV tier byte budget (0 = off)",
    )
    runp.add_argument(
        "--disk-kv-dir", default=None, dest="disk_kv_dir",
        help="directory for the disk KV tier (required with --disk-kv-bytes)",
    )
    runp.add_argument(
        "--kv-remote", action="store_true", dest="kv_remote",
        help="KVBM G4: serve KV blocks to peers and onboard prefixes a "
             "peer already computed (cross-worker, over the transfer plane)",
    )
    runp.add_argument(
        "--kv-economy", action="store_true", dest="kv_economy",
        help="the KV economy (docs/operations.md 'The KV economy'): on "
             "a frontend, KV routing scores lower-tier residency at a "
             "promotion-cost discount and migrates hot prefixes to the "
             "chosen worker when the prefill flops saved beat the bytes "
             "moved; on a worker, publishes tier residency hints, "
             "serves migrate_prefix, and demotes cold pages under HBM "
             "watermark pressure. Default off; routing and the wire are "
             "bit-identical to before when off",
    )
    runp.add_argument(
        "--spec-ngram", type=int, default=0, dest="spec_ngram",
        help="speculative decoding: draft tokens per step proposed by "
             "prompt lookup and verified in one forward pass (0 = off)",
    )
    runp.add_argument(
        "--spec-draft", default=None, dest="spec_draft",
        help="draft-model speculative decoding: a small same-family "
             "model (e.g. llama3-draft for llama3-1b/8b targets; must "
             "share the target's vocabulary) proposes greedy drafts "
             "verified + accepted ON DEVICE per decode step — bit-exact "
             "greedy, exact rejection sampling for temperature>0. "
             "Composes with the overlap pipeline and mixed steps "
             "(unlike --spec-ngram)",
    )
    runp.add_argument(
        "--spec-draft-tokens", type=int, default=4,
        dest="spec_draft_tokens",
        help="drafts proposed and verified per spec step (with "
             "--spec-draft; default 4)",
    )
    runp.add_argument(
        "--spec-draft-checkpoint", default=None,
        dest="spec_draft_checkpoint",
        help="checkpoint dir for the draft weights (default: the draft "
             "model's own default checkpoint, else random init)",
    )
    runp.add_argument(
        "--no-overlap-decode", action="store_false", dest="overlap_decode",
        default=True,
        help="disable the overlapped decode loop (speculative next-step "
             "dispatch with one-step-lagged host readback; on by default "
             "including multi-host SPMD, auto-off with --spec-ngram)",
    )
    runp.add_argument(
        "--no-mixed-steps", action="store_false", dest="mixed_steps",
        default=True,
        help="disable stall-free mixed prefill+decode steps (one fused "
             "dispatch carrying a bounded prefill chunk plus the decode "
             "batch, so decodes emit a token every step while a prompt "
             "burst drains; on by default including multi-host SPMD, "
             "auto-off with --spec-ngram)",
    )
    runp.add_argument(
        "--no-fleet-telemetry", action="store_false",
        dest="fleet_telemetry", default=True,
        help="disable the live fleet telemetry plane (worker SLO "
             "sketches, live tokens/s gauge, fleet-frame publishing; on by "
             "default — host-side metrics only, the token path is "
             "identical either way; docs/observability.md)",
    )
    runp.add_argument(
        "--no-flight-recorder", action="store_false",
        dest="flight_recorder", default=True,
        help="disable the per-step flight recorder (bounded ring served "
             "at /v1/debug/flight and shipped in metrics frames; on by "
             "default, <1%% overhead, host-side only — "
             "docs/observability.md 'Debugging a slow or stuck worker')",
    )
    runp.add_argument(
        "--no-stall-watchdog", action="store_false",
        dest="stall_watchdog", default=True,
        help="disable the per-request stall watchdog (structured "
             "diagnosis of wedged streams: flight window + thread "
             "stacks + trace ids, dynamo_tpu_stalls_total{cause})",
    )
    runp.add_argument(
        "--stall-hard-deadline", type=float, default=None,
        dest="stall_hard_deadline", metavar="SECONDS",
        help="error-finish a stream stalled past this many seconds "
             "instead of hanging the client (default: diagnose-only)",
    )
    runp.add_argument(
        "--quantize", default=None, choices=["int8"],
        help="weight-only quantization (per-output-channel int8 scales)",
    )
    runp.add_argument(
        "--kv-quantize", default=None, choices=["int8", "fp8"],
        dest="kv_quantize",
        help="KV-cache page quantization: pages store int8 (or fp8) rows "
        "with per-token f32 scales, dequantized inside the Pallas "
        "page-walk kernels — halves KV HBM traffic and ~doubles "
        "effective cache capacity (docs/engine.md 'Quantized KV pages')",
    )
    runp.add_argument(
        "--attention-impl", default="auto", dest="attention_impl",
        choices=["auto", "xla", "pallas", "hybrid"],
        help="decode attention kernels (auto = pallas on TPU, else xla; "
        "hybrid = pallas under large-batch XLA-gather fallback)",
    )
    runp.add_argument("--max-context", type=int, default=4096, dest="max_context")
    runp.add_argument("--prefill-chunk", type=int, default=512, dest="prefill_chunk")
    runp.add_argument(
        "--prefill-buckets", type=int, nargs="+", default=None,
        dest="prefill_buckets",
        help="the T buckets a prompt piece is padded to, ascending, the "
        "last one --prefill-chunk (default: powers of two from 32): fewer "
        "buckets are fewer step programs to load, paid for in padding",
    )
    runp.add_argument(
        "--prefill-budget", type=int, default=None, dest="prefill_budget",
        help="prefill tokens per step across sequences (default 4x "
        "prefill-chunk); the saturation-TTFT knob (docs/PERF.md)",
    )
    runp.add_argument(
        "--prefill-policy", default="fixed", dest="prefill_policy",
        choices=["fixed", "adaptive"],
        help="adaptive grows the step budget with the un-prefilled "
        "backlog (to 4x the budget) so arrival bursts drain in O(1) "
        "dispatches; fixed always spends at most --prefill-budget",
    )
    runp.add_argument(
        "--prefill-budget-max", type=int, default=None,
        dest="prefill_budget_max",
        help="adaptive-policy ceiling (default 4x the budget): bounds "
        "the worst-case single prefill dispatch = the longest decode "
        "stall (ITL spike) a running sequence can observe",
    )
    runp.add_argument("--max-seqs", type=int, default=32, dest="max_seqs")
    runp.add_argument("--max-tokens", type=int, default=256, dest="max_tokens")
    runp.add_argument("--dtype", default="bfloat16")
    runp.add_argument("--dp", type=int, default=1)
    runp.add_argument("--tp", type=int, default=1)
    runp.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel devices: long prefills use ring attention",
    )
    runp.add_argument(
        "--ep", type=int, default=1,
        help="expert-parallel devices (MoE models shard experts over them)",
    )
    runp.add_argument(
        "--topology", default="",
        help="combined mesh layout 'tp=N,dp=M[,ep=K][,sp=J]' — overrides "
             "the individual --dp/--tp/--sp/--ep flags; the product must "
             "match the device count (docs/migrating.md)",
    )
    runp.add_argument(
        "--coordinator", default=None,
        help="multi-host: coordinator host:port (same on every host)",
    )
    runp.add_argument(
        "--num-hosts", type=int, default=1, dest="num_hosts",
        help="multi-host: total participating host processes",
    )
    runp.add_argument(
        "--host-id", type=int, default=0, dest="host_id",
        help="multi-host: this process's rank (0..num-hosts-1)",
    )

    fabricp = sub.add_parser("fabric", help="start the fabric server")
    fabricp.add_argument("--host", default="127.0.0.1")
    fabricp.add_argument("--port", type=int, default=4222)
    fabricp.add_argument(
        "--persist-dir", default=None, dest="persist_dir",
        help="WAL directory: state survives server restarts (and, with "
             "--standby-of, makes a promotion's fence bump durable)",
    )
    fabricp.add_argument(
        "--standby-of", default=None, dest="standby_of", metavar="ADDR",
        help="control-plane HA: run as the WARM STANDBY of the primary "
             "at host:port — bootstrap from its snapshot, tail its "
             "journal, answer clients NotPrimary+redirect, and promote "
             "when it is unreachable past --detector-budget "
             "(docs/operations.md 'Control-plane HA')",
    )
    fabricp.add_argument(
        "--peer", action="append", default=[], metavar="ADDR",
        help="other broker addresses (repeatable). On startup a primary "
             "defers to any peer serving at a higher fence instead of "
             "split-braining — give the restarted old primary its "
             "standby's address",
    )
    fabricp.add_argument(
        "--detector-budget", type=float, default=3.0,
        dest="detector_budget", metavar="SECONDS",
        help="standby: promote after the primary has been unreachable "
             "this long (default 3.0)",
    )
    fabricp.add_argument(
        "--no-auto-promote", action="store_false", dest="auto_promote",
        default=True,
        help="standby: never promote on its own — only an explicit "
             "`run fabric --promote` / repl.promote admin op",
    )
    fabricp.add_argument(
        "--promote", default=None, metavar="ADDR",
        help="do not start a broker: tell the STANDBY at host:port to "
             "promote NOW, print its reply, and exit (the manual "
             "failover drill)",
    )

    ctlp = sub.add_parser(
        "ctl", help="inspect/edit model + instance registrations (llmctl)"
    )
    ctlp.add_argument("--fabric", required=True, help="fabric host:port")
    ctl_sub = ctlp.add_subparsers(dest="ctl_cmd", required=True)
    ctl_sub.add_parser("list", help="list models and live instances")
    addp = ctl_sub.add_parser("add", help="register a model entry")
    addp.add_argument("model")
    addp.add_argument("--namespace", default="dynamo")
    addp.add_argument("--component", default="backend")
    addp.add_argument("--endpoint", default="generate")
    addp.add_argument(
        "--router-mode", default="round_robin", dest="router_mode",
        choices=["round_robin", "random", "kv"],
    )
    rmp = ctl_sub.add_parser("remove", help="remove a model's registrations")
    rmp.add_argument("model")

    servep = sub.add_parser("serve", help="serve a service graph (SDK DSL)")
    servep.add_argument("graph", help="pkg.module:RootService")
    servep.add_argument("-f", "--config", default=None, help="YAML config")
    servep.add_argument(
        "--fabric", default=None,
        help="existing fabric host:port (default: spawn one locally)",
    )
    servep.add_argument(
        "--fabric-port", type=int, default=4222, dest="fabric_port",
        help="port for the locally spawned fabric",
    )

    buildp = sub.add_parser(
        "build", help="freeze a service graph into a build manifest"
    )
    buildp.add_argument("graph", help="pkg.module:RootService")
    buildp.add_argument("-f", "--config", default=None, help="YAML config")
    buildp.add_argument("-o", "--output", default="dist", help="output dir")
    buildp.add_argument("--image", default="dynamo-tpu:latest")

    deployp = sub.add_parser(
        "deploy", help="render Kubernetes manifests for a graph"
    )
    deployp.add_argument("graph", help="pkg.module:RootService")
    deployp.add_argument("-f", "--config", default=None, help="YAML config")
    deployp.add_argument("-o", "--output", default="dist", help="output dir")
    deployp.add_argument("--image", default="dynamo-tpu:latest")
    deployp.add_argument(
        "--fabric-host", default="dynamo-fabric", dest="fabric_host",
        help="k8s service name for the fabric control plane",
    )
    deployp.add_argument(
        "--cr", action="store_true",
        help="emit a DynamoGraphDeployment custom resource (for the "
             "operator) instead of raw Deployments/Services",
    )
    deployp.add_argument(
        "--fabric-external", action="store_true", dest="fabric_external",
        help="with --cr: the fabric at --fabric-host is platform-managed "
             "(helm chart); the operator won't render a per-graph fabric",
    )
    deployp.add_argument(
        "--name", default=None,
        help="CR name with --cr (default: derived from the root service)",
    )

    operp = sub.add_parser(
        "operator", help="run the Kubernetes operator (reconciles "
                         "DynamoGraphDeployments; in-cluster credentials)"
    )
    operp.add_argument("--namespace", default="default")
    operp.add_argument("--interval", type=float, default=5.0)

    sub.add_parser("env", help="print the serving environment report")

    routerp = sub.add_parser(
        "router", help="standalone KV-router service (routing-as-a-service)"
    )
    routerp.add_argument("--fabric", required=True, help="fabric host:port")
    routerp.add_argument("--namespace", default="dynamo")
    routerp.add_argument("--component", default="backend")
    routerp.add_argument("--endpoint", default="generate")
    routerp.add_argument(
        "--block-size", type=int, default=64, dest="block_size",
        help="token-block size (must match the workers' page size)",
    )
    routerp.add_argument(
        "--salt", default=None,
        help="hash salt — REQUIRED, must be the served model name "
             "(workers content-address KV blocks with salt=<model>)",
    )
    routerp.add_argument(
        "--host", default="127.0.0.1",
        help="address this router advertises to frontends (must be "
             "routable from other machines in multi-host deployments)",
    )
    routerp.add_argument(
        "--shards", type=int, default=1,
        help="index shards (each with its own event pump thread) — scale "
             "event application past one pump at high fleet event rates",
    )

    metricsp = sub.add_parser("metrics", help="Prometheus metrics service")
    metricsp.add_argument("--fabric", required=True, help="fabric host:port")
    metricsp.add_argument("--component", default="backend")
    metricsp.add_argument("--host", default="127.0.0.1")
    metricsp.add_argument("--port", type=int, default=9091)
    metricsp.add_argument(
        "--log-file", default=None, dest="log_file", metavar="NAME|PATH",
        help="also log (JSONL) to this file; a bare name lands in "
             "DYNTPU_LOG_DIR (default artifacts/log), never the CWD",
    )
    metricsp.add_argument(
        "--trace-sample-rate", type=int, default=None,
        dest="trace_sample_rate", metavar="N",
        help="fleet trace plane: keep 1-in-N HEALTHY traces (anomalous "
             "ones — slow/error/replayed/incomplete — are always kept); "
             "0 keeps none but the anomalies. Default 10, or "
             "DYNTPU_TRACE_SAMPLE_RATE",
    )
    metricsp.add_argument(
        "--trace-window", type=float, default=2.0, dest="trace_window",
        metavar="SECONDS",
        help="trace assembly quiet window before a trace finalizes "
             "through the tail sampler (stragglers arriving later "
             "attach to kept traces; default 2.0)",
    )
    metricsp.add_argument(
        "--trace-keep", type=int, default=512, dest="trace_keep",
        metavar="N",
        help="kept-trace ring capacity at the metrics service "
             "(LRU-evicted; default 512)",
    )

    planp = sub.add_parser("planner", help="autoscale the worker fleet")
    planp.add_argument("--fabric", required=True, help="fabric host:port")
    planp.add_argument(
        "--mode", default="load", choices=["load", "sla", "closed"],
        help="load: KV/queue thresholds; sla: offline perf tables; "
             "closed: the live closed loop — scales on the fleet's "
             "OBSERVED SLO burn/attainment (worker SLO sketches) with "
             "hysteresis bands, per-role cooldowns, and a per-tick "
             "action clamp (docs/operations.md 'Closed-loop "
             "autoscaling & role flips')",
    )
    planp.add_argument(
        "--flip", action="store_true",
        help="closed mode: prefer flipping an idle worker between "
             "prefill/decode roles (drain + re-register; hot KV pages "
             "survive) over kill+spawn. Default off.",
    )
    planp.add_argument(
        "--cooldown", type=float, default=30.0,
        help="closed mode: seconds between scale actions on one role",
    )
    planp.add_argument(
        "--flip-cooldown", type=float, default=60.0, dest="flip_cooldown",
        help="closed mode: seconds between role flips fleet-wide",
    )
    planp.add_argument(
        "--max-actions", type=int, default=2, dest="max_actions",
        help="closed mode: hard per-tick actuation clamp (scales+flips)",
    )
    planp.add_argument(
        "--no-handover", action="store_false", dest="handover",
        help="closed mode: scale-down kills workers instead of retiring "
             "them by live KV handover (docs/operations.md 'Rolling "
             "upgrades & worker handover'). Default: handover preferred, "
             "kill as fallback.",
    )
    planp.add_argument(
        "--rolling-upgrade", action="store_true", dest="rolling_upgrade",
        help="run ONE rolling-upgrade sweep instead of the control loop: "
             "replace every worker one at a time (spawn replacement -> "
             "wait registered -> handover -> cooldown), then exit. Zero "
             "dropped streams; in-flight work continues on warm KV.",
    )
    planp.add_argument(
        "--upgrade-cooldown", type=float, default=5.0,
        dest="upgrade_cooldown",
        help="rolling upgrade: seconds between replaced workers",
    )
    planp.add_argument("--namespace", default="dynamo")
    planp.add_argument("--component", default="backend")
    planp.add_argument("--interval", type=float, default=10.0)
    planp.add_argument("--min-decode", type=int, default=1, dest="min_decode")
    planp.add_argument("--max-decode", type=int, default=8, dest="max_decode")
    planp.add_argument("--min-prefill", type=int, default=0, dest="min_prefill")
    planp.add_argument("--max-prefill", type=int, default=4, dest="max_prefill")
    planp.add_argument(
        "--ttft-ms", type=float, default=200.0, dest="ttft_ms",
        help="SLA mode: time-to-first-token target",
    )
    planp.add_argument(
        "--itl-ms", type=float, default=20.0, dest="itl_ms",
        help="SLA mode: inter-token-latency target",
    )
    planp.add_argument(
        "--perf-table", default=None, dest="perf_table",
        help="SLA mode: JSON from benchmarks/profile_sla.py "
             '({"ttft_vs_rate": [[rate, ms], ...], "itl_vs_rate": [...]})',
    )
    planp.add_argument("--model", default="tiny", help="model spawned workers serve")
    planp.add_argument(
        "--checkpoint", default=None, help="checkpoint dir for spawned workers"
    )
    planp.add_argument(
        "--kv-economy", action="store_true", dest="kv_economy",
        help="price scale decisions with the KV-economy CostModel "
             "(docs/operations.md 'The KV economy'): scale-down hands "
             "over only when the victim's resident KV is worth the "
             "bytes, and each scale-up is followed by a prefix "
             "pre-warm of the newcomer from the hottest peer",
    )
    planp.add_argument(
        "--worker-args", default="", dest="worker_args",
        help="extra flags appended to spawned worker commands",
    )
    planp.add_argument(
        "--connector", default="local", choices=["local", "kube"],
        help="local: spawn worker processes on this host; kube: edit the "
             "DynamoGraphDeployment CR and let the operator reconcile",
    )
    planp.add_argument(
        "--cr-name", default=None, dest="cr_name",
        help="kube connector: DynamoGraphDeployment name",
    )
    planp.add_argument(
        "--k8s-namespace", default="default", dest="k8s_namespace",
        help="kube connector: namespace of the CR",
    )
    def _role_service(value: str) -> str:
        if "=" not in value:
            raise argparse.ArgumentTypeError(
                f"expected role=ServiceName, got {value!r}"
            )
        return value

    planp.add_argument(
        "--role-service", action="append", default=[], dest="role_service",
        type=_role_service,
        help="kube connector: role=ServiceName mapping (repeatable), e.g. "
             "--role-service decode=Worker --role-service "
             "prefill=PrefillWorkerService",
    )

    return p


def _ext_command(
    argv: list[str], out_value: str, tail: list[str], extra: list[str]
) -> list[str]:
    """Assemble the external-engine command from `out=ext:<cmd>` plus any
    argv tokens dynamo-tpu itself did not claim, in their original order.
    The quoted form (`'out=ext:python -m pkg --flag'`) is exact; unquoted
    trailing tokens pass through only if no dynamo-tpu option consumed
    them first (collisions like --model need the quoted form)."""
    import shlex

    cmd = shlex.split(out_value[len("ext:"):])
    pool = list(tail) + list(extra)
    seen_out = False
    for tok in argv:
        if not seen_out:
            seen_out = tok == "out=" + out_value
            continue
        if tok in pool:
            pool.remove(tok)
            cmd.append(tok)
    cmd += pool  # anything left (defensive: tokens before out=)
    if not cmd:
        raise SystemExit("out=ext: needs a command, e.g. "
                         "'out=ext:python -m my_shim'")
    return cmd


def main(argv: Optional[list[str]] = None) -> None:
    p = build_parser()
    raw_argv = list(argv) if argv is not None else sys.argv[1:]
    args, extra_argv = p.parse_known_args(argv)
    if extra_argv and not any(a.startswith("out=ext:") for a in raw_argv):
        p.error(f"unrecognized arguments: {' '.join(extra_argv)}")
    if args.cmd == "planner" and args.connector == "kube":
        if not args.cr_name:
            p.error("--cr-name is required with --connector kube")
        if not args.role_service:
            # Without mappings the connector falls back to service==role,
            # which never matches real CR service names — the planner would
            # start healthy and silently never scale.
            p.error(
                "--connector kube requires at least one --role-service "
                "mapping (e.g. --role-service decode=Worker)"
            )
    configure_logging(log_file=getattr(args, "log_file", None))
    # chaos harness: subprocess workers join fault-injection scenarios
    # via DYNTPU_FAULTS (no-op when unset — dynamo_tpu/testing/faults.py)
    from dynamo_tpu.testing.faults import install_from_env

    install_from_env()
    if getattr(args, "trace", False):
        from dynamo_tpu import telemetry

        telemetry.configure(enabled=True)

    # Manifest/introspection commands don't touch the native hot path —
    # dispatch them before the (possibly minutes-long) native compile.
    if args.cmd in ("build", "deploy"):
        from dynamo_tpu.sdk.build import (
            build_manifest,
            render_k8s,
            write_build,
            write_k8s,
        )
        from dynamo_tpu.sdk.config import load_config

        config = load_config(args.config) if args.config else {}
        manifest = build_manifest(args.graph, config, image=args.image)
        path = write_build(manifest, args.output)
        print(f"wrote {path} ({len(manifest['services'])} services)")
        if args.cmd == "deploy":
            if args.cr:
                import yaml as _yaml

                from dynamo_tpu.sdk.build import _k8s_name

                name = args.name or _k8s_name(args.graph.split(":")[-1])
                cr = {
                    "apiVersion": "dynamo.tpu/v1alpha1",
                    "kind": "DynamoGraphDeployment",
                    "metadata": {"name": name},
                    "spec": {
                        "image": manifest["image"],
                        "fabricHost": args.fabric_host,
                        "services": manifest["services"],
                    },
                }
                if args.fabric_external:
                    # target a platform-managed fabric: the operator must
                    # not render (and fight over) a per-graph one
                    cr["spec"]["fabricExternal"] = True
                os.makedirs(args.output, exist_ok=True)
                kpath = os.path.join(args.output, "graph-deployment.yaml")
                with open(kpath, "w") as f:
                    _yaml.safe_dump(cr, f, sort_keys=False)
                print(f"wrote {kpath} (DynamoGraphDeployment/{name})")
            else:
                objs = render_k8s(manifest, fabric_host=args.fabric_host)
                kpath = write_k8s(objs, args.output)
                print(f"wrote {kpath} ({len(objs)} objects)")
        return

    if args.cmd == "operator":
        from dynamo_tpu.operator.controller import main as operator_main

        operator_main(
            ["--namespace", args.namespace, "--interval", str(args.interval)]
        )
        return

    if args.cmd == "env":
        import json as _json

        from dynamo_tpu.sdk.build import env_report

        print(_json.dumps(env_report(), indent=2))
        return

    # Compile the native hot-path core before serving so no request admission
    # or router construction ever waits on g++ (falls back to Python if the
    # toolchain is missing).
    from dynamo_tpu.native import ensure_built

    ensure_built()

    if args.cmd == "fabric":
        if getattr(args, "promote", None):
            from dynamo_tpu.runtime.fabric.replica import promote_standby

            reply = asyncio.run(promote_standby(args.promote))
            print(json.dumps({"promote": args.promote, "reply": reply}),
                  flush=True)
            sys.exit(0 if reply.get("ok") else 1)
        if getattr(args, "standby_of", None) or getattr(args, "peer", None):
            # HA broker (standby, or a primary that can be fenced by
            # peers); the flag-less path below stays the single-broker
            # server, bit-identical to before
            from dynamo_tpu.runtime.fabric.replica import FabricNode

            async def _ha_main() -> None:
                node = FabricNode(
                    args.host, args.port,
                    persist_dir=args.persist_dir,
                    standby_of=args.standby_of,
                    peers=tuple(args.peer),
                    detector_budget_s=args.detector_budget,
                    auto_promote=args.auto_promote,
                )
                await node.start()
                print(
                    f"fabric {node.role} on {node.address}"
                    + (
                        # live primary address, not args.standby_of: a
                        # primary-eligible node that DEFERRED to a
                        # higher-fenced peer is a standby of that peer
                        f" (standby of {node.server.primary_address})"
                        if node.role == "standby"
                        else ""
                    ),
                    flush=True,
                )
                if node.role == "primary":
                    node.promoted.clear()  # report only LATER promotions
                try:
                    while True:
                        await node.promoted.wait()
                        print(
                            f"fabric PROMOTED to primary on "
                            f"{node.address} (fence "
                            f"{node.fabric.fence})",
                            flush=True,
                        )
                        node.promoted.clear()
                        # a later demotion re-arms the wait
                finally:
                    await node.stop()

            asyncio.run(_ha_main())
            return
        from dynamo_tpu.runtime.fabric.server import _amain

        asyncio.run(_amain(args))
        return

    if args.cmd == "planner":
        asyncio.run(_run_planner(args))
        return

    if args.cmd == "metrics":
        asyncio.run(_run_metrics(args))
        return

    if args.cmd == "router":
        from dynamo_tpu.kv_router.service import run_router

        asyncio.run(run_router(args))
        return

    if args.cmd == "serve":
        asyncio.run(_run_serve(args))
        return

    if args.cmd == "ctl":
        asyncio.run(_run_ctl(args))
        return

    if any(t.startswith("out=ext:") for t in args.io):
        # ext mode: in=/out= must be unique, and every OTHER io token —
        # including stray k=v ones like `config=prod.yaml` or a second
        # `out=jax` — belongs to the engine command. The plain dict parse
        # below would silently swallow them (or worse, reroute the whole
        # invocation to a different engine via last-wins out=).
        io = {}
        leftover = []
        for tok in args.io:
            k, sep, v = tok.partition("=")
            if sep and k in ("in", "out"):
                if k in io:
                    p.error(
                        f"duplicate {k}= with out=ext: — quote the whole "
                        f"engine command ('out=ext:python -m ...')"
                    )
                io[k] = v
                continue
            leftover.append(tok)
        inp = io.get("in", "text")
        args.out = io["out"]
        args.ext_cmd = _ext_command(raw_argv, args.out, leftover, extra_argv)
    else:
        io = dict(kv.split("=", 1) for kv in args.io if "=" in kv)
        inp = io.get("in", "text")
        args.out = io.get("out", "jax")

    if getattr(args, "coordinator", None):
        if inp != "dyn" or args.out != "jax":
            # The lockstep group only exists behind the worker path
            # (in=dyn builds an SpmdEngineRunner on host 0). Any other
            # input on ANY host would build a plain runner whose first
            # jitted dispatch blocks forever in cross-host collectives
            # with no followers participating. Gate BEFORE init_multihost
            # — that call blocks until every host joins, so a post-init
            # check would hang instead of failing fast.
            print(
                "multi-host SPMD serving requires `run in=dyn out=jax` "
                "on every host (put an `in=http` frontend in a separate "
                "process, attached over the fabric)",
                file=sys.stderr,
            )
            sys.exit(2)
        from dynamo_tpu.parallel.mesh import init_multihost

        n = init_multihost(args.coordinator, args.num_hosts, args.host_id)
        print(
            f"multi-host up: host {args.host_id}/{args.num_hosts}, "
            f"{n} global devices",
            flush=True,
        )
        if args.host_id > 0:
            # Follower replica of a cross-host SPMD group: no fabric, no
            # ingress — just mirror the leader's lockstep broadcasts
            # until its shutdown (engine/spmd.py).
            _run_spmd_follower(args)
            return

    if inp == "dyn":
        asyncio.run(_run_worker(args))
    elif inp == "http":
        asyncio.run(_run_http(args))
    elif inp.startswith("batch:"):
        asyncio.run(_run_batch(args, inp.split(":", 1)[1]))
    elif inp in ("text", "stdin"):
        asyncio.run(_run_text(args))
    else:
        print(f"unknown in={inp}", file=sys.stderr)
        sys.exit(2)


if __name__ == "__main__":
    main()
