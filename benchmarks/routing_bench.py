"""KV-aware routing vs round-robin: the reference's headline routing claim
reproduced in simulation at fleet scale.

The reference reports 3x TTFT / 2x mean latency from KV-aware routing on
prefix-heavy real traffic (architecture.md:91). This harness stands up N
batched mock workers (real PageAllocators, real KV events, watermark
scheduler — mocker/engine.py) over a real fabric server, drives the SAME
prefix-tree workload (synthesizer.py, the reference's
data_generator/synthesizer.py shape) through a round-robin router and a
KV router, and reports per-mode TTFT/latency percentiles plus the fleet
prefix-hit rate.

Prefill cost in the mocker is proportional to UNCACHED tokens, so the win
measured here is the same mechanism as on hardware: routing to the worker
that already holds the prefix skips recomputing it.

Usage:  python -m benchmarks.routing_bench [--workers 4] [--requests 200]
Prints one JSON document; --markdown appends a row table to stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time


from benchmarks._procs import pct as _pct


async def _drive_mode(
    mode: str,
    num_workers: int,
    reqs,
    page: int,
    decode_tick_s: float,
    prefill_budget: int,
    concurrency: int,
    num_pages: int,
) -> dict:
    from dynamo_tpu.kv_router import KvRouter, KvRouterConfig
    from dynamo_tpu.mocker import MockEngineArgs
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime import DistributedRuntime, RouterMode
    from dynamo_tpu.runtime.fabric import FabricServer
    from dynamo_tpu.runtime.push_router import PushRouter
    from dynamo_tpu.worker import Worker

    card = ModelDeploymentCard(name="mock-model", kv_page_size=page)
    server = FabricServer(port=0)
    await server.start()
    runtimes, workers = [], []
    try:
        for _ in range(num_workers):
            rt = await DistributedRuntime.create(server.address)
            w = Worker(
                rt, card, engine_kind="mock", namespace="bench",
                metrics_interval=0.05, router_mode=mode,
                # decode-realistic ticks; small prefill budget makes the
                # workload prefill-bound like long-ISL serving
                mock_args=MockEngineArgs(
                    page_size=page, salt=card.name,
                    num_pages=num_pages,
                    decode_s_per_step=decode_tick_s,
                    prefill_tokens_per_step=prefill_budget,
                ),
            )
            await w.start()
            runtimes.append(rt)
            workers.append(w)

        rt_c = await DistributedRuntime.create(server.address)
        runtimes.append(rt_c)
        ep = rt_c.namespace("bench").component("backend").endpoint("generate")
        src = await ep.instance_source()
        if mode == "kv":
            kv = KvRouter(
                rt_c.fabric, "backend", src, block_size=page,
                salt=card.name, config=KvRouterConfig(temperature=0.0),
            )
            await kv.start()
            router = PushRouter(
                src, "generate", mode=RouterMode.KV, kv_chooser=kv.choose
            )
        else:
            kv = None
            router = PushRouter(src, "generate", mode=RouterMode.ROUND_ROBIN)
        await src.wait_for_instances()

        sem = asyncio.Semaphore(concurrency)
        ttfts, latencies = [], []

        async def one(i, r):
            async with sem:
                t0 = time.perf_counter()
                first = None
                req = {
                    "request_id": f"{mode}-{i}",
                    "token_ids": list(r.prompt_tokens),
                    "max_tokens": max(4, min(r.output_len, 32)),
                    "temperature": 0.0, "top_p": 1.0, "top_k": 0,
                    "seed": None, "stop_token_ids": [], "stop_strings": [],
                    "ignore_eos": True, "annotations": {},
                }
                async for item in router.generate(req):
                    if first is None and item.get("token_ids"):
                        first = time.perf_counter() - t0
                ttfts.append(first)
                latencies.append(time.perf_counter() - t0)

        t0 = time.perf_counter()
        await asyncio.gather(*(one(i, r) for i, r in enumerate(reqs)))
        wall = time.perf_counter() - t0

        hit_tokens = sum(
            w.mock.allocator.stats.hit_tokens for w in workers
        )
        query_tokens = sum(
            w.mock.allocator.stats.query_tokens for w in workers
        )
        out = {
            "mode": mode,
            "ttft_ms": {
                "p50": round(_pct(ttfts, 0.5) * 1e3, 1),
                "p95": round(_pct(ttfts, 0.95) * 1e3, 1),
            },
            "latency_ms": {
                "p50": round(_pct(latencies, 0.5) * 1e3, 1),
                "p95": round(_pct(latencies, 0.95) * 1e3, 1),
            },
            "wall_s": round(wall, 2),
            "prefix_hit_rate": round(hit_tokens / max(query_tokens, 1), 3),
        }
        if kv is not None:
            await kv.stop()
        return out
    finally:
        for w in workers:
            await w.stop(drain_timeout=1)
        for rt in runtimes:
            await rt.close()
        await server.stop()


async def bench(args) -> dict:
    from benchmarks.synthesizer import SynthConfig, sharing_stats, synthesize

    reqs = synthesize(
        SynthConfig(
            num_requests=args.requests,
            node_len=args.page,          # one tree node = one KV page
            branching=args.branching,
            depth=args.depth,
            mean_suffix_len=args.suffix,
            mean_output_len=16,
            seed=7,
        )
    )
    share = sharing_stats(reqs, block_size=args.page)
    out = {
        "workload": {
            "requests": args.requests, "workers": args.workers,
            "shared_tree": f"depth {args.depth} x node {args.page}",
            "block_reuse_fraction": round(share["reuse_fraction"], 3),
        },
        "modes": {},
    }
    for mode in ("round_robin", "kv"):
        out["modes"][mode] = await _drive_mode(
            mode, args.workers, reqs, args.page,
            decode_tick_s=args.tick, prefill_budget=args.prefill_budget,
            concurrency=args.concurrency, num_pages=args.pages,
        )
    rr, kvm = out["modes"]["round_robin"], out["modes"]["kv"]
    out["kv_ttft_speedup_p50"] = round(
        rr["ttft_ms"]["p50"] / max(kvm["ttft_ms"]["p50"], 1e-9), 2
    )
    out["kv_latency_speedup_p50"] = round(
        rr["latency_ms"]["p50"] / max(kvm["latency_ms"]["p50"], 1e-9), 2
    )
    return out


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="KV routing vs round robin")
    p.add_argument("--workers", type=int, default=4)
    p.add_argument("--requests", type=int, default=200)
    p.add_argument("--page", type=int, default=16)
    p.add_argument("--pages", type=int, default=128,
                   help="per-worker KV pool pages (bounded: duplicated "
                        "caching under round-robin thrashes, as on HW)")
    p.add_argument("--depth", type=int, default=6)
    p.add_argument("--branching", type=int, default=4)
    p.add_argument("--suffix", type=int, default=32)
    p.add_argument("--concurrency", type=int, default=16)
    p.add_argument(
        "--tick", type=float, default=0.004,
        help="mock decode seconds per step",
    )
    p.add_argument(
        "--prefill-budget", type=int, default=16, dest="prefill_budget",
        help="mock prefill tokens per tick (lower = prefill-bound, like "
             "long-ISL serving)",
    )
    args = p.parse_args(argv)

    print(json.dumps(asyncio.run(bench(args)), indent=1))


if __name__ == "__main__":
    main()
