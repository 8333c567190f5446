"""Concurrency-sweep serving benchmark.

The reference's genai-perf harness shape (benchmarks/llm/perf.sh: ISL 3000 /
OSL 150, concurrency sweep 1-256, streaming) pointed at either:
- the in-process engine (`--mode engine`, default — what the driver's
  bench.py wraps), or
- a live OpenAI frontend (`--mode http --url http://host:port`), measuring
  the full network path.

Per concurrency level: output tok/s, request/s, TTFT p50/p95, ITL p50/p95.
Prints one JSON document; `--csv` emits a sweep table.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import time
from dataclasses import dataclass
from typing import Optional


@dataclass
class RequestResult:
    ttft_s: Optional[float]
    latency_s: float
    output_tokens: int
    itls_s: list[float]


def _percentiles(values, ps=(50, 95)):
    from benchmarks._procs import pct

    return {f"p{p}": pct(values, p / 100) for p in ps}


def summarize(results: list[RequestResult], wall_s: float) -> dict:
    ttfts = [r.ttft_s for r in results if r.ttft_s is not None]
    itls = [v for r in results for v in r.itls_s]
    out_tokens = sum(r.output_tokens for r in results)
    return {
        "requests": len(results),
        "wall_s": round(wall_s, 3),
        "output_tok_s": round(out_tokens / wall_s, 2) if wall_s else 0.0,
        "req_s": round(len(results) / wall_s, 3) if wall_s else 0.0,
        "ttft_ms": {
            k: round(v * 1e3, 2) if v is not None else None
            for k, v in _percentiles(ttfts).items()
        },
        "itl_ms": {
            k: round(v * 1e3, 3) if v is not None else None
            for k, v in _percentiles(itls).items()
        },
    }


# -- engine mode ------------------------------------------------------------


def tpu_bf16_peak_flops() -> Optional[float]:
    """Per-chip bf16 peak for the attached TPU generation (public specs);
    None when not on TPU or the generation is unrecognized."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    # normalize "TPU v5 lite" -> "tpuv5lite" so spaced kinds match
    kind = jax.devices()[0].device_kind.lower().replace(" ", "")
    for tag, peak in (
        ("v6e", 918e12), ("v6", 918e12), ("v5p", 459e12),
        ("v5e", 197e12), ("v5lite", 197e12), ("v4", 275e12),
    ):
        if tag in kind:
            return peak
    return None


def engine_mfu(engine, prompt_tokens: int, output_tokens: int, wall_s: float) -> Optional[float]:
    """Approximate model-FLOPs utilization: ~2*params FLOPs per token
    (prefill and decode both; attention is second-order at these lengths)
    against the chip's bf16 peak. None off-TPU or unknown generation."""
    import jax

    peak = tpu_bf16_peak_flops()
    if peak is None:
        return None
    n_params = sum(int(x.size) for x in jax.tree.leaves(engine.params))
    return (2.0 * n_params * (prompt_tokens + output_tokens) / wall_s) / peak


def bench_engine(
    engine, prompts: list[tuple[list[int], int]], concurrency: int
) -> dict:
    """Closed-loop: keep `concurrency` requests in flight inside the
    engine's step loop; measure per-request TTFT/ITL from step timestamps."""
    from dynamo_tpu.engine.request import SamplingParams

    pending = list(enumerate(prompts))
    timing0 = {
        k: getattr(engine.metrics, k)
        for k in type(engine.metrics).TIMING_FIELDS
    }
    starts: dict[str, float] = {}
    first: dict[str, float] = {}
    last: dict[str, float] = {}
    itls: dict[str, list[float]] = {}
    counts: dict[str, int] = {}
    done: list[str] = []

    def submit_next() -> bool:
        if not pending:
            return False
        i, (toks, osl) = pending.pop(0)
        rid = f"r{i}"
        engine.add_request(
            rid, toks, SamplingParams(max_tokens=osl, ignore_eos=True)
        )
        starts[rid] = time.perf_counter()
        itls[rid] = []
        counts[rid] = 0
        return True

    for _ in range(concurrency):
        submit_next()
    t0 = time.perf_counter()
    while engine.has_work:
        outs = engine.step()
        now = time.perf_counter()
        for o in outs:
            rid = o.request_id
            if o.new_token_ids:
                n = len(o.new_token_ids)
                counts[rid] += n
                if rid not in first:
                    first[rid] = now  # whole first output counts as TTFT
                if rid in last and n > 0:
                    # Fused multi-step decode and speculative acceptance
                    # emit several tokens per step: spread the step interval
                    # so ITL stays per-token, not per-dispatch.
                    gap = (now - last[rid]) / n
                    itls[rid].extend([gap] * n)
                last[rid] = now
            if o.finish_reason is not None:
                done.append(rid)
                submit_next()
    wall = time.perf_counter() - t0
    results = [
        RequestResult(
            ttft_s=(first[rid] - starts[rid]) if rid in first else None,
            latency_s=(last.get(rid, starts[rid]) - starts[rid]),
            output_tokens=counts[rid],
            itls_s=itls[rid],
        )
        for rid in done
    ]
    out = summarize(results, wall)
    mfu = engine_mfu(
        engine,
        prompt_tokens=sum(len(p) for p, _ in prompts[: len(done)]),
        output_tokens=sum(counts[rid] for rid in done),
        wall_s=wall,
    )
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    # the engine's step-phase timing plane, as a DELTA over this call —
    # per-level numbers that exclude warmup/compile from earlier calls
    m = engine.metrics
    out["engine_timing"] = {
        k: round(getattr(m, k) - timing0[k], 1) for k in timing0
    }
    return out


# -- http mode --------------------------------------------------------------


async def _one_http(session, url: str, model: str, prompt_text: str, osl: int):
    payload = {
        "model": model,
        "messages": [{"role": "user", "content": prompt_text}],
        "stream": True,
        "max_tokens": osl,
    }
    t0 = time.perf_counter()
    ttft = None
    prev = None
    itls: list[float] = []
    n = 0
    async with session.post(url + "/v1/chat/completions", json=payload) as resp:
        resp.raise_for_status()
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data:") or line == "data: [DONE]":
                continue
            now = time.perf_counter()
            n += 1
            if ttft is None:
                ttft = now - t0
            else:
                itls.append(now - prev)
            prev = now
    return RequestResult(
        ttft_s=ttft, latency_s=time.perf_counter() - t0, output_tokens=n,
        itls_s=itls,
    )


async def bench_http(
    url: str, model: str, prompts: list[tuple[str, int]], concurrency: int,
    request_timeout_s: float | None = None,
) -> dict:
    """`request_timeout_s` bounds each request's total stream time; timed-out
    or errored requests are counted (summary key `failed`) instead of killing
    the whole run — the surviving requests still yield an honest partial
    measurement."""
    import aiohttp

    queue: asyncio.Queue = asyncio.Queue()
    for p in prompts:
        queue.put_nowait(p)
    results: list[RequestResult] = []
    failures = 0
    # None keeps aiohttp's default (total=300 s); ClientTimeout(total=None)
    # would instead disable the bound and let a wedged stream hang forever
    kw = (
        {"timeout": aiohttp.ClientTimeout(total=request_timeout_s)}
        if request_timeout_s is not None else {}
    )

    async with aiohttp.ClientSession(**kw) as session:

        async def worker():
            nonlocal failures
            while True:
                try:
                    text, osl = queue.get_nowait()
                except asyncio.QueueEmpty:
                    return
                try:
                    results.append(
                        await _one_http(session, url, model, text, osl)
                    )
                except (asyncio.TimeoutError, aiohttp.ClientError):
                    failures += 1

        t0 = time.perf_counter()
        await asyncio.gather(*(worker() for _ in range(concurrency)))
        wall = time.perf_counter() - t0
    out = summarize(results, wall)
    if failures:
        out["failed"] = failures
    return out


def warmup_and_flush(
    url: str, model: str, texts: list[tuple[str, int]], warmup: int,
    concurrency: int, request_timeout_s: float | None = None,
) -> None:
    """Compile-then-flush prelude for HTTP A/B harnesses: drive `warmup`
    uncached random prompts whose lengths span the timed sweep's length
    spread (prefill shapes are bucketed — warming one length leaves other
    buckets to cold-compile inside the timed window), then POST
    /clear_kv_blocks so the timed run starts cold on prefixes but warm on
    XLA. Random prompts share no prefix, so a kv router balances them by
    load across ALL workers."""
    if not warmup:
        return
    import random
    import urllib.request

    r = random.Random(13)
    lens = sorted({len(t) for t, _ in texts})
    picks = [
        lens[i * (len(lens) - 1) // max(1, warmup - 1)]
        for i in range(warmup)
    ]
    osl = texts[0][1]
    warm = [
        ("".join(chr(97 + r.randrange(26)) for _ in range(n)), osl)
        for n in picks
    ]
    asyncio.run(
        bench_http(url, model, warm, concurrency,
                   request_timeout_s=request_timeout_s)
    )
    req = urllib.request.Request(
        f"{url}/clear_kv_blocks", data=b"{}",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.status == 200


# -- CLI --------------------------------------------------------------------


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="concurrency-sweep benchmark")
    p.add_argument("--mode", choices=["engine", "http"], default="engine")
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--model", default="llama3-1b")
    p.add_argument("--num-requests", type=int, default=32, dest="num_requests")
    p.add_argument("--isl", type=int, default=128)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument(
        "--concurrency", default="1,4,16",
        help="comma-separated sweep levels",
    )
    p.add_argument("--num-pages", type=int, default=2048, dest="num_pages")
    p.add_argument("--page-size", type=int, default=64, dest="page_size")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument(
        "--spec-ngram", type=int, default=0, dest="spec_ngram",
        help="engine mode: speculative decoding draft length (0 = off)",
    )
    p.add_argument(
        "--spec-draft", default=None, dest="spec_draft",
        help="engine mode: draft-model speculation (same-vocab small "
        "model, e.g. llama3-draft; composes with overlap + mixed steps)",
    )
    p.add_argument(
        "--spec-draft-tokens", type=int, default=4,
        dest="spec_draft_tokens",
        help="engine mode: drafts proposed per spec step (with "
        "--spec-draft)",
    )
    p.add_argument(
        "--quantize", default=None, choices=["int8"],
        help="engine mode: weight-only quantization",
    )
    p.add_argument(
        "--prefill-budget", type=int, default=None, dest="prefill_budget",
        help="engine mode: prefill tokens per step across sequences "
        "(EngineConfig.prefill_token_budget; default 4x prefill_chunk). "
        "The saturation-TTFT knob: a bigger budget batches more prompts "
        "into one prefill dispatch, draining an arrival burst in fewer, "
        "larger steps at the cost of longer decode stalls while it runs.",
    )
    p.add_argument(
        "--prefill-policy", default="fixed", dest="prefill_policy",
        choices=["fixed", "adaptive"],
        help="engine mode: adaptive grows the step budget with the "
        "un-prefilled backlog (to 4x the budget), draining saturation "
        "bursts in O(1) dispatches without raising the idle-time budget",
    )
    p.add_argument(
        "--prefill-budget-max", type=int, default=None,
        dest="prefill_budget_max",
        help="engine mode: adaptive-policy ceiling (default 4x the "
        "budget); bounds the worst-case single prefill dispatch and so "
        "the ITL spike it can inflict",
    )
    p.add_argument(
        "--prefill-chunk", type=int, default=None, dest="prefill_chunk",
        help="engine mode: per-sequence prefill chunk length",
    )
    p.add_argument(
        "--decode-steps", type=int, default=None, dest="decode_steps",
        help="engine mode: decode steps fused per dispatch (one host sync "
        "per K tokens/seq). Default: engine default (8)",
    )
    p.add_argument(
        "--distribution", default="geometric",
        choices=["geometric", "sharegpt"],
        help="ISL/OSL law; sharegpt = lognormal heavy-tail mixture",
    )
    p.add_argument("--csv", action="store_true")
    args = p.parse_args(argv)


    from benchmarks.synthesizer import SynthConfig, synthesize

    reqs = synthesize(
        SynthConfig(
            num_requests=args.num_requests,
            depth=0,
            mean_suffix_len=args.isl,
            mean_output_len=args.osl,
            distribution=args.distribution,
        )
    )
    levels = [int(x) for x in args.concurrency.split(",")]
    sweep = []
    if args.mode == "engine":
        from dynamo_tpu.engine import EngineConfig
        from dynamo_tpu.engine.engine import JaxEngine

        prompts = [(list(r.prompt_tokens), r.output_len) for r in reqs]
        # Budget pages for the ACTUAL longest sequence — the geometric
        # suffix has a heavy tail and a mean-sized budget trips the
        # scheduler's max-context guard mid-run.
        longest = max(len(p) + osl for p, osl in prompts)
        engine = JaxEngine(
            EngineConfig(
                model=args.model,
                num_pages=args.num_pages,
                page_size=args.page_size,
                max_pages_per_seq=max(8, -(-(longest + 1) // args.page_size)),
                dtype=args.dtype,
                enable_prefix_caching=False,
                spec_ngram=args.spec_ngram,
                spec_draft_model=args.spec_draft,
                spec_draft_tokens=args.spec_draft_tokens,
                quantize=args.quantize,
                prefill_token_budget=args.prefill_budget,
                prefill_budget_policy=args.prefill_policy,
                prefill_budget_max=args.prefill_budget_max,
                **(
                    {"prefill_chunk": args.prefill_chunk}
                    if args.prefill_chunk is not None
                    else {}
                ),
                **(
                    {"decode_steps": args.decode_steps}
                    if args.decode_steps is not None
                    else {}
                ),
            )
        )
        # warmup compiles every program shape the sweep will touch
        bench_engine(engine, prompts[: max(levels)], max(levels))
        for c in levels:
            sweep.append({"concurrency": c, **bench_engine(engine, prompts, c)})
    else:
        texts = [
            (" ".join(str(t) for t in r.prompt_tokens[: args.isl // 4]),
             r.output_len)
            for r in reqs
        ]
        for c in levels:
            sweep.append(
                {
                    "concurrency": c,
                    **asyncio.run(bench_http(args.url, args.model, texts, c)),
                }
            )

    if args.csv:
        cols = ["concurrency", "output_tok_s", "req_s"]
        print(",".join(cols + ["ttft_p50_ms", "itl_p50_ms"]))
        for row in sweep:
            print(
                ",".join(
                    str(x)
                    for x in (
                        row["concurrency"], row["output_tok_s"], row["req_s"],
                        row["ttft_ms"]["p50"], row["itl_ms"]["p50"],
                    )
                )
            )
    else:
        print(json.dumps({"mode": args.mode, "sweep": sweep}, indent=2))


if __name__ == "__main__":
    main()
