"""KV-aware routing vs round-robin against REAL engines over HTTP.

routing_bench.py measures the routing win at fleet scale on mock workers;
this harness is the hardware complement (VERDICT r2: "routing_bench
against real engines"): N real JaxEngine workers behind the real frontend
in each router mode, driven with the same prefix-tree workload over
/v1/chat/completions. The win comes from the same mechanism the reference
claims 3x TTFT for (architecture.md:91): routing a request to the worker
whose paged cache already holds its prefix skips recomputing it.

On one TPU chip the N worker processes timeshare the device — identical
contention in both modes, so the A/B stays fair; absolute numbers are
lower than a one-process-per-chip fleet.

CPU smoke:  python -m benchmarks.routing_engine_bench
TPU:        python -m benchmarks.routing_engine_bench --model llama3-1b \
                --dtype bfloat16 --page 16 --pages 512 --max-context 2048 \
                --depth 4 --suffix 64 --requests 64 --concurrency 8
"""

from __future__ import annotations

import argparse
import asyncio
import json

from benchmarks._procs import ManagedProc as Proc
from benchmarks._procs import cli as _cli
from benchmarks._procs import free_port as _free_port


def _texts(args) -> tuple[list[tuple[str, int]], float]:
    from benchmarks.synthesizer import SynthConfig, sharing_stats, synthesize

    reqs = synthesize(
        SynthConfig(
            num_requests=args.requests,
            node_len=args.page,
            branching=args.branching,
            depth=args.depth,
            mean_suffix_len=args.suffix,
            mean_output_len=args.osl,
            seed=7,
        )
    )
    share = sharing_stats(reqs, block_size=args.page)
    limit = max(4, args.max_context - args.osl - 20)
    # byte tokenizer: one ascii char per token, so shared token prefixes
    # become shared TEXT prefixes and survive the chat template verbatim
    texts = [
        ("".join(chr(97 + (t % 26)) for t in r.prompt_tokens)[:limit],
         args.osl)
        for r in reqs
    ]
    return texts, share["reuse_fraction"]


def run_mode(args, mode: str, texts) -> dict:
    fport, hport = _free_port(), _free_port()
    engine = [
        "--model", args.model, "--dtype", args.dtype,
        "--page-size", str(args.page), "--num-pages", str(args.pages),
        "--max-context", str(args.max_context),
        "--router-mode", mode,
    ]
    if args.decode_steps is not None:
        engine += ["--decode-steps", str(args.decode_steps)]
    procs: list[Proc] = []
    try:
        fb = Proc("fabric", _cli("fabric", "--port", str(fport)))
        procs.append(fb)
        fb.wait_for("listening|fabric server on")
        for i in range(args.workers):
            w = Proc(
                f"worker{i}",
                _cli("run", "in=dyn", "out=jax", *engine,
                     "--fabric", f"127.0.0.1:{fport}"),
            )
            procs.append(w)
            w.wait_for(r"worker \w+ up", timeout=900)
        fe = Proc(
            "frontend",
            _cli("run", "in=http", "out=dyn",
                 "--fabric", f"127.0.0.1:{fport}", "--port", str(hport)),
        )
        procs.append(fe)
        fe.wait_for("listening on")
        fe.wait_for("model attached", timeout=120)

        from benchmarks.perf import bench_http, warmup_and_flush

        warmup_and_flush(
            f"http://127.0.0.1:{hport}", args.model, texts, args.warmup,
            args.concurrency,
        )

        out = asyncio.run(
            bench_http(
                f"http://127.0.0.1:{hport}", args.model, texts,
                args.concurrency,
            )
        )
        out["mode"] = mode
        return out
    except BaseException:
        import sys

        for p in procs:
            rc = p.proc.poll()
            print(f"--- {p.name}: {'alive' if rc is None else rc} "
                  f"({p.log_path})", file=sys.stderr)
            try:
                with open(p.log_path) as f:
                    print("\n".join(f.read().splitlines()[-20:]),
                          file=sys.stderr)
            except OSError:
                pass
        raise
    finally:
        for p in reversed(procs):
            p.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="real-engine routing A/B")
    p.add_argument("--model", default="tiny")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--page", type=int, default=4)
    p.add_argument("--pages", type=int, default=128)
    p.add_argument("--max-context", type=int, default=96, dest="max_context")
    p.add_argument("--depth", type=int, default=3)
    p.add_argument("--branching", type=int, default=4)
    p.add_argument("--suffix", type=int, default=8)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--warmup", type=int, default=8)
    p.add_argument("--osl", type=int, default=8)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--decode-steps", type=int, default=None,
                   dest="decode_steps",
                   help="worker decode fusion (steps per dispatch)")
    args = p.parse_args(argv)

    texts, reuse = _texts(args)
    results = {
        "workload": {
            "requests": args.requests, "workers": args.workers,
            "block_reuse_fraction": round(reuse, 3),
            "model": args.model,
        },
        "modes": {},
    }
    # round_robin first: neither mode inherits a warm cache from the other
    # (each mode boots a fresh fleet), so order only affects XLA's on-disk
    # compile cache, which warms identically for both.
    for mode in ("round_robin", "kv"):
        results["modes"][mode] = run_mode(args, mode, texts)
    rr, kv = results["modes"]["round_robin"], results["modes"]["kv"]
    if rr.get("ttft_ms") and kv.get("ttft_ms"):
        results["kv_ttft_speedup_p50"] = round(
            rr["ttft_ms"]["p50"] / max(kv["ttft_ms"]["p50"], 1e-9), 2
        )
        results["kv_ttft_speedup_p95"] = round(
            rr["ttft_ms"]["p95"] / max(kv["ttft_ms"]["p95"], 1e-9), 2
        )
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
