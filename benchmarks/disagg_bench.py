"""Agg vs disagg A/B: boot both topologies, drive identical traffic,
compare TTFT/ITL/throughput.

The reference's headline disagg claim (+30% throughput/GPU single node,
2x two nodes — architecture.md:75) comes from exactly this A/B: same
model, same traffic, aggregated vs disaggregated prefill/decode. This
harness launches the real serving stack via the CLI for each topology:

  agg:    fabric + 1 decode worker                + frontend
  disagg: fabric + 1 decode worker (remote prefill) + N prefill + frontend

and drives a long-ISL streaming workload over HTTP (benchmarks/perf.py's
bench_http), emitting one JSON document with both sweeps and the ratios.

CPU smoke: --model tiny --isl 24 --max-context 64. TPU: every engine
here is its own OS process and a chip belongs to one process at a time,
and nothing assigns a device to a child — on a one-chip host the second
engine process fails at start-up (platform.require_platform), on a
four-chip host the first child takes all four. Until the launcher
assigns devices (ROADMAP R4/R5) this A/B runs on the CPU only.

Usage: python -m benchmarks.disagg_bench --model llama3-8b --isl 3000 ...
"""

from __future__ import annotations

import argparse
import asyncio
import json

from benchmarks._procs import ManagedProc as Proc
from benchmarks._procs import cli as _cli
from benchmarks._procs import free_port as _free_port


def run_topology(args, disagg: bool) -> dict:
    fport, hport = _free_port(), _free_port()
    engine = [
        "--model", args.model, "--page-size", str(args.page_size),
        "--num-pages", str(args.num_pages), "--dtype", args.dtype,
        "--max-context", str(args.max_context),
    ]
    if args.quantize:
        engine += ["--quantize", args.quantize]
    if args.decode_steps is not None:
        engine += ["--decode-steps", str(args.decode_steps)]
    procs = []
    try:
        fb = Proc("fabric", _cli("fabric", "--port", str(fport)))
        procs.append(fb)
        fb.wait_for("listening|fabric server on")
        decode_flags = list(engine)
        if disagg:
            decode_flags += [
                "--disagg", "--max-local-prefill", str(args.max_local_prefill),
            ]
        d = Proc(
            "decode",
            _cli("run", "in=dyn", "out=jax", *decode_flags,
                 "--fabric", f"127.0.0.1:{fport}"),
        )
        procs.append(d)
        # two-stage wait: "booting" appears pre-engine-construction, so a
        # process that never starts fails in 180s instead of burning the
        # full engine-bringup budget; compiles after that get the long wait.
        d.wait_for(r"worker booting", timeout=180)
        d.wait_for(r"worker \w+ up", timeout=900)
        if disagg:
            for i in range(args.prefill_workers):
                p = Proc(
                    f"prefill{i}",
                    _cli("run", "in=dyn", "out=jax", *engine,
                         "--role", "prefill",
                         "--fabric", f"127.0.0.1:{fport}"),
                )
                procs.append(p)
                p.wait_for(r"worker booting", timeout=180)
                p.wait_for(r"prefill worker \w+ up", timeout=900)
        fe = Proc(
            "frontend",
            _cli("run", "in=http", "out=dyn",
                 "--fabric", f"127.0.0.1:{fport}", "--port", str(hport)),
        )
        procs.append(fe)
        fe.wait_for("listening on")
        fe.wait_for("model attached", timeout=120)

        from benchmarks.perf import bench_http
        from benchmarks.synthesizer import SynthConfig, synthesize

        reqs = synthesize(
            SynthConfig(
                num_requests=args.requests, depth=0,
                mean_suffix_len=args.isl, mean_output_len=args.osl, seed=3,
            )
        )
        # byte tokenizer serving: ship text whose TOKEN length ~= isl
        # (ascii chars map 1:1); clamp under the context budget
        limit = max(4, args.max_context - args.osl - 20)
        texts = [
            ("".join(chr(97 + (t % 26)) for t in r.prompt_tokens)[:limit],
             args.osl)
            for r in reqs
        ]
        from benchmarks.perf import warmup_and_flush

        warmup_and_flush(
            f"http://127.0.0.1:{hport}", args.model, texts, args.warmup,
            args.concurrency, request_timeout_s=args.request_timeout,
        )

        out = asyncio.run(
            bench_http(
                f"http://127.0.0.1:{hport}", args.model, texts,
                args.concurrency,
                request_timeout_s=args.request_timeout,
            )
        )
        out["topology"] = "disagg" if disagg else "agg"
        return out
    except BaseException:
        import sys

        for p in procs:
            rc = p.proc.poll()
            print(
                f"--- {p.name}: {'alive' if rc is None else f'EXITED {rc}'}"
                f" ({p.log_path})", file=sys.stderr,
            )
            try:
                with open(p.log_path) as f:
                    print("\n".join(f.read().splitlines()[-30:]),
                          file=sys.stderr)
            except OSError:
                pass
        raise
    finally:
        for p in reversed(procs):
            p.stop()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="agg vs disagg A/B")
    p.add_argument("--model", default="tiny")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--quantize", default=None, choices=[None, "int8"])
    p.add_argument("--page-size", type=int, default=4, dest="page_size")
    p.add_argument("--num-pages", type=int, default=256, dest="num_pages")
    p.add_argument("--max-context", type=int, default=64, dest="max_context")
    p.add_argument("--max-local-prefill", type=int, default=8,
                   dest="max_local_prefill")
    p.add_argument("--prefill-workers", type=int, default=1,
                   dest="prefill_workers")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--warmup", type=int, default=0)
    p.add_argument("--isl", type=int, default=24)
    p.add_argument("--osl", type=int, default=8)
    p.add_argument("--concurrency", type=int, default=4)
    p.add_argument("--decode-steps", type=int, default=None,
                   dest="decode_steps",
                   help="worker decode fusion (steps per dispatch)")
    p.add_argument("--request-timeout", type=float, default=None,
                   dest="request_timeout",
                   help="per-request total-stream bound in seconds; timed-out"
                   " requests are counted, not fatal")
    p.add_argument("--out", default=None,
                   help="also write the JSON here incrementally after each"
                   " topology, so a wedge mid-phase keeps the finished phase")
    args = p.parse_args(argv)

    def _flush(results: dict) -> None:
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    results: dict = {}
    # provenance: the platform the worker processes are told to use
    # (this parent never touches jax — it would take the chip from them)
    import os

    results["platform"] = os.environ.get("JAX_PLATFORMS") or "default"
    results["agg"] = run_topology(args, disagg=False)
    _flush(results)
    results["disagg"] = run_topology(args, disagg=True)
    agg, dis = results["agg"], results["disagg"]
    if agg.get("output_tok_s") and dis.get("output_tok_s"):
        results["disagg_throughput_ratio"] = round(
            dis["output_tok_s"] / agg["output_tok_s"], 3
        )
        if agg["ttft_ms"]["p50"] and dis["ttft_ms"]["p50"]:
            results["disagg_ttft_ratio"] = round(
                agg["ttft_ms"]["p50"] / dis["ttft_ms"]["p50"], 3
            )
    _flush(results)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
