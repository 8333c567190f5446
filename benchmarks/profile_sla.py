"""SLA profiler: build the per-worker perf tables the SLA planner consumes.

Reference parity: benchmarks/profiler/profile_sla.py sweeps PARALLEL
CONFIGS (TP) and picks the one meeting the TTFT/ITL targets
(profile_sla.py:81-84), interpolating metric-vs-load to pre-compute
planner tables (docs sla_planner.md). Here:

- `profile(...)` sweeps closed-loop concurrency against ONE engine config,
  recording (achieved req/s -> TTFT ms, ITL ms);
- `sweep_parallel_configs(...)` runs that per (tp, dp) mesh config and
  SELECTS the config with the highest SLA-feasible rate PER CHIP — the
  quantity that decides deployment cost.

Emits the JSON `dynamo-tpu planner --mode sla --perf-table` loads: the
top-level `ttft_vs_rate`/`itl_vs_rate` are the SELECTED config's rows
(back-compatible), with every swept config under `configs` so the planner
can re-select against ITS OWN targets at load time:

    {"ttft_vs_rate": [[req_s, ttft_p50_ms], ...],
     "itl_vs_rate":  [[req_s, itl_p50_ms], ...],
     "selected": {"tp": T, "dp": D},
     "sla": {"ttft_ms": ..., "itl_ms": ...},
     "configs": [{"tp": ..., "dp": ..., "ttft_vs_rate": ...,
                  "itl_vs_rate": ..., "sla_rate": ...,
                  "sla_rate_per_chip": ...}, ...],
     "meta": {...}}
"""

from __future__ import annotations

import argparse
import json


# selection policy shared with the planner's load-time re-selection
from dynamo_tpu.planner.perf_model import (  # noqa: E402
    select_parallel_config,
    sla_feasible_rate,
)


def sweep_parallel_configs(
    parallel: list[tuple[int, int]],
    ttft_target_ms: float = 200.0,
    itl_target_ms: float = 20.0,
    model: str = "tiny",
    num_requests: int = 32,
    isl: int = 64,
    osl: int = 32,
    concurrency_levels=(1, 2, 4, 8),
    base_engine_config=None,
    quantize: str | None = None,
    num_pages: int = 2048,
    page_size: int = 64,
) -> dict:
    """Profile each (tp, dp) config and select the SLA-best per chip.

    Reference: profiler sweeps TP and picks the config meeting TTFT/ITL
    (profile_sla.py:81-84); per-chip normalization is what makes a tp=4
    config that's 1.5x faster still LOSE to tp=1 on cost."""
    from dataclasses import replace

    from dynamo_tpu.engine import EngineConfig

    configs = []
    for tp, dp in parallel:
        if base_engine_config is not None:
            # a supplied config owns its page geometry, but an explicit
            # quantize request must not be silently dropped — profiling
            # bf16 when the caller asked for int8 would poison the
            # planner's tables
            cfg = replace(base_engine_config, tp=tp, dp=dp)
            if quantize is not None:
                cfg = replace(cfg, quantize=quantize)
        else:
            cfg = None
        t = profile(
            model=model, num_requests=num_requests, isl=isl, osl=osl,
            concurrency_levels=concurrency_levels, engine_config=cfg,
            tp=tp, dp=dp, quantize=quantize,
            num_pages=num_pages, page_size=page_size,
        )
        rate = sla_feasible_rate(t, ttft_target_ms, itl_target_ms)
        configs.append(
            {
                "tp": tp, "dp": dp,
                "ttft_vs_rate": t["ttft_vs_rate"],
                "itl_vs_rate": t["itl_vs_rate"],
                "sla_rate": round(rate, 4),
                "sla_rate_per_chip": round(rate / (tp * dp), 4),
                "meta": t["meta"],
            }
        )
    best = select_parallel_config(configs, ttft_target_ms, itl_target_ms)
    feasible = [c for c in configs if c["sla_rate"] > 0]
    return {
        "ttft_vs_rate": best["ttft_vs_rate"],
        "itl_vs_rate": best["itl_vs_rate"],
        "selected": {"tp": best["tp"], "dp": best["dp"]},
        "sla": {"ttft_ms": ttft_target_ms, "itl_ms": itl_target_ms},
        "configs": configs,
        "meta": {
            "model": model, "isl": isl, "osl": osl,
            "sla_feasible": bool(feasible),
        },
    }


def profile(
    model: str = "tiny",
    num_requests: int = 32,
    isl: int = 64,
    osl: int = 32,
    concurrency_levels=(1, 2, 4, 8),
    engine_config=None,
    tp: int = 1,
    dp: int = 1,
    quantize: str | None = None,
    num_pages: int = 2048,
    page_size: int = 64,
    decode_steps: int | None = None,
) -> dict:
    from benchmarks.perf import bench_engine
    from benchmarks.synthesizer import SynthConfig, synthesize
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine

    reqs = synthesize(
        SynthConfig(
            num_requests=num_requests, depth=0,
            mean_suffix_len=isl, mean_output_len=osl,
        )
    )
    prompts = [(list(r.prompt_tokens), r.output_len) for r in reqs]
    # Budget pages for the actual longest sequence (geometric tail).
    longest = max(len(p) + o for p, o in prompts)
    cfg = engine_config or EngineConfig(
        model=model,
        num_pages=num_pages,
        page_size=page_size,
        max_pages_per_seq=max(8, -(-(longest + 1) // page_size)),
        dtype="bfloat16",
        enable_prefix_caching=False,
        tp=tp,
        dp=dp,
        quantize=quantize,
        **({"decode_steps": decode_steps} if decode_steps is not None
           else {}),
    )
    # A caller-supplied config has a fixed context budget: clamp prompts to
    # it (the synthesizer's geometric tail would trip the admission guard).
    prompts = [
        (p[: max(1, cfg.max_context - o - 1)], o) for p, o in prompts
    ]
    engine = JaxEngine(cfg)
    # compile every shape before the timed sweeps
    bench_engine(engine, prompts[: max(concurrency_levels)],
                 max(concurrency_levels))

    ttft_rows, itl_rows, sweep = [], [], []
    for c in concurrency_levels:
        s = bench_engine(engine, prompts, c)
        sweep.append({"concurrency": c, **s})
        if s["req_s"] and s["ttft_ms"]["p50"] is not None:
            ttft_rows.append([s["req_s"], s["ttft_ms"]["p50"]])
        if s["req_s"] and s["itl_ms"]["p50"] is not None:
            itl_rows.append([s["req_s"], s["itl_ms"]["p50"]])
    return {
        "ttft_vs_rate": sorted(ttft_rows),
        "itl_vs_rate": sorted(itl_rows),
        "meta": {
            "model": model, "isl": isl, "osl": osl,
            "concurrency_levels": list(concurrency_levels),
            "sweep": sweep,
        },
    }


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="profile one worker for the SLA planner")
    p.add_argument("--model", default="llama3-1b")
    p.add_argument("--num-requests", type=int, default=32, dest="num_requests")
    p.add_argument("--isl", type=int, default=128)
    p.add_argument("--osl", type=int, default=64)
    p.add_argument("--concurrency", default="1,2,4,8,16")
    p.add_argument(
        "--parallel", default=None,
        help='comma-separated TPxDP mesh configs to sweep, e.g. "1x1,2x1,4x1"'
             " — selects the SLA-best per chip (omit = single default config)",
    )
    p.add_argument("--quantize", default=None, choices=[None, "int8"],
                   help="weight-only quantization (8B-class models on one "
                        "16 GB chip need int8)")
    p.add_argument("--num-pages", type=int, default=2048, dest="num_pages")
    p.add_argument("--page-size", type=int, default=64, dest="page_size")
    p.add_argument("--ttft-target", type=float, default=200.0, dest="ttft_target")
    p.add_argument("--itl-target", type=float, default=20.0, dest="itl_target")
    p.add_argument("-o", "--output", default=None, help="write JSON here")
    p.add_argument(
        "--decode-steps", type=int, default=None, dest="decode_steps",
        help="decode steps fused per dispatch",
    )
    args = p.parse_args(argv)


    levels = [int(x) for x in args.concurrency.split(",")]
    if args.parallel:
        parallel = [
            (int(t), int(d))
            for t, d in (s.split("x") for s in args.parallel.split(","))
        ]
        table = sweep_parallel_configs(
            parallel,
            ttft_target_ms=args.ttft_target,
            itl_target_ms=args.itl_target,
            model=args.model,
            num_requests=args.num_requests,
            isl=args.isl,
            osl=args.osl,
            concurrency_levels=levels,
            quantize=args.quantize,
            num_pages=args.num_pages,
            page_size=args.page_size,
        )
    else:
        table = profile(
            model=args.model,
            num_requests=args.num_requests,
            isl=args.isl,
            osl=args.osl,
            concurrency_levels=levels,
            quantize=args.quantize,
            num_pages=args.num_pages,
            page_size=args.page_size,
            decode_steps=args.decode_steps,
        )
    text = json.dumps(table, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        print(f"wrote {args.output}")
    else:
        print(text)


if __name__ == "__main__":
    main()
