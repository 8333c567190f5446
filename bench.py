"""Benchmark: serving throughput of the JaxEngine on one TPU chip.

Workload (genai-perf-inspired, scaled to one chip — BASELINE.md): N
concurrent requests, random prompts, fixed output length, continuous
batching with paged KV + prefix caching off (worst case). Reports output
tokens/sec/chip, p50 TTFT, p50 ITL, and approximate MFU.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...,
   "extras": {...}}

Platform contract: the run measures a TPU or fails. With no TPU attached
it exits non-zero unless the operator said JAX_PLATFORMS=cpu, which selects
the labeled CPU mode (metric `output_tok_s_cpu_fallback`, tiny workload,
extras.platform="cpu", vs_baseline against the CPU record) that the
contract tests drive. Any unexpected crash still emits one structured JSON
line instead of a bare traceback.

vs_baseline compares against `published.output_tok_s_per_chip` (TPU) or
`published.cpu_output_tok_s` (CPU fallback) in BASELINE.json; 1.0 until a
prior round has published.
"""

from __future__ import annotations

import json
import os
import sys
import time

def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)
    _ledger_append(obj)


def _ledger_append(payload: dict) -> None:
    """Append this emission to artifacts/perf_ledger.jsonl (the
    perf-regression ledger — scripts/perf_diff.py diffs rounds from
    it). Best-effort: a ledger problem must never fail the bench run
    itself. DYNTPU_ROUND names the row's round (driver rounds export
    it); DYNTPU_PERF_LEDGER overrides the path, empty string disables."""
    path = os.environ.get("DYNTPU_PERF_LEDGER")
    if path == "":
        return
    try:
        from dynamo_tpu.telemetry import perf_ledger

        row = perf_ledger.row_from_bench(
            payload, os.environ.get("DYNTPU_ROUND", "adhoc")
        )
        perf_ledger.append_row(row, path or perf_ledger.DEFAULT_LEDGER)
    except Exception as e:
        print(f"bench: perf_ledger append failed: {e}", file=sys.stderr)


def _make_echo_driver(num_requests: int, tokens: int):
    """`drive(engine, tag) -> (tokens, seconds)`: the shared concurrent
    echo workload of the harness/tracing A/Bs."""
    import asyncio

    from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
    from dynamo_tpu.runtime.context import Context

    prompt = list(range(1, tokens + 1))

    async def drive(engine, tag):
        async def one(i):
            req = PreprocessedRequest(
                request_id=f"{tag}{i}", token_ids=prompt, max_tokens=tokens
            )
            n = 0
            ctx = Context(request_id=req.request_id)
            async for item in engine.generate(ctx, req):
                n += len(item["token_ids"])
            return n

        t0 = time.time()
        counts = await asyncio.gather(*[one(i) for i in range(num_requests)])
        return sum(counts), time.time() - t0

    return drive


def _ext_harness_ab(num_requests: int = 8, tokens: int = 64) -> dict:
    """Per-token overhead of the subprocess external-engine harness: the
    SAME echo workload through an in-process EchoEngine vs the torch-free
    reference worker behind the wire protocol (spawn + frames + msgpack +
    checksums). The delta prices the isolation boundary a foreign engine
    pays per token (docs/external_engines.md 'Level 2')."""
    import asyncio

    from dynamo_tpu.engine.async_engine import EchoEngine
    from dynamo_tpu.external.client import SubprocessEngine

    drive = _make_echo_driver(num_requests, tokens)

    async def run():
        n_in, t_in = await drive(EchoEngine(), "warm-in")
        n_in, t_in = await drive(EchoEngine(), "in")
        ext = SubprocessEngine(
            [sys.executable, "-m", "dynamo_tpu.external.reference_worker",
             "--model", "bench-ext", "--metrics-interval", "60"],
            name="bench-ext",
        )
        await ext.start()
        try:
            await drive(ext, "warm-ext")
            n_ext, t_ext = await drive(ext, "ext")
        finally:
            await ext.stop()
        return {
            "requests": num_requests,
            "tokens_per_arm": n_in,
            "inproc_tok_s": round(n_in / t_in, 1) if t_in else None,
            "subprocess_tok_s": round(n_ext / t_ext, 1) if t_ext else None,
            "wire_overhead_us_per_token": round(
                (t_ext / n_ext - t_in / n_in) * 1e6, 2
            ),
        }

    return asyncio.run(run())


def _spec_ab(
    model: str = "tiny", draft: str = None, pairs: int = 3,
    num_requests: int = 8, osl: int = 48, spec_tokens: int = 4,
) -> dict:
    """Draft-model speculative decoding A/B (ISSUE 9): the decode-bound
    workload (tiny prompts, batch <= 8, long outputs) with the fused
    draft+verify path on vs off. BOTH arms run in ONE warm engine — the
    draft stays loaded, `eng._spec_draft` toggles the routing — and the
    arms interleave per pair so box-load drift cancels.

    The ASSERTED number is the deterministic dispatch-level model, not
    the wall ratio: modeled_decode_tok_s_ratio =
    (tokens/dispatch spec-on / tokens/dispatch spec-off) x
    (ms/dispatch spec-off / ms/dispatch spec-on), medians over pairs.
    tokens/dispatch on the spec arm is B x (1 + accept_rate x S) — the
    microbench priced at the MEASURED acceptance rate — and ms/dispatch
    is each arm's engine-measured decode phase time over many
    dispatches. A `modeled_at` curve extrapolates the ratio to other
    acceptance rates (what a distilled draft would buy), since the
    default draft here is SELF-draft (draft == target params, greedy
    acceptance ~1): the upper-bound harness that exercises the whole
    fused pipeline without needing a distilled checkpoint."""
    import gc

    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    draft = draft or model
    base = EngineConfig.for_tests() if model == "tiny" else None
    over = {
        "model": model,
        "spec_draft_model": draft,
        "spec_draft_tokens": spec_tokens,
        "num_pages": max(256, num_requests * 8),
        "page_size": 16,
        "max_pages_per_seq": 16,
        "prefill_chunk": 64,
        "decode_buckets": (1, 2, 4, 8),
        "max_seqs": max(8, num_requests),
        "decode_steps": 1,  # spec competes with classic stepping; the
        # fused-K path is a different lever (it can't beat the roofline
        # per REQUEST, only amortize syncs)
        "enable_prefix_caching": False,
    }
    if base is not None:
        cfg = EngineConfig(**{**base.__dict__, **over})
    else:
        cfg = EngineConfig(**over)
    eng = JaxEngine(cfg)
    rng = np.random.default_rng(0)

    def drive(tag: str) -> dict:
        m = eng.metrics
        keys = (
            "time_decode_ms", "decode_dispatches", "generated_tokens",
            "spec_drafted", "spec_accepted",
        )
        before = {k: getattr(m, k) for k in keys}
        t0 = time.perf_counter()
        for i in range(num_requests):
            eng.add_request(
                f"{tag}{i}",
                [int(x) for x in rng.integers(1, 200, 12)],
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        gen = 0
        while eng.has_work:
            for out in eng.step():
                gen += len(out.new_token_ids)
        elapsed = time.perf_counter() - t0
        eng.drain_overlap()
        d = {k: getattr(m, k) - v for k, v in before.items()}
        disp = max(1, d["decode_dispatches"])
        return {
            "tok_s": round(gen / elapsed, 1),
            "ms_per_dispatch": round(d["time_decode_ms"] / disp, 4),
            "tok_per_dispatch": round(d["generated_tokens"] / disp, 3),
            "accept_rate": round(
                d["spec_accepted"] / max(1, d["spec_drafted"]), 4
            ),
            "decode_dispatches": d["decode_dispatches"],
        }

    # warm both arms (compiles + caches)
    eng._spec_draft = True
    drive("warm_on")
    eng._spec_draft = False
    drive("warm_off")
    on_runs, off_runs = [], []
    for p in range(pairs):
        eng._spec_draft = True
        on_runs.append(drive(f"on{p}"))
        eng._spec_draft = False
        off_runs.append(drive(f"off{p}"))
    del eng
    gc.collect()

    import statistics

    def med(runs, k):
        return statistics.median(r[k] for r in runs)

    rate = med(on_runs, "accept_rate")
    ms_on, ms_off = med(on_runs, "ms_per_dispatch"), med(
        off_runs, "ms_per_dispatch"
    )
    tpd_on, tpd_off = med(on_runs, "tok_per_dispatch"), med(
        off_runs, "tok_per_dispatch"
    )
    modeled = (
        (tpd_on / tpd_off) * (ms_off / ms_on)
        if tpd_off and ms_on
        else None
    )
    # extrapolation: at acceptance r the spec arm lands B*(1 + r*S)
    # tokens per dispatch at the measured spec-dispatch cost
    modeled_at = {}
    if modeled is not None and rate > 0:
        per_accept = tpd_on / (1.0 + rate * spec_tokens)
        for r in (0.5, 0.7, 0.9):
            modeled_at[str(r)] = round(
                (per_accept * (1.0 + r * spec_tokens) / tpd_off)
                * (ms_off / ms_on),
                3,
            )
    return {
        "model": model,
        "draft": draft,
        "spec_tokens": spec_tokens,
        "batch": num_requests,
        "pairs": pairs,
        "spec_on": {
            "tok_s": med(on_runs, "tok_s"),
            "ms_per_dispatch": ms_on,
            "tok_per_dispatch": tpd_on,
            "accept_rate": rate,
        },
        "spec_off": {
            "tok_s": med(off_runs, "tok_s"),
            "ms_per_dispatch": ms_off,
            "tok_per_dispatch": tpd_off,
        },
        "wall_tok_s_ratio": round(
            med(on_runs, "tok_s") / max(1e-9, med(off_runs, "tok_s")), 3
        ),
        "modeled_decode_tok_s_ratio": (
            round(modeled, 3) if modeled is not None else None
        ),
        "modeled_at_accept_rate": modeled_at,
    }


def _multihost_pipeline_ab(
    model: str = "tiny", pairs: int = 3, num_requests: int = 8,
    osl: int = 64, decode_steps: int = 8, topology: str = "tp=2,dp=2",
) -> dict:
    """The fast decode pipeline carried across hosts (ISSUE 20): under a
    FORCED multi-host mesh (EngineConfig.force_multihost over the CPU
    device grid — the engine takes the multi-controller code paths
    without a fabric), the fused decode scan (`decode_steps` tokens a
    dispatch) ON vs the old multi-host behavior (the pre-lift auto-off:
    synchronous per-token stepping). ONE warm engine; the arms swap the
    engine's `decode_steps` live and interleave per pair so box-load
    drift cancels. The TIMED arms keep overlap off (the CPU backend
    serializes the dispatch launched ahead, billing the ON arm for
    pipelining the chip gets free); a separate UN-timed probe drive then
    runs with overlap re-enabled and reports its engagement
    (`overlap_probe`) — proof the multi-host overlap path works, without
    letting its CPU artifact pollute the model.

    The ASSERTED number is the deterministic dispatch-level model:
    modeled_ms_per_token_ratio =
    (ms/dispatch / tok/dispatch, pipeline off) / (same, pipeline on) —
    the per-token host sync the lift removes from every replica's
    lockstep loop. Wall tok/s rides along unasserted."""
    import dataclasses
    import gc

    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    base = EngineConfig.for_tests() if model == "tiny" else None
    over = {
        "model": model,
        "topology": topology,
        "force_multihost": True,
        "num_pages": max(256, num_requests * 8),
        "page_size": 16,
        "max_pages_per_seq": 16,
        "prefill_chunk": 64,
        "decode_buckets": (1, 2, 4, 8),
        "max_seqs": max(8, num_requests),
        "decode_steps": 1,
        "overlap_decode": False,
        "enable_prefix_caching": False,
    }
    if base is not None:
        cfg = EngineConfig(**{**base.__dict__, **over})
    else:
        cfg = EngineConfig(**over)
    eng = JaxEngine(cfg)
    assert eng._multiproc, "force_multihost must engage the SPMD paths"
    rng = np.random.default_rng(0)

    def drive(tag: str) -> dict:
        m = eng.metrics
        keys = (
            "time_decode_ms", "decode_dispatches", "generated_tokens",
            "overlap_hits",
        )
        before = {k: getattr(m, k) for k in keys}
        t0 = time.perf_counter()
        for i in range(num_requests):
            eng.add_request(
                f"{tag}{i}",
                [int(x) for x in rng.integers(1, 200, 12)],
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        gen = 0
        while eng.has_work:
            for out in eng.step():
                gen += len(out.new_token_ids)
        elapsed = time.perf_counter() - t0
        eng.drain_overlap()
        d = {k: getattr(m, k) - v for k, v in before.items()}
        disp = max(1, d["decode_dispatches"])
        return {
            "tok_s": round(gen / elapsed, 1),
            "ms_per_dispatch": round(d["time_decode_ms"] / disp, 4),
            "tok_per_dispatch": round(d["generated_tokens"] / disp, 3),
            "decode_dispatches": d["decode_dispatches"],
            "overlap_hits": d["overlap_hits"],
        }

    def fuse(k: int) -> None:
        eng.config = dataclasses.replace(eng.config, decode_steps=k)

    fuse(decode_steps)
    drive("warm_on")
    fuse(1)
    drive("warm_off")
    on_runs, off_runs = [], []
    for p in range(pairs):
        fuse(decode_steps)
        on_runs.append(drive(f"on{p}"))
        fuse(1)
        off_runs.append(drive(f"off{p}"))
    # un-timed probe: the overlap path itself, live on the forced
    # multi-host mesh (its timing is a CPU serialization artifact)
    fuse(decode_steps)
    eng._overlap_enabled = True
    probe = drive("probe")
    del eng
    gc.collect()

    import statistics

    def med(runs, k):
        return statistics.median(r[k] for r in runs)

    ms_on, ms_off = med(on_runs, "ms_per_dispatch"), med(
        off_runs, "ms_per_dispatch"
    )
    tpd_on, tpd_off = med(on_runs, "tok_per_dispatch"), med(
        off_runs, "tok_per_dispatch"
    )
    modeled = (
        (ms_off / tpd_off) / (ms_on / tpd_on)
        if tpd_off and tpd_on and ms_on
        else None
    )
    return {
        "model": model,
        "topology": topology,
        "decode_steps": decode_steps,
        "batch": num_requests,
        "pairs": pairs,
        "pipeline_on": {
            "tok_s": med(on_runs, "tok_s"),
            "ms_per_dispatch": ms_on,
            "tok_per_dispatch": tpd_on,
        },
        "pipeline_off": {
            "tok_s": med(off_runs, "tok_s"),
            "ms_per_dispatch": ms_off,
            "tok_per_dispatch": tpd_off,
        },
        "overlap_probe": {"overlap_hits": probe["overlap_hits"]},
        "wall_tok_s_ratio": round(
            med(on_runs, "tok_s") / max(1e-9, med(off_runs, "tok_s")), 3
        ),
        "modeled_ms_per_token_ratio": (
            round(modeled, 3) if modeled is not None else None
        ),
    }


def _mixed_ab(model: str = "tiny", pairs: int = 1) -> dict:
    """Stall-free mixed prefill+decode steps A/B (ISSUE 5): the c=32
    saturation workload — a few long-running decodes with a steady
    arrival stream of chunked prompts against a FIXED prefill budget —
    with `mixed_steps` on vs off. The XOR scheduler stalls every running
    decode for each arrival's whole prefill drain, so pooled ITL p95
    sits at several step times; mixed steps carry the decode batch
    inside every prefill dispatch, collapsing ITL p95 toward one step
    while TTFT p50 (arrival -> first token, still one prefill chunk per
    step either way) stays within a few percent.

    Noise control on a shared box: BOTH arms run in ONE engine (the
    scheduler's `mixed_enabled` flag toggles per step or per drive), so
    they share a warm jit cache. Workload-level wall numbers here carry
    per-run correlated bias of ±10% (a load burst hits the two program
    working sets asymmetrically), so — exactly like the trace_overhead
    A/B — the ASSERTED ratios are deterministic: the TTFT ratio comes
    from a back-to-back per-chunk-stratum program microbench, and the
    ITL ratio prices each arm's deterministic step schedule with
    stratified step-cost medians from randomized-interleaved drives
    (policy coin-tossed per step). Raw wall ratios ride along
    unasserted. Prompts are long and chunks big (1536/512) so one
    chunk's quadratic attention dominates the decode rider, as it does
    on chip with 512–2048-token chunks; overlap_decode is off in both
    arms (the CPU backend serializes the speculative dispatch, which
    would bill the mixed arm for pipelining the chip gets free)."""
    import statistics

    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    early, osl_early = 2, 112
    #: arrivals take their first token and finish (osl 1): the decode
    #: batch stays the 2 long-running rows, so the rider the TTFT ratio
    #: prices is the steady decode batch, not a backlog-inflated one
    isl_late, osl_late = 1536, 1
    #: saturation: 3 drain steps per prompt, arrivals every 3/4 steps
    #: (avg 3.5) — just under XOR capacity, so the backlog stays alive
    #: and strict prefill priority starves decodes for whole cycles
    late_gaps = (4, 3)
    num_late = 30  # c=32: 2 long decodes + 30 arrivals
    rng = np.random.default_rng(11)
    late_prompts = [
        [int(x) for x in rng.integers(1, 200, isl_late)]
        for _ in range(num_late)
    ]

    eng = JaxEngine(
        EngineConfig(
            model=model,
            num_pages=448,
            page_size=16,
            max_pages_per_seq=97,
            decode_buckets=(1, 2, 4, 8, 16, 32, 64),
            prefill_chunk=512,
            prefill_token_budget=512,  # fixed budget: 3 steps/prompt
            max_seqs=64,
            decode_steps=1,
            dtype="float32",
            enable_prefix_caching=False,
            mixed_steps=True,
            overlap_decode=False,
        )
    )

    def drive(tag: str, coin=None, n_late: int = num_late) -> dict:
        """Step-driven arrivals: every `late_every` engine steps another
        chunked prompt lands while the early requests decode through —
        identical arrival pattern (in steps) for both arms. Per-step
        durations are collected BY BATCH KIND; with `coin` set, the
        scheduling policy flips randomly every step, so mixed and
        prefill step costs sample the identical machine load."""
        m = eng.metrics
        submit_t, submit_step, first_t, first_step = {}, {}, {}, {}
        emits: dict = {}
        emit_steps: dict = {}
        step_ms: dict = {"mixed": [], "prefill": [], "decode": []}
        #: per-step (kind, chunk-index) labels. Step costs are
        #: MULTI-MODAL by chunk index (the first chunk skips the history
        #: gather; later chunks attend over more history), so medians
        #: must stratify by chunk or they hop between modes.
        labels: list = []
        samples: dict = {}
        prefill_reqs: dict = {}
        prev_computed: dict = {}
        step_i, sent = 0, 0
        chunk_sz = eng.config.prefill_chunk

        def add(rid, prompt, osl):
            submit_t[rid] = time.perf_counter()
            submit_step[rid] = step_i
            req = eng.add_request(
                rid, prompt,
                SamplingParams(max_tokens=osl, ignore_eos=True),
            )
            if len(prompt) > chunk_sz:
                prefill_reqs[rid] = req
                prev_computed[rid] = 0

        for i in range(early):
            add(f"{tag}e{i}", [i + 1, i + 2, i + 3], osl_early)
        next_at = late_gaps[0]
        while eng.has_work or sent < n_late:
            if sent < n_late and step_i >= next_at:
                add(f"{tag}l{sent}", late_prompts[sent], osl_late)
                sent += 1
                next_at += late_gaps[sent % len(late_gaps)]
            if coin is not None:
                eng.scheduler.mixed_enabled = bool(coin.integers(0, 2))
            kinds0 = (
                m.mixed_dispatches, m.prefill_dispatches,
                m.decode_dispatches,
            )
            t0 = time.perf_counter()
            outs = eng.step()
            dt = time.perf_counter() - t0
            if m.mixed_dispatches > kinds0[0]:
                kind = "mixed"
            elif m.prefill_dispatches > kinds0[1]:
                kind = "prefill"
            elif m.decode_dispatches > kinds0[2]:
                kind = "decode"
            else:
                kind = None
            chunk_idx = None
            for rid, req in list(prefill_reqs.items()):
                done = min(req.num_computed_tokens, len(req.prompt_tokens))
                if done > prev_computed[rid]:
                    chunk_idx = prev_computed[rid] // chunk_sz
                    prev_computed[rid] = done
                if req.is_finished or done >= len(req.prompt_tokens):
                    prefill_reqs.pop(rid)
                    prev_computed.pop(rid, None)
            labels.append((kind, chunk_idx))
            if kind is not None:
                step_ms[kind].append(dt * 1000.0)
                samples.setdefault((kind, chunk_idx), []).append(
                    (step_i, dt * 1000.0)
                )
            for out in outs:
                now = time.perf_counter()
                if out.is_first and out.request_id not in first_t:
                    first_t[out.request_id] = now
                    first_step[out.request_id] = step_i
                if out.new_token_ids:
                    emits.setdefault(out.request_id, []).append(now)
                    emit_steps.setdefault(out.request_id, []).append(step_i)
            step_i += 1
        itls = []
        for times in emits.values():
            itls.extend(b - a for a, b in zip(times, times[1:]))
        itls.sort()
        ttfts = sorted(first_t[r] - submit_t[r] for r in first_t)
        ttft_steps = sorted(
            first_step[r] - submit_step[r] + 1 for r in first_t
        )
        return {
            "itl_p95_wall_ms": itls[int(len(itls) * 0.95)] * 1000.0,
            "ttft_p50_wall_ms": ttfts[len(ttfts) // 2] * 1000.0,
            "ttft_p50_steps": ttft_steps[len(ttft_steps) // 2],
            "step_ms": step_ms,
            "samples": samples,
            "labels": labels,
            "emit_steps": emit_steps,
            "mixed_dispatches": m.mixed_dispatches,
        }

    def arm(on: bool, tag: str) -> dict:
        eng.scheduler.mixed_enabled = on
        return drive(tag)

    # warmup with random interleaving: compiles every program variant of
    # BOTH policies in one (shortened) pass
    drive("warm", coin=np.random.default_rng(7), n_late=8)
    # randomized interleaved phase: the per-step-kind cost medians that
    # feed the TTFT comparison — mixed and prefill steps alternate by
    # coin toss, so any load burst hits both kinds alike
    rnds = [drive("rnd", coin=np.random.default_rng(97))]

    def microbench(reps: int = 16) -> tuple[dict, dict]:
        """Deterministic per-chunk-stratum cost ratio of the MIXED
        program vs the pure prefill program it replaces: identical
        synthetic inputs, the two programs alternating back-to-back in
        a tight loop, per-iteration pair ratios, median over reps.
        Workload-level wall numbers on this shared box carry per-run
        correlated bias of ±10% (a load burst hits the two program
        working sets asymmetrically) — this is the same reasoning as
        the trace_overhead A/B's deterministic span microbench."""
        import jax

        mp = eng.config.max_pages_per_seq
        chunk = eng.config.prefill_chunk
        n_chunks = isl_late // chunk
        b_dec = eng.config.decode_bucket_for(early)
        p_pages = eng.allocator.allocate(isl_late // 16 + 1)
        d_pages = [eng.allocator.allocate(10) for _ in range(b_dec)]
        rngl = np.random.default_rng(5)
        ratios, prefill_ms = {}, {}
        try:
            for c in range(n_chunks):
                first_chunk, psamp = c == 0, c == n_chunks - 1
                rows = b_dec + (1 if psamp else 0)
                host = {
                    "p": (
                        rngl.integers(1, 200, (1, chunk)).astype(np.int32),
                        (np.arange(chunk, dtype=np.int32) + c * chunk)[
                            None
                        ],
                        np.ones((1, chunk), bool),
                        np.zeros((1, mp), np.int32),
                    ),
                    "d": (
                        np.full((b_dec, 1), 7, np.int32),
                        np.full((b_dec, 1), 80, np.int32),
                        np.ones((b_dec, 1), bool),
                        np.zeros((b_dec, mp), np.int32),
                    ),
                    "last": np.full(1, chunk - 1, np.int32),
                    "samp": (
                        np.zeros(rows, np.float32),
                        np.ones(rows, np.float32),
                        np.zeros(rows, np.int32),
                        np.zeros(rows, np.uint32),
                        np.zeros(rows, np.int32),
                    ),
                    "samp1": (
                        np.zeros(1, np.float32), np.ones(1, np.float32),
                        np.zeros(1, np.int32), np.zeros(1, np.uint32),
                        np.zeros(1, np.int32),
                    ),
                    "last1": np.full(1, chunk - 1, np.int32),
                }
                host["p"][3][0, : len(p_pages)] = p_pages
                for i, pg in enumerate(d_pages):
                    host["d"][3][i, : len(pg)] = pg
                dev = jax.device_put(host)
                mixed_fn = eng._get_step_fn(
                    "mixed", b_dec, chunk, greedy=True,
                    first_chunk=first_chunk, b_pre=1, psamp=psamp,
                )
                if psamp:
                    pre_fn = eng._get_step_fn(
                        "prefill", 1, chunk, greedy=True,
                        first_chunk=first_chunk,
                    )
                else:
                    pre_fn = eng._get_step_fn(
                        "prefill_nosample", 1, chunk,
                        first_chunk=first_chunk,
                    )

                def run_mixed():
                    out = mixed_fn(
                        eng.params, *dev["d"][:3], eng.kv, dev["d"][3],
                        *dev["p"], dev["last"], *dev["samp"],
                    )
                    eng.kv = out[-1]
                    jax.block_until_ready(out[0])

                def run_pre():
                    if psamp:
                        out = pre_fn(
                            eng.params, *dev["p"][:3], eng.kv,
                            dev["p"][3], dev["last1"], *dev["samp1"],
                        )
                        eng.kv = out[-1]
                        jax.block_until_ready(out[0])
                    else:
                        eng.kv = pre_fn(
                            eng.params, *dev["p"][:3], eng.kv,
                            dev["p"][3],
                        )
                        jax.block_until_ready(eng.kv.k)

                run_mixed()
                run_pre()  # warm both
                ms_ms, ps_ms = [], []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    run_mixed()
                    t1 = time.perf_counter()
                    run_pre()
                    t2 = time.perf_counter()
                    ms_ms.append((t1 - t0) * 1000.0)
                    ps_ms.append((t2 - t1) * 1000.0)
                # min-of-mins, not median-of-pair-ratios: timing noise on
                # a shared box is strictly ADDITIVE (preemption, cache
                # pollution), so the minimum over reps converges on the
                # true program cost while a load burst that lands inside
                # one pair's window skews its ratio arbitrarily — the
                # estimator that let ttft_p50_ratio flake to 1.17 on a
                # clean tree under box load
                ratios[c] = min(ms_ms) / min(ps_ms)
                prefill_ms[c] = min(ps_ms)
        finally:
            eng.allocator.free(p_pages)
            for pg in d_pages:
                eng.allocator.free(pg)
        return ratios, prefill_ms

    stratum_ratio, stratum_prefill_ms = microbench()
    n_pairs = 16 * len(stratum_ratio)

    #: absolute per-stratum prices for gap modeling — taken from the
    #: PREFILL samples only and shared by BOTH arms (mixed steps price
    #: as prefill x step_ratio), so their own noise mostly cancels in
    #: the ITL ratio.
    by_stratum: dict = {}
    decode_samples, prefill_samples = [], []
    for rnd in rnds:
        for (kind, c), v in rnd["samples"].items():
            if kind == "prefill" and c is not None:
                by_stratum.setdefault(c, []).extend(x for _, x in v)
        prefill_samples.extend(rnd["step_ms"]["prefill"])
        decode_samples.extend(rnd["step_ms"]["decode"])
    med_prefill = {
        c: statistics.median(v) for c, v in by_stratum.items() if v
    }
    med_prefill_all = statistics.median(prefill_samples)
    med_decode = (
        statistics.median(decode_samples) if decode_samples else 0.0
    )
    #: drain-cost-weighted combination: what carrying the decode batch
    #: costs one prompt's WHOLE drain (= its TTFT, queue wait aside —
    #: and under saturation the mixed queue drains no slower: mixed
    #: steps move one chunk per step too, without spending steps on
    #: pure decode). Weights are the microbench's own per-stratum
    #: prefill times, keeping the asserted number fully deterministic.
    weight_total = sum(stratum_prefill_ms.values())
    step_ratio = (
        sum(
            stratum_prefill_ms[c] * r for c, r in stratum_ratio.items()
        )
        / weight_total
    )

    def price(kind, chunk_idx) -> float:
        if kind is None:
            return 0.0
        if kind == "decode":
            return med_decode
        base = med_prefill.get(chunk_idx, med_prefill_all)
        if kind == "mixed":
            return base * stratum_ratio.get(chunk_idx, step_ratio)
        return base

    def modeled_itl_p95(drv) -> float:
        """Gap cost from the arm drive's DETERMINISTIC step schedule:
        each inter-token gap spans a known sequence of (step kind,
        chunk) labels; price them with the shared stratified medians.
        Load bursts cannot move this — only the scheduling policy can."""
        gaps = []
        for steps in drv["emit_steps"].values():
            for a, b in zip(steps, steps[1:]):
                gaps.append(
                    sum(
                        price(*drv["labels"][s])
                        for s in range(a + 1, b + 1)
                    )
                )
        gaps.sort()
        return gaps[int(len(gaps) * 0.95)]

    itl_ratios, itl_wall_ratios = [], []
    res = {}
    disp0 = 0
    for rep in range(pairs):
        arms = [(True, "mixed_on"), (False, "mixed_off")]
        if rep % 2:
            arms.reverse()  # cancel any first-arm bias
        for on, tag in arms:
            res[tag] = arm(on, f"p{rep}{tag}")
        assert res["mixed_on"]["mixed_dispatches"] > disp0
        disp0 = res["mixed_on"]["mixed_dispatches"]
        itl_ratios.append(
            modeled_itl_p95(res["mixed_off"])
            / modeled_itl_p95(res["mixed_on"])
        )
        itl_wall_ratios.append(
            res["mixed_off"]["itl_p95_wall_ms"]
            / res["mixed_on"]["itl_p95_wall_ms"]
        )
    # TTFT p50 ratio: a prompt's first token needs its chunks drained —
    # the same number of chunk steps in both arms, each costing
    # step_ratio more under mixed (and under saturation the mixed queue
    # drains no slower: mixed steps move one chunk per step too, without
    # spending steps on pure decode). The paired per-step cost ratio IS
    # the TTFT p50 ratio; wall TTFTs per arm ride along for reference.
    ttft_ratio = step_ratio

    def strip(r):  # step lists are bulky; keep the medians
        return {
            **{
                k: v
                for k, v in r.items()
                if k not in ("step_ms", "samples", "labels", "emit_steps")
            },
            "step_ms_p50": {
                k: round(statistics.median(v), 2) if v else None
                for k, v in r["step_ms"].items()
            },
        }

    return {
        "workload": (
            f"c={early + num_late} saturation: {early} long decodes + "
            f"steady {isl_late}-token arrivals, fixed budget 512"
        ),
        "pairs": pairs,
        "mixed_on": strip(res["mixed_on"]),
        "mixed_off": strip(res["mixed_off"]),
        #: chunk-stratified prefill step medians (randomized interleaved
        #: drives) + the microbench's per-stratum mixed/prefill program
        #: ratios — the deterministic basis of both asserted numbers
        "prefill_step_ms_p50": {
            f"c{c}": round(v, 2) for c, v in sorted(med_prefill.items())
        },
        "decode_step_ms_p50": round(med_decode, 2),
        "microbench_step_ratio": round(step_ratio, 3),
        "microbench_pairs": n_pairs,
        "stratum_ratios": {
            f"c{c}": round(r, 3) for c, r in sorted(stratum_ratio.items())
        },
        #: XOR itl_p95 / mixed itl_p95, each priced over the arm's
        #: deterministic step schedule with the stratified medians —
        #: >= 2 is the acceptance bar; the raw wall ratio rides along
        "itl_p95_ratio": round(statistics.median(itl_ratios), 3),
        "itl_p95_wall_ratio": round(
            statistics.median(itl_wall_ratios), 3
        ),
        #: mixed ttft_p50 / XOR ttft_p50 (one prompt's drain cost, from
        #: the back-to-back program microbench) — within 15% is the bar
        #: (noise-robust min-based estimator; the DETERMINISTIC part of
        #: the claim is the step-schedule equality below, asserted tight)
        "ttft_p50_ratio": round(ttft_ratio, 3),
        #: steps from arrival to first token, per arm — fully determined
        #: by the scheduling policy (one chunk per step either way), so
        #: the contract asserts exact equality: mixed steps do not delay
        #: a prompt's drain by even one step
        "ttft_p50_steps_on": res["mixed_on"]["ttft_p50_steps"],
        "ttft_p50_steps_off": res["mixed_off"]["ttft_p50_steps"],
    }


def _trace_overhead_ab(num_requests: int = 8, tokens: int = 64) -> dict:
    """Distributed-tracing overhead A/B (ISSUE 4 acceptance): the SAME
    echo workload through the subprocess harness — where every traced hop
    fires (engine span, trace context on the generate frame, child span
    shipped back as a `span` frame) — with tracing off vs on.

    This box's background load swings short echo runs by tens of percent
    — far above the span layer's true cost — so the empirical A/B runs
    INTERLEAVED (alternating-order off/on pairs, median per-pair ratio:
    a slow window hits both arms and cancels) and is reported as a
    sanity band, while the <3% claim is pinned by `modeled_overhead_pct`:
    a deterministic microbench of the per-request span work (parent span
    + event + adopted child span) divided by the measured per-request
    serving time. The model is conservative — it charges the whole span
    fan to the critical path."""
    import asyncio
    import statistics

    from dynamo_tpu import telemetry
    from dynamo_tpu.external.client import SubprocessEngine

    drive = _make_echo_driver(num_requests, tokens)

    def span_layer_us_per_request(iters: int = 4000) -> float:
        """Deterministic cost of one traced request's span work in THIS
        process: the engine span contextmanager, a first_token event, and
        adopting the child's shipped span into the ring."""
        telemetry.configure(enabled=True, ring_size=8)
        child = {
            "trace_id": "0" * 32, "span_id": "1" * 16,
            "parent_id": None, "name": "child.generate",
            "service": "ext-child", "start_ts": 0.0, "duration_ms": 1.0,
            "status": "ok", "attrs": {}, "events": [],
        }
        t0 = time.perf_counter()
        for _ in range(iters):
            with telemetry.span(
                "engine.generate", service="engine",
                attrs={"request_id": "bench"},
            ) as sp:
                sp.add_event("first_token")
                child["trace_id"] = sp.trace_id
                telemetry.record_span_dict(dict(child))
        us = (time.perf_counter() - t0) / iters * 1e6
        telemetry.configure(enabled=False)
        return us

    async def run(pairs: int = 6):
        ext = SubprocessEngine(
            [sys.executable, "-m", "dynamo_tpu.external.reference_worker",
             "--model", "bench-trace", "--metrics-interval", "60"],
            name="bench-trace",
        )
        await ext.start()
        ratios = []
        offs, ons = [], []
        try:
            await drive(ext, "warm-trace")
            for rep in range(pairs):
                arms = [(False, "off"), (True, "on")]
                if rep % 2:
                    arms.reverse()  # cancel any first-arm bias
                rate = {}
                for on, tag in arms:
                    telemetry.configure(
                        enabled=on, ring_size=64 if on else None
                    )
                    n, t = await drive(ext, f"{tag}-{rep}-")
                    rate[tag] = n / t if t else 0.0
                if rate["off"] and rate["on"]:
                    ratios.append(rate["on"] / rate["off"])
                    offs.append(rate["off"])
                    ons.append(rate["on"])
        finally:
            telemetry.configure(enabled=False)
            await ext.stop()
        ratio = statistics.median(ratios) if ratios else None
        off_med = statistics.median(offs) if offs else None
        span_us = span_layer_us_per_request()
        modeled = None
        if off_med:
            request_us = tokens / off_med * 1e6  # wall us per request
            modeled = round(span_us / request_us * 100.0, 3)
        return {
            "requests": num_requests,
            "pairs": len(ratios),
            "trace_off_tok_s": round(off_med, 1) if off_med else None,
            "trace_on_tok_s": (
                round(statistics.median(ons), 1) if ons else None
            ),
            "measured_overhead_pct": (
                round((1.0 - ratio) * 100.0, 2) if ratio else None
            ),
            "span_layer_us_per_request": round(span_us, 2),
            "modeled_overhead_pct": modeled,
        }

    return asyncio.run(run())


def _slo_overhead_ab(pairs: int = 3, osl: int = 32, n_req: int = 8) -> dict:
    """Fleet-telemetry overhead A/B (ISSUE 6 acceptance): the SLO
    sketches + SLA accounting + fleet-frame serialization must cost <1%
    of token throughput. Like trace_overhead, this box's load noise on a
    short tiny-engine run dwarfs the true cost, so the <1% claim is
    pinned by `modeled_overhead_pct` — a deterministic microbench of the
    per-token SLO work (one sketch observe per token + the finish-time
    SLA judgement amortized over the request) against the measured
    per-token serving time — while the interleaved wall A/B (one warm
    engine, `fleet_telemetry` toggled per drive, alternating-order
    pairs) rides along as a sanity band. to_wire() (the per-publish
    fleet frame, ~1/s per worker) is priced separately."""
    import statistics

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.telemetry.slo import SloTracker

    tr = SloTracker()
    iters = 20_000
    t0 = time.perf_counter()
    for i in range(iters):
        tr.observe("itl_ms", 10.0 + (i & 15))
    observe_us = (time.perf_counter() - t0) / iters * 1e6
    t0 = time.perf_counter()
    for _ in range(2_000):
        tr.finish_request(
            ttft_ms=100.0, itl_ms=10.0, e2e_ms=500.0, tokens=osl
        )
    finish_us = (time.perf_counter() - t0) / 2_000 * 1e6
    t0 = time.perf_counter()
    for _ in range(200):
        tr.to_wire()
    wire_us = (time.perf_counter() - t0) / 200 * 1e6

    eng = JaxEngine(EngineConfig.for_tests())
    slo_tracker = eng.slo

    def drive(tag: str) -> tuple[float, int]:
        for i in range(n_req):
            eng.add_request(
                f"{tag}-{i}", [1 + i, 2, 3, 4],
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        t0 = time.perf_counter()
        done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        eng.allocator.clear_cache()
        toks = sum(len(v) for v in done.values())
        return (toks / dt if dt else 0.0), toks

    drive("warm")  # compile every program before the timed arms
    rates: dict = {"on": [], "off": []}
    on_tokens = on_observes = on_finishes = 0
    for rep in range(pairs):
        arms = [("on", True), ("off", False)]
        if rep % 2:
            arms.reverse()  # cancel any first-arm bias
        for tag, on in arms:
            eng.slo = slo_tracker if on else None
            eng._fleet_telemetry = on
            if on:
                obs0 = sum(
                    sk.count for sk in slo_tracker.sketches.values()
                )
                fin0 = slo_tracker.requests_total
            rate, toks = drive(f"{tag}{rep}")
            rates[tag].append(rate)
            if on:
                on_tokens += toks
                on_observes += (
                    sum(sk.count for sk in slo_tracker.sketches.values())
                    - obs0
                )
                on_finishes += slo_tracker.requests_total - fin0
    eng.slo = slo_tracker
    eng._fleet_telemetry = True
    on_med = statistics.median(rates["on"])
    off_med = statistics.median(rates["off"])
    modeled = measured = None
    # the engine observes once per EMISSION (a fused dispatch's delivery
    # spreads one observe over its tokens): price the MEASURED call
    # pattern, not a one-observe-per-token worst case
    obs_per_token = on_observes / on_tokens if on_tokens else 1.0
    fin_per_token = on_finishes / on_tokens if on_tokens else 1.0 / osl
    if off_med:
        serving_us_per_token = 1e6 / off_med
        modeled = round(
            (observe_us * obs_per_token + finish_us * fin_per_token)
            / serving_us_per_token * 100.0,
            3,
        )
        measured = round((1.0 - on_med / off_med) * 100.0, 2)
    return {
        "pairs": pairs,
        "telemetry_on_tok_s": round(on_med, 1),
        "telemetry_off_tok_s": round(off_med, 1),
        "observe_us": round(observe_us, 3),
        "finish_request_us": round(finish_us, 3),
        "frame_to_wire_us": round(wire_us, 2),
        "observes_per_token": round(obs_per_token, 4),
        "modeled_overhead_pct": modeled,
        "measured_overhead_pct": measured,
    }


def _handover_ab() -> dict:
    """Worker-handover A/B (ISSUE 12 acceptance): TTFT of a CONTINUED
    stream when its prompt blocks arrived warm via handover vs
    replay-by-recompute, plus the bytes-moved vs prefill-flops-saved
    accounting. The headline numbers are DETERMINISTIC by construction:
    blocks/bytes moved follow exactly from the workload shape and the
    canonical wire format, flops saved is the standard 2·P·T over the
    cached tokens, and `modeled_ttft_ratio` counts prefill-chunk
    dispatches (uncached/chunk vs total/chunk) — the wall-clock TTFT
    pair rides along as a sanity band only (box noise).

    Engine-level: the same export/adopt calls the Worker handover op
    drives (engine.handover_metas / export_blocks_by_hash /
    prepare+commit_handover_adopt); the transfer-plane hop is covered by
    tests/test_handover.py. The accounting itself (2·P·T, wire bytes,
    chunk-counted modeled ratio) lives in kv_economy.CostModel — the
    ONE pricing function the router, the planner and this bench share
    (ISSUE 18)."""
    from dataclasses import replace

    import jax
    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.kv_economy import CostModel
    from dynamo_tpu.tokens import hash_token_blocks

    cfg = replace(EngineConfig.for_tests(), max_pages_per_seq=32)
    prompt = [((i * 37) % 211) + 1 for i in range(48)]
    n_emit = 8

    # retiring side: serve once (registers prompt + generated blocks),
    # then export the whole registered set in the canonical wire format
    a = JaxEngine(cfg)
    a.add_request(
        "warm", prompt,
        SamplingParams(temperature=0.0, max_tokens=n_emit, ignore_eos=True),
    )
    emitted = a.run_to_completion()["warm"]
    metas = a.handover_metas()
    t0 = time.perf_counter()
    emetas, k, v = a.export_blocks_by_hash([h for h, _, _ in metas])
    export_s = time.perf_counter() - t0
    bytes_moved = int(k.nbytes + v.nbytes)
    blocks_moved = len(emetas)
    block_bytes = bytes_moved // blocks_moved

    # successor: compile-warm its programs on a DISJOINT prompt so the
    # cold/warm TTFT pair measures prefill work, not XLA compiles
    b = JaxEngine(cfg)
    b.add_request(
        "jit", [7] * len(prompt),
        SamplingParams(temperature=0.0, max_tokens=n_emit, ignore_eos=True),
    )
    b.run_to_completion()
    b.allocator.clear_cache()

    continuation = list(prompt) + [int(t) for t in emitted]

    def ttft(tag: str) -> float:
        b.add_request(
            tag, continuation,
            SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True),
        )
        t0 = time.perf_counter()
        for _ in range(10_000):
            outs = b.step()
            if any(o.request_id == tag and o.new_token_ids for o in outs):
                dt = time.perf_counter() - t0
                b.run_to_completion()  # drain the tail
                return dt
        raise RuntimeError("no first token")

    # replay-by-recompute: the continuation prefills from scratch
    ttft_cold_s = ttft("cold")
    b.allocator.clear_cache()

    # warm handover: adopt the exported blocks, then the SAME
    # continuation prefix-hits them
    t0 = time.perf_counter()
    pages, kept, want = b.prepare_handover_adopt(emetas)
    b.inject_pages(
        pages,
        np.ascontiguousarray(k[:, :, want]),
        np.ascontiguousarray(v[:, :, want]),
    )
    adopted = b.commit_handover_adopt(pages, kept)
    adopt_s = time.perf_counter() - t0
    hashes = hash_token_blocks(
        continuation, block_size=cfg.page_size, salt=cfg.model
    )
    cached_tokens = b.allocator.match_length(hashes) * cfg.page_size
    ttft_warm_s = ttft("warmc")

    n_params = sum(int(x.size) for x in jax.tree.leaves(b.params))
    cm = CostModel(
        params=n_params, block_bytes=block_bytes, page_size=cfg.page_size
    )
    flops_saved = cm.flops_saved(cached_tokens)
    assert cm.bytes_moved(blocks_moved) == bytes_moved
    return {
        "prompt_tokens": len(prompt),
        "emitted_tokens": len(emitted),
        "page_size": cfg.page_size,
        "params": n_params,
        "blocks_moved": blocks_moved,
        "block_bytes": block_bytes,
        "bytes_moved": bytes_moved,
        "blocks_adopted": adopted,
        "cached_tokens": cached_tokens,
        "prefill_flops_saved": flops_saved,
        "flops_saved_per_byte": round(flops_saved / bytes_moved, 2),
        "export_s": round(export_s, 4),
        "adopt_s": round(adopt_s, 4),
        "ttft_cold_s": round(ttft_cold_s, 4),
        "ttft_warm_s": round(ttft_warm_s, 4),
        "measured_ttft_ratio": round(ttft_warm_s / ttft_cold_s, 3)
        if ttft_cold_s
        else None,
        # deterministic: prefill-chunk dispatches the warm continuation
        # skips vs the cold one — the pinned contract number
        "modeled_ttft_ratio": round(
            cm.modeled_ttft_ratio(
                len(continuation), cached_tokens, cfg.prefill_chunk
            ),
            4,
        ),
    }


def _prefix_migration_ab() -> dict:
    """Per-prefix KV migration A/B (ISSUE 18 acceptance): a multi-turn
    chat session's turn-2 TTFT when only the session's HOT PREFIX CHAIN
    migrated to a fresh worker vs cold prefill, priced by the shared
    kv_economy CostModel. Unlike `_handover_ab` (the whole registered
    set moves with its worker), this moves exactly the chain the next
    request will hit — the router's migrate_prefix shape: export the
    matched hashes, adopt on the destination, re-serve.

    Deterministic headline: turn 1 is 32 tokens (8 full blocks at
    page_size=4 — the source's registered chain covers the prompt;
    decode tokens ride uncached), 8 emitted; turn 2 re-sends the
    history plus 8 user tokens → 48 total, 16 uncached → 1 warm prefill
    chunk vs 3 cold at chunk=16 (modeled_ttft_ratio 1/3).
    should_migrate must hold at this shape — the bench run re-checks
    the same pricing fn the router gates on."""
    from dataclasses import replace

    import jax
    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.kv_economy import CostModel
    from dynamo_tpu.tokens import hash_token_blocks

    cfg = replace(EngineConfig.for_tests(), max_pages_per_seq=32)
    turn1 = [((i * 37) % 211) + 1 for i in range(32)]
    n_emit = 8

    # the session's home worker: serve turn 1 (prompt + generated blocks
    # register as they fill), then export ONLY the chain turn 2 needs
    a = JaxEngine(cfg)
    a.add_request(
        "turn1", turn1,
        SamplingParams(temperature=0.0, max_tokens=n_emit, ignore_eos=True),
    )
    emitted = a.run_to_completion()["turn1"]
    history = list(turn1) + [int(t) for t in emitted]
    turn2 = history + [((i * 53) % 211) + 1 for i in range(8)]
    chain = hash_token_blocks(
        history, block_size=cfg.page_size, salt=cfg.model
    )
    t0 = time.perf_counter()
    exported = a.export_blocks_by_hash([int(h) for h in chain])
    export_s = time.perf_counter() - t0
    if exported is None:
        raise RuntimeError("hot prefix chain not resident on the source")
    emetas, k, v = exported
    bytes_moved = int(k.nbytes + v.nbytes)
    blocks_moved = len(emetas)
    block_bytes = bytes_moved // blocks_moved

    # the fresh worker the router redirected to: compile-warm on a
    # disjoint prompt so the TTFT pair measures prefill work only
    b = JaxEngine(cfg)
    b.add_request(
        "jit", [7] * len(turn2),
        SamplingParams(temperature=0.0, max_tokens=n_emit, ignore_eos=True),
    )
    b.run_to_completion()
    b.allocator.clear_cache()

    def ttft(tag: str) -> float:
        b.add_request(
            tag, turn2,
            SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True),
        )
        t0 = time.perf_counter()
        for _ in range(10_000):
            outs = b.step()
            if any(o.request_id == tag and o.new_token_ids for o in outs):
                dt = time.perf_counter() - t0
                b.run_to_completion()  # drain the tail
                return dt
        raise RuntimeError("no first token")

    # cold: the suppressed-migration path — turn 2 prefills from scratch
    ttft_cold_s = ttft("cold")
    b.allocator.clear_cache()

    # warm: adopt the migrated chain, then the SAME turn 2 prefix-hits
    t0 = time.perf_counter()
    pages, kept, want = b.prepare_handover_adopt(emetas)
    b.inject_pages(
        pages,
        np.ascontiguousarray(k[:, :, want]),
        np.ascontiguousarray(v[:, :, want]),
    )
    adopted = b.commit_handover_adopt(pages, kept)
    adopt_s = time.perf_counter() - t0
    hashes = hash_token_blocks(
        turn2, block_size=cfg.page_size, salt=cfg.model
    )
    cached_tokens = b.allocator.match_length(hashes) * cfg.page_size
    ttft_warm_s = ttft("warmc")

    n_params = sum(int(x.size) for x in jax.tree.leaves(b.params))
    cm = CostModel(
        params=n_params, block_bytes=block_bytes, page_size=cfg.page_size
    )
    price = cm.price(blocks_moved)
    return {
        "turn1_tokens": len(turn1),
        "turn2_tokens": len(turn2),
        "emitted_tokens": len(emitted),
        "page_size": cfg.page_size,
        "params": n_params,
        "blocks_moved": blocks_moved,
        "block_bytes": block_bytes,
        "bytes_moved": bytes_moved,
        "blocks_adopted": adopted,
        "cached_tokens": cached_tokens,
        "prefill_flops_saved": cm.flops_saved(cached_tokens),
        "flops_saved_per_byte": round(price.flops_saved_per_byte, 2),
        # the router's gate, re-evaluated on the bench shape: this move
        # must clear the break-even threshold
        "should_migrate": cm.should_migrate(blocks_moved),
        "export_s": round(export_s, 4),
        "adopt_s": round(adopt_s, 4),
        "ttft_cold_s": round(ttft_cold_s, 4),
        "ttft_warm_s": round(ttft_warm_s, 4),
        "measured_ttft_ratio": round(ttft_warm_s / ttft_cold_s, 3)
        if ttft_cold_s
        else None,
        # deterministic: 1 warm prefill chunk vs 3 cold (16 uncached vs
        # 48 total at chunk=16) — the pinned contract number
        "modeled_ttft_ratio": round(
            cm.modeled_ttft_ratio(
                len(turn2), cached_tokens, cfg.prefill_chunk
            ),
            4,
        ),
    }


def _flight_overhead_ab(pairs: int = 4, osl: int = 32, n_req: int = 8) -> dict:
    """Flight-recorder overhead A/B (ISSUE 7 acceptance): the per-step
    record — one small dict build + deque append, ONCE per engine step
    regardless of batch size — must cost <1% of token throughput. Like
    trace/slo_overhead, this box's load noise dwarfs the true cost on a
    short tiny-engine run, so the <1% claim is pinned by
    `modeled_overhead_pct`: a deterministic microbench of record_step()
    priced at the MEASURED records-per-token rate of the same drive (a
    decode step amortizes one record over its whole batch), while the
    interleaved wall A/B (one warm engine, `flight` nulled per arm,
    alternating-order pairs) rides along as a sanity band."""
    import statistics

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import EngineMetrics, JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.telemetry.flight import FlightRecorder

    # deterministic microbench: one per-step record against live-ish
    # counters (the delta loop is the dominant cost)
    fl = FlightRecorder(512)
    fm = EngineMetrics()
    iters = 20_000
    t0 = time.perf_counter()
    for i in range(iters):
        fm.generated_tokens += 8
        fm.time_decode_dispatch_ms += 0.5
        fl.record_step(
            fm, kind="decode", step_ms=1.0, n_decode=8, b_decode=8,
            waiting=0, running=8, free_pages=100, active_pages=28,
            watermark=28,
        )
    record_us = (time.perf_counter() - t0) / iters * 1e6

    eng = JaxEngine(EngineConfig.for_tests())
    recorder = eng.flight

    def drive(tag: str) -> tuple[float, int]:
        for i in range(n_req):
            eng.add_request(
                f"{tag}-{i}", [1 + i, 2, 3, 4],
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        t0 = time.perf_counter()
        done = eng.run_to_completion()
        dt = time.perf_counter() - t0
        eng.allocator.clear_cache()
        toks = sum(len(v) for v in done.values())
        return (toks / dt if dt else 0.0), toks

    drive("warm")  # compile every program before the timed arms
    rates: dict = {"on": [], "off": []}
    on_records = on_tokens = 0
    for rep in range(pairs):
        arms = [("on", True), ("off", False)]
        if rep % 2:
            arms.reverse()  # cancel any first-arm bias
        for tag, on in arms:
            eng.flight = recorder if on else None
            if on:
                rec0 = recorder._seq
            rate, toks = drive(f"{tag}{rep}")
            rates[tag].append(rate)
            if on:
                on_records += recorder._seq - rec0
                on_tokens += toks
    eng.flight = recorder
    on_med = statistics.median(rates["on"])
    off_med = statistics.median(rates["off"])
    records_per_token = on_records / on_tokens if on_tokens else 1.0
    modeled = measured = None
    if off_med:
        serving_us_per_token = 1e6 / off_med
        modeled = round(
            record_us * records_per_token / serving_us_per_token * 100.0, 3
        )
        measured = round((1.0 - on_med / off_med) * 100.0, 2)
    return {
        "pairs": pairs,
        "flight_on_tok_s": round(on_med, 1),
        "flight_off_tok_s": round(off_med, 1),
        "record_us": round(record_us, 3),
        "records_per_token": round(records_per_token, 4),
        "modeled_overhead_pct": modeled,
        "measured_overhead_pct": measured,
    }


def _kv_index_overhead_ab(pairs: int = 4, osl: int = 32, n_req: int = 8) -> dict:
    """KV index sequencing overhead A/B (ISSUE 13 acceptance): the
    sequence stamp + rolling-digest fold added to the KV event publish
    path must cost <1% of token throughput. The stamp runs in the
    worker's async publish loop — off the token path entirely — and KV
    events are RARE relative to tokens (one stored event per full page
    = 1/page_size per generated token, plus evictions), so the honest
    claim is the DETERMINISTIC model: a microbench of the REAL
    Worker._stamp_kv_events hot path priced at the measured
    events-per-token rate of a live drive. The interleaved wall A/B
    (same engine, publish-tick simulation stamping on/off per arm)
    rides along as a sanity band."""
    import statistics

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.worker import Worker

    card = ModelDeploymentCard(name="tiny", kv_page_size=4)
    w = Worker(None, card, engine_kind="echo")

    # deterministic microbench: the real stamping path over realistic
    # single-hash stored/removed batches (what the allocator emits)
    batch = [
        {
            "kind": "stored" if i % 3 else "removed",
            "block_hashes": [(i * 2654435761) & ((1 << 64) - 1)],
            "parent_hash": None,
            "token_blocks": [[1, 2, 3, 4]],
        }
        for i in range(64)
    ]
    iters = 2_000
    t0 = time.perf_counter()
    for _ in range(iters):
        for ev in batch:
            ev.pop("seq", None)
        w._stamp_kv_events(batch)
    stamp_us = (time.perf_counter() - t0) / (iters * len(batch)) * 1e6

    events = []
    eng = JaxEngine(
        EngineConfig.for_tests(), on_kv_event=lambda e: events.append(e)
    )
    wire = Worker._kv_event_wire

    def drive(tag: str, stamp: bool) -> tuple[float, int, int]:
        del events[:]
        for i in range(n_req):
            eng.add_request(
                f"{tag}-{i}", [1 + i, 2, 3, 4],
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        t0 = time.perf_counter()
        done = eng.run_to_completion()
        # the publish-tick work the sequencing adds, in-line so the arm
        # pays it inside the timed window
        batch = [wire(e) for e in events]
        if stamp:
            w._stamp_kv_events(batch)
        dt = time.perf_counter() - t0
        eng.allocator.clear_cache()
        toks = sum(len(v) for v in done.values())
        return (toks / dt if dt else 0.0), toks, len(batch)

    drive("warm", False)
    rates: dict = {"on": [], "off": []}
    ev_total = tok_total = 0
    for rep in range(pairs):
        arms = [("on", True), ("off", False)]
        if rep % 2:
            arms.reverse()
        for tag, stamp in arms:
            rate, toks, nev = drive(f"{tag}{rep}", stamp)
            rates[tag].append(rate)
            if stamp:
                ev_total += nev
                tok_total += toks
    on_med = statistics.median(rates["on"])
    off_med = statistics.median(rates["off"])
    events_per_token = ev_total / tok_total if tok_total else 1.0
    modeled = measured = None
    if off_med:
        serving_us_per_token = 1e6 / off_med
        modeled = round(
            stamp_us * events_per_token / serving_us_per_token * 100.0, 4
        )
        measured = round((1.0 - on_med / off_med) * 100.0, 2)
    return {
        "pairs": pairs,
        "seq_on_tok_s": round(on_med, 1),
        "seq_off_tok_s": round(off_med, 1),
        "stamp_us": round(stamp_us, 4),
        "events_per_token": round(events_per_token, 4),
        "modeled_overhead_pct": modeled,
        "measured_overhead_pct": measured,
    }


def _trace_plane_overhead_ab(
    pairs: int = 3, osl: int = 32, n_req: int = 8
) -> dict:
    """Fleet trace plane overhead A/B (ISSUE 14 acceptance): span
    SHIPPING (sink append + msgpack batch pack) + phase-histogram
    EXEMPLAR stamping on a warm engine must cost <1% of token
    throughput. Like the sibling telemetry A/Bs, the <1% claim is the
    DETERMINISTIC model — a microbench of the per-span ship work and
    the per-observe exemplar delta priced at the MEASURED
    spans/request and observes/token of a live traced drive — while
    the interleaved wall A/B rides along as a sanity band. The model
    is conservative twice over: the batch pack actually runs in the
    async publish loop (off the token path), and every engine-thread
    observe is charged the exemplar-stamped price."""
    import statistics

    import msgpack

    from dynamo_tpu import telemetry
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.telemetry import phases as _phases
    from dynamo_tpu.telemetry import traceplane

    # -- microbench 1: one shipped span (open+close through the sink)
    # plus its amortized share of a 64-span msgpack batch pack
    telemetry.configure(enabled=True, ring_size=8)
    traceplane.ensure_shipping()
    iters = 3_000
    t0 = time.perf_counter()
    for _ in range(iters):
        with telemetry.span("engine.generate", service="engine") as sp:
            sp.add_event("first_token")
    span_us = (time.perf_counter() - t0) / iters * 1e6
    batch = traceplane.drain_spans()[:64]
    t0 = time.perf_counter()
    for _ in range(200):
        msgpack.packb(batch, use_bin_type=True, default=repr)
    pack_us_per_span = (
        (time.perf_counter() - t0) / (200 * max(1, len(batch))) * 1e6
    )
    ship_us_per_span = span_us + pack_us_per_span

    # -- microbench 2: exemplar-stamped observe vs plain observe
    tid = "ab" * 16
    t0 = time.perf_counter()
    for i in range(20_000):
        _phases.observe("decode_step_ms", 1.0 + (i & 7), trace_id=tid)
    stamped_us = (time.perf_counter() - t0) / 20_000 * 1e6
    telemetry.configure(enabled=False)
    _phases.phase_histograms.reset()
    t0 = time.perf_counter()
    for i in range(20_000):
        _phases.observe("decode_step_ms", 1.0 + (i & 7))
    plain_us = (time.perf_counter() - t0) / 20_000 * 1e6
    exemplar_us = max(0.0, stamped_us - plain_us)

    # -- the interleaved wall A/B on one warm engine, measuring the
    # live spans/request + observes/token rates for the model
    eng = JaxEngine(EngineConfig.for_tests())

    def drive(tag: str, on: bool) -> tuple[float, int, int, int]:
        if on:
            telemetry.configure(enabled=True, ring_size=64)
            traceplane.ensure_shipping()
            traceplane.drain_spans()
        obs0 = sum(
            sum(c) for c in _phases.phase_histograms._counts.values()
        )
        t0 = time.perf_counter()
        reqs = []
        for i in range(n_req):
            # the traced path exactly as AsyncEngineRunner drives it:
            # one engine span per request, trace id stamped on the
            # engine-side Request (exemplars + breakdown enrichment)
            if on:
                with telemetry.span(
                    "engine.generate", service="engine"
                ) as sp:
                    req = eng.add_request(
                        f"{tag}-{i}", [1 + i, 2, 3, 4],
                        SamplingParams(temperature=0.0, max_tokens=osl),
                    )
                    req.trace_id = sp.trace_id
            else:
                req = eng.add_request(
                    f"{tag}-{i}", [1 + i, 2, 3, 4],
                    SamplingParams(temperature=0.0, max_tokens=osl),
                )
            reqs.append(req)
        done = eng.run_to_completion()
        shipped = 0
        if on:
            spans = traceplane.drain_spans()
            msgpack.packb(spans, use_bin_type=True, default=repr)
            shipped = len(spans)
        dt = time.perf_counter() - t0
        if on:
            telemetry.configure(enabled=False)
        eng.allocator.clear_cache()
        toks = sum(len(v) for v in done.values())
        obs = (
            sum(sum(c) for c in _phases.phase_histograms._counts.values())
            - obs0
        )
        return (toks / dt if dt else 0.0), toks, shipped, obs

    drive("warm", False)
    rates: dict = {"on": [], "off": []}
    span_total = tok_total = obs_total = 0
    for rep in range(pairs):
        arms = [("on", True), ("off", False)]
        if rep % 2:
            arms.reverse()
        for tag, on in arms:
            rate, toks, shipped, obs = drive(f"{tag}{rep}", on)
            rates[tag].append(rate)
            if on:
                span_total += shipped
                tok_total += toks
                obs_total += obs
    telemetry.configure(enabled=False)
    traceplane.disable_shipping()
    _phases.phase_histograms.reset()
    on_med = statistics.median(rates["on"])
    off_med = statistics.median(rates["off"])
    spans_per_token = span_total / tok_total if tok_total else 1.0
    observes_per_token = obs_total / tok_total if tok_total else 1.0
    modeled = measured = None
    if off_med:
        serving_us_per_token = 1e6 / off_med
        modeled = round(
            (
                ship_us_per_span * spans_per_token
                + exemplar_us * observes_per_token
            )
            / serving_us_per_token
            * 100.0,
            4,
        )
        measured = round((1.0 - on_med / off_med) * 100.0, 2)
    return {
        "pairs": pairs,
        "trace_plane_on_tok_s": round(on_med, 1),
        "trace_plane_off_tok_s": round(off_med, 1),
        "ship_us_per_span": round(ship_us_per_span, 3),
        "exemplar_us_per_observe": round(exemplar_us, 4),
        "spans_per_token": round(spans_per_token, 4),
        "observes_per_token": round(observes_per_token, 4),
        "modeled_overhead_pct": modeled,
        "measured_overhead_pct": measured,
    }


def _failover_blackout() -> dict:
    """Control-plane failover blackout (ISSUE 15 acceptance): primary +
    warm standby in-process, a steady ringed publisher, SIGKILL-
    equivalent primary death. `blackout_ms` spans the last successful
    publish before the kill to the FIRST successful publish after the
    standby promoted (detector budget 0.3s here). Plus the replication-
    overhead A/B: the journal tap is the only cost replication adds to
    the publish path, measured by interleaved tap-on/tap-off batches on
    the fabric publish path and MODELED against the measured wire
    publish round-trip (<2% target, asserted in test_bench_contract —
    wall ratios on this box swing with load, so the deterministic model
    is the claim)."""
    import asyncio
    import statistics
    import time as _time

    from dynamo_tpu.runtime.fabric import (
        FabricNode,
        FabricServer,
        RemoteFabric,
    )
    from dynamo_tpu.runtime.fabric.local import LocalFabric

    async def drive() -> dict:
        primary = FabricServer(port=0)
        await primary.start()
        node = FabricNode(
            port=0, standby_of=primary.address, detector_budget_s=0.3,
            orphan_grace=10.0,
        )
        await node.start()
        client = await RemoteFabric.connect(
            f"{primary.address},{node.address}"
        )
        try:
            # steady-state wire publish cost (standby attached — the
            # deployed configuration)
            for _ in range(20):  # warm
                await client.publish("kv_events.bench", {"i": -1}, b"x" * 64)
            t0 = _time.perf_counter()
            n_wire = 200
            for i in range(n_wire):
                await client.publish("kv_events.bench", {"i": i}, b"x" * 64)
            wire_us = (_time.perf_counter() - t0) / n_wire * 1e6

            # blackout: publish at a tight cadence, kill, time to the
            # first success on the promoted standby
            before = after = 0
            last_ok = _time.perf_counter()
            for i in range(50):
                await client.publish("kv_events.bench", {"b": i}, b"x")
                before += 1
                last_ok = _time.perf_counter()
            primary.kill()
            first_ok = None
            deadline = _time.perf_counter() + 30.0
            while first_ok is None and _time.perf_counter() < deadline:
                try:
                    await client.publish("kv_events.bench", {"a": after}, b"x")
                    first_ok = _time.perf_counter()
                except (ConnectionError, RuntimeError, OSError):
                    await asyncio.sleep(0.005)
            if first_ok is None:
                return {"error": "no publish succeeded after the kill"}
            for i in range(20):
                await client.publish("kv_events.bench", {"a": i}, b"x")
                after += 1
            return {
                "blackout_ms": round((first_ok - last_ok) * 1000.0, 1),
                "detector_budget_ms": 300.0,
                "publishes_before": before,
                "publishes_after": after + 1,
                "promoted_fence": node.fabric.fence,
                "wire_publish_us": round(wire_us, 1),
            }
        finally:
            await client.close()
            await node.stop()
            await primary.stop()

    async def tap_ab(wire_us: float) -> dict:
        """Interleaved journal-tap on/off batches on the publish path."""
        f = LocalFabric()
        n, reps = 400, 6
        base_runs, tap_runs = [], []
        q = None
        for r in range(2 * reps):
            tap = r % 2 == 1
            if tap and q is None:
                q = f.repl_attach()
            if not tap and q is not None:
                f.repl_detach(q)
                q = None
            t0 = _time.perf_counter()
            for i in range(n):
                await f.publish("kv_events.ab", {"i": i}, b"x" * 64)
            us = (_time.perf_counter() - t0) / n * 1e6
            (tap_runs if tap else base_runs).append(us)
            if q is not None:
                while not q.empty():  # drain like a live standby would
                    q.get_nowait()
        base_us = statistics.median(base_runs)
        tap_us = statistics.median(tap_runs)
        tap_cost = max(0.0, tap_us - base_us)
        return {
            "publish_path_base_us": round(base_us, 3),
            "publish_path_tap_us": round(tap_us, 3),
            "tap_cost_us": round(tap_cost, 3),
            # the model: replication adds tap_cost to every wire publish
            # that costs wire_us end to end — THIS is the deployment
            # overhead claim (<2%)
            "modeled_repl_overhead_pct": round(
                tap_cost / wire_us * 100.0, 4
            ) if wire_us else None,
            # the raw in-process path ratio (microseconds on
            # microseconds) — NOT a deployment overhead; reported so the
            # tap cost itself is visible
            "tap_path_ratio_pct": round(
                (tap_us / base_us - 1.0) * 100.0, 2
            ) if base_us else None,
        }

    async def run():
        doc = await drive()
        if "error" in doc:
            return doc
        doc.update(await tap_ab(doc["wire_publish_us"]))
        return doc

    return asyncio.run(run())


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dynamo_tpu.platform import require_platform

    try:
        platform = require_platform()
    except RuntimeError as e:
        # no JSON line: a run that found no chip has no result
        raise SystemExit(f"bench.py: {e}")

    if platform == "cpu":
        # The CPU cannot run the TPU workload (llama3-1b x 128 requests
        # would take hours); the labeled CPU mode runs a CPU-feasible
        # configuration and says so in extras. vs_baseline compares against
        # the CPU record (cpu_output_tok_s), never the TPU one.
        model = os.environ.get("BENCH_MODEL", "tiny")
        num_requests = int(os.environ.get("BENCH_REQUESTS", "16"))
        isl = int(os.environ.get("BENCH_ISL", "64"))
        osl = int(os.environ.get("BENCH_OSL", "32"))
    else:
        model = os.environ.get("BENCH_MODEL", "llama3-1b")
        num_requests = int(os.environ.get("BENCH_REQUESTS", "128"))
        isl = int(os.environ.get("BENCH_ISL", "128"))
        osl = int(os.environ.get("BENCH_OSL", "64"))

    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    chunk = -(-max(128, isl) // 64) * 64  # page-aligned prefill chunk
    # One wave: every request resident at once (weights amortize across
    # the whole batch), pages sized for prompt+output per sequence.
    pages_per_seq = -(-(isl + osl + 1) // 64)

    def make_engine(
        attention_impl: str,
        overlap: bool = True,
        decode_steps: int = None,
        kv_quantize: str = "env",
    ) -> JaxEngine:
        if kv_quantize == "env":
            # chip stage: BENCH_KV_QUANTIZE=int8 runs the headline with
            # quantized pages
            kv_quantize = os.environ.get("BENCH_KV_QUANTIZE") or None
        cfg = EngineConfig(
            model=model,
            num_pages=max(512, num_requests * (pages_per_seq + 1)),
            page_size=64,
            max_pages_per_seq=max(16, pages_per_seq + 1),
            # Buckets up to and INCLUDING one that fits the whole batch, so
            # decode really runs as one wave (the scheduler caps batches at
            # decode_buckets[-1]).
            decode_buckets=tuple(
                b for b in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
                if b < num_requests
            ) + (num_requests,),
            prefill_chunk=chunk,
            # Whole-workload dispatches: all prompts prefill in one batched
            # program; decode fuses K steps per host sync.
            prefill_token_budget=num_requests * chunk,
            decode_steps=(
                decode_steps
                if decode_steps is not None
                else int(os.environ.get("BENCH_DECODE_STEPS", "64"))
            ),
            max_seqs=max(32, num_requests),
            dtype="bfloat16",
            enable_prefix_caching=False,
            # llama3-8b bf16 (16GB) exceeds a v5e chip's HBM; int8
            # weight-only (BENCH_QUANTIZE=int8) fits it alongside the KV
            # pages.
            quantize=os.environ.get("BENCH_QUANTIZE") or None,
            kv_quantize=kv_quantize,
            attention_impl=attention_impl,
            overlap_decode=overlap,
            # chip stage bench_1b_tp: BENCH_TOPOLOGY=tp=4,dp=2 runs the
            # headline on the combined mesh layout; params place through
            # the logical-axis rule table (ISSUE 20)
            topology=os.environ.get("BENCH_TOPOLOGY", ""),
        )
        return JaxEngine(cfg)

    # Serving-config sweep: the pallas page-walk decode is latency-optimal
    # at small batch but issues O(B x pages) DMA descriptors per layer;
    # "hybrid" gates large decode buckets onto the XLA gather. The bench
    # measures both on TPU and reports the BEST (per-impl numbers in
    # extras) — picking a serving config is legitimate tuning, hiding the
    # loser would not be.
    default_impls = "auto,hybrid" if platform == "tpu" else "auto"
    impls = [
        i.strip()
        for i in os.environ.get("BENCH_ATTENTION", default_impls).split(",")
        if i.strip()
    ]

    eng = make_engine(impls[0])

    import jax

    n_params = sum(int(x.size) for x in jax.tree.leaves(eng.params))
    # MoE: FLOPs/token follow the ACTIVE parameters (top_k of E experts),
    # not the resident total — MFU from total params would overstate ~8x
    # for deepseek-v2-lite. Routed expert leaves are named we_*.
    acfg = eng.adapter.config
    n_experts = getattr(acfg, "n_routed_experts", 0) or getattr(
        acfg, "num_experts", 0
    )
    top_k = getattr(acfg, "num_experts_per_tok", None) or getattr(
        acfg, "top_k", 0
    )
    n_active = n_params
    if n_experts and top_k:
        expert_elems = sum(
            int(leaf.size)
            for path, leaf in jax.tree_util.tree_leaves_with_path(eng.params)
            if any(
                getattr(k, "key", "").startswith("we_")
                and not getattr(k, "key", "").endswith("_scale")
                for k in path
            )
        )
        n_active = n_params - expert_elems + expert_elems * top_k // n_experts

    rng = np.random.default_rng(0)
    prompts = [
        [int(x) for x in rng.integers(1, 32000, isl)] for _ in range(num_requests)
    ]

    def run_timed(eng) -> dict:
        # Warmup with the SAME workload (all requests, same osl) so every
        # decode bucket, fused-step count, and prefill program the timed
        # run uses is compiled before the timer starts — otherwise tok/s
        # and TTFT measure XLA (the fused decode K adapts to remaining
        # max_tokens, so a short warmup osl would compile the wrong K).
        for i, p in enumerate(prompts):
            eng.add_request(
                f"warm{i}", p,
                SamplingParams(temperature=0.0, max_tokens=osl),
            )
        eng.run_to_completion()
        eng.allocator.clear_cache()

        # decode phase split (dispatch/sync/postprocess + overlap
        # counters) is reported as deltas over the TIMED section only
        phase0 = {
            k: getattr(eng.metrics, k)
            for k in (
                "time_decode_dispatch_ms", "time_decode_sync_ms",
                "time_decode_host_ms", "overlap_dispatches",
                "overlap_hits", "overlap_rollbacks",
            )
        }
        t0 = time.time()
        submit = {}
        first_token = {}
        last_token = {}
        tokens_of = {}
        for i, p in enumerate(prompts):
            rid = f"r{i}"
            submit[rid] = time.time()
            eng.add_request(
                rid, p, SamplingParams(temperature=0.0, max_tokens=osl)
            )
        generated = 0
        while eng.has_work:
            for out in eng.step():
                now = time.time()
                generated += len(out.new_token_ids)
                tokens_of[out.request_id] = tokens_of.get(
                    out.request_id, 0
                ) + len(out.new_token_ids)
                if out.is_first and out.request_id not in first_token:
                    first_token[out.request_id] = now
                last_token[out.request_id] = now
        elapsed = time.time() - t0
        ttfts = sorted(first_token[r] - submit[r] for r in first_token)
        itls = sorted(
            (last_token[r] - first_token[r]) / (tokens_of[r] - 1)
            for r in first_token
            if tokens_of.get(r, 0) > 1
        )
        return {
            "tok_s": generated / elapsed,
            "p50_ttft": ttfts[len(ttfts) // 2] if ttfts else float("nan"),
            "p50_itl": itls[len(itls) // 2] if itls else float("nan"),
            "elapsed": elapsed,
            "generated": generated,
            "decode_phases": {
                k: round(getattr(eng.metrics, k) - v, 2)
                for k, v in phase0.items()
            },
        }

    per_impl = {impls[0]: run_timed(eng)}
    for impl in impls[1:]:
        import gc

        del eng
        gc.collect()
        eng = make_engine(impl)
        per_impl[impl] = run_timed(eng)
    best_impl = max(per_impl, key=lambda k: per_impl[k]["tok_s"])
    best = per_impl[best_impl]

    # Overlap on/off A/B (CPU mode only): the overlapped decode
    # loop's win lives where per-step syncs dominate, so the A/B runs
    # the same workload at decode_steps=1 (classic stepping) with
    # overlap_decode on vs off. The TPU headline number already runs
    # with overlap on (fused K amortizes most of what's left).
    overlap_ab = None
    if platform != "tpu" and os.environ.get("BENCH_OVERLAP_AB", "1") != "0":
        import gc

        ab_steps = int(os.environ.get("BENCH_OVERLAP_AB_STEPS", "1"))
        overlap_ab = {"decode_steps": ab_steps}
        for tag, ov in (("overlap_on", True), ("overlap_off", False)):
            del eng
            gc.collect()
            eng = make_engine(best_impl, overlap=ov, decode_steps=ab_steps)
            r = run_timed(eng)
            ph = r["decode_phases"]
            overlap_ab[tag] = {
                "tok_s": round(r["tok_s"], 2),
                "decode_dispatch_ms": ph["time_decode_dispatch_ms"],
                "decode_sync_ms": ph["time_decode_sync_ms"],
                "decode_host_ms": ph["time_decode_host_ms"],
            }
        off_tok_s = overlap_ab["overlap_off"]["tok_s"]
        overlap_ab["speedup"] = (
            round(overlap_ab["overlap_on"]["tok_s"] / off_tok_s, 3)
            if off_tok_s
            else None
        )

    # KV-quant on/off A/B (CPU mode; on the chip BENCH_KV_QUANTIZE=int8
    # runs the headline with quantized pages): same workload with
    # int8 pages vs model-dtype pages, plus the pool-byte gauges so the
    # ~2x effective-capacity claim rides the record next to the tok/s.
    kvquant_ab = None
    if platform != "tpu" and os.environ.get("BENCH_KVQUANT_AB", "1") != "0":
        import gc

        kvquant_ab = {}
        for tag, kvq in (("kv_fp", None), ("kv_int8", "int8")):
            del eng
            gc.collect()
            eng = make_engine(best_impl, kv_quantize=kvq)
            r = run_timed(eng)
            kvquant_ab[tag] = {
                "tok_s": round(r["tok_s"], 2),
                "kv_pool_bytes": eng.metrics.kv_pool_bytes,
                "kv_pool_bytes_dense_equiv": (
                    eng.metrics.kv_pool_bytes_dense_equiv
                ),
            }
        fp_tok_s = kvquant_ab["kv_fp"]["tok_s"]
        kvquant_ab["speedup"] = (
            round(kvquant_ab["kv_int8"]["tok_s"] / fp_tok_s, 3)
            if fp_tok_s
            else None
        )
        kvquant_ab["capacity_ratio"] = round(
            kvquant_ab["kv_int8"]["kv_pool_bytes_dense_equiv"]
            / max(kvquant_ab["kv_int8"]["kv_pool_bytes"], 1),
            3,
        )

    # Subprocess external-engine harness A/B (CPU only: the harness is
    # engine-agnostic plumbing; its cost doesn't depend on the chip): the
    # per-token price of the wire hop, reported next to the headline.
    ext_ab = None
    if platform != "tpu" and os.environ.get("BENCH_EXT_AB", "1") != "0":
        try:
            ext_ab = _ext_harness_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            ext_ab = {"error": f"{type(e).__name__}: {e}"}

    # Mixed prefill+decode steps A/B (ISSUE 5): burst-drain ITL p95 with
    # the decode batch riding every prefill dispatch vs XOR scheduling.
    # Runs by default in CPU mode (tiny); BENCH_MIXED_AB=1 forces it on
    # TPU with the headline model.
    mixed_ab = None
    default_mixed = "1" if platform != "tpu" else "0"
    if os.environ.get("BENCH_MIXED_AB", default_mixed) != "0":
        try:
            mixed_ab = _mixed_ab(
                model=os.environ.get(
                    "BENCH_MIXED_MODEL",
                    "tiny" if platform != "tpu" else model,
                ),
                pairs=int(
                    os.environ.get(
                        "BENCH_MIXED_PAIRS",
                        "1",
                    )
                ),
            )
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            mixed_ab = {"error": f"{type(e).__name__}: {e}"}

    # Distributed-tracing on/off A/B (ISSUE 4): tracing must be free when
    # off and near-free when on; the per-request span fan (frontend ->
    # router -> engine -> child) rides the same echo workload.
    trace_ab = None
    if platform != "tpu" and os.environ.get("BENCH_TRACE_AB", "1") != "0":
        try:
            trace_ab = _trace_overhead_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            trace_ab = {"error": f"{type(e).__name__}: {e}"}

    # Fleet-telemetry on/off A/B (ISSUE 6): the SLO sketch + fleet
    # publishing layer must stay under 1% of token throughput.
    slo_ab = None
    if platform != "tpu" and os.environ.get("BENCH_SLO_AB", "1") != "0":
        try:
            slo_ab = _slo_overhead_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            slo_ab = {"error": f"{type(e).__name__}: {e}"}

    # Flight-recorder on/off A/B (ISSUE 7): the per-step record append
    # must stay under 1% of token throughput.
    flight_ab = None
    if platform != "tpu" and os.environ.get("BENCH_FLIGHT_AB", "1") != "0":
        try:
            flight_ab = _flight_overhead_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            flight_ab = {"error": f"{type(e).__name__}: {e}"}

    # Worker-handover A/B (ISSUE 12): warm-handover continuation TTFT vs
    # replay-by-recompute + bytes-moved vs prefill-flops-saved.
    handover_ab = None
    if platform != "tpu" and os.environ.get("BENCH_HANDOVER_AB", "1") != "0":
        try:
            handover_ab = _handover_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            handover_ab = {"error": f"{type(e).__name__}: {e}"}

    # Per-prefix KV migration A/B (ISSUE 18): turn-2 TTFT after
    # migrating only the session's hot prefix chain vs cold prefill,
    # priced by the shared kv_economy CostModel. Runs by default on the
    # CPU mode; BENCH_PREFIXMIG=1 forces it on TPU.
    prefixmig_ab = None
    default_prefixmig = "1" if platform != "tpu" else "0"
    if os.environ.get("BENCH_PREFIXMIG", default_prefixmig) != "0":
        try:
            prefixmig_ab = _prefix_migration_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            prefixmig_ab = {"error": f"{type(e).__name__}: {e}"}

    # KV index sequencing A/B (ISSUE 13): the sequence stamp + digest
    # fold on the event publish path must stay under 1% of token
    # throughput.
    kv_index_ab = None
    if platform != "tpu" and os.environ.get("BENCH_KV_INDEX_AB", "1") != "0":
        try:
            kv_index_ab = _kv_index_overhead_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            kv_index_ab = {"error": f"{type(e).__name__}: {e}"}

    # Fleet trace plane A/B (ISSUE 14): span shipping + exemplar
    # stamping on a warm engine must stay under 1% of token throughput.
    trace_plane_ab = None
    if platform != "tpu" and os.environ.get(
        "BENCH_TRACE_PLANE_AB", "1"
    ) != "0":
        try:
            trace_plane_ab = _trace_plane_overhead_ab()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            trace_plane_ab = {"error": f"{type(e).__name__}: {e}"}

    # Control-plane failover blackout + replication overhead (ISSUE 15):
    # warm-standby promotion window under a SIGKILL'd primary, and the
    # journal tap's cost on the publish path (<2% modeled).
    failover_ab = None
    if platform != "tpu" and os.environ.get(
        "BENCH_FAILOVER_AB", "1"
    ) != "0":
        try:
            failover_ab = _failover_blackout()
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            failover_ab = {"error": f"{type(e).__name__}: {e}"}

    # Draft-model speculative decoding A/B (ISSUE 9): decode tok/s with
    # the fused draft+verify path on vs off at batch <= 8. Runs by
    # default on the CPU fallback (tiny self-draft — acceptance ~1, the
    # upper-bound harness); BENCH_SPEC=1 forces it on TPU with the headline
    # model + llama3-draft — random-init unless BENCH_SPEC_DRAFT points
    # at a distilled draft, so read modeled_at_accept_rate there).
    # Deliberately LAST among the A/Bs: its engine compiles/gc churn
    # must not sit right before the telemetry wall-overhead sanity
    # bands, which are the load-sensitive ones.
    spec_ab = None
    default_spec = "1" if platform != "tpu" else "0"
    if os.environ.get("BENCH_SPEC", default_spec) != "0":
        try:
            spec_ab = _spec_ab(
                model=os.environ.get(
                    "BENCH_SPEC_MODEL",
                    "tiny" if platform != "tpu" else model,
                ),
                draft=os.environ.get(
                    "BENCH_SPEC_DRAFT",
                    None if platform != "tpu" else "llama3-draft",
                ),
                pairs=int(os.environ.get("BENCH_SPEC_PAIRS", "3")),
            )
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            spec_ab = {"error": f"{type(e).__name__}: {e}"}

    # Multi-host pipeline A/B (ISSUE 20): the decode pipeline carried
    # across hosts vs the old multi-host auto-off, under the forced
    # multi-host CPU mesh. Runs by default in CPU mode (tiny);
    # BENCH_MULTIHOST forces it with the headline model.
    multihost_ab = None
    default_mh = "1" if platform != "tpu" else "0"
    if os.environ.get("BENCH_MULTIHOST", default_mh) != "0":
        try:
            multihost_ab = _multihost_pipeline_ab(
                model=os.environ.get(
                    "BENCH_MULTIHOST_MODEL",
                    "tiny" if platform != "tpu" else model,
                ),
                pairs=int(os.environ.get("BENCH_MULTIHOST_PAIRS", "3")),
                topology=os.environ.get(
                    "BENCH_MULTIHOST_TOPOLOGY", "tp=2,dp=2"
                ),
            )
        except Exception as e:  # noqa: BLE001 — A/B failure must not kill
            # the headline artifact
            multihost_ab = {"error": f"{type(e).__name__}: {e}"}

    tok_s = best["tok_s"]
    p50_ttft = best["p50_ttft"]
    p50_itl = best["p50_itl"]
    elapsed = best["elapsed"]
    generated = best["generated"]

    # Approximate MFU: decode is ~2*params FLOPs/token; prefill adds
    # 2*params per prompt token (attention FLOPs are second-order at these
    # sequence lengths). Peak resolved per TPU generation.
    from benchmarks.perf import tpu_bf16_peak_flops

    peak = tpu_bf16_peak_flops()
    total_tokens = generated + num_requests * isl
    mfu = (
        (2.0 * n_active * total_tokens / elapsed) / peak
        if peak is not None
        else float("nan")
    )

    # vs_baseline compares like with like: each (platform, model, quantize)
    # config scores against ITS OWN published record — an 8B number divided
    # by the 1B target would read as a regression (round-3 verdict).
    published = {}
    try:
        with open(os.path.join(os.path.dirname(__file__), "BASELINE.json")) as f:
            published = json.load(f).get("published", {})
    except Exception:
        pass
    if platform != "tpu":
        baseline = float(published.get("cpu_output_tok_s", 0.0) or 0.0)
        baseline_workload = published.get("cpu_note", "cpu fallback")
    elif os.environ.get("BENCH_KV_QUANTIZE"):
        # kv-quant chip stages score against their own records, never the
        # fp-page ones (same like-with-like rule as the int8-weights 8B)
        key = f"{model.replace('-', '_').replace('.', '_')}_kv_" + (
            os.environ["BENCH_KV_QUANTIZE"]
        )
        rec = published.get(key, {})
        baseline = float(rec.get("output_tok_s_per_chip", 0.0) or 0.0)
        baseline_workload = rec.get(
            "workload", f"{model} kv {os.environ['BENCH_KV_QUANTIZE']}"
        )
    elif model == "llama3-8b" and os.environ.get("BENCH_QUANTIZE") == "int8":
        rec = published.get("llama3_8b_int8", {})
        baseline = float(rec.get("output_tok_s_per_chip", 0.0) or 0.0)
        baseline_workload = rec.get("workload", "llama3-8b int8")
    elif model == "llama3-1b" and not os.environ.get("BENCH_QUANTIZE"):
        baseline = float(published.get("output_tok_s_per_chip", 0.0) or 0.0)
        baseline_workload = published.get("workload", "llama3-1b")
    else:
        # no published record for this config yet: first measurement is
        # its own baseline
        baseline, baseline_workload = 0.0, f"none published for {model}"
    vs = tok_s / baseline if baseline > 0 else 1.0

    # A CPU fallback is a degraded measurement of a TPU framework: label
    # it in the metric name and carry the newest chip-measured artifact
    # (payload + age) so the round record holds a TPU number either way.
    metric = "output_tok_s_per_chip"
    tpu_latest = None
    kernel_check = None
    disagg_ab = None

    def _stamp(path: str) -> dict:
        mt = os.path.getmtime(path)
        return {
            "age_hours": round((time.time() - mt) / 3600.0, 1),
            "recorded_utc": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime(mt)
            ),
        }

    if platform != "tpu":
        metric = "output_tok_s_cpu_fallback"
        art_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "artifacts", "tpu"
        )
        try:
            candidates = [
                os.path.join(art_dir, f)
                for f in os.listdir(art_dir)
                if f.startswith("bench_") and f.endswith(".json")
            ]
            candidates = [p for p in candidates if os.path.getsize(p) > 0]
            # prefer the headline config's artifact; fall back to newest
            headline = os.path.join(art_dir, "bench_1b.json")
            newest = (
                headline
                if headline in candidates
                else max(candidates, key=os.path.getmtime)
            )
            with open(newest) as f:
                payload = json.load(f)
            tpu_latest = {
                "file": os.path.basename(newest),
                **_stamp(newest),
                "payload": payload,
            }
        except (OSError, ValueError):
            tpu_latest = None
        # also carry the freshest on-chip kernel numerics proof (its own
        # extras key — latest_tpu_artifact keeps its file/payload/age
        # shape) — it can be newer than any bench artifact
        try:
            kp = os.path.join(art_dir, "pallas_check.json")
            with open(kp) as f:
                kdoc = json.load(f)
            if kdoc.get("platform") == "tpu":
                kernel_check = {
                    "all_ok": kdoc.get("all_ok"),
                    **_stamp(kp),
                }
        except (OSError, ValueError):
            pass
        # the round's headline A/B (disagg vs agg on chip) rides along
        # too — it is the reference's own north-star comparison. Same
        # provenance rule as kernel_check: only chip-declared artifacts.
        try:
            ap = os.path.join(art_dir, "disagg_ab.json")
            with open(ap) as f:
                adoc = json.load(f)
            if (
                adoc.get("platform") == "tpu"
                and "disagg_throughput_ratio" in adoc
            ):
                disagg_ab = {
                    "disagg_throughput_ratio": adoc[
                        "disagg_throughput_ratio"
                    ],
                    "disagg_ttft_ratio": adoc.get("disagg_ttft_ratio"),
                    **_stamp(ap),
                }
        except (OSError, ValueError):
            pass

    emit(
        {
            "metric": metric,
            "value": round(tok_s, 2),
            "unit": "tok/s",
            "vs_baseline": round(vs, 3),
            "extras": {
                "platform": platform,
                "model": model,
                "params": n_params,
                "num_requests": num_requests,
                "isl": isl,
                "osl": osl,
                "p50_ttft_s": round(p50_ttft, 4),
                "p50_itl_s": round(p50_itl, 5) if p50_itl == p50_itl else None,
                "mfu": round(mfu, 4) if mfu == mfu else None,
                "elapsed_s": round(elapsed, 2),
                "generated_tokens": generated,
                # decode phase split of the headline run (docs/engine.md
                # "The decode loop"): sync ≈ 0 means the overlapped
                # pipeline has taken the host readback off the critical
                # path
                "decode_dispatch_ms": best["decode_phases"][
                    "time_decode_dispatch_ms"
                ],
                "decode_sync_ms": best["decode_phases"][
                    "time_decode_sync_ms"
                ],
                "decode_host_ms": best["decode_phases"][
                    "time_decode_host_ms"
                ],
                "overlap_hits": best["decode_phases"]["overlap_hits"],
                "overlap_rollbacks": best["decode_phases"][
                    "overlap_rollbacks"
                ],
                **({"overlap_ab": overlap_ab} if overlap_ab else {}),
                **({"mixed_ab": mixed_ab} if mixed_ab else {}),
                **({"spec_ab": spec_ab} if spec_ab else {}),
                **(
                    {"multihost_pipeline_ab": multihost_ab}
                    if multihost_ab
                    else {}
                ),
                **({"kvquant_ab": kvquant_ab} if kvquant_ab else {}),
                **({"ext_harness_ab": ext_ab} if ext_ab else {}),
                **({"trace_overhead": trace_ab} if trace_ab else {}),
                **({"slo_overhead": slo_ab} if slo_ab else {}),
                **({"flight_overhead": flight_ab} if flight_ab else {}),
                **({"handover_ab": handover_ab} if handover_ab else {}),
                **(
                    {"prefix_migration_ab": prefixmig_ab}
                    if prefixmig_ab
                    else {}
                ),
                **(
                    {"kv_index_overhead": kv_index_ab} if kv_index_ab else {}
                ),
                **(
                    {"trace_plane_overhead": trace_plane_ab}
                    if trace_plane_ab
                    else {}
                ),
                **(
                    {"failover_blackout": failover_ab}
                    if failover_ab
                    else {}
                ),
                **(
                    {"kv_quantize": os.environ["BENCH_KV_QUANTIZE"]}
                    if os.environ.get("BENCH_KV_QUANTIZE")
                    else {}
                ),
                "baseline_workload": baseline_workload,
                **({"latest_tpu_artifact": tpu_latest} if tpu_latest else {}),
                **({"kernel_check": kernel_check} if kernel_check else {}),
                **({"disagg_ab_chip": disagg_ab} if disagg_ab else {}),
                "attention_impl": best_impl,
                "attention_impls": {
                    k: {
                        "tok_s": round(v["tok_s"], 2),
                        "p50_ttft_s": round(v["p50_ttft"], 4),
                        "p50_itl_s": (
                            round(v["p50_itl"], 5)
                            if v["p50_itl"] == v["p50_itl"]
                            else None
                        ),
                    }
                    for k, v in per_impl.items()
                },
            },
        }
    )


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # last resort: structured artifact, not a traceback
        emit(
            {
                "metric": "output_tok_s_per_chip",
                "value": 0.0,
                "unit": "tok/s",
                "vs_baseline": 0.0,
                "error": f"{type(e).__name__}: {e}",
            }
        )
        sys.exit(1)
