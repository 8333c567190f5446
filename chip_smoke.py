#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dynamo-tpu still serves on the chip.

    python chip_smoke.py             # one TPU chip: serve + checks + references
    python chip_smoke.py --chips 4   # four chips: ONLY the tp=4 path and the
                                     # tp=1 engine it is compared with

Everything runs in this one process, because a chip belongs to one process
at a time. The default run

1. *device*   reports versions, devices, HBM limit, the compile cache in
              force, and builds+loads the native library (failure = error);
2. *serve*    builds the server the way `python -m dynamo_tpu.cli.run run
              in=http out=jax --model llama3-1b --dtype bfloat16` does
              (cli/run.py `_start_http`, the CLI's default flags, seeded
              random weights at the published Llama-3.2-1B widths) and
              sends chat and completion requests over HTTP: streamed and
              unary, greedy and seeded-sampled, two concurrent (a mixed
              step), one prompt longer than --prefill-chunk (a chunked
              prefill), one prompt repeated (prefix-cache hits);
3. *checks*   what makes a green run mean something: attention_impl
              resolved to pallas, Mosaic kernels inside the served decode
              and prefill programs, compile counts, zero compiles on the
              repeated request, overlap on and hit, mixed steps ran, the
              debug endpoints answer from the device's memory_stats;
4. *reference* the served greedy streams, teacher-forced through a second
              engine on the repo's plain XLA path (same seed, built after
              the first is stopped): argmax agreement and log-prob drift
              against the tolerance printed with them;
5. *kernels*  the four Pallas kernels (bf16 and int8 pages) against their
              XLA references at the model's shapes.

One JSON object per phase goes to stdout; the last line is exactly
`{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`.
Without a TPU (or with any failed phase or check) that line says
`"ok": false` and the exit code is 1. `--rehearse` walks the same phases
on whatever backend is there at the `tiny` preset, to debug control flow
without a chip; a rehearsal is never a pass, so it too ends `"ok": false`.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import sys
import time

#: argmax agreement and |Δ log-prob| budget of the teacher-forced
#: comparisons (served pallas path vs plain XLA; tp=4 vs tp=1): bf16
#: accumulation-order noise over 16 layers measures well inside it. The
#: log-prob budget also bounds how far below the reference's own best a
#: served token may sit, i.e. which argmax flips count as near-ties
MIN_ARGMAX_AGREEMENT = 0.90
MAX_LOGPROB_DRIFT = 0.25
#: |Δ| budget of a kernel's normalized output against its XLA reference
KERNEL_TOL = 0.05

#: the published Llama-3.2-1B widths the chip run must serve at
LLAMA_1B = dict(
    num_layers=16, hidden_size=2048, num_heads=32, num_kv_heads=8,
    head_dim=64, vocab_size=128256,
)

#: chip run: the CLI's defaults (512 pages x 64, max context 4096,
#: attention_impl auto, overlap and mixed steps on)
CHIP = dict(model="llama3-1b", dtype="bfloat16", flags=[], tp=4)
#: --rehearse: same phases, toy size; pallas is asked for by name because
#: `auto` resolves to xla off the chip (the kernels then run interpreted)
REHEARSAL = dict(
    model="tiny",
    dtype="float32",
    flags=[
        "--page-size", "4", "--num-pages", "256",
        "--max-context", "256", "--prefill-chunk", "16",
        "--attention-impl", "pallas",
    ],
    tp=2,
)

FAILED: list[str] = []


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(name: str, passed: bool, detail=None) -> bool:
    """Record one named check; a failed one fails the run."""
    if not passed:
        FAILED.append(name)
    # "passed", not "ok": that key belongs to the last line alone
    emit("check", name=name, passed=bool(passed), detail=detail)
    return bool(passed)


# -- 1. device ---------------------------------------------------------------


def phase_device(chips: int, rehearse: bool) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from dynamo_tpu import native
    from dynamo_tpu.platform import (
        device_hbm_bytes,
        enable_persistent_compile_cache,
    )

    cache_dir = enable_persistent_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(devices),
    }
    stats = d0.memory_stats() or {}
    fresh_build = not native.lib_path().exists()
    if native.ensure_built() is None:
        raise RuntimeError(
            "native library failed to build or load (see the log above); "
            "the smoke does not run on the Python fallbacks"
        )
    emit(
        "device",
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=metadata.version("libtpu"),
        devices=[str(d) for d in devices],
        **device,
        hbm_bytes_limit=stats.get("bytes_limit"),
        compile_cache_dir=cache_dir,
        native={"library": str(native.lib_path()), "built_now": fresh_build},
    )
    on_chip = device["platform"] == "tpu"
    if not rehearse:
        check("platform_is_tpu", on_chip, device["platform"])
        check("device_count", len(devices) == chips, len(devices))
    if on_chip:
        # raises for a device_kind the capacity table does not know
        emit("capacity", kind=device["kind"], hbm_bytes=device_hbm_bytes())
    return device


class CacheCounter:
    """Persistent-compile-cache traffic of this process, from jax's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def doc(self) -> dict:
        return {"persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}


# -- 2. serve ----------------------------------------------------------------


def cli_args(size: dict, extra: tuple = ()):
    """The argparse namespace `dynamo_tpu.cli.run main()` would hand
    `_run_http` for `run in=http out=jax --model <model> <flags>`."""
    from dynamo_tpu.cli.run import build_parser

    args = build_parser().parse_args([
        "run", "in=http", "out=jax", "--model", size["model"],
        "--dtype", size["dtype"], *size["flags"], *extra,
        "--port", "0",  # any free port
    ])
    args.out = "jax"  # main() splits the in=/out= tokens the same way
    return args


def make_prompts(args, vocab_size: int, seed: int) -> dict:
    """Token-id prompts sized from the server's own chunk and page."""
    import numpy as np

    rng = np.random.default_rng(seed)
    chunk, page = args.prefill_chunk, args.page_size
    hi = min(vocab_size, 32000)

    def ids(n):
        return [int(x) for x in rng.integers(1, hi, n)]

    return {
        "short": ids(max(10, chunk // 12)),
        # longer than --prefill-chunk: prefilled in two pieces
        "long": ids(chunk + chunk // 2 + 3),
        # two whole pages and a tail: the repeat hits two cached pages
        "repeat": ids(2 * page + page // 8 + 1),
    }


class Streams:
    """Tee on the seam between frontend and engine: per request id, the
    token ids in and out (so the reference can be teacher-forced on
    exactly what was served) and when the first token left the engine.
    Requests still travel HTTP -> preprocess -> engine -> postprocess ->
    HTTP. The first-token mark is taken here because the client cannot
    see it: these presets serve with the byte tokenizer, ids past its
    range render as no text, and the frontend sends no chunk for no text
    — at a 128,256-token vocabulary a whole stream arrives as one final
    chunk."""

    def __init__(self, pipeline):
        self.by_id: dict[str, dict] = {}
        #: one-shot: called at the first token of the next request to start
        self.on_next_first_token = None
        inner = pipeline.engine_fn

        async def tee(ctx, pre):
            rec = self.by_id[pre.request_id] = {
                "prompt": list(pre.token_ids), "out": [], "t_first": None,
            }
            hook, self.on_next_first_token = self.on_next_first_token, None
            async for item in inner(ctx, pre):
                ids = item.get("token_ids", ())
                if ids and rec["t_first"] is None:
                    rec["t_first"] = time.perf_counter()
                    if hook is not None:
                        hook()
                rec["out"].extend(ids)
                yield item

        pipeline.engine_fn = tee


async def send(session, base: str, streams: Streams, name: str, path: str,
               body: dict) -> dict:
    """POST one request and print its line: status, tokens out, time to
    first token (at the engine seam, see Streams), first chunk at the
    client (streams only), total time. Returns that plus the chosen-token
    log-probs."""
    t0 = time.perf_counter()
    first_chunk = None
    logprobs: list[float] = []
    usage: dict = {}
    rid = detail = None

    def take(choice):
        lp = choice.get("logprobs") or {}
        if "content" in lp:  # chat
            logprobs.extend(e["logprob"] for e in lp["content"] or ())
        else:  # legacy completions
            logprobs.extend(lp.get("token_logprobs") or ())

    async with session.post(base + path, json=body) as resp:
        status = resp.status
        if status != 200:
            detail = (await resp.text())[:500]
        elif body.get("stream"):
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                doc = json.loads(line[6:])
                rid = doc.get("id", rid)
                usage = doc.get("usage") or usage
                for choice in doc.get("choices", ()):
                    if first_chunk is None:
                        first_chunk = time.perf_counter() - t0
                    take(choice)
        else:
            doc = await resp.json()
            rid, usage = doc.get("id"), doc.get("usage") or {}
            for choice in doc.get("choices", ()):
                take(choice)
    total = time.perf_counter() - t0
    t_first = (streams.by_id.get(rid) or {}).get("t_first")
    out = {
        "request": name, "path": path, "status": status, "id": rid,
        "stream": bool(body.get("stream")),
        "tokens_out": usage.get("completion_tokens"),
        "prompt_tokens": usage.get("prompt_tokens"),
        "cached_tokens": (usage.get("prompt_tokens_details") or {}).get(
            "cached_tokens", 0
        ),
        "ttft_s": None if t_first is None else round(t_first - t0, 4),
        "first_chunk_s": None if first_chunk is None else round(first_chunk, 4),
        "total_s": round(total, 4),
        "logprobs": logprobs,
    }
    emit("request", **{k: v for k, v in out.items() if k != "logprobs"},
         detail=detail)
    check(f"status_200:{name}", status == 200, detail)
    check(f"first_token_seen:{name}",
          out["ttft_s"] is not None and 0 < out["ttft_s"] <= total,
          out["ttft_s"])
    return out


async def fetch(session, base: str, path: str):
    async with session.get(base + path) as resp:
        text = await resp.text()
        check(f"status_200:GET {path}", resp.status == 200, text[:200])
        return text


async def drive(base: str, model: str, prompts: dict, engine,
                streams: Streams, full: bool) -> dict:
    """The request mix. `full` = the one-chip run; the four-chip run
    sends only the greedy requests its comparison needs."""
    import aiohttp

    greedy = {"temperature": 0, "ext": {"ignore_eos": True}}
    done: dict[str, dict] = {}
    timeout = aiohttp.ClientTimeout(total=300)  # a cold first request: ~20 s
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async def completion(name, prompt, **kw):
            done[name] = await send(
                session, base, streams, name, "/v1/completions",
                {"model": model, "prompt": prompt, "logprobs": 0,
                 **greedy, **kw},
            )

        async def chat(name, text, **kw):
            done[name] = await send(
                session, base, streams, name, "/v1/chat/completions",
                {"model": model,
                 "messages": [{"role": "user", "content": text}], **kw},
            )

        async def pair(streamed: str, text: str, stream_kw: dict,
                       beside: str, beside_text: str) -> None:
            """Two at once: `beside` is sent when the first token of
            `streamed` leaves the engine, so its prefill rides a mixed
            step beside the other's decode."""
            first_token = asyncio.Event()

            async def second():
                await first_token.wait()
                await chat(beside, beside_text, max_tokens=24,
                           logprobs=True, **greedy)

            other = asyncio.create_task(second())
            streams.on_next_first_token = first_token.set
            try:
                await chat(
                    streamed, text, stream=True, max_tokens=192,
                    stream_options={"include_usage": True},
                    ext={"ignore_eos": True}, **stream_kw,
                )
            finally:
                first_token.set()  # a failed stream must not strand `other`
            await other

        await completion("greedy_unary", prompts["short"], max_tokens=64)
        await completion(
            "greedy_stream_chunked", prompts["long"], max_tokens=32,
            stream=True, stream_options={"include_usage": True},
        )
        if full:
            # beside a speculated-ahead decode (overlap on) the mixed step
            # is a prefill dispatch next to the in-flight decode ...
            await pair(
                "chat_sampled_stream", "Tell me about TPUs.",
                {"temperature": 0.8, "top_p": 0.9, "seed": 7},
                "chat_greedy_unary_concurrent", "Name four Pallas kernels.",
            )
            # ... and beside a penalized stream, whose history lives on
            # the host and cannot be speculated ahead, it is the FUSED
            # prefill+decode program
            await pair(
                "chat_penalized_stream", "Count the pages.",
                {"temperature": 0, "frequency_penalty": 0.5},
                "chat_greedy_unary_beside_penalized", "And the slots?",
            )
            # the same prompt three times: the 2nd hits the prefix cache
            # (and may compile the cache-hit prefill shape), the 3rd takes
            # the 2nd's path again and must compile nothing
            await completion("repeat_1", prompts["repeat"], max_tokens=16)
            await completion("repeat_2", prompts["repeat"], max_tokens=16)
            compiles_before = engine.metrics.compiles
            await completion("repeat_3", prompts["repeat"], max_tokens=16)
            done["compiles_on_repeat_3"] = (
                engine.metrics.compiles - compiles_before
            )
            done["endpoints"] = {
                path: await fetch(session, base, path)
                for path in ("/health", "/metrics", "/v1/debug/memory",
                             "/v1/debug/programs")
            }
    return done


def count_mosaic_calls(engine) -> dict:
    """Wrap this engine's `_cache_jit` so that each program's first call
    also counts the Mosaic kernels in its lowered text (`tpu_custom_call`;
    0 where the Pallas kernels run interpreted or the path is XLA's).
    The call after it reuses the lowering, so this costs the text only.
    Returns the counts, filled as programs load: str(cache_key) -> n."""
    counts: dict[str, int] = {}
    install = engine._cache_jit

    def cache_jit(kind, cache_key, jitted):
        first_call = install(kind, cache_key, jitted)

        def counted(*args, **kwargs):
            counts[str(cache_key)] = (
                jitted.lower(*args, **kwargs).as_text()
                .count("tpu_custom_call")
            )
            return first_call(*args, **kwargs)

        engine._jit_cache[cache_key] = counted
        return counted

    engine._cache_jit = cache_jit
    return counts


async def phase_serve(size: dict, seed: int, full: bool,
                      extra_flags: tuple = ()):
    """Start the server, drive it, collect what the checks need, stop it.
    Returns (facts, greedy streams for the reference)."""
    from dynamo_tpu.cli.run import _start_http, _stop_engine

    args = cli_args(size, extra_flags)
    t0 = time.perf_counter()
    svc, runner, _watcher = await _start_http(args)
    boot_s = time.perf_counter() - t0
    try:
        engine = runner.engine
        mosaic_calls = count_mosaic_calls(engine)
        cfg = getattr(engine.adapter.config, "base", engine.adapter.config)
        emit(
            "serve_up", model=args.model, boot_s=round(boot_s, 2),
            attention_impl=cfg.attention_impl, tp=args.tp,
            num_pages=args.num_pages, page_size=args.page_size,
            max_context=args.max_context, prefill_chunk=args.prefill_chunk,
            widths={k: getattr(cfg, k) for k in LLAMA_1B},
        )
        streams = Streams(svc.manager.get(args.model))
        prompts = make_prompts(args, cfg.vocab_size, seed)
        done = await drive(
            f"http://{args.host}:{svc.port}", args.model, prompts, engine,
            streams, full,
        )
        m = engine.metrics
        facts = {
            "args": args,
            "cfg": cfg,
            "done": done,
            "metrics": {
                k: getattr(m, k)
                for k in ("compiles", "compile_ms", "overlap_dispatches",
                          "overlap_hits", "overlap_rollbacks",
                          "prefill_dispatches", "decode_dispatches",
                          "mixed_dispatches", "generated_tokens")
            },
            "overlap_enabled": engine._overlap_enabled,
            "programs": [
                dict(p, mosaic_calls=mosaic_calls.get(p["key"]))
                for p in engine.programs_report()["programs"]
            ],
            "mesh": engine.mesh_report(),
            "memory": engine.memory_report(),
        }
        greedy = []
        for name, r in done.items():
            if not isinstance(r, dict) or "logprobs" not in r:
                continue
            rec = streams.by_id.get(r["id"])
            check(f"stream_captured:{name}", rec is not None, r["id"])
            if rec is None or not r["logprobs"]:
                continue  # sampled request: no log-probs asked for
            check(
                f"logprob_per_token:{name}",
                len(r["logprobs"]) == len(rec["out"]) == r["tokens_out"],
                [len(r["logprobs"]), len(rec["out"]), r["tokens_out"]],
            )
            greedy.append({"name": name, **rec, "logprobs": r["logprobs"]})
        return facts, greedy
    finally:
        await svc.stop()
        await _stop_engine(runner)


# -- 3. checks ---------------------------------------------------------------


def phase_checks(facts: dict, on_chip: bool, cache: CacheCounter) -> None:
    cfg, done, m = facts["cfg"], facts["done"], facts["metrics"]
    emit("engine_metrics", **m, **cache.doc(),
         overlap_enabled=facts["overlap_enabled"])
    check("attention_impl_is_pallas", cfg.attention_impl == "pallas",
          cfg.attention_impl)
    by_kind: dict[str, list] = {}
    for p in facts["programs"]:
        by_kind.setdefault(p["kind"], []).append(p["mosaic_calls"])
    emit("programs", mosaic_calls_by_kind=by_kind,
         compile_ms_by_kind={
             kind: round(sum(p["compile_ms"] for p in facts["programs"]
                             if p["kind"] == kind), 1)
             for kind in by_kind
         })
    for kind in ("prefill", "prefill_nosample", "mixed"):
        check(f"dispatched:{kind}", kind in by_kind, sorted(by_kind))
    check("dispatched:decode", any(k.startswith("decode") for k in by_kind),
          sorted(by_kind))
    if on_chip:
        # every served step program carries the attention kernel and the
        # DMA writer (interpreted kernels leave no custom call behind);
        # `feed` picks the token ids of a dispatch launched ahead: no step
        check(
            "mosaic_kernels_in_every_step_program",
            all(n is not None and n >= 2
                for kind, calls in by_kind.items() if kind != "feed"
                for n in calls),
            by_kind,
        )
    check("compiled_something", m["compiles"] > 0 and m["compile_ms"] > 0,
          [m["compiles"], m["compile_ms"]])
    check("repeat_compiles_nothing", done["compiles_on_repeat_3"] == 0,
          done["compiles_on_repeat_3"])
    check("prefix_cache_hit", done["repeat_2"]["cached_tokens"] > 0
          and done["repeat_3"]["cached_tokens"] > 0,
          [done[k]["cached_tokens"] for k in ("repeat_2", "repeat_3")])
    check("chunked_prefill_happened",
          done["greedy_stream_chunked"]["prompt_tokens"]
          > facts["args"].prefill_chunk,
          done["greedy_stream_chunked"]["prompt_tokens"])
    check("overlap_still_enabled", facts["overlap_enabled"])
    check("overlap_hit", m["overlap_hits"] > 0, m["overlap_hits"])
    check("mixed_step_ran", m["mixed_dispatches"] > 0, m["mixed_dispatches"])

    health = json.loads(done["endpoints"]["/health"])
    check("health_ok", health.get("status") == "ok", health)
    check("metrics_exposition",
          "dynamo_tpu_" in done["endpoints"]["/metrics"])
    programs = json.loads(done["endpoints"]["/v1/debug/programs"])
    check("debug_programs_lists_engine", bool(programs.get("engines")))
    memory = next(iter(
        json.loads(done["endpoints"]["/v1/debug/memory"])["engines"].values()
    ))
    totals = memory["totals"]
    emit("memory", source=memory["source"], **totals)
    if on_chip:
        check("memory_from_memory_stats",
              memory["source"] == "memory_stats", memory["source"])
        # Llama-3.2-1B in bf16: 1.24 G parameters, 2.47 GB
        check("weights_about_2_5_gb",
              2.3e9 < totals["weights_bytes"] < 2.7e9,
              totals["weights_bytes"])
        check("peak_hbm_within_chip",
              totals["weights_bytes"] + totals["kv_pool_bytes"]
              <= totals["peak_bytes"] < 16e9, totals["peak_bytes"])
    check("kv_pool_accounted", totals["kv_pool_bytes"] > 0,
          totals["kv_pool_bytes"])


# -- 4. teacher-forced reference ----------------------------------------------


def teacher_forced(engine, greedy: list[dict]) -> dict:
    """Run `engine`'s model over each served stream (prompt + the tokens
    the served path chose) in one pass, and compare, token by token, its
    argmax and its log-prob of the served token with what was served."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    adapter, page = engine.adapter, engine.config.page_size
    longest = max(len(g["prompt"]) + len(g["out"]) for g in greedy)
    t = -(-longest // 128) * 128  # one padded length: one compile
    n_pages = t // page
    assert n_pages < engine.config.num_pages, "reference needs more pages"
    pt = np.zeros((1, engine.config.max_pages_per_seq), np.int32)
    pt[0, :n_pages] = np.arange(1, n_pages + 1)  # page 0 is the null page
    rows = max(len(g["out"]) for g in greedy)

    @jax.jit
    def run(params, tokens, valid, kv, pt, at):
        positions = jnp.arange(t, dtype=jnp.int32)[None]
        hidden, kv = adapter.forward_hidden(
            params, tokens, positions, valid, kv, pt
        )
        logits = adapter.compute_logits(params, hidden[0, at])
        return jax.nn.log_softmax(logits.astype(jnp.float32)), kv

    agree = total = 0
    drift = top_gap = 0.0
    per_request = []
    for g in greedy:
        seq = g["prompt"] + g["out"]
        tokens = np.zeros((1, t), np.int32)
        tokens[0, : len(seq)] = seq
        valid = np.arange(t)[None] < len(seq)
        # served token i was sampled from the logits at position p+i-1
        at = np.zeros(rows, np.int32)
        n = len(g["out"])
        at[:n] = len(g["prompt"]) - 1 + np.arange(n)
        logp, _kv = run(
            engine.params, jnp.asarray(tokens), jnp.asarray(valid),
            engine.kv, jnp.asarray(pt), jnp.asarray(at),
        )
        logp = np.asarray(logp)[:n]
        served = np.asarray(g["out"])
        of_served = logp[np.arange(n), served]
        same = int((logp.argmax(-1) == served).sum())
        d = float(np.abs(of_served - np.asarray(g["logprobs"])).max())
        # how far below the reference's own best the served token sits:
        # ~0 where an argmax flip is a near-tie (seeded random weights
        # have many), large where the served path chose wrongly
        gap = float((logp.max(-1) - of_served).max())
        agree, total = agree + same, total + n
        drift, top_gap = max(drift, d), max(top_gap, gap)
        per_request.append({"request": g["name"], "tokens": n,
                            "argmax_agree": same,
                            "max_logprob_drift": round(d, 4),
                            "max_gap_to_reference_best": round(gap, 4)})
    return {
        "tokens": total,
        "argmax_agreement": round(agree / total, 4),
        "max_logprob_drift": round(drift, 4),
        "max_gap_to_reference_best": round(top_gap, 4),
        "tolerance": {"min_argmax_agreement": MIN_ARGMAX_AGREEMENT,
                      "max_logprob_drift": MAX_LOGPROB_DRIFT,
                      "max_gap_to_reference_best": MAX_LOGPROB_DRIFT},
        "per_request": per_request,
    }


def phase_reference(label: str, facts: dict, greedy: list[dict],
                    **overrides) -> None:
    """A second engine from the served one's own EngineConfig (same seed:
    JaxEngine seeds its random weights with key 0), with `overrides`."""
    import dataclasses

    from dynamo_tpu.cli.run import _engine_config
    from dynamo_tpu.engine.engine import JaxEngine

    config = dataclasses.replace(_engine_config(facts["args"]), **overrides)
    engine = JaxEngine(config)
    result = teacher_forced(engine, greedy)
    emit("reference", against=label, **result)
    check(f"argmax_agreement_vs_{label}",
          result["argmax_agreement"] >= MIN_ARGMAX_AGREEMENT,
          result["argmax_agreement"])
    check(f"logprob_drift_vs_{label}",
          result["max_logprob_drift"] < MAX_LOGPROB_DRIFT,
          result["max_logprob_drift"])
    check(f"flips_are_near_ties_vs_{label}",
          result["max_gap_to_reference_best"] < MAX_LOGPROB_DRIFT,
          result["max_gap_to_reference_best"])


# -- 5. kernels --------------------------------------------------------------


def phase_kernels(size: dict, seed: int) -> None:
    """The four Pallas kernels against XLA at the model's head shapes,
    through the model's own cache layout (`init_kv`: head-dim padding,
    slot-minor scale planes), with bf16 and with int8 pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import paged_gather_kv
    from dynamo_tpu.models.registry import get_model
    from dynamo_tpu.ops.flash_prefill import (
        flash_prefill_attention,
        paged_prefill_attention,
    )
    from dynamo_tpu.ops.kv_update import paged_write
    from dynamo_tpu.ops.paged_attention import paged_decode_attention

    adapter = get_model(
        size["model"], dtype=size["dtype"], attention_impl="pallas"
    )
    cfg = adapter.config
    hq, hkv, d, dp = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                      cfg.kv_head_dim)
    g = hq // hkv
    page, pages, mp, layer = 64, 48, 8, 1
    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def rand(*shape):
        """Random rows in the real lanes, zeros in the pad lanes."""
        x = jax.random.normal(next(keys), (*shape, d), cfg.dtype)
        return jnp.pad(x, [(0, 0)] * len(shape) + [(0, dp - d)])

    def attend(q, k, v, mask):
        """Dense f32 reference: q [T,Hq,D], k/v [K,Hkv,D], mask [T,K]."""
        s = jnp.einsum(
            "thd,khd->htk", q.astype(jnp.float32) / math.sqrt(d),
            jnp.repeat(k.astype(jnp.float32), g, axis=1),
        )
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum(
            "htk,khd->thd", p, jnp.repeat(v.astype(jnp.float32), g, axis=1)
        )

    def report(name, err, tol=KERNEL_TOL):
        err = float(err)
        emit("kernel", kernel=name, max_abs_err=err, tolerance=tol)
        check(f"kernel:{name}", err <= tol, err)

    # flash prefill (no pages): two rows, one with a ragged tail
    b, t = 2, 384
    q, k, v = rand(b, t, hq), rand(b, t, hkv), rand(b, t, hkv)
    lens = jnp.asarray([t, 200], jnp.int32)
    out = flash_prefill_attention(q, k, v, lens, scale_dim=d)
    pos = jnp.arange(t)
    err = 0.0
    for i in range(b):
        n = int(lens[i])
        ref = attend(q[i], k[i], v[i], pos[:, None] >= pos[None, :])
        err = max(err, float(jnp.max(jnp.abs(
            out[i, :n].astype(jnp.float32) - ref[:n]
        ))))
    report("flash_prefill", err)

    for kvq in (None, "int8"):
        tag = kvq or "bf16"
        # fill a pool through the XLA scatter: 6 pages of history a row
        hist_t = 6 * page
        ks, vs = (rand(cfg.num_layers, b, hist_t, hkv) for _ in range(2))
        pt = jnp.asarray(
            np.arange(1, 1 + b * mp, dtype=np.int32).reshape(b, mp)
        )
        hpos = jnp.tile(jnp.arange(hist_t, dtype=jnp.int32)[None], (b, 1))
        hvalid = jnp.ones((b, hist_t), bool)

        def write(use_kernel):
            kv = adapter.init_kv(pages, page, kv_quantize=kvq)
            out = paged_write(
                kv.k, kv.v, ks, vs, pt, hpos, hvalid, use_kernel=use_kernel,
                k_scale=kv.k_scale, v_scale=kv.v_scale,
            )
            return type(kv)(*out)

        kv, kv_kernel = write(False), write(True)
        report(f"paged_write_{tag}", max(
            float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b_.astype(jnp.float32)
            )))
            for a, b_ in zip(kv, kv_kernel) if a is not None
        ), tol=0.0)
        k_hist, v_hist = paged_gather_kv(kv, jnp.int32(layer), pt,
                                         jnp.float32)  # [B, MP*S, Hkv, D]
        kpos = jnp.arange(mp * page)

        # decode: one query a row over ragged histories
        hist = jnp.asarray([hist_t, 130], jnp.int32)
        qd = rand(b, hq)
        acc, _m, l = paged_decode_attention(
            qd, kv.k, kv.v, jnp.int32(layer), pt, hist, scale_dim=d,
            k_scale=kv.k_scale, v_scale=kv.v_scale,
        )
        got = acc / jnp.maximum(l, 1e-30)[..., None]
        err = 0.0
        for i in range(b):
            ref = attend(qd[i][None], k_hist[i], v_hist[i],
                         (kpos < hist[i])[None])
            err = max(err, float(jnp.max(jnp.abs(got[i] - ref[0]))))
        report(f"paged_decode_{tag}", err)

        # prefill chunk over paged history, causal inside the chunk
        tc = 256
        qc, kc, vc = rand(b, tc, hq), rand(b, tc, hkv), rand(b, tc, hkv)
        hist = jnp.asarray([2 * page, page], jnp.int32)
        cur = jnp.asarray([tc, 130], jnp.int32)
        out = paged_prefill_attention(
            qc, kc, vc, kv.k, kv.v, jnp.int32(layer), pt, hist, cur,
            scale_dim=d, k_scale=kv.k_scale, v_scale=kv.v_scale,
        )
        cpos = jnp.arange(tc)
        err = 0.0
        for i in range(b):
            h, c = int(hist[i]), int(cur[i])
            kk = jnp.concatenate(
                [k_hist[i, :h], kc[i].astype(jnp.float32)], axis=0
            )
            vv = jnp.concatenate(
                [v_hist[i, :h], vc[i].astype(jnp.float32)], axis=0
            )
            mask = jnp.concatenate(
                [jnp.ones((tc, h), bool), cpos[:, None] >= cpos[None, :]],
                axis=1,
            )
            ref = attend(qc[i], kk, vv, mask)
            err = max(err, float(jnp.max(jnp.abs(
                out[i, :c].astype(jnp.float32) - ref[:c]
            ))))
        report(f"paged_prefill_{tag}", err)


# -- the four-chip path ------------------------------------------------------


def check_sharding(facts: dict, tp: int) -> None:
    mesh, memory = facts["mesh"], facts["memory"]
    emit("mesh", mesh=mesh["mesh"], kv_sharding=mesh["kv_sharding"],
         param_groups={k: {"params": v["params"], "bytes": v["bytes"]}
                       for k, v in mesh["param_groups"].items()})
    emit("memory_per_device", source=memory["source"],
         devices=memory["devices"])
    check("mesh_has_tp_devices",
          mesh["mesh"] is not None and mesh["mesh"]["shape"]["tp"] == tp
          and len(memory["devices"]) >= tp, mesh["mesh"])
    # a group that came out replicated although the rule table shards one
    # of its logical axes over tp would be the silent failure of PR 20
    tp_axes = {r[0] for r in mesh["logical_axis_rules"] if "tp" in str(r[1:])}
    misplaced = [
        spec for spec, grp in mesh["param_groups"].items()
        if "tp" not in spec
        and any(ax in str(grp["logical"]) for ax in tp_axes)
    ]
    check("no_sharded_group_replicated", not misplaced, misplaced)
    check("kv_pool_sharded_over_tp", "tp" in mesh["kv_sharding"],
          mesh["kv_sharding"])
    totals = memory["totals"]
    for field in ("weights_bytes", "kv_pool_bytes"):
        shares = [d[field] / totals[field] for d in memory["devices"].values()
                  if d[field]]
        check(f"quarter_each:{field}",
              len(shares) == tp
              and all(abs(s - 1 / tp) < 0.1 / tp for s in shares),
              [round(s, 4) for s in shares])


# -- main --------------------------------------------------------------------


async def run(chips: int, rehearse: bool, seed: int, device: dict) -> None:
    size = REHEARSAL if rehearse else CHIP
    on_chip = device["platform"] == "tpu"
    cache = CacheCounter()
    if chips == 4:
        tp = size["tp"]
        facts, greedy = await phase_serve(
            size, seed, full=False, extra_flags=("--tp", str(tp))
        )
        emit("engine_metrics", **facts["metrics"], **cache.doc())
        check("attention_impl_is_pallas",
              facts["cfg"].attention_impl == "pallas")
        check_sharding(facts, tp)
        gc.collect()
        phase_reference("tp1", facts, greedy, tp=1)
        return
    facts, greedy = await phase_serve(size, seed, full=True)
    if not rehearse:
        widths = {k: getattr(facts["cfg"], k) for k in LLAMA_1B}
        check("published_llama_3_2_1b_widths", widths == LLAMA_1B, widths)
    phase_checks(facts, on_chip, cache)
    gc.collect()  # the served engine's HBM goes before the next is built
    phase_reference("xla", facts, greedy, attention_impl="xla")
    gc.collect()
    phase_kernels(size, seed)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the tp=4 path and its tp=1 comparison")
    ap.add_argument("--seed", type=int, default=0, help="prompt seed")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on any backend; never a pass")
    ns = ap.parse_args()
    device = {"platform": None, "kind": None, "count": 0}
    t0 = time.perf_counter()
    try:
        device = phase_device(ns.chips, ns.rehearse)
        if not FAILED:
            asyncio.run(run(ns.chips, ns.rehearse, ns.seed, device))
    except Exception as e:  # noqa: BLE001 — any phase that raises fails the run
        import traceback

        traceback.print_exc()
        FAILED.append(f"{type(e).__name__}: {e}"[:500])
    emit("summary", failed=FAILED, seconds=round(time.perf_counter() - t0, 1),
         rehearsal_passed=(not FAILED) if ns.rehearse else None)
    ok = not FAILED and not ns.rehearse and device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
