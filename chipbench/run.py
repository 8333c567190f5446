"""python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, because a chip belongs to one process: build the server the
way `python -m dynamo_tpu.cli.run run in=http out=jax --model <preset>
<serve flags of the configuration file>` does, drive it over HTTP from
an asyncio client in this process, stop it, check the outputs, print the
contract's one JSON line last. Earlier lines are free-form JSON notes.
What belongs to one configuration arrives as files its own file names:
its reference and served widths (`reference_module`), its byte counts
(`costs_module`); see `manifest.module_of`. `--manifest` reads another
BENCHMARK.json (tests).

Without a TPU the run fails and prints no result. `JAX_PLATFORMS=cpu`
asks for a rehearsal: the same control flow at the configuration's
`rehearsal` preset, whose last line says `platform: cpu` and
`correct: false` — a rehearsal is never a result.

Set-up ends where the window opens: imports, weights, tokenizer, server,
and the closed loop's ramp, which loads every step program the window
uses and runs on into the window without a break (chipbench/client.py).
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench import (client, costs, manifest, reference, stats,  # noqa: E402
                       tokenizer, trace, traffic)

#: the traced slice of the window: long enough for hundreds of decode
#: steps, short enough that the trace stays some tens of MB
TRACE_SLICE_S = 4.0


def note(note_: str, **fields) -> None:
    print(json.dumps({"note": note_, **fields}, default=str), flush=True)


def peaks_for(kind: str) -> dict:
    with open(manifest.HERE / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(
            f"device_kind {kind!r} is not in chipbench/peaks.json: add its "
            "published peaks with their source"
        )
    return table[kind]


def device_or_exit(chips: int) -> dict:
    """The device as jax reports it. No accelerator, or fewer chips than
    the cell asks for, ends the run with no result — unless the operator
    said JAX_PLATFORMS=cpu, which asks for a rehearsal."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform == "cpu" and os.environ.get("JAX_PLATFORMS") == "cpu":
        return device
    if d0.platform != "tpu" or len(devices) < chips:
        print(f"chipbench: need {chips} TPU chip(s), jax found {device}",
              file=sys.stderr)
        raise SystemExit(3)
    return device


def memory_peak_bytes() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices()
    )


class EngineFailures(logging.Handler):
    """The engine's runner logs a raising `step()` and tries again, so a
    program the compiler refuses shows as streams that never finish.
    The benchmark fails loudly instead."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.first: str | None = None

    def emit(self, record):
        if self.first is None and "engine step failed" in record.getMessage():
            self.first = self.format(record)[-3000:]

    def check(self) -> None:
        if self.first is not None:
            raise SystemExit(f"chipbench: the engine failed a step:\n"
                             f"{self.first}")

    async def watch(self) -> None:
        """Streams of a failed engine never end, so neither would the
        client: leave at once, with the error and no result."""
        while self.first is None:
            await asyncio.sleep(1.0)
        print(f"chipbench: the engine failed a step:\n{self.first}",
              file=sys.stderr, flush=True)
        sys.stdout.flush()
        os._exit(1)


class Seam:
    """Tee on the seam between frontend and engine (traced runs only),
    as chip_smoke.Streams: per request id, when the first token left the
    engine."""

    def __init__(self, pipeline):
        self.by_id: dict[str, dict] = {}
        inner = pipeline.engine_fn

        async def tee(ctx, pre):
            rec = self.by_id[pre.request_id] = {"t_first": None}
            async for item in inner(ctx, pre):
                if rec["t_first"] is None and item.get("token_ids"):
                    rec["t_first"] = time.perf_counter()
                yield item

        pipeline.engine_fn = tee


class FlightDrain:
    """The flight recorder's ring holds 512 steps, fewer than a window
    dispatches: read it out twice a second and keep every record once."""

    def __init__(self, engine):
        self.flight = engine.flight
        self.records: list[dict] = []
        self.lost = 0
        self._next = None
        self._task = None

    def poll(self) -> None:
        if self.flight is None:
            return
        for rec in self.flight.snapshot():
            if self._next is None or rec["seq"] >= self._next:
                if self._next is not None and rec["seq"] > self._next:
                    self.lost += rec["seq"] - self._next
                self.records.append(rec)
                self._next = rec["seq"] + 1

    async def _loop(self):
        while True:
            self.poll()
            await asyncio.sleep(0.5)

    def start(self) -> None:
        if self.flight is not None:
            self._next = (self.flight.snapshot(1) or [{"seq": -1}])[-1]["seq"] + 1
            self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self.poll()


async def traced_slice(out_dir: str, start_at: float, info: dict) -> None:
    """Trace TRACE_SLICE_S of the window from `start_at` (perf_counter).
    start/stop run in a thread so the load generator keeps its clock."""
    import jax

    loop = asyncio.get_running_loop()
    await asyncio.sleep(max(0.0, start_at - time.perf_counter()))
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    await loop.run_in_executor(None, jax.profiler.start_trace, out_dir)
    info["wall_start"] = time.time()
    try:
        await asyncio.sleep(TRACE_SLICE_S)
    finally:
        info["wall_stop"] = time.time()
        await loop.run_in_executor(None, jax.profiler.stop_trace)


FAILURES = EngineFailures()


async def idle(engine, timeout: float = 60.0) -> None:
    """Wait until the engine holds no request (cut streams are aborted
    asynchronously); fail if it failed a step meanwhile."""
    t = time.perf_counter()
    while engine.scheduler.has_work:
        if time.perf_counter() - t > timeout:
            raise SystemExit("chipbench: the engine did not go idle")
        await asyncio.sleep(0.05)
    FAILURES.check()


class Programs:
    """The step programs the engine builds, as they appear: the engine
    cannot enumerate its family — (kind, rows, T or fused steps, greedy,
    mm, first_chunk, logprobs, penalties, bias, prefill rows, prefill
    samples) — so the ramp's traffic has to touch it, and this says what
    it touched, when, and what each first call cost."""

    def __init__(self, engine):
        self.engine = engine
        self.t = time.perf_counter()
        self.seen: list[dict] = []
        self._known: set = set()
        self._task = None

    def poll(self) -> None:
        for key, p in list(self.engine.programs.items()):
            if key not in self._known:
                self._known.add(key)
                self.seen.append({
                    "at_s": round(time.perf_counter() - self.t, 2),
                    "key": str(key), "first_call_ms": p["compile_ms"]})

    async def _loop(self):
        while True:
            self.poll()
            await asyncio.sleep(0.25)

    def start(self) -> None:
        self.t = time.perf_counter()
        self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)
        self.poll()


async def greedy_streams(base, model, vocab, n=2, prompt_len=48, out_len=64):
    """After the window, outside every timing: greedy streams with the
    chosen token's log-prob, for the teacher-forced reference."""
    import aiohttp
    import numpy as np

    rng = np.random.default_rng(1234)
    streams = []
    async with aiohttp.ClientSession() as session:
        for _ in range(n):
            prompt = [int(x) for x in
                      rng.integers(traffic.FIRST_ID, vocab, prompt_len)]
            body = {"model": model, "prompt": prompt, "max_tokens": out_len,
                    "temperature": 0, "logprobs": 0,
                    "ext": {"ignore_eos": True}}
            async with session.post(base + "/v1/completions",
                                    json=body) as resp:
                doc = await resp.json()
                if resp.status != 200:
                    raise RuntimeError(f"greedy stream refused: {doc}")
            choice = doc["choices"][0]
            streams.append({
                "prompt": prompt,
                "out": tokenizer.ids_of(choice["text"]),
                "logprobs": choice["logprobs"]["token_logprobs"],
            })
    return streams


def check_reference(params, hf: dict, streams, tol: dict,
                    ref=reference) -> dict:
    """`ref` is the configuration's reference module
    (`manifest.module_of(conf, "reference_module", ...)`)."""
    res = ref.compare(params, hf, streams)
    res["tolerance"] = tol
    res["passed"] = bool(
        all(len(s["out"]) == len(s["logprobs"]) == 64 for s in streams)
        and res["argmax_agreement"] >= tol["min_argmax_agreement"]
        and res["max_logprob_drift"] <= tol["max_logprob_drift"]
        and res["max_gap_to_reference_best"] <= tol["max_logprob_drift"]
        # a configuration may also state a limit on the mean over tokens
        and res["mean_logprob_drift"] <= tol.get("max_mean_logprob_drift",
                                                 float("inf"))
    )
    return res


def served_widths(cfg, ref=reference) -> dict:
    """The served model's sizes under the configuration file's keys:
    the reference module's own `served_widths(cfg)` where it has one
    (another architecture has other widths), else these seven."""
    if hasattr(ref, "served_widths"):
        return ref.served_widths(cfg)
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "vocab_size": cfg.vocab_size,
        "head_dim": cfg.head_dim,
    }


def end_to_end(red: dict, setup_s: float, strict: bool) -> dict:
    """Every end-to-end metric, by name, as a function to call: a cell
    computes only those the manifest lists for it, so a tail that its
    sample cannot carry fails only where it is asked for."""
    def p95(values):
        if strict:
            return stats.tail(values, 95)
        return stats.percentile(values, 95) if values else None

    return {
        "itl_p95_ms": lambda: p95(red["gaps_ms"]),
        "output_tok_s": lambda: red["output_tok_s"],
        "setup_s": lambda: setup_s,
    }


async def run_cell(ns, man: dict, cell: dict, device: dict) -> dict:
    from dynamo_tpu.cli.run import _start_http, _stop_engine, build_parser

    on_chip = device["platform"] == "tpu"
    conf = manifest.config_of(man, cell)
    mix = manifest.traffic_of(cell)
    serve = conf if on_chip else conf["rehearsal"]
    ref_mod = manifest.module_of(conf, "reference_module", reference)
    if not on_chip:
        mix = {**mix, **mix.get("rehearsal", {})}
    hf = conf if on_chip else serve["hf"]
    preset, flags = serve["preset"], list(serve["serve_flags"])
    vocab = hf["vocab_size"]
    tok_dir = tokenizer.ensure(vocab, manifest.RUN_DIR / "tokenizers")
    args = build_parser().parse_args([
        "run", "in=http", "out=jax", "--model", preset, *flags,
        "--tokenizer", str(tok_dir), "--port", "0",
    ])
    args.out = "jax"  # main() splits the in=/out= tokens the same way
    logging.getLogger("dynamo_tpu").addHandler(FAILURES)
    t = time.perf_counter()
    svc, runner, _watcher = await _start_http(args)
    engine = runner.engine
    watch = asyncio.create_task(FAILURES.watch())
    try:
        cfg = getattr(engine.adapter.config, "base", engine.adapter.config)
        widths = served_widths(cfg, ref_mod)
        memory = engine.memory_report()["totals"]
        note("serve_up", model=preset, flags=flags,
             boot_s=round(time.perf_counter() - t, 2),
             attention_impl=cfg.attention_impl, widths=widths,
             num_pages=args.num_pages, max_seqs=args.max_seqs,
             memory=memory)
        structural = {
            # every key the module returns, against the file's own
            "widths_as_published": all(
                k in hf and widths[k] == hf[k] for k in widths),
            "attention_impl_pallas": (cfg.attention_impl == "pallas"
                                      or not on_chip),
        }
        base = f"http://{args.host}:{svc.port}"
        if ns.trace:
            seam = Seam(svc.manager.get(args.model))
        gc.collect()

        # -- ramp and measured window, one closed loop ----------------------
        programs = Programs(engine)
        drain = FlightDrain(engine)
        tracing = None
        trace_info: dict = {}
        trace_dir = str(manifest.RUN_DIR / "trace" / cell["name"])
        at_start: dict = {}
        at_end: dict = {}

        def window_opens(t0: float) -> None:
            nonlocal tracing
            at_start["setup_s"] = t0 - _T_PROCESS
            at_start["m0"] = engine.metrics.to_dict()
            at_start["programs"] = len(engine.programs)
            if ns.trace:
                drain.start()
                tracing = asyncio.create_task(traced_slice(
                    trace_dir, t0 + max(0.0, (ns.seconds - TRACE_SLICE_S) / 2),
                    trace_info))

        def window_closes() -> None:
            # counters are read here: the streams are ended next and
            # drain through small batches that belong to no window
            at_end["m1"] = engine.metrics.to_dict()

        drv = client.Driver(base, preset, mix, ns.seed,
                            at_window_start=window_opens,
                            at_window_end=window_closes)
        plan = traffic.plan(mix, ns.seed, vocab)
        programs.start()
        t0 = await drv.run(plan, ns.seconds)
        await programs.stop()
        wall0 = time.time() - (time.perf_counter() - t0)
        setup_s, m0, m1 = at_start["setup_s"], at_start["m0"], at_end["m1"]
        if tracing is not None:
            await tracing
        t = time.perf_counter()
        await idle(engine)
        idle_s = time.perf_counter() - t
        await drain.stop()
        red = client.reduce(drv.results, t0, ns.seconds)
        ramp_s = t0 - programs.t
        note("ramp", seconds=round(ramp_s, 2), tokens=plan.ramp_tokens,
             programs=at_start["programs"],
             first_calls_s=round(sum(
                 p["first_call_ms"] for p in programs.seen
                 if p["at_s"] <= ramp_s) / 1000.0, 2))
        note("programs", seen=programs.seen)
        note("window", attempted=red["attempted"], failed=red["failed"],
             failures=red["failures"], cut=red["cut"],
             samples={"ttft": len(red["ttft_ms"]), "gaps": len(red["gaps_ms"])},
             output_tok_s=red["output_tok_s"],
             itl_p95_ms=stats.percentile(red["gaps_ms"], 95)
             if red["gaps_ms"] else None,
             late_p95_ms=stats.percentile(red["late_ms"], 95)
             if red["late_ms"] else None,
             compiles_in_window=m1["compiles"] - m0["compiles"],
             compiled=[p for p in programs.seen if p["at_s"] > ramp_s],
             preemptions=m1["preemptions"] - m0["preemptions"],
             idle_after_s=round(idle_s, 2))
        if ns.trace:
            note("flight", steps_read=len(drain.records), lost=drain.lost)

        streams = await greedy_streams(base, preset, vocab)
        peak = memory_peak_bytes()
    finally:
        watch.cancel()
        await svc.stop()
        await _stop_engine(runner)

    # -- correctness, outside every timing: the served engine's own
    # parameter tree through the plain reference, cache freed first -----
    params = engine.params
    engine.kv = None
    gc.collect()
    ref = check_reference(params, hf, streams, conf["reference_tolerance"],
                          ref_mod)
    note("reference", **ref)
    correct = bool(
        on_chip and red["attempted"] > 0 and red["failed"] == 0
        and ref["passed"] and all(structural.values())
    )
    note("correct", on_chip=on_chip, requests_ok=red["failed"] == 0,
         reference=ref["passed"], **structural)

    wanted = manifest.metrics_of(
        man, "per_layer" if ns.trace else "end_to_end", cell["name"])
    metrics: dict = {}
    result = {"correct": correct, "attempted": red["attempted"],
              "failed": red["failed"], "metrics": metrics,
              "device": {**device, "memory_peak_bytes": peak}}
    if not ns.trace:
        values = end_to_end(red, setup_s, strict=on_chip)
        for m in wanted:
            value = values[m["name"]]()
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return result

    xplane = trace.find_xplane(trace_dir)
    planes = trace.load(xplane) if xplane else {}
    note("trace_file", path=xplane, lines=trace.describe(planes),
         bytes=os.path.getsize(xplane) if xplane else 0)
    reduced = trace.reduce(planes)
    ctx = {
        "client": red, "results": drv.results, "t0": t0,
        "seconds": ns.seconds, "seam": seam.by_id,
        # steps of the window alone (the recorder stamps wall-clock time)
        "flight": [r for r in drain.records
                   if wall0 <= r["ts"] <= wall0 + ns.seconds],
        "flight_lost": drain.lost,
        "engine": {k: m1[k] - m0[k] for k in m1
                   if isinstance(m1[k], (int, float))},
        "engine_now": m1, "memory": memory,
        "trace": reduced, "trace_info": trace_info,
        "hf": hf, "weights": serve.get("weights", {}),
        "costs": manifest.module_of(conf, "costs_module", costs),
        "page_size": args.page_size,
        "kernels": cfg.attention_impl in ("pallas", "hybrid"),
        "peaks": peaks_for(device["kind"]) if on_chip else None,
    }
    for m in wanted:
        value = manifest.layer_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reduced:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        note("trace", modules=reduced["modules"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (tests; the default is "
                         "the checkout's)")
    ns = ap.parse_args(argv)
    man = manifest.load(ns.manifest)
    cell = manifest.cell(man, ns.workload)
    if ns.seconds is None:
        ns.seconds = float(man["run_seconds"])
    device = device_or_exit(cell["chips"])
    note("device", **device, run_dir=str(manifest.RUN_DIR),
         compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
         or str(manifest.ROOT / ".jax_cache"))
    print(json.dumps(asyncio.run(run_cell(ns, man, cell, device))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
