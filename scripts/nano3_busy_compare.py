"""python scripts/nano3_busy_compare.py [--cell NAME] [--rehearse] [--seed N]

The comparison a state model's cell's own `correct` cannot make (its two
greedy streams of 48 + 64 tokens cross no chunk boundary and run beside
no other row): ONE request under the cell's own shapes, outside every
timing, teacher-forced against the configuration's plain reference
(its `reference_module`) on logits. `--cell` is `nano3-chat-churn` (the
default), `falconh1-longdoc`, `sala-longctx`, `keye-longctx` or
`dots3-longctx`.

The engine is the cell's configuration's (its preset and serve flags,
launch-ahead on, fused 8-step dispatches, mixed steps). The other slots
are kept busy with the cell's traffic (prompt and answer lengths from
its traffic file, sampled 0.7 / 0.9, a new request for every one that
ends, so admissions run beside the target all the way). The target: a
prompt of ~1,300 tokens for `nano3-chat-churn` (three chunks of 512),
~5,000 for `falconh1-longdoc` (ten chunks) or 12,288 for `sala-longctx`
(24 chunks, the last eight past `dense_len`, so that every decoded token
selects 64 of its 193 pages) and for `keye-longctx` (24 chunks, twenty of
them past `topk`, every decoded token attending 2,048 of its 12.3k tokens;
no state: a thrown-away dispatch advanced its pages and index keys) and
for `dots3-longctx` (the same walk over a LATENT cache in 3 layers, and 6
window layers whose rings, ONE generation a slot, a thrown-away dispatch
has written in place after they wrapped eleven times), the
last chunk padded into
its bucket, then 64 greedy tokens with their log-probs. Rollbacks are FORCED before and among the compared
tokens: a neighbour is aborted while a dispatch launched ahead is on the
device (during the target's prefill, and twice during its decode), so
the target's state has been advanced by a dispatch that was then thrown
away. The target's 64 chosen-token log-probs then go through the
reference's `compare` under the configuration's `reference_tolerance`.

Without a TPU (`--rehearse`, JAX_PLATFORMS=cpu) the same walk at the
rehearsal preset: never a result.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    import jax

    from chipbench import manifest, reference, traffic
    from chipbench.run import check_reference
    from dynamo_tpu.cli.run import _engine_config, build_parser
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=20260928)
    ap.add_argument("--prompt", type=int, default=None)
    ap.add_argument("--cell", default="nano3-chat-churn")
    ns = ap.parse_args(argv)
    man = manifest.load()
    cell = manifest.cell(man, ns.cell)
    conf = manifest.config_of(man, cell)
    mix = manifest.traffic_of(cell)
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not ns.rehearse:
        raise SystemExit("no TPU: say --rehearse (JAX_PLATFORMS=cpu)")
    serve = conf if on_chip else conf["rehearsal"]
    hf = conf if on_chip else serve["hf"]
    if not on_chip:
        mix = {**mix, **mix["rehearsal"]}
    args = build_parser().parse_args([
        "run", "in=http", "out=jax", "--model", serve["preset"],
        *serve["serve_flags"]])
    args.out = "jax"
    eng = JaxEngine(_engine_config(args))
    cfg = eng.config
    vocab = hf["vocab_size"]
    rng = np.random.default_rng(ns.seed)
    n_prompt = ns.prompt or (
        {"falconh1-longdoc": 5000, "sala-longctx": 12288,
         "keye-longctx": 12288, "dots3-longctx": 12288}.get(ns.cell, 1300)
        if on_chip
        else 2 * cfg.prefill_chunk + 11)
    shape = np.random.default_rng(7)
    counter = iter(range(1 << 30))

    def background():
        p = traffic.draw(mix["prompt_tokens"], shape, 1)[0]
        o = traffic.draw(mix["output_tokens"], shape, 1)[0]
        rid = f"bg{next(counter)}"
        eng.add_request(
            rid, [int(v) for v in rng.integers(traffic.FIRST_ID, vocab, p)],
            SamplingParams(max_tokens=o, temperature=0.7, top_p=0.9,
                           ignore_eos=True, seed=int(rng.integers(1 << 30))))
        return rid

    live = {background() for _ in range(cfg.max_seqs - 1)}
    target = {"toks": [], "lps": [], "done": False}
    prompt = [int(v) for v in rng.integers(traffic.FIRST_ID, vocab, n_prompt)]
    forced = []  # (what the target had when a rollback was forced)
    steps = 0
    added = False

    def force_rollback(tag: str) -> None:
        """Abort a decoding neighbour while a dispatch launched ahead is
        on the device: the next schedule() rolls that dispatch back."""
        if eng._inflight is None:
            return
        victims = [r for r in eng._inflight.reqs
                   if r.request_id in live and r.request_id != "target"]
        if not victims:
            return
        rb0 = eng.metrics.overlap_rollbacks
        rid = victims[len(victims) // 2].request_id
        eng.abort_request(rid)
        live.discard(rid)
        live.add(background())
        forced.append({"at": tag, "target_tokens": len(target["toks"]),
                       "rollbacks_before": rb0})

    marks = {"prefill": False, "d8": False, "d32": False}
    while not target["done"]:
        for o in eng.step():
            if o.request_id == "target":
                target["toks"].extend(o.new_token_ids)
                target["lps"].extend(o.logprobs or ())
                target["done"] = o.finish_reason is not None
            elif o.finish_reason is not None and o.request_id in live:
                live.discard(o.request_id)
                live.add(background())
        steps += 1
        running = eng.scheduler.num_running()
        if not added and running >= cfg.max_seqs - 1 and steps > 20:
            eng.add_request("target", prompt, SamplingParams(
                max_tokens=64, temperature=0.0, ignore_eos=True, logprobs=0))
            added = True
            continue
        if not added:
            continue
        req = next((r for r in eng.scheduler.running
                    if r.request_id == "target"), None)
        if req is None:
            continue
        n = len(target["toks"])
        if (not marks["prefill"] and 0 < req.num_computed_tokens
                < len(prompt)):
            force_rollback("during the target's prefill")
            marks["prefill"] = bool(forced)
        elif not marks["d8"] and n >= 8:
            force_rollback("after 8 decoded tokens")
            marks["d8"] = len(forced) >= 2
        elif not marks["d32"] and n >= 32:
            force_rollback("after 32 decoded tokens")
            marks["d32"] = len(forced) >= 3
    m = eng.metrics.to_dict()
    for rid in list(live):
        eng.abort_request(rid)
    eng.drain_overlap()
    print(json.dumps({
        "note": "served", "prompt_tokens": n_prompt,
        "chunks": -(-n_prompt // cfg.prefill_chunk),
        "tokens": len(target["toks"]), "steps": steps, "forced": forced,
        "overlap_hits": m["overlap_hits"],
        "overlap_rollbacks": m["overlap_rollbacks"],
        "state_restores": m["state_restores"],
        "state_resets": m["state_resets"],
        "state_slots_live": m["state_slots_live"],
        "mixed_dispatches": m["mixed_dispatches"],
        "decode_dispatches": m["decode_dispatches"],
        "preemptions": m["preemptions"],
        # a selecting model's counts of what its walks and its sparse
        # chunks' tiles read (0 elsewhere), every row of the run
        **{k: m.get(k, 0) for k in (
            "walk_pages_named", "walk_pages_live", "chunk_pages_read",
            "chunk_pages_named")}}), flush=True)
    params = eng.params
    eng.kv = None
    gc.collect()
    ref = manifest.module_of(conf, "reference_module", reference)
    ref.PROBE = False
    stream = {"prompt": prompt, "out": target["toks"],
              "logprobs": target["lps"]}
    res = check_reference(params, hf, [stream], conf["reference_tolerance"],
                          ref)
    # check_reference's shape gate is the harness's (64 tokens a stream)
    # a state model restores a slot for every dispatch thrown away; a
    # model whose only state is pages, or KV written by position in a slot
    # (a window's ring: benign in place), just rolls back
    undone = (m["state_restores"]
              if eng._stateful and not eng.adapter.state_in_place
              else m["overlap_rollbacks"])
    ok = bool(res["passed"] and len(forced) >= 2 and undone >= len(forced)
              and m["overlap_hits"] > 0)
    print(json.dumps({"note": "busy_compare", "on_chip": on_chip,
                      "passed": ok, "rollbacks_forced": len(forced), **res}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
