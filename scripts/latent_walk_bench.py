"""python scripts/latent_walk_bench.py [--rehearse] [--contexts 8192,13312,17920]

A full layer's decode attention of dots3-note-prev alone on the chip, the
two candidates ISSUE 48 names, at the cell's shapes (32 rows, 128 heads,
3 layers of a 9,000-page latent pool, 2,048 tokens chosen a row):

- `bits`: the page walk of ops/paged_attention.py over EVERY page of the
  row under a bit a cached token (`latent` + `token_bits`; models/mla.py
  `_latent_decode`): reads 1,280 B and multiplies 295 kFLOP a cached
  token, whatever it attends;
- `gather`: the 2,048 chosen tokens' latent and rope-key ROWS gathered out
  of the pools in XLA (1,024 B + 256 B a token) and attended densely in
  the absorbed form.

Wall time of a jitted call over the 3 layers, the median of `--calls`
after a warm-up, `block_until_ready` around each (ms a layer). `--rehearse`
(JAX_PLATFORMS=cpu) walks both at a tiny size: never a number.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import dots3, mla
    from dynamo_tpu.ops import token_select as ts
    from dynamo_tpu.ops.paged_attention import decode_work_list

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--contexts", default="8192,13312,17920")
    ap.add_argument("--calls", type=int, default=20)
    ns = ap.parse_args(argv)
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not ns.rehearse:
        raise SystemExit("no TPU: say --rehearse (JAX_PLATFORMS=cpu)")
    if on_chip:
        cfg = dots3.Dots3Config.dots3_1chip()
        pages, page, rows = 9000, 64, 32
        contexts = [int(c) for c in ns.contexts.split(",")]
    else:
        cfg = dots3.Dots3Config.tiny()
        pages, page, rows, contexts = 64, 4, 2, [40]
    import dataclasses

    cfg = dataclasses.replace(cfg, attention_impl="pallas")
    geo = cfg.full_geo
    c, r, hn, topk = (geo.kv_lora_rank, geo.kv_rope_dim, geo.num_heads,
                      cfg.index_topk)
    key = jax.random.key(0)
    k_pool = jax.random.normal(
        key, (cfg.full_layers, pages, page, 1, c), cfg.dtype)
    v_pool = jax.random.normal(
        jax.random.fold_in(key, 1), (cfg.full_layers, pages, page, 1, r),
        cfg.dtype)
    mp = -(-max(contexts) // page) + 1
    rng = np.random.default_rng(0)

    def bits(k_pool, v_pool, qd, c_cur, pe_cur, tables, hist, chosen, work):
        out = 0.0
        for li in range(cfg.full_layers):
            out += mla._latent_decode(
                qd, c_cur, pe_cur, k_pool, v_pool, jnp.int32(li), tables,
                hist, geo, work, None, chosen)
        return out

    def gather(k_pool, v_pool, qd, c_cur, pe_cur, tables, hist, chosen,
               work):
        # the chosen tokens' positions (the walk's own token apart), then
        # their rows out of the flat pools
        n = chosen.shape[1]
        cached = chosen & (jnp.arange(n)[None] < hist[:, None])
        at = jnp.argsort(~cached, axis=1, stable=True)[:, :topk]
        live = jnp.take_along_axis(cached, at, axis=1)
        page_of = jnp.take_along_axis(tables, at // page, axis=1)
        row = page_of * page + at % page  # [B, topk]
        out = 0.0
        scale = geo.softmax_scale
        for li in range(cfg.full_layers):
            lat = k_pool.reshape(-1, c)[li * pages * page + row]
            rope = v_pool.reshape(-1, r)[li * pages * page + row]
            sc = scale * (
                jnp.einsum("bhc,bkc->bhk", qd[..., :c], lat,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhr,bkr->bhk", qd[..., c:], rope,
                             preferred_element_type=jnp.float32))
            own = scale * (
                jnp.einsum("bhc,bc->bh", qd[..., :c].astype(jnp.float32),
                           c_cur.astype(jnp.float32))
                + jnp.einsum("bhr,br->bh", qd[..., c:].astype(jnp.float32),
                             pe_cur.astype(jnp.float32)))
            own = jnp.where(
                jnp.take_along_axis(chosen, hist[:, None], axis=1), own,
                -1e30)  # its own token, where the row chose it
            sc = jnp.concatenate([
                jnp.where(live[:, None], sc, -1e30), own[..., None]], -1)
            p = jax.nn.softmax(sc, axis=-1)
            out += jnp.einsum(
                "bhk,bkc->bhc", p[..., :-1].astype(cfg.dtype), lat,
                preferred_element_type=jnp.float32) + (
                p[..., -1:] * c_cur.astype(jnp.float32)[:, None])
        return out

    for context in contexts:
        tables = jnp.asarray(np.stack([
            rng.permutation(np.arange(1, pages))[:mp] for _ in range(rows)
        ]), jnp.int32)
        hist = jnp.full((rows,), context - 1, jnp.int32)
        scores = jax.random.normal(
            jax.random.fold_in(key, context), (rows, mp * page), jnp.float32)
        chosen = ts.select_tokens(scores, hist + 1, topk)
        qd = jax.random.normal(key, (rows, hn, c + r), cfg.dtype) * 0.05
        c_cur = jax.random.normal(key, (rows, c), cfg.dtype)
        pe_cur = jax.random.normal(key, (rows, r), cfg.dtype)
        work = decode_work_list(tables, hist)
        # (the pools go in as arguments: closed over, 2.2 GB of constants
        # would be baked into each program)
        args = (k_pool, v_pool, qd, c_cur, pe_cur, tables, hist, chosen,
                work)
        line = {"note": "latent_walk", "context": context, "rows": rows,
                "chosen": int(chosen[0].sum()), "on_chip": on_chip}
        outs = {}
        for name, fn in (("bits", bits), ("gather", gather)):
            f = jax.jit(fn)
            outs[name] = jax.block_until_ready(f(*args))
            times = []
            for _ in range(ns.calls if on_chip else 1):
                t = time.perf_counter()
                jax.block_until_ready(f(*args))
                times.append(time.perf_counter() - t)
            line[f"{name}_ms_per_layer"] = round(
                1e3 * statistics.median(times) / cfg.full_layers, 4)
        err = float(jnp.max(jnp.abs(outs["bits"] - outs["gather"]))
                    / jnp.max(jnp.abs(outs["gather"])))
        line["largest_difference_share"] = err
        print(json.dumps(line), flush=True)
        if err > 0.05:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
