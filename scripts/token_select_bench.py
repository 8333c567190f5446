"""Keye-VL's token selection alone, on the chip: wall time a layer (a
jitted loop over the 8 layers of `keye-longctx`'s pools, 9,000 pages of
64, timed whole with `block_until_ready`) of

- `index_scores` (a line of its own, PR 45): the index scores as plain
  XLA (`ts.index_scores` over the gathered copy `index_keys_of` makes)
  against the kernel that reads the pool in place (`keye_vl.step_scores`
  over `ops/index_scores.paged_index_scores`), a decode step's 32 rows
  and a 512-query chunk: ms a layer, the kernel's own events from a
  profiler trace, the bytes it fetches and their floor at the chip's HBM
  rate, the largest difference of a live score, the selections' agreement;
- `index`: a decode step's index scores as the program computes them;
- `select`: the exact top 2,048 as a mask;
- `walk_bits`: `ops/paged_attention.paged_decode_attention` over the
  rows' WHOLE contexts under the selection's bit a token, and
  `walk_unmasked`, the same walk with no bits;
- `chunk`: a 512-query chunk's scores, selection and
  `sparse_chunk.token_chunk_attention` over 12,288 cached tokens;

with the error of `walk_bits` and `chunk` against dense float32 attention
under the same selection. (PR 43 also timed a gather of the chosen K and V
rows here, 2.62 ms a layer, and deleted it.)

    python scripts/token_select_bench.py [--rows 32] [--context 8192 13312
        17920] [--seed N] [--only index_scores] [--set INDEX_DEPTH=2]
        [--rehearse]

One JSON line a context on stdout; refuses a backend that is not a TPU
unless `--rehearse` (the tiny preset's widths, interpreted, never a
number).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def timed(fn, *args, n=5):
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3, out


def kernel_ms(fn, args, kernel: str, calls: int = 3):
    """ms a call inside `kernel`'s own device events, from a trace of
    `calls` calls (None where the trace names none: off the chip)."""
    import tempfile

    import jax

    from chipbench import hostspans, trace

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(calls):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path = trace.find_xplane(tmp)
        devices = hostspans.load(path)["devices"] if path else {}
    for dev in devices.values():
        took = [end - start for name, start, end, _ in dev["ops"]
                if name.startswith("%" + kernel)]
        return sum(took) / calls * 1e3 if took else None
    return None


def index_scores_section(layers, pool, tables, rows, context, t_chunk, cfg,
                         keys, peak) -> dict:
    """XLA against the kernel at one context: a decode step of `rows`
    rows at `context` tokens and a `t_chunk`-query chunk whose last query
    stands there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.keye_vl import index_keys_of, step_scores
    from dynamo_tpu.ops import index_scores as ix
    from dynamo_tpu.ops import token_select as ts

    n_l, page = 2 * pool.shape[0], pool.shape[2]
    nj, di, topk = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    dt = pool.dtype
    item = jnp.dtype(dt).itemsize

    def case(b, t, first):
        pos = first + jnp.broadcast_to(
            jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        qi = jax.random.normal(keys[0], (b, t, nj, di), dt)
        w = jax.random.normal(keys[1], (b, t, nj), jnp.float32) / math.sqrt(
            nj * di)
        own = jax.random.normal(keys[2], (b, t, di), dt)
        valid = jnp.ones((b, t), bool)
        tb = tables[:b]
        xla = layers(lambda li, kp: ts.index_scores(
            qi, w, index_keys_of(kp, li, tb, own, pos)))
        kernel = layers(lambda li, kp: step_scores(
            qi, w, own, tb, pos, valid, kp, li))
        ms_x, ref = timed(xla, pool, n=3)
        ms_k, got = timed(kernel, pool, n=3)
        live = np.arange(ref.shape[-1])[None, None] <= np.asarray(pos)[
            ..., None]
        diff = float(jnp.max(jnp.where(live[None], jnp.abs(got - ref), 0.0)))
        ctx = (pos + 1).reshape(-1)
        pick = jax.jit(lambda x: ts.select_tokens(
            x.reshape(b * t, -1), ctx, topk))
        li = n_l - 1
        same = float(jnp.mean(jnp.all(pick(got[li]) == pick(ref[li]),
                                      axis=-1)))
        name = "paged_index_scores" + ("_chunk" if t > 1 else "")
        own_ms = kernel_ms(kernel, (pool,), name)
        return (ms_x / n_l, ms_k / n_l,
                None if own_ms is None else own_ms / n_l, diff, same,
                float(jnp.std(ref[li][live])))

    dx, dk, down, ddiff, dsame, dstd = case(rows, 1, context - 1)
    hist = (context - t_chunk) // page * page
    cx, ck, cown, cdiff, csame, cstd = case(1, t_chunk, hist)
    # what the kernel fetches: whole pages of a pair row a token
    pages = -(-(context - 1) // page)
    fetched = rows * pages * page * pool.shape[3] * item
    floor_ms = fetched / peak["hbm_bytes_per_s"] * 1e3
    tiles = -(-t_chunk // ix.INDEX_BLOCK_Q)
    keys_read = -(-hist // page) * page
    flop = 2.0 * t_chunk * nj * pool.shape[3] * (keys_read + t_chunk)
    out = {
        "context": context, "rows": rows, "layers": n_l,
        "chunk_history": hist, "platform": jax.default_backend(),
        "decode_max_abs_diff": ddiff, "decode_scores_std": dstd,
        "decode_selection_agreement": dsame,
        "chunk_max_abs_diff": cdiff, "chunk_scores_std": cstd,
        "chunk_selection_agreement": csame,
        "ms_a_layer": {"decode_xla": dx, "decode_kernel": dk,
                       "chunk_xla": cx, "chunk_kernel": ck},
        "kernel_own_ms_a_layer": {"paged_index_scores": down,
                                  "paged_index_scores_chunk": cown},
        "decode_bytes_fetched": fetched, "decode_floor_ms": floor_ms,
        "decode_program_hbm_share": 100.0 * floor_ms / dk,
        "chunk_bytes_fetched": tiles * keys_read * pool.shape[3] * item,
        "chunk_out_bytes": t_chunk * tables.shape[1] * page * 4,
        "chunk_gflop_executed": flop / 1e9,
        "blocking": {k: getattr(ix, k) for k in (
            "INDEX_BLOCK_PAGES", "INDEX_DEPTH", "INDEX_BLOCK_Q",
            "INDEX_COLUMNS")},
    }
    if down:
        out["decode_kernel_hbm_share"] = 100.0 * floor_ms / down
    if cown:
        out["chunk_kernel_mxu_share"] = 100.0 * flop / (
            cown * 1e-3) / peak["bf16_flops_per_s"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--context", type=int, nargs="*",
                    default=[8192, 13312, 17920])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only", choices=["index_scores"],
                    help="that section alone")
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="a module constant of ops/index_scores.py")
    ns = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.keye_vl import KeyeVLConfig, step_scores
    from dynamo_tpu.ops import index_scores as ix
    from dynamo_tpu.ops import token_select as ts
    from dynamo_tpu.ops.paged_attention import paged_decode_attention
    from dynamo_tpu.ops.sparse_chunk import token_chunk_attention

    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not ns.rehearse:
        print("token_select_bench: no TPU (say --rehearse)", file=sys.stderr)
        return 3
    if on_chip:
        cfg, pages, page, max_ctx = (KeyeVLConfig.keye_vl2_1chip(), 9000, 64,
                                     18432)
        rows, contexts, t_chunk = ns.rows, ns.context, 512
    else:
        cfg, pages, page, max_ctx = KeyeVLConfig.tiny(), 64, 4, 64
        rows, contexts, t_chunk = 3, [40], 8
    for item in ns.set:
        name, value = item.split("=", 1)
        if not hasattr(ix, name):
            raise SystemExit(f"ops/index_scores.py has no {name}")
        setattr(ix, name, int(value) if value.isdigit() else value)
    peak = json.loads((ROOT / "chipbench" / "peaks.json").read_text()).get(
        jax.devices()[0].device_kind) or {
        "hbm_bytes_per_s": float("nan"), "bf16_flops_per_s": float("nan")}
    n_l, hq, hkv, d = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim)
    nj, di, topk = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    mp = max_ctx // page
    dt = cfg.dtype
    key = jax.random.key(ns.seed)
    ks = jax.random.split(key, 8)
    k_pool = jax.random.normal(ks[0], (n_l, pages, page, hkv, d), dt)
    v_pool = jax.random.normal(ks[1], (n_l, pages, page, hkv, d), dt)
    ki_pool = jax.random.normal(
        ks[2], (-(-n_l // 2), pages, page, 2 * di), dt)
    rng = np.random.default_rng(ns.seed)
    scale = 1.0 / math.sqrt(d)

    def layers(fn):
        """`fn(layer)` over every layer, the outputs stacked."""
        return jax.jit(lambda *a: jax.lax.map(
            lambda li: fn(li, *a), jnp.arange(n_l, dtype=jnp.int32)))

    for context in contexts:
        need = -(-context // page)
        tables = np.zeros((rows, mp), np.int32)
        for r in range(rows):
            tables[r, :need] = rng.permutation(np.arange(1, pages))[:need]
        tables = jnp.asarray(tables)
        print(json.dumps({"index_scores": index_scores_section(
            layers, ki_pool, tables, rows, context, t_chunk, cfg, ks[3:6],
            peak)}), flush=True)
        if ns.only:
            continue
        pos = jnp.full((rows, 1), context - 1, jnp.int32)
        ctx = pos[:, 0] + 1
        q = jax.random.normal(ks[3], (rows, hq, d), dt)
        qi = jax.random.normal(ks[4], (rows, 1, nj, di), dt)
        w = jax.random.normal(ks[5], (rows, 1, nj), jnp.float32) / math.sqrt(
            nj * di)
        own = jax.random.normal(ks[6], (rows, 1, di), dt)

        index = layers(lambda li, kp: step_scores(
            qi, w, own, tables, pos, jnp.ones((rows, 1), bool), kp, li)[:, 0])
        ms_index, sc = timed(index, ki_pool)
        select = jax.jit(lambda s: jax.lax.map(
            lambda x: ts.select_tokens(x, ctx, topk), s))
        ms_select, chosen_d = timed(select, sc)
        q_s = (q.astype(jnp.float32) * scale).astype(dt)
        walk = layers(lambda li, kp, vp: paged_decode_attention(
            q, kp, vp, li, tables, ctx)[0])
        ms_walk, _ = timed(walk, k_pool, v_pool)

        def under_bits(li, kp, vp, ch):
            acc, _, l = paged_decode_attention(
                q, kp, vp, li, tables, ctx, token_bits=ch[li])
            return acc / l[..., None]

        bits = layers(under_bits)
        ms_bits, got_bits = timed(bits, k_pool, v_pool, chosen_d)

        # against dense float32 attention under the same set
        def dense(li):
            kk = k_pool[li][tables].reshape(rows, mp * page, hkv, d)
            vv = v_pool[li][tables].reshape(rows, mp * page, hkv, d)
            return ts.masked_attention(
                q_s[:, None], kk, vv, chosen_d[li][:, None])
        err_bits = max(float(jnp.max(jnp.abs(
            got_bits[li] - dense(li)[:, 0]))) for li in (0, n_l - 1))

        # a prompt chunk over the context less itself
        hist = (context - t_chunk) // page * page
        cpos = hist + jnp.arange(t_chunk, dtype=jnp.int32)[None]
        cvalid = jnp.ones((1, t_chunk), bool)
        cq = jax.random.normal(ks[3], (1, t_chunk, hq, d), dt)
        ck = jax.random.normal(ks[4], (1, t_chunk, hkv, d), dt)
        cv = jax.random.normal(ks[5], (1, t_chunk, hkv, d), dt)
        cqi = jax.random.normal(ks[6], (1, t_chunk, nj, di), dt)
        cw = jax.random.normal(ks[7], (1, t_chunk, nj), jnp.float32)
        cown = jax.random.normal(ks[0], (1, t_chunk, di), dt)
        cq_s = (cq.astype(jnp.float32) * scale).astype(dt)
        t1 = tables[:1]

        def chosen_of(li, kp):
            sc = step_scores(cqi, cw, cown, t1, cpos, cvalid, kp, li)
            return ts.select_tokens(
                sc[0], cpos[0] + 1, topk)[None]

        chunk_sel = layers(chosen_of)
        ms_csel, chosen = timed(chunk_sel, ki_pool, n=3)
        chunk = layers(lambda li, kp, vp, ch: token_chunk_attention(
            cq_s, ck, cv, kp, vp, li, t1, ch[li],
            jnp.asarray([hist], jnp.int32), cvalid))
        ms_chunk, cgot = timed(chunk, k_pool, v_pool, chosen, n=3)
        li = n_l - 1
        kk = k_pool[li][t1].reshape(1, mp * page, hkv, d).at[
            0, hist:hist + t_chunk].set(ck[0])
        vv = v_pool[li][t1].reshape(1, mp * page, hkv, d).at[
            0, hist:hist + t_chunk].set(cv[0])
        cerr = float(jnp.max(jnp.abs(cgot[li].astype(jnp.float32)
                                     - ts.masked_attention(
                                         cq_s, kk, vv, chosen[li]))))
        flops = 4.0 * hq * d * float(jnp.sum(chosen[li]))
        print(json.dumps({
            "context": context, "rows": rows, "layers": n_l,
            "platform": jax.default_backend(),
            "ms_a_layer": {
                "index": ms_index / n_l, "select": ms_select / n_l,
                "walk_bits": ms_bits / n_l,
                "walk_unmasked": ms_walk / n_l,
                "chunk_index_select": ms_csel / n_l,
                "chunk_attention": ms_chunk / n_l},
            "walk_bits_max_abs_err": err_bits,
            "chunk_max_abs_err": cerr,
            "attended_mean": float(jnp.mean(jnp.sum(chosen_d[0], axis=1))),
            "chunk_kernel_tflops": flops / (ms_chunk / n_l * 1e-3) / 1e12,
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
