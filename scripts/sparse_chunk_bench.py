"""A sparse layer's prompt-chunk attention alone, on the chip: device time
of `models/minicpm_sala.py`'s `sparse_attention` for a chunk (scopes
`attn/select` and `attn/flash` of a MiniCPM-SALA mixed step: the
compressed keys, the selection, and the chunk over the cached pages it
chose and over itself) in the 4 sparse layers of `sala-longctx`'s pools
(9,000 pages of 64, a KV head a row), from a profiler trace, beside the
time the MXU needs for the multiplications under the selection.

    python scripts/sparse_chunk_bench.py [--case NAME ...] [--tree DIR]
                     [--sweep NAME=V1,V2 ...] [--seed N] [--rehearse]
                     [--only select]

`--tree DIR` times another checkout's `dynamo_tpu` on the same inputs
(PR 41's, from `git archive`: every query of a sparse chunk walked a page
list of its own through the decode kernel, and a `dense-*` case, every
query under `dense_len`, ran `latent_prefill_attention` fed a zero latent
half). `--sweep` re-times under each value of a module constant of
`ops/sparse_chunk.py` or `ops/block_scores.py` (the blocking). One JSON
line a case and setting on stdout; refuses a backend that is not a TPU
unless `--rehearse` (the tiny preset's widths, interpreted, never a
number).

`--only select` (PR 46) times the SELECTION alone (scope `attn/select`),
XLA -> kernel, at 64 x 1 decode rows (32 sequences) and one 512-token
piece over 8,192 / 12,288 / 16,384 tokens in the same 4 layers: the path
without kernels whole and SPLIT into programs of their own (the gathered
copy with the fresh windows put in; scores, softmax and the pool onto
blocks; the sorts and the walk's list), the kernel path whole
(`paged_block_scores`, `blocks_of_scores`, `decode_lists(counted=True)`)
with the kernel's own events beside it, the largest difference of a block
score and the share of (query, block) bits the two selections agree on.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PEAKS = json.loads((ROOT / "chipbench" / "peaks.json").read_text())

#: chunk rows (the T bucket), valid rows of each prompt piece, tokens
#: cached before each (whole pages, as the engine's chunks start)
CASES = {
    "sparse-8k": dict(t=512, cur=[512], hist=[8192]),
    "sparse-12k": dict(t=512, cur=[512], hist=[12288]),
    "sparse-16k": dict(t=512, cur=[512], hist=[16384]),
    "two-pieces": dict(t=512, cur=[512, 512], hist=[8192, 14336]),
    # one dense piece beside three sparse ones, one of them part padding
    "four-pieces": dict(t=512, cur=[512, 300, 512, 512],
                        hist=[9216, 12288, 4096, 16384]),
    "tail-32": dict(t=32, cur=[17, 32], hist=[9216, 15872]),
    "dense-0": dict(t=512, cur=[512], hist=[0]),
    "dense-3584": dict(t=512, cur=[512], hist=[3584]),
    "dense-7168": dict(t=512, cur=[512], hist=[7168]),
    # the chunk whose last query is the first under the sparse rule
    "crossing-7680": dict(t=512, cur=[512], hist=[7680]),
}
REHEARSAL = {
    "rehearsal": dict(t=16, cur=[16, 9, 0], hist=[36, 24, 0]),
    "rehearsal-dense": dict(t=8, cur=[8], hist=[16]),
}


def make_case(cfg, case: dict, seed: int, page: int, pages: int, mp: int):
    """Seeded operands as `sparse_mixer` hands them on: a KV head a
    virtual row (b * Hkv + h) over the pools [L, P * Hkv, S, 1, D]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hkv, g, d = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    b, t, layers = len(case["cur"]), case["t"], cfg.sparse_layers
    rng = np.random.default_rng(seed)
    pt = np.zeros((b, mp), np.int32)
    for i, h in enumerate(case["hist"]):
        used = -(-(h + t) // page)
        pt[i, :used] = rng.choice(np.arange(1, pages), used, replace=False)
    keys = jax.random.split(jax.random.key(seed), 6)
    dc = cfg.attn_cfg.kv_head_dim  # a cached row: D in whole lanes
    pool = (layers, pages * hkv, page, 1, dc)
    hist = np.repeat(np.asarray(case["hist"], np.int32), hkv)
    cur = np.repeat(np.asarray(case["cur"], np.int32), hkv)
    normal = lambda k, shape: jax.random.normal(k, shape, cfg.dtype)  # noqa: E731
    return dict(
        q=normal(keys[0], (b * hkv, t, g, d)),
        k=normal(keys[1], (b * hkv, t, 1, d)),
        v=normal(keys[2], (b * hkv, t, 1, d)),
        k_pool=normal(keys[3], pool), v_pool=normal(keys[4], pool),
        kc_pool=normal(keys[5], (
            layers, pages * hkv * cfg.sparse.per_block, dc)),
        tables=jnp.asarray((pt[:, None, :] * hkv + np.arange(hkv)[
            None, :, None]).reshape(b * hkv, mp)),
        pos=jnp.asarray(hist[:, None] + np.arange(t, dtype=np.int32)[None]),
        valid=jnp.asarray(np.arange(t)[None] < cur[:, None]),
        hist=hist, cur=cur,
    )


ARGS = ("q", "k", "v", "k_pool", "v_pool", "kc_pool", "tables", "pos",
        "valid")


def program(sala, cfg):
    """Every sparse layer's chunk attention in one program, as a step's
    layer scan calls it."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import KVPages

    def fn(q, k, v, k_pool, v_pool, kc_pool, tables, pos, valid):
        kv = KVPages(k=k_pool, v=v_pool)

        def layer(_, li):
            with jax.named_scope("attn"):
                attn, _, _, _, walk = sala.sparse_attention(
                    q, k, v, kv, kc_pool, li, tables, pos, valid, cfg)
            return None, (attn, walk[-2:])

        _, out = jax.lax.scan(
            layer, None, jnp.arange(k_pool.shape[0], dtype=jnp.int32))
        return out

    return jax.jit(fn)


def reference(sala, cfg, data: dict, layer: int):
    """Float32 attention of the chunk under the selection the program's
    own `select_blocks` makes of the same operands (a near-tie at rank
    `topk` that another program's sums break the other way would show as
    an error of a tenth: PERF.md 6, PR 41), keys and values as the pool
    and the chunk hold them: [B', T, G, D]."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import KVPages
    from dynamo_tpu.ops import sparse_select as ss

    @jax.jit
    def ref(q, k, v, k_pool, v_pool, kc_pool, tables, pos, valid):
        scale = 1.0 / math.sqrt(q.shape[-1])
        kc, _ = sala.compressed_keys_of(
            k, KVPages(k=k_pool, v=v_pool), kc_pool, layer, tables, pos,
            valid, cfg)
        sel = ss.select_blocks(q, kc, pos, cfg.sparse, scale)
        with jax.default_matmul_precision("highest"):
            return ss.masked_attention(
                q.astype(jnp.float32), k_pool[layer][None].astype(jnp.float32),
                v_pool[layer][None].astype(jnp.float32), 0, tables, pos, sel,
                cfg.sparse, scale,
                hist_len=jnp.where(valid[:, 0], pos[:, 0], 0),
                k_cur=k[:, :, 0].astype(jnp.float32),
                v_cur=v[:, :, 0].astype(jnp.float32),
                cur_pos=jnp.where(valid, pos, 1 << 30))

    return ref(*(data[n] for n in ARGS))


def multiply_flops(cfg, case: dict) -> float:
    """2 x D for a score and 2 x D for its weighted value, for every
    query head, valid query and key UNDER THE RULE (all of them while the
    context is under `dense_len`, else `topk` blocks of which the query's
    own is part), one layer."""
    dims = cfg.sparse
    seen = 0
    for h, n in zip(case["hist"], case["cur"]):
        for i in range(int(n)):
            ctx = int(h) + i + 1
            seen += ctx if ctx < dims.dense_len else min(
                ctx, (dims.topk - 1) * dims.block_size
                + i % dims.block_size + 1)
    return 4.0 * cfg.num_heads * cfg.head_dim * seen


def program_seconds(trace_dir: str) -> dict:
    """Of `jit_fn`'s events in a trace: their summed device time and
    count, the self time under `attn/select` and `attn/flash`, and the
    time inside each Pallas kernel's own events."""
    from chipbench import hostspans, sparsescopes, trace

    loaded = sparsescopes.load_deep(trace.find_xplane(trace_dir))
    per_scope = hostspans.scope_self_s(loaded, "jit_fn") or {}
    kernels: dict = {}
    for dev in loaded["devices"].values():
        for name, start, end, _scope in dev["ops"]:
            for kernel in ("sparse_chunk_attention", "paged_decode_attention",
                           "latent_prefill_attention"):
                if name.startswith("%" + kernel):
                    kernels[kernel] = kernels.get(kernel, 0.0) + end - start
        break  # one chip
    return {"seconds": per_scope.get("_seconds", 0.0),
            "calls": per_scope.get("_count", 0),
            "scopes": {k: v for k, v in per_scope.items()
                       if not k.startswith("_")},
            "kernels": kernels}


SELECT_CASES = {
    f"{kind}-{ctx // 1024}k": dict(
        t=t, cur=[t] * rows, hist=[ctx - t + 1 if t == 1 else ctx] * rows)
    for ctx in (8192, 12288, 16384)
    for kind, t, rows in (("decode", 1, 32), ("piece", 512, 1))
}
SELECT_REHEARSAL = {
    "decode": dict(t=1, cur=[1, 1, 0], hist=[67, 35, 0]),
    "piece": dict(t=16, cur=[16, 9], hist=[36, 24]),
}


def select_programs(sala, cfg):
    """The selection of every sparse layer as programs of their own: the
    path without kernels whole and in its three parts (each part takes
    the part before as an argument), and the kernel path."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import KVPages
    from dynamo_tpu.ops import block_scores as bs
    from dynamo_tpu.ops import sparse_select as ss

    dims, hkv = cfg.sparse, cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def layers(fn, n, *xs):
        return jax.lax.scan(
            lambda _, a: (None, fn(*a)), None,
            (jnp.arange(n, dtype=jnp.int32), *xs))[1]

    def lists(sel, tables, pos, counted):
        if sel.shape[1] > 1:
            return sel
        return ss.decode_lists(sel[:, 0], tables, pos[:, 0], dims,
                               counted=counted)

    def copy(q, k, k_pool, v_pool, kc_pool, tables, pos, valid):
        kv = KVPages(k=k_pool, v=v_pool)
        return layers(lambda li: sala.compressed_keys_of(
            k, kv, kc_pool, li, tables, pos, valid, cfg)[0],
            k_pool.shape[0])

    def scores(q, kc, pos):
        return layers(lambda li, kc_l: ss.pooled_scores(
            q, kc_l, pos, dims, scale), kc.shape[0], kc)

    def sorts(score, tables, pos):
        return layers(lambda li, sc: lists(
            ss.ranked_blocks(sc, pos, dims), tables, pos, False),
            score.shape[0], score)

    def xla(q, k, k_pool, v_pool, kc_pool, tables, pos, valid):
        kv = KVPages(k=k_pool, v=v_pool)

        def layer(li):
            with jax.named_scope("attn"), jax.named_scope("select"):
                kc, _ = sala.compressed_keys_of(
                    k, kv, kc_pool, li, tables, pos, valid, cfg)
                sc = ss.pooled_scores(q, kc, pos, dims, scale)
                return sc, lists(ss.ranked_blocks(sc, pos, dims), tables,
                                 pos, False)

        return layers(layer, k_pool.shape[0])

    def kernel(q, k, k_pool, v_pool, kc_pool, tables, pos, valid):
        kv = KVPages(k=k_pool, v=v_pool)

        def layer(li):
            with jax.named_scope("attn"), jax.named_scope("select"):
                fresh = sala.fresh_keys_of(k, kv, li, tables, pos, valid, cfg)
                sc = bs.paged_block_scores(
                    q, kc_pool, li, tables, pos, valid, fresh, dims, scale,
                    hkv)
                return sc, lists(ss.blocks_of_scores(sc, pos, dims), tables,
                                 pos, True)

        return layers(layer, k_pool.shape[0])

    return {n: jax.jit(f) for n, f in dict(
        copy=copy, scores=scores, sorts=sorts, xla=xla,
        kernel=kernel).items()}


def module_seconds(trace_dir: str, names) -> dict:
    """Summed device seconds and calls of each `jit_<name>` in a trace,
    the time inside the kernel `paged_block_scores`' own events, and the
    ten longest operations inside `jit_kernel`."""
    from chipbench import sparsescopes, trace

    loaded = sparsescopes.load_deep(trace.find_xplane(trace_dir))
    out = {n: [0.0, 0] for n in names}
    own, inside = 0.0, {}
    for dev in loaded["devices"].values():
        spans = []
        for name, start, end in dev["modules"]:
            if name.startswith("jit_") and name[4:] in out:
                out[name[4:]][0] += end - start
                out[name[4:]][1] += 1
            if name == "jit_kernel":
                spans.append((start, end))
        for name, start, end, _scope in dev["ops"]:
            if name.startswith("%paged_block_scores"):
                own += end - start
            if (not name.startswith("%while")
                    and any(a <= start < b for a, b in spans)):
                inside[name] = inside.get(name, 0.0) + end - start
        break  # one chip
    return out, own, sorted(inside.items(), key=lambda kv: -kv[1])[:10]


def measure_select(sala, name: str, case: dict, seed: int,
                   rehearse: bool) -> dict:
    """One line of `--only select`: us a layer of each program."""
    import jax
    import numpy as np
    from dataclasses import replace

    cfg = (sala.MiniCPMSALAConfig.tiny() if rehearse
           else sala.MiniCPMSALAConfig.minicpm_sala_9b(range(9, 25)))
    cfg = replace(cfg, attention_impl="pallas")
    page, pages, mp = (4, 40, 20) if rehearse else (64, 9000, 288)
    data = make_case(cfg, case, seed, page, pages, mp)
    progs = select_programs(sala, cfg)
    whole = tuple(data[n] for n in (
        "q", "k", "k_pool", "v_pool", "kc_pool", "tables", "pos", "valid"))
    kc = jax.block_until_ready(progs["copy"](*whole))
    sc_x, sel_x = jax.block_until_ready(progs["xla"](*whole))
    sc_k, sel_k = jax.block_until_ready(progs["kernel"](*whole))
    calls = {"copy": whole, "scores": (data["q"], kc, data["pos"]),
             "sorts": (sc_x, data["tables"], data["pos"]),
             "xla": whole, "kernel": whole}
    live = np.asarray(data["valid"])[None, :, :, None] & (
        np.arange(mp)[None, None, None]
        <= (np.asarray(data["pos"]) // page)[None, ..., None])
    ok = np.asarray(data["valid"])  # a padding query's bits are nobody's
    same = [(np.asarray(a) == np.asarray(b))[:, ok[:, 0] if case["t"] == 1
                                             else ok]
            for a, b in zip(jax.tree.leaves(sel_x), jax.tree.leaves(sel_k))]
    layers = cfg.sparse_layers
    out = {
        "case": name, "t": case["t"], "sequences": len(case["cur"]),
        "hist": case["hist"][0], "layers": layers,
        "max_score_diff": float(np.max(np.abs(np.where(
            live, np.asarray(sc_x) - np.asarray(sc_k), 0.0)))),
        # decode: the lists' (pages, lens); a piece: its [T, NB] bits
        "selection_agreement": float(np.mean([x.mean() for x in same])),
        "device": jax.devices()[0].device_kind,
    }
    if rehearse:
        return out
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            for n, args in calls.items():
                jax.block_until_ready(progs[n](*args))
        jax.profiler.stop_trace()
        secs, own, ops = module_seconds(tmp, calls)
    us = {n: round(s / max(c, 1) / layers * 1e6, 1)
          for n, (s, c) in secs.items()}
    n = max(secs["kernel"][1], 1) * layers
    out.update(us_per_layer=us,
               kernel_own_us_per_layer=round(own / n * 1e6, 1),
               # the kernel path's longest operations (loops left out)
               kernel_path_ops_us_per_layer={
                   k: round(v / n * 1e6, 1) for k, v in ops})
    return out


def measure(sala, name: str, case: dict, seed: int,
            rehearse: bool) -> dict:
    import jax
    import numpy as np
    from dataclasses import replace

    cfg = (sala.MiniCPMSALAConfig.tiny() if rehearse
           else sala.MiniCPMSALAConfig.minicpm_sala_9b(range(9, 25)))
    cfg = replace(cfg, attention_impl="pallas")
    # the cell's pools: --num-pages 9000 --max-context 18432
    page, pages, mp = (4, 40, 20) if rehearse else (64, 9000, 288)
    data = make_case(cfg, case, seed, page, pages, mp)
    fn = program(sala, cfg)
    args = tuple(data[n] for n in ARGS)
    got, read = jax.block_until_ready(fn(*args))
    layers = cfg.sparse_layers
    flops = multiply_flops(cfg, case)
    out = {
        "case": name, "pieces": len(case["cur"]),
        "t": case["t"], "hist": case["hist"], "cur": case["cur"],
        "layers": layers, "finite": bool(np.isfinite(
            np.asarray(got, np.float32)).all()),
        "gflop_per_layer": flops / 1e9,
        # pages the tiles of rows past `dense_len` read and pages their
        # queries named, a layer (zeros on a tree from before the tiles)
        "chunk_pages": np.asarray(read[0]).tolist(),
        "device": jax.devices()[0].device_kind,
    }
    g, d = cfg.num_heads // cfg.num_kv_heads, cfg.head_dim
    want = np.asarray(reference(sala, cfg, data, 0))
    live = np.asarray(data["valid"])
    err = np.abs(np.asarray(got[0], np.float32).reshape(
        *live.shape, g, d) - want)[live]
    out.update(max_abs_err=float(err.max()) if err.size else 0.0,
               mean_abs_err=float(err.mean()) if err.size else 0.0)
    if rehearse:
        return out
    peak = PEAKS[out["device"]]["bf16_flops_per_s"]
    with tempfile.TemporaryDirectory() as tmp:
        jax.block_until_ready(fn(*args))
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        read_back = program_seconds(tmp)
    n = max(read_back["calls"], 1) * layers
    per_layer = read_back["seconds"] / n
    out.update(
        calls=read_back["calls"], us_per_layer=per_layer * 1e6,
        scope_us_per_layer={k: round(v / n * 1e6, 1)
                            for k, v in sorted(read_back["scopes"].items())},
        kernel_us_per_layer={k: round(v / n * 1e6, 1)
                             for k, v in read_back["kernels"].items()},
        mxu_floor_us=flops / peak * 1e6,
        mxu_share=100.0 * flops / peak / per_layer if per_layer else None,
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", action="append",
                    choices=sorted({**CASES, **SELECT_CASES}))
    ap.add_argument("--tree", default=str(ROOT),
                    help="the checkout whose dynamo_tpu is timed")
    ap.add_argument("--sweep", action="append", default=[],
                    metavar="NAME=V1,V2",
                    help="a constant of ops/sparse_chunk or ops/block_scores")
    ap.add_argument("--only", choices=["select"],
                    help="the selection alone, XLA split -> kernel")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args()
    sys.path.insert(0, str(ROOT))  # chipbench's trace readers
    sys.path.insert(0, str(Path(ns.tree).resolve()))
    import jax

    if jax.default_backend() != "tpu" and not ns.rehearse:
        print("sparse_chunk_bench: no TPU; --rehearse for the CPU",
              file=sys.stderr)
        return 2
    from dynamo_tpu.models import minicpm_sala as sala

    try:
        from dynamo_tpu.ops import sparse_chunk
    except ImportError:  # a tree from before the tile kernel
        sparse_chunk = None
    try:
        from dynamo_tpu.ops import block_scores
    except ImportError:  # a tree from before the selection's kernel
        block_scores = None
    names, values = [], []
    for item in ns.sweep:
        key, vals = item.split("=", 1)
        owner = next((m for m in (sparse_chunk, block_scores)
                      if hasattr(m, key)), None)
        if owner is None:
            raise SystemExit(f"no ops module of the bench has {key}")
        names.append((owner, key))
        values.append([int(x) for x in vals.split(",")])
    if ns.only == "select":
        table = SELECT_REHEARSAL if ns.rehearse else SELECT_CASES
        run = measure_select
    else:
        table = REHEARSAL if ns.rehearse else CASES
        run = measure
    cases = {n: table[n] for n in (
        ns.case if ns.case and not ns.rehearse else table)}
    failed = 0
    for setting in itertools.product(*values):
        for (owner, key), value in zip(names, setting):
            setattr(owner, key, value)
        for name, case in cases.items():
            try:
                doc = run(sala, name, case, ns.seed, ns.rehearse)
            except Exception as e:  # noqa: BLE001 — the others still run
                doc = {"case": name,
                       "error": f"{type(e).__name__}: {e}"[:2000]}
                failed += 1
            doc["set"] = {k: v for (_, k), v in zip(names, setting)}
            doc["tree"] = ns.tree
            print(json.dumps(doc), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
