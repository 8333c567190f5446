"""Pallas TPU flash attention for prefill chunks (causal, GQA).

The XLA first-chunk path (models/llama.py:_chunk_only_attention →
paged_attention) materializes fp32 scores [B, Hkv, g, T, T] in HBM — at
the north-star ISL (3000) that is hundreds of MB of score traffic per
layer. This kernel computes the same causal attention with an online
softmax: scores live in VMEM one [BQ·g, BK] tile at a time, K/V stream
through VMEM once, nothing is materialized.

Grid: (B, Hkv, T/BQ) — one cell per (sequence, kv head, query block); the
g query heads sharing a kv head fold into the tile's rows. The causal
frontier prunes key blocks strictly above the diagonal, and a per-sequence
`valid_len` (scalar-prefetched) masks the padding tail, matching the
fallback's semantics (invalid queries produce ignored rows).

Parity note: the reference gets its prefill kernels from vLLM/TRT-LLM
(engine-delegated, SURVEY.md §2.9); here the engine is first-class so the
kernel lives in-tree, next to the decode kernel (ops/paged_attention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: q/k tile rows; T is padded to a multiple (masked out)
BLOCK = 128


def _prefill_kernel(
    # scalar prefetch
    len_ref,  # [B] int32 valid token counts
    # inputs (VMEM blocks)
    q_ref,  # [1, 1, G, BQ, D]
    k_ref,  # [1, 1, T, D]
    v_ref,  # [1, 1, T, D]
    # output
    o_ref,  # [1, 1, G, BQ, D]
    *,
    scale_dim: int,
    block: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    g, bq, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
    valid = len_ref[b]
    scale = 1.0 / math.sqrt(scale_dim)

    q = q_ref[0, 0].astype(jnp.float32).reshape(g * bq, d) * scale
    # absolute query positions per folded row; built 2D via rem — Mosaic
    # cannot lower a (g, bq) -> (g*bq,) cross-lane reshape of an iota
    row_pos = (
        jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (g * bq, 1), 0), bq)
        + qi * bq
    )  # [G*BQ, 1]

    acc0 = jnp.zeros((g * bq, d), jnp.float32)
    m0 = jnp.full((g * bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((g * bq, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        # ref-sliced with pl.ds: Mosaic lowers dynamic indexing on refs,
        # not lax.dynamic_slice on loaded values
        k_blk = k_ref[0, 0, pl.ds(j * block, block), :].astype(
            jnp.float32
        )  # [BK, D]
        v_blk = v_ref[0, 0, pl.ds(j * block, block), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G*BQ, BK]
        col_pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) + j * block
        mask = (col_pos <= row_pos) & (col_pos < valid)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    # causal frontier: key blocks 0..qi inclusive (BQ == BK aligned)
    acc, m, l = jax.lax.fori_loop(0, qi + 1, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)  # masked rows stay finite
    o_ref[0, 0] = out.reshape(g, bq, d).astype(o_ref.dtype)


def _hist_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    pt_ref,  # [B, MP] int32 page tables (SMEM)
    hist_ref,  # [B] int32 — tokens already in the cache (chunk start)
    cur_ref,  # [B] int32 — valid tokens in THIS chunk
    # then (positional, extra scale refs only when `quantized`):
    #   q_ref,  # [1, BQ, HQ, D] VMEM (post-rope, unscaled)
    #   kcur_ref,  # [1, T, Hkv, D] VMEM — this chunk's keys (post-rope)
    #   vcur_ref,  # [1, T, Hkv, D] VMEM
    #   k_hbm,  # [L, P, S, Hkv, D] ANY (narrow dtype when quantized)
    #   v_hbm,
    #   [ks_hbm, vs_hbm]  # [L, P, Hkv, S'] f32 scale planes (quantized)
    # output:
    #   o_ref,  # [1, BQ, HQ, D]
    # scratch:
    #   k_scr,  # [2, S, Hkv, D] VMEM
    #   v_scr,
    #   [ks_scr, vs_scr]  # [2, Hkv, S'] f32 VMEM (quantized)
    #   sem,  # [2 or 4, 2] DMA semaphores
    *refs,
    page_size: int,
    scale_dim: int,
    num_kv_heads: int,
    quantized: bool,
):
    if quantized:
        (q_ref, kcur_ref, vcur_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         o_ref, k_scr, v_scr, ks_scr, vs_scr, sem) = refs
    else:
        (q_ref, kcur_ref, vcur_ref, k_hbm, v_hbm,
         o_ref, k_scr, v_scr, sem) = refs
        ks_hbm = vs_hbm = ks_scr = vs_scr = None
    b = pl.program_id(0)
    qi = pl.program_id(1)
    li = layer_ref[0]
    bq, hq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    t = kcur_ref.shape[1]
    g = hq // num_kv_heads
    s = page_size
    hist = hist_ref[b]
    cur = cur_ref[b]
    used = pl.cdiv(hist, s)

    planes = [(k_hbm, k_scr), (v_hbm, v_scr)]
    if quantized:
        planes += [(ks_hbm, ks_scr), (vs_hbm, vs_scr)]

    def copies(slot, i):
        return tuple(
            pltpu.make_async_copy(
                src.at[li, pt_ref[b, i]], dst.at[slot], sem.at[pi, slot]
            )
            for pi, (src, dst) in enumerate(planes)
        )

    @pl.when(used > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    scale = 1.0 / math.sqrt(scale_dim)
    # per-head query tiles [G·BQ, D], group-major like the cache layout
    q = q_ref[0].astype(jnp.float32) * scale  # [BQ, HQ, D]

    def qh_tile(h):
        return (
            q[:, h * g : (h + 1) * g]
            .transpose(1, 0, 2)
            .reshape(g * bq, d)
        )

    # -- history pages (every key position < hist: no causal test) --------
    def body(i, carry):
        ms, ls, accs = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < used)
        def _():
            for c in copies(1 - slot, i + 1):
                c.start()

        for c in copies(slot, i):
            c.wait()
        kp = k_scr[slot].astype(jnp.float32)  # [S, Hkv, D]
        vp = v_scr[slot].astype(jnp.float32)
        if quantized:
            # dequant in VMEM right after the page lands, folded into
            # this page's slice of the online softmax: key scales
            # multiply score columns, value scales weight columns (the
            # slot-minor planes are lane-oriented like both)
            ksc = ks_scr[slot][:, :s]  # [Hkv, S]
            vsc = vs_scr[slot][:, :s]
        key_pos = i * s + jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
        key_mask = key_pos < hist  # [1, S] — the last page may be partial

        m_out, l_out, a_out = [], [], []
        for h in range(num_kv_heads):  # static unroll
            scores = jax.lax.dot_general(
                qh_tile(h), kp[:, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G·BQ, S]
            if quantized:
                scores = scores * ksc[h : h + 1]
            scores = jnp.where(key_mask, scores, -1e30)
            m_new = jnp.maximum(
                ms[h], jnp.max(scores, axis=1, keepdims=True)
            )
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(ms[h] - m_new)
            l_new = ls[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = p * vsc[h : h + 1] if quantized else p
            a_new = accs[h] * corr + jax.lax.dot_general(
                pv, vp[:, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_out.append(m_new)
            l_out.append(l_new)
            a_out.append(a_new)
        return tuple(m_out), tuple(l_out), tuple(a_out)

    init = (
        tuple(
            jnp.full((g * bq, 1), -jnp.inf, jnp.float32)
            for _ in range(num_kv_heads)
        ),
        tuple(jnp.zeros((g * bq, 1), jnp.float32) for _ in range(num_kv_heads)),
        tuple(jnp.zeros((g * bq, d), jnp.float32) for _ in range(num_kv_heads)),
    )
    ms, ls, accs = jax.lax.fori_loop(0, used, body, init)

    # -- the current chunk (causal within the chunk, padding masked) -------
    # Key blocks strictly above the causal diagonal are pruned: block j
    # only matters for q block qi when j <= qi (BQ-aligned), mirroring
    # _prefill_kernel's frontier loop.
    # [G*BQ, 1], built via rem (see _prefill_kernel's row_pos note)
    row_rel = qi * bq + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (g * bq, 1), 0), bq
    )

    def cur_body(j, carry):
        ms, ls, accs = carry
        col_rel = j * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
        cmask = (col_rel <= row_rel) & (col_rel < cur)  # [G·BQ, BQ]
        m_out, l_out, a_out = [], [], []
        for h in range(num_kv_heads):
            kc = kcur_ref[0, pl.ds(j * bq, bq), h, :].astype(
                jnp.float32
            )  # [BQ, D]
            vc = vcur_ref[0, pl.ds(j * bq, bq), h, :].astype(jnp.float32)
            scores = jax.lax.dot_general(
                qh_tile(h), kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G·BQ, BQ]
            scores = jnp.where(cmask, scores, -1e30)
            m_new = jnp.maximum(
                ms[h], jnp.max(scores, axis=1, keepdims=True)
            )
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(ms[h] - m_new)
            l_new = ls[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            a_new = accs[h] * corr + jax.lax.dot_general(
                p, vc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_out.append(m_new)
            l_out.append(l_new)
            a_out.append(a_new)
        return tuple(m_out), tuple(l_out), tuple(a_out)

    ms, ls, accs = jax.lax.fori_loop(0, qi + 1, cur_body, (ms, ls, accs))
    outs = []
    for h in range(num_kv_heads):
        out = accs[h] / jnp.maximum(ls[h], 1e-30)  # [G·BQ, D]
        outs.append(out.reshape(g, bq, d))
    # [HQ(group-major), BQ, D] -> [BQ, HQ, D]
    o_ref[0] = (
        jnp.concatenate(outs, axis=0).transpose(1, 0, 2).astype(o_ref.dtype)
    )


def paged_prefill_attention(
    q: jax.Array,  # [B, T, Hq, D] post-rope chunk queries (D lane-padded)
    k_cur: jax.Array,  # [B, T, Hkv, D] this chunk's keys (post-rope)
    v_cur: jax.Array,  # [B, T, Hkv, D]
    k_cache: jax.Array,  # [L, P, S, Hkv, D] stacked cache (history)
    v_cache: jax.Array,
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, MP] int32
    hist_lens: jax.Array,  # [B] int32 — tokens already written to pages
    cur_lens: jax.Array,  # [B] int32 — valid tokens in this chunk
    *,
    scale_dim: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    k_scale: jax.Array | None = None,  # [L, P, Hkv, S'] f32 (quantized pools)
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """History-chunk prefill attention: paged history walked with
    double-buffered DMA (read once per q block) + the in-register current
    chunk, one online softmax over both — replaces the XLA
    gather-then-attend path, which materializes the whole history densely
    in HBM before a single matmul touches it. With `k_scale`/`v_scale`
    the history pages are quantized; each page's scale plane rides its
    DMA pipeline and rows dequantize in VMEM.

    Returns [B, T, Hq, D]; rows past cur_lens are unspecified.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from functools import partial

        from jax.sharding import PartitionSpec as P

        def sharded(q_, kc_, vc_, k_, v_, layer_, pt_, hl_, cl_, *scales):
            return paged_prefill_attention(
                q_, kc_, vc_, k_, v_, layer_, pt_, hl_, cl_,
                scale_dim=scale_dim, interpret=interpret, mesh=None,
                k_scale=scales[0] if scales else None,
                v_scale=scales[1] if scales else None,
            )

        in_specs = [
            P(None, None, "tp", None),
            P(None, None, "tp", None),
            P(None, None, "tp", None),
            P(None, None, None, "tp", None),
            P(None, None, None, "tp", None),
            P(), P(), P(), P(),
        ]
        args = [q, k_cur, v_cur, k_cache, v_cache, layer, page_tables,
                hist_lens, cur_lens]
        if quantized:
            in_specs += [P(None, None, "tp", None), P(None, None, "tp", None)]
            args += [k_scale, v_scale]
        fn = jax.shard_map(
            sharded,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(None, None, "tp", None),
            check_vma=False,
        )
        return fn(*args)

    b, t, hq, d = q.shape
    hkv, s = k_cache.shape[3], k_cache.shape[2]
    bq = BLOCK
    tp = -(-t // bq) * bq
    if tp != t:
        qpad = ((0, 0), (0, tp - t), (0, 0), (0, 0))
        q = jnp.pad(q, qpad)
        k_cur = jnp.pad(k_cur, qpad)  # BQ-aligned key blocks for the
        v_cur = jnp.pad(v_cur, qpad)  # frontier loop (cur masks the tail)

    in_specs = [
        pl.BlockSpec(
            (1, bq, hq, d),
            lambda bi, qi, li, pt, hl, cl: (bi, qi, 0, 0),
        ),
        pl.BlockSpec(
            (1, tp, hkv, d),
            lambda bi, qi, li, pt, hl, cl: (bi, 0, 0, 0),
        ),
        pl.BlockSpec(
            (1, tp, hkv, d),
            lambda bi, qi, li, pt, hl, cl: (bi, 0, 0, 0),
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch_shapes = [
        pltpu.VMEM((2, s, hkv, d), k_cache.dtype),
        pltpu.VMEM((2, s, hkv, d), v_cache.dtype),
    ]
    operands = [q, k_cur, v_cur, k_cache, v_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch_shapes += [
            pltpu.VMEM((2, *k_scale.shape[2:]), jnp.float32),
            pltpu.VMEM((2, *v_scale.shape[2:]), jnp.float32),
        ]
        operands += [k_scale, v_scale]
    scratch_shapes.append(
        pltpu.SemaphoreType.DMA((4 if quantized else 2, 2))
    )

    grid = (b, tp // bq)
    out = pl.pallas_call(
        functools.partial(
            _hist_kernel,
            page_size=s,
            scale_dim=scale_dim or d,
            num_kv_heads=hkv,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, bq, hq, d),
                lambda bi, qi, li, pt, hl, cl: (bi, qi, 0, 0),
            ),
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((b, tp, hq, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
        # the static kv-head unroll holds per-head f32 accumulators; at
        # llama3 shapes (Hkv=8, G=4, BQ=128, D=128) that is ~19MB of
        # scoped VMEM — above Mosaic's 16MB default, well under v5e's 128MB
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        page_tables.astype(jnp.int32),
        hist_lens.astype(jnp.int32),
        cur_lens.astype(jnp.int32),
        *operands,
    )
    return out[:, :t]


def flash_prefill_attention(
    q: jax.Array,  # [B, T, Hq, D] post-rope (D may be lane-padded)
    k: jax.Array,  # [B, T, Hkv, D] post-rope
    v: jax.Array,  # [B, T, Hkv, D]
    valid_len: jax.Array,  # [B] int32 — contiguous valid prefix length
    *,
    scale_dim: int | None = None,
    interpret: bool | None = None,
    mesh=None,
) -> jax.Array:
    """Causal flash attention over one prefill chunk. Returns
    [B, T, Hq, D]; rows at positions >= valid_len are unspecified (the
    engine ignores them, same contract as the XLA fallback).

    `interpret` defaults to True off-TPU so tests run the kernel on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from functools import partial

        from jax.sharding import PartitionSpec as P

        fn = jax.shard_map(
            partial(
                flash_prefill_attention,
                scale_dim=scale_dim, interpret=interpret, mesh=None,
            ),
            mesh=mesh,
            in_specs=(
                P(None, None, "tp", None),
                P(None, None, "tp", None),
                P(None, None, "tp", None),
                P(),
            ),
            out_specs=P(None, None, "tp", None),
            check_vma=False,
        )
        return fn(q, k, v, valid_len)

    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    tp = -(-t // BLOCK) * BLOCK
    if tp != t:
        pad = ((0, 0), (0, tp - t), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # head-major layouts: q [B, Hkv, G, T, D] (the g heads of a kv group
    # are adjacent because Hq ordering is group-major), k/v [B, Hkv, T, D]
    qh = q.reshape(b, tp, hkv, g, d).transpose(0, 2, 3, 1, 4)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    grid = (b, hkv, tp // BLOCK)
    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, scale_dim=scale_dim or d, block=BLOCK
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, g, BLOCK, d),
                    lambda bi, hi, qi, ln: (bi, hi, 0, qi, 0),
                ),
                pl.BlockSpec(
                    (1, 1, tp, d), lambda bi, hi, qi, ln: (bi, hi, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, tp, d), lambda bi, hi, qi, ln: (bi, hi, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, BLOCK, d),
                lambda bi, hi, qi, ln: (bi, hi, 0, qi, 0),
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, tp, d), q.dtype),
        interpret=interpret,
        name="flash_prefill_attention",
    )(valid_len.astype(jnp.int32), qh, kh, vh)
    # back to [B, T, Hq, D]
    out = out.transpose(0, 3, 1, 2, 4)
    return out.reshape(b, tp, hq, d)[:, :t]
