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

Three kernels: `flash_prefill_attention` (a first chunk: no history),
`paged_prefill_attention` (a GQA chunk over paged history and itself) and
`latent_prefill_attention` (the same for a latent cache, models/mla.py:
one row a token that is key and value at once, a rope key beside it, bf16
operands on the MXU, a block of pages a turn; a body of its own at the
end of the file, since those needs conflict with the GQA body's). After
them `latent_plain_attention` (a latent cache attended in the PLAIN form,
keys and values up-projected a block at a time in VMEM), the two banded
kernels by position (`window_prefill_attention`, `ring_prefill_attention`)
and `gather_pages`.

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


# ---------------------------------------------------------------------------
# A latent cache (models/mla.py): one row a token that is key AND value
# ---------------------------------------------------------------------------

#: history pages one turn of the latent kernel takes (512 keys at S = 64:
#: a block as wide as the MXU's pass over it is long, PR 25's finding)
LATENT_BLOCK_PAGES = 8
#: chunk rows of one grid cell; the heads fold into its rows (x H)
LATENT_BLOCK_Q = 128
#: chunk keys of one turn over the chunk itself
LATENT_BLOCK_CUR = 256
_MASKED = -1e30  # finite: a padded query row stays NaN-free


def _latent_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    pt_ref,  # [B, MP] int32 page tables (SMEM)
    hist_ref,  # [B] int32: tokens already in the cache (chunk start)
    cur_ref,  # [B] int32: valid tokens in THIS chunk
    # inputs
    ql_ref,  # [1, H, BQ, C] VMEM: the absorbed queries, scaled
    qp_ref,  # [1, H, BQ, R] VMEM: their rope part, scaled, zeros past it
    lcur_ref,  # [1, T, C] VMEM: this chunk's latent rows (staged)
    rcur_ref,  # [1, T, R] VMEM: this chunk's rope keys as cached
    # then (positional; `masked` adds the two in brackets):
    #   [mh_ref]  # [1, BQ, MPP * S] VMEM int32: query x cached key
    #   [mo_ref]  # [1, BQ, T] VMEM int32: query x chunk key
    #   lat_hbm,  # [L, P, S, C] ANY: the latent pool
    #   rope_hbm,  # [L, P, S, R] ANY: the rope-key pool
    # output
    #   o_ref,  # [1, H, BQ, C] in the queries' dtype
    # scratch
    #   lat_scr,  # [2, PB*S, C] VMEM: a slot is a block of pages
    #   rope_scr,  # [2, PB*S, R]
    #   m_scr,  # [H*BQ, 128] f32 running max (every lane the same)
    #   l_scr,  # [H*BQ, 128] f32 running denominator
    #   acc_scr,  # [H*BQ, C] f32
    #   sem,  # [2, 2] DMA semaphores: [plane, slot]
    *refs,
    page_size: int,
    block_pages: int,
    block_cur: int,
    masked: bool = False,
):
    mh_ref = mo_ref = None
    if masked:
        mh_ref, mo_ref, *refs = refs
    (lat_hbm, rope_hbm, o_ref, lat_scr, rope_scr, m_scr, l_scr, acc_scr,
     sem) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    li = layer_ref[0]
    hn, bq, c = ql_ref.shape[1], ql_ref.shape[2], ql_ref.shape[3]
    t = lcur_ref.shape[1]
    s, pb, mp = page_size, block_pages, pt_ref.shape[1]
    rows = hn * bq
    hist = hist_ref[b]
    cur = cur_ref[b]
    n_blk = pl.cdiv(hist, pb * s)
    mxu = lat_scr.dtype  # the pool's dtype is the model's: no cast to f32

    def copies(slot, blk):
        """The DMAs of history block `blk` into `slot`, one a page and
        plane. A block's last pages may lie past the row's history: they
        fetch whatever page the table names there (clamped to its width)
        and their keys are masked, so every turn moves `pb` pages and a
        wait is its start's twin."""
        out = []
        for p in range(pb):
            page = pt_ref[b, jnp.minimum(blk * pb + p, mp - 1)]
            for pi, (src, dst) in enumerate(
                ((lat_hbm, lat_scr), (rope_hbm, rope_scr))
            ):
                out.append(pltpu.make_async_copy(
                    src.at[li, page],
                    dst.at[slot, pl.ds(p * s, s)],
                    sem.at[pi, slot],
                ))
        return out

    @pl.when(n_blk > 0)  # the first block lands under the chunk's own turns
    def _():
        for cp in copies(0, 0):
            cp.start()

    ql = ql_ref[0].reshape(rows, c)
    qp = qp_ref[0].reshape(rows, qp_ref.shape[3])
    # chunk-relative index of each folded row's query; built 2D via rem
    # (see _prefill_kernel's row_pos note)
    row_rel = qi * bq + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), bq
    )

    def fold(lat, rope, mask, first=False, keep=None):
        """One turn of the online softmax over keys `lat` [K, C] (they are
        the values too) and `rope` [K, R]; `first` starts the state;
        `keep` [BQ, K] bool masks further, a query token at a time."""
        sc = jax.lax.dot_general(
            ql, lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qp, rope, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H*BQ, K]
        sc = jnp.where(mask, sc, _MASKED)
        if keep is not None:  # one set a query token, shared by its heads
            sc = jnp.where(
                keep[None], sc.reshape(hn, bq, -1), _MASKED
            ).reshape(rows, -1)
        m_cur = jnp.max(sc, axis=1, keepdims=True)
        m_new = m_cur if first else jnp.maximum(m_scr[:, :1], m_cur)
        p = jnp.exp(sc - m_new)
        l_new = jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(mxu), lat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H*BQ, C]
        if not first:
            corr = jnp.exp(m_scr[:, :1] - m_new)
            l_new += corr * l_scr[:, :1]
            pv += corr * acc_scr[...]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = pv

    # -- the chunk over itself: causal by position, padding masked, key
    # blocks wholly above this cell's diagonal skipped ------------------------
    for j in range(t // block_cur):
        def turn(j=j):
            at = pl.ds(j * block_cur, block_cur)
            key = j * block_cur + jax.lax.broadcasted_iota(
                jnp.int32, (block_cur, 1), 0
            )
            # rows past `cur` may hold anything: as values a zero weight
            # does not silence a NaN, so they go in as zeros
            lat = jnp.where(key < cur, lcur_ref[0, at, :], 0).astype(mxu)
            col = j * block_cur + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_cur), 1
            )
            fold(
                lat, rcur_ref[0, at, :],
                (col <= row_rel) & (col < cur), first=j == 0,
                keep=None if mo_ref is None else mo_ref[0, :, at] != 0,
            )

        if j == 0:
            turn()
        else:
            pl.when(j * block_cur < (qi + 1) * bq)(turn)

    # -- the history: every key lies before the chunk, the last block's
    # tail past `hist` masked ------------------------------------------------
    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            for cp in copies(1 - slot, i + 1):
                cp.start()

        for cp in copies(slot, i):
            cp.wait()
        key_pos = i * (pb * s) + jax.lax.broadcasted_iota(
            jnp.int32, (1, pb * s), 1
        )
        keep = None
        if mh_ref is not None:
            at = pl.ds(pl.multiple_of(i * (pb * s), pb * s), pb * s)
            keep = mh_ref[0, :, at] != 0
        fold(lat_scr[slot], rope_scr[slot], key_pos < hist, keep=keep)
        return 0

    jax.lax.fori_loop(0, n_blk, body, 0)
    # (a query none of whose keys is chosen has no sum: only padding rows)
    inv = 1.0 / (jnp.maximum(l_scr[:, :1], 1e-30) if masked else l_scr[:, :1])
    o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype).reshape(hn, bq, c)


def latent_prefill_attention(
    q_lat: jax.Array,  # [B, T, H, C] absorbed queries, SCALED, model dtype
    q_pe: jax.Array,  # [B, T, H, R] their rope part, scaled, zeros past it
    lat_cur: jax.Array,  # [B, T, C] this chunk's latent rows
    rope_cur: jax.Array,  # [B, T, R] this chunk's rope keys as cached
    k_cache: jax.Array,  # [L, P, S, 1, C] the latent pool (history)
    v_cache: jax.Array,  # [L, P, S, 1, R] the rope-key pool
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, MP] int32
    hist_lens: jax.Array,  # [B] int32: tokens already written to pages
    cur_lens: jax.Array,  # [B] int32: valid tokens in this chunk
    *,
    chosen: jax.Array | None = None,  # [B, T, MP * S] bool, by position
    interpret: bool | None = None,
    mesh=None,
) -> jax.Array:
    """A prefill chunk of a latent-cache model (models/mla.py, absorbed
    form) over its paged history and over itself, one online softmax:
    `softmax(q_lat . latent + q_pe . rope_key) . latent` with one latent
    row (key and value at once) and one rope key a token. The H heads fold
    into the tile's rows ([H x BQ, C + R] against [K, C + R]: one KV
    "head", a group of H); history pages arrive `LATENT_BLOCK_PAGES` a
    turn by double-buffered DMA from the stacked pools, `layer` a
    prefetched scalar; operands reach the MXU in the dtype they come in,
    scores, softmax, sums and the accumulator are float32 and live in
    VMEM a tile at a time. A history of 0 runs no turn.

    `chosen` names the keys each query attends, by position: every history
    page is still read and scored, and a key not chosen is masked, as
    `ops/sparse_chunk.token_chunk_attention` does for GQA pools; one set a
    query token, shared by the tile's heads. Who still hands it one
    (models/dots3.py): a window layer's SHORT piece (a 32-token tail, the
    ramp's short prompts: `window_attend`, the window as a mask over the
    slot's ring pages) and `full_attend` with T > 1, which only the
    benchmark's reference and the tests call since a full layer's prompt
    piece attends in the plain form (`latent_plain_attention` below).

    Returns o_lat [B, T, H, C] in the queries' dtype (the float32
    quotient rounded once, on the way out: what the caller's value
    up-projection takes); rows past cur_lens are unspecified, and what
    rows past cur_lens hold on the way in reaches no other row.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if chosen is not None and mesh is not None:
        raise ValueError("a latent chunk under chosen keys runs on one chip")
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        # heads are independent; the one-row cache replicates over tp
        heads = P(None, None, "tp", None)
        fn = jax.shard_map(
            functools.partial(
                latent_prefill_attention, interpret=interpret, mesh=None
            ),
            mesh=mesh,
            in_specs=(heads, heads) + (P(),) * 8,
            out_specs=heads,
            check_vma=False,
        )
        return fn(q_lat, q_pe, lat_cur, rope_cur, k_cache, v_cache, layer,
                  page_tables, hist_lens, cur_lens)

    b, t, hn, c = q_lat.shape
    s, r = k_cache.shape[2], v_cache.shape[4]
    if k_cache.shape[3] != 1 or (
        q_pe.shape[-1], lat_cur.shape[-1], rope_cur.shape[-1]
    ) != (r, c, r):
        raise ValueError(
            "a latent chunk takes a one-row cache and rows as it caches "
            f"them; got pools {k_cache.shape} / {v_cache.shape}, rows "
            f"{lat_cur.shape} / {rope_cur.shape}, q_pe {q_pe.shape}"
        )
    # a tile's [H x BQ, .] rows stay what 16 heads of LATENT_BLOCK_Q make;
    # under `chosen` (64-128 heads of a 512-1,024-wide latent) a tile's
    # accumulator stays 2**19 numbers: Mosaic unrolls a tile's dots into
    # MXU passes, and a [2048, 1024] tile took the TPU's compiler 12 s a
    # step program (PERF.md 6, PR 48)
    bq = min(LATENT_BLOCK_Q, t, max(8, 16 * LATENT_BLOCK_Q // hn)) if (
        chosen is None) else min(t, max(8, (1 << 19) // (hn * c)))
    tp = -(-t // bq) * bq
    pb = min(LATENT_BLOCK_PAGES, page_tables.shape[1])
    if tp != t:  # whole query blocks; `cur_lens` masks the tail
        q_lat, q_pe, lat_cur, rope_cur = (
            jnp.pad(x, ((0, 0), (0, tp - t)) + ((0, 0),) * (x.ndim - 2))
            for x in (q_lat, q_pe, lat_cur, rope_cur)
        )
    # head-major queries: a cell's [H, BQ, .] block is its folded tile
    ql = q_lat.transpose(0, 2, 1, 3)
    qp = q_pe.transpose(0, 2, 1, 3)
    # (under `chosen` the chunk over itself is ONE turn: a fold less)
    block_cur = math.gcd(tp, LATENT_BLOCK_CUR) if chosen is None else tp

    def q_block(width):
        return pl.BlockSpec(
            (1, hn, bq, width), lambda bi, qi, li, pt, hl, cl: (bi, 0, qi, 0)
        )

    def chunk_block(width):
        return pl.BlockSpec(
            (1, tp, width), lambda bi, qi, li, pt, hl, cl: (bi, 0, 0)
        )

    masks, mask_specs = [], []
    if chosen is not None:
        mp = page_tables.shape[1]
        mpp = -(-mp // pb) * pb
        pos = jnp.arange(mp * s, dtype=jnp.int32)[None, None]
        live = chosen & (
            jnp.arange(t, dtype=jnp.int32)[None] < cur_lens[:, None]
        )[..., None]
        # the chunk's own keys: columns `hist` .. `hist + T` of the mask
        own = jax.vmap(lambda m, h: jax.lax.dynamic_slice_in_dim(
            jnp.pad(m, ((0, 0), (0, t))), h, t, axis=1))(live, hist_lens)
        pad_t = ((0, 0), (0, tp - t))
        masks = [
            jnp.pad((live & (pos < hist_lens[:, None, None])).astype(
                jnp.int32), pad_t + ((0, (mpp - mp) * s),)),
            jnp.pad(own.astype(jnp.int32), pad_t + ((0, tp - t),)),
        ]
        mask_specs = [
            pl.BlockSpec((1, bq, width),
                         lambda bi, qi, li, pt, hl, cl: (bi, qi, 0))
            for width in (mpp * s, tp)
        ]

    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, page_size=s, block_pages=pb, block_cur=block_cur,
            masked=chosen is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, tp // bq),
            in_specs=[
                q_block(c), q_block(r), chunk_block(c), chunk_block(r),
                *mask_specs,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_block(c),
            scratch_shapes=[
                pltpu.VMEM((2, pb * s, c), k_cache.dtype),
                pltpu.VMEM((2, pb * s, r), v_cache.dtype),
                pltpu.VMEM((hn * bq, 128), jnp.float32),
                pltpu.VMEM((hn * bq, 128), jnp.float32),
                pltpu.VMEM((hn * bq, c), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hn, tp, c), q_lat.dtype),
        interpret=interpret,
        name="latent_prefill_attention",
        # a [16 x 128, 512] tile holds 4 MB of accumulator, as much again
        # of scores and of weights, and its blocks twice: ~45 MB at
        # DeepSeek-V2-Lite's widths, of v5e's 128. The limit assumes a
        # chip with 128 MB of VMEM (v5e, v6e), as `paged_prefill_
        # attention`'s above does: one with less refuses it at compile
        # time, and wants a smaller LATENT_BLOCK_Q and a limit of its own
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        page_tables.astype(jnp.int32),
        hist_lens.astype(jnp.int32),
        cur_lens.astype(jnp.int32),
        ql, qp, lat_cur, rope_cur, *masks,
        # a page as [S, C] rows: the same bytes
        k_cache.reshape(*k_cache.shape[:3], c),
        v_cache.reshape(*v_cache.shape[:3], r),
    )
    return out[:, :, :t].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# A latent cache attended in the PLAIN form (models/dots3.py: a full layer's
# prompt piece), its keys and values up-projected a block at a time in VMEM
# ---------------------------------------------------------------------------

#: history pages one turn takes (1,024 keys at S = 64). A turn's fixed work
#: (a head's running state read and written, the lane reductions, the
#: weights' tiles loaded into the MXU for 1,024 latent rows and not 512)
#: halves with its length: a layer's block at one 512-token piece over
#: 8,192 tokens read 11.3 / 6.3 / 5.7 / 5.7 ms at 4 / 8 / 16 / 32 pages
#: (PERF.md 6, PR 53); the last block's tail past the history is wasted
PLAIN_BLOCK_PAGES = 16
#: heads of one grid cell: a block of latent rows lands once for them all
#: (4, 8 and 16 read the same)
PLAIN_HEADS = 8
#: queries of one softmax pass: a head's [T, keys] scores are taken 128
#: rows at a time, the next rows' product issued before this pass's
#: vector work, so a [128, 1024] float32 tile is live and not [512, 1024]
#: (7.9 -> 6.3 ms a layer's block at blocks of 8 pages; 64 rows read 7.2).
#: Two or four heads a turn of the head loop, their chains side by side,
#: gained 0.4-1 % over one and cost 2.3 s of a step program's compile
PLAIN_ROWS = 128


def _plain_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    pt_ref,  # [B, MP] int32 page tables (SMEM)
    hist_ref,  # [B] int32: tokens already in the cache (the piece's start)
    cur_ref,  # [B] int32: valid tokens in THIS piece
    # inputs
    q_ref,  # [1, T, G x (n + R)] VMEM: G heads' queries as projected, scaled
    w_ref,  # [C, G x (n + v)] VMEM: those heads' columns of `wkv_b`
    lcur_ref,  # [1, T, C] VMEM: the piece's latent rows, zeros past `cur`
    rcur_ref,  # [1, T, R] VMEM: its rope keys as cached, zeros past `cur`
    mo_ref,  # [1, T, T] VMEM int8: query x the piece's own key
    mh_hbm,  # [B, T, NB x K] ANY int8: query x cached key, by position
    lat_hbm,  # [L, P, S, C] ANY: the latent pool
    rope_hbm,  # [L, P, S, R] ANY: the rope-key pool
    # output
    o_ref,  # [1, T, G x v] float32
    # scratch
    lat_scr,  # [2, K, C] VMEM: a slot is a block of pages
    rope_scr,  # [2, K, R]
    mh_scr,  # [2, T, K] int8: the block's columns of the mask
    bias_scr,  # [T, max(K, T)] f32: 0 where the turn's key is attended
    k_scr,  # [max(K, T), n + R]: a head's keys, the rope key beside them
    m_scr,  # [G, T, 128] f32 running max (every lane the same)
    l_scr,  # [G, T, 128] f32 running denominator
    acc_scr,  # [G, T, v] f32
    sem,  # [3, 2] DMA semaphores: [plane, slot]
    *,
    page_size: int,
    block_pages: int,
    nope: int,
    step: int,
):
    b = pl.program_id(1)
    li = layer_ref[0]
    t = lcur_ref.shape[1]
    n, g, v = nope, acc_scr.shape[0], acc_scr.shape[2]
    qd, wd = q_ref.shape[2] // g, w_ref.shape[1] // g
    s, pb, mp = page_size, block_pages, pt_ref.shape[1]
    kb = pb * s
    hist = hist_ref[b]
    cur = cur_ref[b]
    n_blk = pl.cdiv(hist, kb)
    mxu = lat_scr.dtype  # the pool's dtype is the model's: no cast to f32

    def copies(slot, blk):
        """The DMAs of history block `blk` into `slot`: one a page and
        plane (`_latent_kernel`'s: pages past the row's history fetch
        whatever the table names there, their keys masked) and the
        block's columns of the mask."""
        out = []
        for p in range(pb):
            page = pt_ref[b, jnp.minimum(blk * pb + p, mp - 1)]
            for pi, (src, dst) in enumerate(
                ((lat_hbm, lat_scr), (rope_hbm, rope_scr))
            ):
                out.append(pltpu.make_async_copy(
                    src.at[li, page],
                    dst.at[slot, pl.ds(p * s, s)],
                    sem.at[pi, slot],
                ))
        out.append(pltpu.make_async_copy(
            mh_hbm.at[b, :, pl.ds(pl.multiple_of(blk * kb, kb), kb)],
            mh_scr.at[slot], sem.at[2, slot],
        ))
        return out

    @pl.when(n_blk > 0)  # the first block lands under the piece's own turn
    def _():
        for cp in copies(0, 0):
            cp.start()

    def turn(lat, rope, keys: int, first=False):
        """One turn of every head's online softmax over `keys` latent rows
        `lat()` [keys, C] and rope keys `rope()` [keys, R], under the bias
        in `bias_scr[:, :keys]`: a head's keys and values come out of ONE
        product with its columns of `wkv_b` (float32 sums rounded once to
        the pool's dtype), the one rope key a token beside every head's
        keys; then, `step` queries at a time, `q_h K_h^T`, the softmax
        turn, `p V_h`. A loop over the heads, so that a turn's body is
        compiled once; `first` starts the state."""
        k_scr[:keys, n:] = rope()

        def head(h, _):
            kv = jax.lax.dot_general(
                lat(), w_ref[:, pl.ds(pl.multiple_of(h * wd, wd), wd)],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [keys, n + v]
            k_scr[:keys, :n] = kv[:, :n].astype(mxu)
            vh = kv[:, n:].astype(mxu)
            at = pl.ds(pl.multiple_of(h * qd, qd), qd)

            def scores(c):
                rows = pl.ds(c * step, step)
                return jax.lax.dot_general(
                    q_ref[0, rows, at], k_scr[:keys],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) + bias_scr[rows, :keys]  # [step, keys]

            ahead = scores(0)
            for c in range(t // step):
                rows = pl.ds(c * step, step)
                sc = ahead
                if c + 1 < t // step:
                    ahead = scores(c + 1)
                m_cur = jnp.max(sc, axis=1, keepdims=True)
                m_new = m_cur if first else jnp.maximum(
                    m_scr[h, rows, :1], m_cur)
                p = jnp.exp(sc - m_new)
                l_new = jnp.sum(p, axis=1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(mxu), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [step, v]
                if not first:
                    corr = jnp.exp(m_scr[h, rows, :1] - m_new)
                    l_new += corr * l_scr[h, rows, :1]
                    pv += corr * acc_scr[h, rows]
                m_scr[h, rows] = jnp.broadcast_to(
                    m_new, (step, m_scr.shape[-1]))
                l_scr[h, rows] = jnp.broadcast_to(
                    l_new, (step, l_scr.shape[-1]))
                acc_scr[h, rows] = pv
            return 0

        jax.lax.fori_loop(0, g, head, 0)

    # -- the piece over itself: causal by position, padding masked ----------
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    bias_scr[:, :t] = jnp.where(
        (mo_ref[0].astype(jnp.int32) != 0) & (col <= row) & (col < cur),
        0.0, _MASKED)
    turn(lambda: lcur_ref[0], lambda: rcur_ref[0], t, first=True)

    # -- the history: every key lies before the piece, the last block's
    # tail past `hist` masked ------------------------------------------------
    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            for cp in copies(1 - slot, i + 1):
                cp.start()

        for cp in copies(slot, i):
            cp.wait()
        key_pos = i * kb + jax.lax.broadcasted_iota(jnp.int32, (1, kb), 1)
        bias_scr[:, :kb] = jnp.where(
            (mh_scr[slot].astype(jnp.int32) != 0) & (key_pos < hist),
            0.0, _MASKED)
        turn(lambda: lat_scr[slot], lambda: rope_scr[slot], kb)
        return 0

    jax.lax.fori_loop(0, n_blk, body, 0)
    # (a query none of whose keys is chosen has no sum: only padding rows)
    for h in range(g):
        o_ref[0, :, h * v:(h + 1) * v] = acc_scr[h] / jnp.maximum(
            l_scr[h, :, :1], 1e-30)


def latent_plain_attention(
    q: jax.Array,  # [B, T, H, n + R] queries as projected, SCALED: the nope
    # part, then the rope part in the cached rope key's columns (zeros past)
    wkv_b: jax.Array,  # [C, H, n + v]: W_UK | W_UV a head
    lat_cur: jax.Array,  # [B, T, C] this piece's latent rows
    rope_cur: jax.Array,  # [B, T, R] this piece's rope keys as cached
    k_cache: jax.Array,  # [L, P, S, 1, C] the latent pool (history)
    v_cache: jax.Array,  # [L, P, S, 1, R] the rope-key pool
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, MP] int32
    hist_lens: jax.Array,  # [B] int32: tokens already written to pages
    cur_lens: jax.Array,  # [B] int32: valid tokens in this piece
    chosen: jax.Array,  # [B, T, MP * S] bool, by position
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """A prompt piece of a latent-cache model over its paged history and
    over itself in the PLAIN form, as latent models prefill: `softmax(q_h .
    (W_UK,h c | k_r)) . W_UV,h c` a head, under the keys `chosen` names a
    query (by position: a learned indexer's choice, models/dots3.py; one
    set a query token, shared by its heads; every history page is still
    read and scored, a key not chosen masked). The cache holds latent rows,
    and K and V of 128 heads over 8k-18k keys are ~1 GB a layer and piece,
    so the up-projection happens HERE: a grid cell is (a row's piece,
    `PLAIN_HEADS` heads) with ALL the piece's queries, a turn takes one
    block of `PLAIN_BLOCK_PAGES` history pages (double-buffered DMA from
    the stacked pools with the block's columns of the int8 mask, `layer`
    and the page tables prefetched; `_latent_kernel`'s page discipline)
    and per head ONE product of the block with the head's columns of
    `wkv_b` gives its keys and values in VMEM (float32 sums rounded once
    to the pool's dtype), shared by the piece's T queries: 2 x (n + R + v)
    FLOP a (query, key, head) and 2 x C x (n + v) / T for the up-projection
    where the absorbed form (`latent_prefill_attention`) costs 2 x (2 C +
    R) and needs a query and an output of the latent's width. The piece's
    own rows, in hand and not cached yet, are the first turn. Operands
    reach the MXU in the pool's dtype; scores, softmax, sums and the
    accumulator are float32 in VMEM. No query and no output of the latent's
    width exists.

    Returns [B, T, H, v] float32; rows past cur_lens are unspecified, and
    what rows past cur_lens hold on the way in reaches no other row.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, hn, qd = q.shape
    c, nv = wkv_b.shape[0], wkv_b.shape[2]
    s, r = k_cache.shape[2], v_cache.shape[4]
    n = qd - r
    v = nv - n
    mp = page_tables.shape[1]
    if k_cache.shape[3] != 1 or (
        lat_cur.shape[-1], rope_cur.shape[-1], wkv_b.shape[1]
    ) != (c, r, hn) or chosen.shape != (b, t, mp * s):
        raise ValueError(
            "a plain latent piece takes a one-row cache, rows as it caches "
            f"them and a key mask by position; got pools {k_cache.shape} / "
            f"{v_cache.shape}, rows {lat_cur.shape} / {rope_cur.shape}, q "
            f"{q.shape}, wkv_b {wkv_b.shape}, chosen {chosen.shape}"
        )
    g = math.gcd(hn, PLAIN_HEADS)
    pb = min(PLAIN_BLOCK_PAGES, mp)
    kb = pb * s
    wide = max(kb, t)
    # rows past `cur` may hold anything: as keys and values a zero weight
    # does not silence a NaN, so they go in as zeros
    live = (jnp.arange(t, dtype=jnp.int32)[None]
            < cur_lens[:, None])[..., None]
    lat_cur = jnp.where(live, lat_cur, 0).astype(k_cache.dtype)
    rope_cur = jnp.where(live, rope_cur, 0).astype(v_cache.dtype)
    # the piece's own keys: columns `hist` .. `hist + T` of the mask
    own = jax.vmap(lambda m, h: jax.lax.dynamic_slice_in_dim(
        jnp.pad(m, ((0, 0), (0, t))), h, t, axis=1))(chosen, hist_lens)
    mh = jnp.pad(chosen.astype(jnp.int8),
                 ((0, 0), (0, 0), (0, -(mp * s) % kb)))

    def heads(width):
        return pl.BlockSpec(
            (1, t, g * width), lambda hi, bi, li, pt, hl, cl: (bi, 0, hi))

    def piece(width):
        return pl.BlockSpec(
            (1, t, width), lambda hi, bi, li, pt, hl, cl: (bi, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _plain_kernel, page_size=s, block_pages=pb, nope=n,
            step=math.gcd(t, PLAIN_ROWS)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # (a group's columns of `wkv_b` fetched once for all the rows)
            grid=(hn // g, b),
            in_specs=[
                heads(qd),
                pl.BlockSpec((c, g * nv),
                             lambda hi, bi, li, pt, hl, cl: (0, hi)),
                piece(c), piece(r), piece(t),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=heads(v),
            scratch_shapes=[
                pltpu.VMEM((2, kb, c), k_cache.dtype),
                pltpu.VMEM((2, kb, r), v_cache.dtype),
                pltpu.VMEM((2, t, kb), jnp.int8),
                pltpu.VMEM((t, wide), jnp.float32),
                pltpu.VMEM((wide, qd), k_cache.dtype),
                pltpu.VMEM((g, t, 128), jnp.float32),
                pltpu.VMEM((g, t, 128), jnp.float32),
                pltpu.VMEM((g, t, v), jnp.float32),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, hn * v), jnp.float32),
        interpret=interpret,
        name="latent_plain_attention",
        # at 512 queries, 8 heads of 128 | 128 | 128 and blocks of 1,024
        # keys of a 512-wide latent: the cell's queries, weights and output
        # twice (the pipeline's two buffers) 12 MB, the heads' running
        # state 6, a turn's blocks, masks, bias and keys 7, a head's
        # float32 keys | values 1 and a pass's scores and weights 0.5 each
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        page_tables.astype(jnp.int32),
        hist_lens.astype(jnp.int32),
        cur_lens.astype(jnp.int32),
        q.reshape(b, t, hn * qd), wkv_b.reshape(c, hn * nv), lat_cur,
        rope_cur, own.astype(jnp.int8), mh,
        # a page as [S, C] rows: the same bytes
        k_cache.reshape(*k_cache.shape[:3], c),
        v_cache.reshape(*v_cache.shape[:3], r),
    )
    return out.reshape(b, t, hn, v)


# ---------------------------------------------------------------------------
# A band by position (models/dots3.py: a window layer's prompt piece)
# ---------------------------------------------------------------------------


def _window_kernel(
    q_ref,  # [1, BQ, G x D]: G heads' queries, scaled
    k_ref,  # [1, K, G x D]: those heads' keys
    v_ref,  # [1, K, G x Dv]: their values
    qpos_ref,  # [1, BQ, 1] int32: the queries' positions
    kpos_ref,  # [1, 1, K] int32: the keys' positions; negative: no key
    o_ref,  # [1, BQ, G x Dv]
    *,
    window: int,
    width: int,
    heads: int,
):
    bq = o_ref.shape[1]
    d, dv = q_ref.shape[2] // heads, o_ref.shape[2] // heads
    # (one tile takes every column: its first is 0, whatever BQ)
    cols = pl.ds(pl.multiple_of(pl.program_id(2) * bq, 128)
                 if k_ref.shape[1] > width else 0, width)
    at = qpos_ref[0]  # [BQ, 1]
    key = kpos_ref[0, :, cols]  # [1, W]
    keep = (key >= 0) & (key <= at) & (key >= at - (window - 1))  # [BQ, W]
    # the heads' chains are independent: the scheduler lays one head's
    # softmax under another's MXU passes
    for g in range(heads):
        s = jax.lax.dot_general(
            q_ref[0, :, g * d:(g + 1) * d], k_ref[0, cols, g * d:(g + 1) * d],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [BQ, W]
        s = jnp.where(keep, s, _MASKED)
        p = jnp.where(
            keep, jnp.exp(s - jnp.max(s, axis=1, keepdims=True)), 0.0)
        v = v_ref[0, cols, g * dv:(g + 1) * dv]
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, :, g * dv:(g + 1) * dv] = (
            o / jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        ).astype(o_ref.dtype)


def window_prefill_attention(
    q: jax.Array,  # [B, T, H, D] queries, SCALED
    k: jax.Array,  # [B, K, H, D] keys
    v: jax.Array,  # [B, K, H, Dv]
    q_pos: jax.Array,  # [B, T] int32
    k_pos: jax.Array,  # [B, K] int32; negative: the row holds no key
    *,
    window: int,
    interpret: bool | None = None,
) -> jax.Array:
    """A prompt piece's attention under a sliding window stated by
    POSITION, its few keys in hand as heads (models/dots3.py
    `window_piece`: the ring rows in reach, then the piece's own T rows,
    then padding): a query at position t attends the keys whose position
    lies in `[t - (window - 1), t]`. The caller lays the keys out so that
    query j reaches no column before j and none after `K - T + j`; a grid
    cell, (row of the batch, 4 heads, tile of 256 queries), then takes the
    `K - T + 256` columns from its tile's first on as ONE tile (896 keys
    at the published 513 in a ring page of 64): one softmax, no chain of
    corrections, no mask array, the columns no query of the tile can reach
    not read. Heads are picked by the block index out of the arrays as they
    come, [.., H x D]: nothing is transposed. Operands reach the MXU in the
    dtype they come in; scores and softmax are float32 in VMEM.

    Returns [B, T, H, Dv] in the queries' dtype; a query with no key in its
    band (padding) gets zeros.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, hn, d = q.shape
    kk, dv = k.shape[1], v.shape[3]
    bq = 256 if t % 256 == 0 else t
    g = math.gcd(hn, 4)
    width = kk - t + bq

    def heads(rows, n):
        return pl.BlockSpec((1, rows, g * n), (
            (lambda bi, h, qi: (bi, qi, h)) if rows == bq
            else (lambda bi, h, qi: (bi, 0, h))))

    out = pl.pallas_call(
        functools.partial(_window_kernel, window=window, width=width,
                          heads=g),
        grid=(b, hn // g, t // bq),
        in_specs=[
            heads(bq, d), heads(kk, d), heads(kk, dv),
            pl.BlockSpec((1, bq, 1), lambda bi, h, qi: (bi, qi, 0)),
            pl.BlockSpec((1, 1, kk), lambda bi, h, qi: (bi, 0, 0)),
        ],
        out_specs=heads(bq, dv),
        out_shape=jax.ShapeDtypeStruct((b, t, hn * dv), q.dtype),
        interpret=interpret,
        name="window_prefill_attention",
        # a cell's keys and values stay in VMEM, twice (the pipeline's two
        # buffers): 7.1 MB at 1,152 keys of 4 x (256 + 128)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024
        ),
    )(
        q.reshape(b, t, hn * d), k.reshape(b, kk, hn * d),
        v.reshape(b, kk, hn * dv),
        q_pos.astype(jnp.int32)[..., None], k_pos.astype(jnp.int32)[:, None],
    )
    return out.reshape(b, t, hn, dv)


# ---------------------------------------------------------------------------
# A band by position over GQA rows (models/cohere2_moe.py: a window layer's
# prompt piece over its slot's ring and itself)
# ---------------------------------------------------------------------------

#: chunk queries of one grid cell; the G query heads of a KV head fold into
#: its rows (x G: 2,048 rows at 16 heads a KV head)
RING_BLOCK_Q = 128
#: ring rows one turn takes at most (a divisor of the ring's length)
RING_BLOCK_K = 512


def _ring_kernel(
    live_ref,  # [B, R / BK] int32 (scalar prefetch): a ring tile holds a key
    # in reach of the piece
    q_ref,  # [1, BQ, G x D]: the queries of one KV head's G heads, SCALED
    kcur_ref,  # [1, T, D]: that KV head's keys of the piece itself
    vcur_ref,  # [1, T, Dv]
    ring_k_ref,  # [1, 1, R, D]: the row's ring of that KV head
    ring_v_ref,  # [1, 1, R, Dv]
    qpos_ref,  # [1, BQ, 1] int32: the queries' positions
    rpos_ref,  # [1, 1, R] int32: the position a ring row holds; < 0: none
    cpos_ref,  # [1, 1, T] int32: the piece's positions; < 0: padding
    *rest,  # [sink_ref [1, G, 128] f32: a head's sink logit, every lane],
    # o_ref [1, BQ, G x Dv], then the scratch:
    # m_scr [G x BQ, 128] f32 running max (every lane the same),
    # l_scr [G x BQ, 128] f32 running denominator, acc_scr [G x BQ, Dv] f32
    window: int,
    block_k: int,
    banded: bool = False,
):
    sink_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    b = pl.program_id(0)
    qi = pl.program_id(2)
    bq, t = q_ref.shape[1], kcur_ref.shape[1]
    d, dv = kcur_ref.shape[2], vcur_ref.shape[2]
    g = q_ref.shape[2] // d
    # the G heads' tiles one under the other: one dot a key tile for all
    q = jnp.concatenate(
        [q_ref[0, :, i * d:(i + 1) * d] for i in range(g)], axis=0)
    at = jnp.concatenate([qpos_ref[0]] * g, axis=0)  # [G x BQ, 1]
    # the running softmax is STARTED here where a turn may not be the first
    # to run: under a sink at (its logit, 1, 0), which is the sink exactly (a
    # key of value 0 in every query's softmax); where own tiles BEFORE the
    # band are skipped (`banded`) at (masked, 0, 0)
    started = banded or sink_ref is not None
    if started:
        if sink_ref is None:
            m_scr[...] = jnp.full(m_scr.shape, _MASKED, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        else:
            m_scr[...] = jnp.concatenate([
                jnp.broadcast_to(sink_ref[0, i:i + 1, :], (bq, 128))
                for i in range(g)], axis=0)
            l_scr[...] = jnp.ones(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def fold(k, v, key, first=False):
        """One turn of the online softmax over the keys `k` [K, D] at the
        positions `key` [1, K] (negative: none) and their values `v`."""
        keep = (key >= 0) & (key <= at) & (key >= at - (window - 1))
        sc = jnp.where(keep, jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), _MASKED)  # [G x BQ, K]
        m_cur = jnp.max(sc, axis=1, keepdims=True)
        m_new = m_cur if first else jnp.maximum(m_scr[:, :1], m_cur)
        p = jnp.where(keep, jnp.exp(sc - m_new), 0.0)
        l_new = jnp.sum(p, axis=1, keepdims=True)
        # (a row that holds no key is what its slot's last owner left, or a
        # piece's padding: rows the model computed or the pool's zeros, so
        # finite, and a zero weight silences them)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [G x BQ, Dv]
        if not first:
            corr = jnp.exp(m_scr[:, :1] - m_new)
            l_new += corr * l_scr[:, :1]
            pv += corr * acc_scr[...]
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = pv

    # -- the piece over itself: key tiles wholly after this cell's queries
    # skipped (positions ascend along a piece), and under `banded` the tiles
    # wholly before the first query's window ----------------------------------
    back = -(-(window - 1) // bq)  # own tiles behind a query tile in reach
    for j in range(t // bq):
        def turn(j=j):
            rows = pl.ds(j * bq, bq)
            fold(kcur_ref[0, rows, :], vcur_ref[0, rows, :],
                 cpos_ref[0, :, rows], first=j == 0 and not started)

        if banded:
            pl.when((j <= qi) & (j >= qi - back))(turn)
        elif j == 0:
            turn()
        else:
            pl.when(j <= qi)(turn)

    # -- the ring: rows as they lie, a tile none of whose rows the piece's
    # first query reaches skipped; under `banded` every tile, for the query
    # tiles that stand a window or more past the piece's first token ----------
    def body(i, _):
        def turn():
            rows = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
            fold(ring_k_ref[0, 0, rows, :], ring_v_ref[0, 0, rows, :],
                 rpos_ref[0, :, rows])

        reach = live_ref[b, i] != 0
        if banded:  # a ring key lies before the piece's first position
            reach &= qi * bq <= window - 2
        pl.when(reach)(turn)
        return 0

    jax.lax.fori_loop(0, ring_k_ref.shape[2] // block_k, body, 0)
    out = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
    for i in range(g):
        o_ref[0, :, i * dv:(i + 1) * dv] = out[i * bq:(i + 1) * bq].astype(
            o_ref.dtype)


def _gather_kernel(layer_ref, pages_ref, pool_ref, out_ref, sem):
    """Every named page of layer `layer_ref[0]` into its place of the
    output, HBM to HBM: all copies out before any wait (the targets are
    disjoint)."""
    def copy(i):
        return pltpu.make_async_copy(
            pool_ref.at[layer_ref[0], pages_ref[i]], out_ref.at[i], sem)

    def start(i, _):
        copy(i).start()
        return 0

    def drain(i, _):
        copy(i).wait()
        return 0

    jax.lax.fori_loop(0, out_ref.shape[0], start, 0)
    jax.lax.fori_loop(0, out_ref.shape[0], drain, 0)


def gather_pages(pool: jax.Array, layer: jax.Array, pages: jax.Array,
                 *, use_kernel: bool | None = None) -> jax.Array:
    """The pages `pages` [n] of layer `layer` of a pool [L, P, S, Hkv, D],
    one after the other: [n x S, Hkv, D], a copy. As a kernel of whole-page
    DMAs (the TPU's default) the pool goes in as it lies: XLA's own gather,
    in a loop over the rows of a batch and followed by a transpose, was
    answered by the compiler with a transposed copy of the WHOLE pool
    outside the loop (1 GB each for the rings of models/cohere2_moe.py:
    the compile for the described v5e, PR 52)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    n, shape = pages.shape[0], pool.shape[2:]
    if not use_kernel:
        return jax.lax.dynamic_index_in_dim(
            pool, layer, 0, keepdims=False)[pages].reshape(
            n * shape[0], *shape[1:])
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA],
        ),
        out_shape=jax.ShapeDtypeStruct((n, *shape), pool.dtype),
        name="gather_pages",
    )(jnp.asarray(layer, jnp.int32).reshape(1), pages.astype(jnp.int32),
      pool)
    return out.reshape(n * shape[0], *shape[1:])


def ring_prefill_attention(
    q: jax.Array,  # [B, T, Hq, D] queries, SCALED
    k_cur: jax.Array,  # [B, T, Hkv, D] the piece's own keys
    v_cur: jax.Array,  # [B, T, Hkv, Dv]
    ring_k: jax.Array,  # [B, Hkv, R, D] each row's cached keys, by KV head
    ring_v: jax.Array,  # [B, Hkv, R, Dv]
    q_pos: jax.Array,  # [B, T] int32
    ring_pos: jax.Array,  # [B, R] int32: the position a row holds; < 0: none
    cur_pos: jax.Array,  # [B, T] int32, ascending; < 0: padding
    *,
    window: int,
    sinks: jax.Array | None = None,  # [Hq] f32: a sink logit a query head
    interpret: bool | None = None,
) -> jax.Array:
    """A prompt piece's attention under a sliding window stated by POSITION
    over GQA rows: the keys are the piece's own rows and its sequence's
    RING as the rows lie (a ring row holds the position `ring_pos` says, in
    no order: the band is a mask by position, not a range of columns), the
    ring handed in a KV head at a time. A query at position t attends the
    keys whose position lies in `[t - (window - 1), t]`. A grid cell is (row
    of the batch, KV head, tile of `RING_BLOCK_Q` queries): the G query
    heads that share the KV head fold into the tile's rows, so a key tile
    is read once for all of them; the band is walked as a CHAIN of key
    tiles under a running max and sum (4,608 ring rows are 9 tiles of 512),
    the piece's own tiles first, tiles after the cell's queries and ring
    tiles the piece's first query cannot reach skipped. Operands reach the
    MXU in the dtype they come in; scores and softmax are float32 in VMEM.

    The values may be narrower than the keys (`Dv` beside `D`:
    models/mimo_v2.py), and `sinks` adds one learned logit a query head to
    every query's softmax, a key of value 0: the running softmax starts at
    (the logit, 1, 0) where it starts at the first tile's without. Where the
    window is shorter than the piece (128 keys under a piece of 512) a query
    tile reaches the own tiles from `ceil((window - 1) / RING_BLOCK_Q)`
    behind it on, and the ring only where it stands less than a window past
    the piece's first token: the other turns are skipped. A window as long
    as the piece or longer, values as wide as the keys and no sink is the
    code it was.

    Returns [B, T, Hq, Dv] in the queries' dtype; a query with no key in its
    band (padding) gets zeros.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, hq, d = q.shape
    hkv, dv = k_cur.shape[2], v_cur.shape[3]
    g = hq // hkv
    r = ring_k.shape[2]
    bq = RING_BLOCK_Q if t % RING_BLOCK_Q == 0 else t
    # some query tile of the piece stands a whole own tile past its window
    banded = -(-(window - 1) // bq) < t // bq - 1
    bk = next(n for n in (RING_BLOCK_K, 256, 128, 64, 32, 16, 8, 4, 2, 1)
              if r % n == 0)
    first = jnp.min(jnp.where(cur_pos >= 0, cur_pos, 1 << 30), axis=1)
    live = jnp.any(
        ((ring_pos >= 0) & (ring_pos >= first[:, None] - (window - 1))
         ).reshape(b, r // bk, bk), axis=-1).astype(jnp.int32)
    def tile(w):
        return pl.BlockSpec((1, bq, g * w), lambda bi, h, qi, lv: (bi, qi, h))

    def piece(w):
        return pl.BlockSpec((1, t, w), lambda bi, h, qi, lv: (bi, 0, h))

    def ring(w):
        return pl.BlockSpec((1, 1, r, w), lambda bi, h, qi, lv: (bi, h, 0, 0))

    sink_spec, sink_arg = [], []
    if sinks is not None:
        sink_spec = [pl.BlockSpec((1, g, 128), lambda bi, h, qi, lv: (h, 0, 0))]
        sink_arg = [jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(hkv, g, 1), (hkv, g, 128))]
    out = pl.pallas_call(
        functools.partial(
            _ring_kernel, window=window, block_k=bk, banded=banded),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, t // bq),
            in_specs=[
                tile(d), piece(d), piece(dv), ring(d), ring(dv),
                pl.BlockSpec((1, bq, 1), lambda bi, h, qi, lv: (bi, qi, 0)),
                pl.BlockSpec((1, 1, r), lambda bi, h, qi, lv: (bi, 0, 0)),
                pl.BlockSpec((1, 1, t), lambda bi, h, qi, lv: (bi, 0, 0)),
                *sink_spec,
            ],
            out_specs=tile(dv),
            scratch_shapes=[
                pltpu.VMEM((g * bq, 128), jnp.float32),
                pltpu.VMEM((g * bq, 128), jnp.float32),
                pltpu.VMEM((g * bq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, hq * dv), q.dtype),
        interpret=interpret,
        name="ring_prefill_attention",
        # a row's ring of one KV head, keys and values, twice (the
        # pipeline's two buffers): 4.7 MB at 4,608 rows of 128; a turn's
        # scores and weights [2048, 512] float32 4 MB each
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024
        ),
    )(
        live, q.reshape(b, t, hq * d), k_cur.reshape(b, t, hkv * d),
        v_cur.reshape(b, t, hkv * dv), ring_k, ring_v,
        q_pos.astype(jnp.int32)[..., None],
        ring_pos.astype(jnp.int32)[:, None],
        cur_pos.astype(jnp.int32)[:, None],
        *sink_arg,
    )
    return out.reshape(b, t, hq, dv)
