"""Pallas TPU block scores of the block-sparse rule (ops/sparse_select.py,
models/minicpm_sala.py), read out of the compressed-key pool IN PLACE:

    P[t, j] = sum_g softmax_j(q[t, g] . Kc[j] * scale)      (float32)
    B[t, b] = max P[t, j],  j in [cpb b - reach, cpb b + cpb - 1]

for every window `j` a query has seen whole, a KV head at a time, the
sequence's compressed keys streamed page by page into VMEM, the scores,
the softmax, the sum over the KV head's query heads and the pool onto
blocks kept there, and [queries, blocks] float32 written once. Plain XLA
gathered a copy of every row's keys ([B', NB x cpb, D], 18.9 MB a layer
at 64 virtual rows), rewrote it to put one fresh window in, held [B', T,
G, NC] float32 scores in HBM and sorted the blocks: 1.67 ms a layer for
16 us of bytes (PERF.md 6, PR 41, 46). `ss.select_blocks` over
`ss.gather_compressed` / `ss.with_fresh` stays what this is judged
against, and the path off the TPU.

The pool is [L, P x Hkv x cpb, D]: a PAGE's compressed keys, `cpb` a KV
head, lie side by side (`Hkv x cpb` rows of D: 8 x 128 bf16, 2 KB), so
one copy a page serves every KV head of the sequence. A page lands as it
lies in a slot [pages, Hkv x cpb, D]; row `h cpb + i` of every page, read
at a stride, is the PLANE of the windows `j = cpb p + i` of KV head `h`
in page order, [pages, D]. A KV head's scores are `cpb` dots against its
planes, [G x BQ, pages] each: one softmax runs over the `cpb` of them, and
the pool onto blocks is a maximum ACROSS planes (the windows that start
in a block) and with the last `reach` planes moved one lane on (the
windows of the block before that reach in), no lane-strided pass.

The windows that END inside the step are not in the pool yet
(`ss.fresh_windows`; one at most a decode row, T / stride contiguous ones
a chunk): they arrive as an operand [Hkv x cpb, WP, D], a plane's in page
order from the first page they touch, and a one-hot product puts them in
their places in the plane (exact: one term a key), which is what
`ss.with_fresh` does to the gathered copy.

One body serves both kinds of step: a decode row is a tile of one query
(grid (sequences, 1)), a prompt chunk `SELECT_BLOCK_Q` queries a tile
(grid (sequences, tiles)), its sequence's keys fetched once for all its
tiles. A sequence's pages go `SELECT_BLOCK_PAGES` copies a block, none of
them under a branch (past the pages a query can see the table names the
layer's null page), into one of `SELECT_DEPTH` slots, a sequence AHEAD of
the one being scored.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.sparse_select import SparseDims

#: pages one block of copies holds (2 KB each at 8 x 128 bf16; the
#: kernel at 32 decode sequences of 12,288 tokens: 8 / 16 / 32 = 193 / 184
#: / 179 us a layer, PERF.md 6, PR 46)
SELECT_BLOCK_PAGES = 32
#: slots of a whole sequence's pages: one is scored while the rest land
#: (three read what two read)
SELECT_DEPTH = 2
#: chunk queries of one grid step; the KV head's query heads fold into its
#: rows (x G: 1,024 rows of float32 scores over 4 planes of 384 pages, 6
#: MB; a 512-token piece over 12,288: 16 / 32 / 64 / 128 = 169 / 114 / 97 /
#: 89 us a layer)
SELECT_BLOCK_Q = 64

_MASKED = -1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _scores_kernel(
    # scalar prefetch
    pt_ref,  # [B, MPP] int32: each sequence's pages as rows of the flat
    #          pool, the layer's null page past those a query can see
    nblk_ref,  # [B + DEPTH] int32: blocks of copies a sequence takes
    j0_ref,  # [B] int32: the first window that ends inside the step
    m_ref,  # [B] int32: how many do
    # operands
    q_ref,  # [1, 1, Hkv * G * BQ, D] VMEM: a tile's queries, KV head then
    #         query head major
    last_ref,  # [1, 1, G * BQ, 1] VMEM int32: each row's newest whole
    #            window (-1: none)
    f_ref,  # [1, Hkv * cpb, WP, D] VMEM: the fresh windows by plane
    pool,  # [L * P, Hkv * cpb, D] ANY: the compressed-key pool as it lies
    o_ref,  # [1, Hkv, BQ, MPP] float32: block scores by KV head
    k_scr,  # [DEPTH, MPP, Hkv * cpb, D] VMEM: a slot is a sequence's pages
    sem,  # [DEPTH] DMA semaphores
    *,
    block_pages: int,
    per_block: int,
    reach: int,
    kv_heads: int,
    scale: float,
    nt: int,
):
    b, qt = pl.program_id(0), pl.program_id(1)
    pb, cpb = block_pages, per_block
    depth, mpp = k_scr.shape[0], k_scr.shape[1]
    rows = last_ref.shape[2]
    bq = o_ref.shape[2]
    wp = f_ref.shape[2]
    f32, i32 = jnp.float32, jnp.int32
    slot = lax.rem(b, depth)

    def fetch():
        """Start the copies of the sequence `DEPTH - 1` ahead (of the
        first `DEPTH` at the first step), then wait for this one's: a
        block is `pb` copies traced once and unrolled where the kernel is
        lowered (a copy traced is ~20 ms of the serving host's time,
        PERF.md 6, PR 45), and one wait for the bytes they signal between
        them."""
        def sequence(r, c):
            rs = lax.rem(r, depth)

            def block(i, c2):
                def page(k, c3):
                    at = i * pb + k
                    pltpu.make_async_copy(
                        pool.at[pt_ref[r, at]], k_scr.at[rs, at], sem.at[rs],
                    ).start()
                    return c3

                return lax.fori_loop(0, pb, page, c2, unroll=True)

            return lax.fori_loop(0, nblk_ref[r], block, c)

        lax.fori_loop(jnp.where(b == 0, 0, b + depth - 1), b + depth,
                      sequence, 0)

        def arrive(i, c):
            pltpu.make_async_copy(
                k_scr.at[slot, pl.ds(0, pb)], k_scr.at[slot, pl.ds(0, pb)],
                sem.at[slot]).wait()
            return c

        lax.fori_loop(0, nblk_ref[b], arrive, 0)

    if nt == 1:
        fetch()
    else:
        pl.when(qt == 0)(fetch)

    last = last_ref[0, 0]  # [rows, 1]
    j0, m = j0_ref[b], m_ref[b]
    p0 = lax.div(j0, cpb)
    page = lax.broadcasted_iota(i32, (mpp, 1), 0)
    col = lax.broadcasted_iota(i32, (1, wp), 1)
    blk = lax.broadcasted_iota(i32, (1, mpp), 1)
    any_seen = last >= 0

    def dot(x, y):  # x [M, K] . y [N, K] -> float32 [M, N]
        return lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), preferred_element_type=f32)

    for h in range(kv_heads):
        qh = q_ref[0, 0, h * rows:(h + 1) * rows, :]
        planes = []
        for i in range(cpb):
            k = k_scr[slot, :, h * cpb + i, :]  # [MPP, D]: windows cpb p + i
            j = page * cpb + i
            fresh = (j >= j0) & (j < j0 + m)
            if wp == 1:  # a decode row: one window at most, one key a plane
                put = f_ref[0, h * cpb + i]
            else:  # a one-hot product: exact, one term a key
                put = lax.dot_general(
                    ((page - p0 == col) & fresh).astype(k.dtype),
                    f_ref[0, h * cpb + i], (((1,), (0,)), ((), ())),
                    preferred_element_type=f32).astype(k.dtype)
            k = jnp.where(fresh, put, k)
            planes.append(jnp.where(
                blk * cpb + i <= last, dot(qh, k) * scale, _MASKED))
        top = functools.reduce(
            jnp.maximum, [p.max(axis=1, keepdims=True) for p in planes])
        planes = [jnp.exp(p - top) for p in planes]
        total = functools.reduce(
            jnp.add, [p.sum(axis=1, keepdims=True) for p in planes])
        # a row that has seen no window reads exp(0) everywhere: nothing
        inv = jnp.where(any_seen, 1.0 / total, 0.0)

        def heads(p):  # summed over the KV head's query heads: [BQ, MPP]
            p = p * inv
            if bq == 1:
                return p.sum(axis=0, keepdims=True)
            return p.reshape(rows // bq, bq, mpp).sum(axis=0)

        planes = [heads(p) for p in planes]
        score = functools.reduce(jnp.maximum, planes)
        for r in range(reach):  # the windows of the block before
            score = jnp.maximum(score, jnp.where(
                blk == 0, -1.0, pltpu.roll(planes[cpb - 1 - r], 1, axis=1)))
        o_ref[0, h] = score


def paged_block_scores(
    q: jax.Array,  # [B', T, G, D]: a KV head's query heads, normed
    kc_pool: jax.Array,  # [L, P * Hkv * cpb, Dc] the compressed keys
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B', MP] int32: the VIRTUAL rows' (b * Hkv + h)
    positions: jax.Array,  # [B', T]
    valid: jax.Array,  # [B', T]
    fresh,  # `ss.fresh_windows` of the step: (kc [B', T, Dc], ends, j)
    dims: SparseDims,
    scale: float,
    kv_heads: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """The rule's block scores of each valid query for its KV head, before
    the forced blocks (`ss.pooled_scores` of the gathered copy): float32
    [B', T, MP]; a block no window of which the query has seen reads 0, as
    does every block of a padding query. The virtual rows come `kv_heads`
    a sequence, as `models/minicpm_sala.virtual_rows` makes them."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    i32 = jnp.int32
    bv, t, g, d = q.shape
    n_l, pool_rows, dc = kc_pool.shape
    mp = tables.shape[1]
    hkv, cpb, reach = kv_heads, dims.per_block, dims.reach
    kk, st = dims.kernel_size, dims.kernel_stride
    pr = hkv * cpb  # pool rows a page
    if bv % hkv or pool_rows % pr or d > dc or reach > cpb:
        raise ValueError(
            f"pool {kc_pool.shape}, q {q.shape}, {hkv} KV heads, {cpb} "
            f"windows a block of which {reach} reach on")
    b, n_p = bv // hkv, pool_rows // pr
    pb = SELECT_BLOCK_PAGES
    mpp = _round_up(mp, max(128, pb))
    bq = min(SELECT_BLOCK_Q, t)
    tp = _round_up(t, bq)
    nt = tp // bq
    depth = SELECT_DEPTH

    # a sequence's rows: every KV head's positions are the sequence's
    pos, ok = positions[::hkv], valid[::hkv]
    n = pos + 1
    last = jnp.where(ok & (n >= kk), (n - kk) // st, -1).astype(i32)
    # the pages a query of the sequence can see a window of
    need = jnp.clip(jnp.max(last, axis=1) // cpb + 1, 0, mp)
    pages = jnp.pad(tables[::hkv].astype(i32) // hkv,
                    ((0, 0), (0, mpp - mp)))
    pages = jnp.asarray(layer, i32) * n_p + jnp.where(
        jnp.arange(mpp, dtype=i32)[None] < need[:, None], pages, 0)
    nblk = jnp.pad(-(-need // pb), (0, depth)).astype(i32)

    # the fresh windows by plane, from the first page they touch
    fkc, ends, fj = fresh
    ends = ends[::hkv] & ok
    m = jnp.sum(ends, axis=1, dtype=i32)
    j0 = jnp.where(m > 0, jnp.min(jnp.where(
        ends, fj[::hkv], jnp.iinfo(i32).max), axis=1), 0).astype(i32)
    windows = -(-t // st)  # T tokens in a row end no more
    wp = (windows + cpb - 2) // cpb + 1  # pages they touch
    wp = wp if wp == 1 else _round_up(wp, 16)
    at = ((j0[:, None, None] // cpb + jnp.arange(wp, dtype=i32)[None, None])
          * cpb + jnp.arange(cpb, dtype=i32)[None, :, None])  # [B, cpb, WP]
    tok = jnp.clip(at * st + (kk - 1) - pos[:, :1, None], 0, t - 1)
    f = jnp.take_along_axis(
        fkc.reshape(b, hkv, t, dc).astype(kc_pool.dtype),
        tok.reshape(b, 1, cpb * wp, 1), axis=2).reshape(b, pr, wp, dc)

    # tiles of queries, KV head then query head major: [B, NT, Hkv G BQ, Dc]
    qp = jnp.pad(q.astype(kc_pool.dtype),
                 ((0, 0), (0, tp - t), (0, 0), (0, dc - d)))
    qp = qp.reshape(b, hkv, nt, bq, g, dc).transpose(
        0, 2, 1, 4, 3, 5).reshape(b, nt, hkv * g * bq, dc)
    lastp = jnp.pad(last, ((0, 0), (0, tp - t)), constant_values=-1)
    lastp = jnp.broadcast_to(
        lastp.reshape(b, nt, 1, bq), (b, nt, g, bq)).reshape(b, nt, g * bq, 1)

    out = pl.pallas_call(
        functools.partial(
            _scores_kernel, block_pages=pb, per_block=cpb, reach=reach,
            kv_heads=hkv, scale=scale, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nt),
            in_specs=[
                pl.BlockSpec((1, 1, hkv * g * bq, dc),
                             lambda bi, ti, *_: (bi, ti, 0, 0)),
                pl.BlockSpec((1, 1, g * bq, 1),
                             lambda bi, ti, *_: (bi, ti, 0, 0)),
                pl.BlockSpec((1, pr, wp, dc), lambda bi, ti, *_: (bi, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, hkv, bq, mpp), lambda bi, ti, *_: (bi, 0, ti, 0)),
            scratch_shapes=[
                pltpu.VMEM((depth, mpp, pr, dc), kc_pool.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, tp, mpp), jnp.float32),
        interpret=interpret,
        name="paged_block_scores",
        # a sequence's fetch runs ahead of the one scored: in order, on
        # one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(pages, nblk, j0, m, qp, lastp, f,
      # the pool's pages in one row of layers x pages: the same bytes
      kc_pool.reshape(n_l * n_p, pr, dc))
    return out.reshape(bv, tp, mpp)[:, :t, :mp]
