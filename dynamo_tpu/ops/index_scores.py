"""Pallas TPU index scores of a learned indexer (models/keye_vl.py, the
rule of ops/token_select.py), read out of the index-key pool IN PLACE:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

for every cached token `s` of a row, its index keys streamed page by page
through VMEM and the scores written once. Plain XLA gathered a copy of
every row's keys with the other layer's half ([B, MP x S, 128], 151 MB a
layer at 32 rows x 18k), picked the half, put the own token in and ran 16
head products over it: 1.0 ms a layer for 0.07 ms of bytes (PERF.md 6, PR
43, 45). `ts.index_scores` over `keye_vl.index_keys_of` stays what this is
judged against, and the path off the TPU.

The pool is [L / 2, P, S, 2 Di], two layers' keys side by side in a row
(`keye_vl.index_pool`). A page is DMA'd as it lies, both halves (the
fetch is a 256-byte pair row a token); the queries arrive with the
layer's half of the lanes filled and zeros in the other, so ONE dot of
the 2 Di-wide rows scores the layer's keys and adds exact zeros for its
neighbour's. All `J` heads of a tile of queries are one matmul ([J x BQ,
2 Di] against a block's keys), relu, the float32 head weights and the sum
over heads follow in float32.

One body serves both kinds of step: a decode row is a tile of one query
(grid (rows, 1)), a prompt chunk `INDEX_BLOCK_Q` queries a tile (grid
(rows, tiles)); a chunk also scores its OWN keys, which the pool does not
hold yet, in a turn of their own into a second output. A grid step takes
its row's cached pages `INDEX_BLOCK_PAGES` a block through `INDEX_DEPTH`
slots; the fetches run AHEAD of the scoring across grid steps (a cursor
in SMEM walks the same (step, block) order), so the pipeline stays full
from row to row: a decode row is 4-9 blocks, three of which the DMA's
latency spans. A block is always `INDEX_BLOCK_PAGES` copies, none of them
under a branch (a row's last block fetches the null page for the pages
the row does not hold: 16 KB each, half a block a row on average), so
that the compiler issues a block's copies beside the scoring of another;
positions not cached read 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: cached pages one block holds (2,048 keys, 512 KB at S 64 and 2 Di 128;
#: 16 lost 11 % of a decode row's speed, 64 3 %)
INDEX_BLOCK_PAGES = 32
#: slots: one block is scored while up to three land (two slots lost 40 %
#: on the chip, three nothing)
INDEX_DEPTH = 4
#: chunk queries of one grid step; their heads fold into its rows (x J)
INDEX_BLOCK_Q = 128
#: keys one dot takes. A chunk's tile: its [J x BQ, keys] float32 scores
#: stay 4 MB (512 beat 256 by 12 %, 128 lost 20: PERF.md 6, PR 45). A
#: decode row: between two dots a share of the next block's copies goes out
INDEX_COLUMNS = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _index_kernel(
    # scalar prefetch
    pt_ref,  # [B + 1, MPP] int32: each row's pages as rows of the flat
    #          pool, the null page past its cached ones; a last row of
    #          nothing but the null page
    hist_ref,  # [B] int32: tokens the pool holds of each row
    next_ref,  # [B * NT + 1] int32: the first grid step from each on
    #            whose row has cached pages (B * NT: none)
    # then (positional; `own` adds the two in brackets):
    #   q_ref,  # [1, 1, J * BQ, 2 Di] VMEM: a tile's queries, head-major,
    #           # in the layer's half of the lanes
    #   w_ref,  # [1, 1, J * BQ, 1] VMEM float32: their heads' weights
    #   [kown_ref]  # [1, TP, 2 Di] VMEM: this chunk's own keys, same lanes
    #   pool,  # [L / 2 * P, S, 2 Di] ANY: the index-key pool as it lies
    #   o_ref,  # [1, BQ, MPP * S] float32: scores by cached position
    #   [own_ref]  # [1, BQ, TP] float32: scores of the chunk's own keys
    #   k_scr,  # [DEPTH, PB * S, 2 Di] VMEM: a slot is a block of pages
    #   sem,  # [DEPTH] DMA semaphores
    #   cur,  # [4] int32 SMEM: the fetches' (grid step, block), blocks
    #         # fetched, blocks scored; lives across grid steps
    *refs,
    page_size: int,
    block_pages: int,
    heads: int,
    columns: int,
    own: bool,
    n_rows: int,
    nt: int,
):
    if own:
        q_ref, w_ref, kown_ref, pool, o_ref, own_ref, k_scr, sem, cur = refs
    else:
        q_ref, w_ref, pool, o_ref, k_scr, sem, cur = refs
    b, qt = pl.program_id(0), pl.program_id(1)
    steps = n_rows * nt
    s, pb = page_size, block_pages
    n = pb * s
    depth = k_scr.shape[0]
    bq = q_ref.shape[2] // heads
    f32 = jnp.float32

    def blocks_of(row):
        return pl.cdiv(pl.cdiv(hist_ref[row], s), pb)

    def starts(unroll=True):
        """The block under the cursor as `pb` copies to start, and what
        moves the cursor on: block i lands in slot i % DEPTH. No branch:
        a block is `pb` copies whatever the row holds (the table names
        the null page past its pages), and past the last block the null
        row's, so that the copies' issue shares a basic block with the
        scoring beside it."""
        step, kb, count = cur[0], cur[1], cur[2]
        live = step < steps
        row = jnp.where(live, lax.div(step, nt), n_rows)
        first = jnp.where(live, kb, 0) * pb
        slot = lax.rem(count, depth)

        def start(lo, pages):
            """`pages` copies from the block's page `lo` on, traced once
            and unrolled where the kernel is lowered (`unroll`): a copy
            traced is ~20 ms of the serving host's time, and 32 of them
            in each of a step program's kernels were 5 s of every first
            call, from the compile cache or not (PERF.md 6, PR 45)."""
            def page(i, c):
                at = pl.multiple_of((lo + i) * s, s)
                pltpu.make_async_copy(
                    pool.at[pt_ref[row, first + lo + i]],
                    k_scr.at[slot, pl.ds(at, s)],
                    sem.at[slot],
                ).start()
                return c

            lax.fori_loop(0, pages, page, 0, unroll=unroll)

        def move():
            more = kb + 1 < blocks_of(jnp.minimum(row, n_rows - 1))
            cur[0] = jnp.where(
                live & ~more, next_ref[jnp.minimum(step + 1, steps)], step)
            cur[1] = jnp.where(live & more, kb + 1, 0)
            cur[2] = count + 1

        return start, move

    def arrive(slot):
        """Wait for a block: ONE wait for the slot's bytes, which is what
        its `pb` copies signal between them (a DMA semaphore counts
        bytes; the source of a wait is not read)."""
        pltpu.make_async_copy(
            k_scr.at[slot], k_scr.at[slot], sem.at[slot]).wait()

    @pl.when((b == 0) & (qt == 0))
    def _():
        cur[0] = next_ref[0]
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0

        def prime(_, c):  # once a call: a loop a block, a loop a page
            start, move = starts(unroll=False)
            start(0, pb)
            move()
            return c

        lax.fori_loop(0, depth - 1, prime, 0)

    q = q_ref[0, 0]  # [J * BQ, 2 Di]
    w = w_ref[0, 0]  # [J * BQ, 1]

    def score(k):
        """[BQ, keys] float32: the tile's queries against keys [keys, 2
        Di], every head in one dot."""
        sc = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        sc = jnp.maximum(sc, 0.0) * w
        if bq == 1:
            return jnp.sum(sc, axis=0, keepdims=True)
        return jnp.sum(sc.reshape(heads, bq, -1), axis=0)

    # positions past the row's last block are never visited
    o_ref[...] = jnp.zeros_like(o_ref)

    if own:  # the chunk over itself, while the first pages land
        tp = kown_ref.shape[1]
        if tp % columns:  # a short chunk: one turn, whatever its width
            own_ref[0] = score(kown_ref[0])
        else:
            def own_turn(j, c):
                at = pl.ds(pl.multiple_of(j * columns, columns), columns)
                own_ref[0, :, at] = score(kown_ref[0, at, :])
                return c

            lax.fori_loop(0, tp // columns, own_turn, 0)

    hist = hist_ref[b]

    def block(kb, c):
        done = cur[3]
        slot = lax.rem(done, depth)
        arrive(slot)
        cur[3] = done + 1
        start, move = starts()
        turns = n // columns

        def turn(j, c2):
            lo = pl.multiple_of(j * columns, columns)
            at = pl.multiple_of(kb * n + lo, columns)
            pos = at + lax.broadcasted_iota(jnp.int32, (1, columns), 1)
            # the last page's tail is not the row's yet, and past it the
            # slot holds the null page
            o_ref[0, :, pl.ds(at, columns)] = jnp.where(
                pos < hist, score(k_scr[slot, pl.ds(lo, columns), :]), 0.0)
            return c2

        if bq == 1 and pb % turns == 0:
            # a decode row is bound by the ISSUE of its copies (20 ns of
            # HBM time a page): each dot stands beside its share of the
            # block fetched next, in program order, so that the compiler
            # issues them under it (0.178 -> 0.162 ms a layer at 13k)
            def share(j, c2):
                start(j * (pb // turns), pb // turns)
                return turn(j, c2)

            lax.fori_loop(0, turns, share, 0, unroll=True)
        else:  # a chunk's tile is bound by its dots: one body a turn
            start(0, pb)
            lax.fori_loop(0, turns, turn, 0)
        move()
        return c

    lax.fori_loop(0, blocks_of(b), block, 0)

    @pl.when((b == n_rows - 1) & (qt == nt - 1))
    def _():  # the blocks fetched past the last one
        for i in range(depth - 1):
            arrive(lax.rem(cur[3] + i, depth))


def paged_index_scores(
    qi: jax.Array,  # [B, T, J, Di] the index queries, post-rope
    w: jax.Array,  # [B, T, J] float32: their heads' weights, scaled
    ki_pool: jax.Array,  # [L / 2, P, S, 2 Di] the index keys (history)
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, MP] int32
    hist: jax.Array,  # [B] int32: tokens of each row the pool holds
    ki_own: jax.Array | None = None,  # [B, T, Di]: this chunk's own keys
    *,
    paired: bool = True,
    interpret: bool | None = None,
):
    """The index scores of each query over its row's CACHED tokens, by
    position: float32 [B, T, MP * S], 0 from `hist` on. With `ki_own`
    also the scores over the step's own keys, float32 [B, T, T] (query x
    own key, no causal mask: the selection masks by context), for the
    caller to put in at the rows' `hist`.

    `paired` False reads a pool [L, P, S, Di] that holds ONE layer's key a
    row (models/dots3.py: a 128-wide key fills the lanes by itself): the
    same kernel, a query in the whole row and the layer's pages at `layer
    * P`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, nj, di = qi.shape
    pairs, n_p, s, lanes = ki_pool.shape
    mp = tables.shape[1]
    if lanes != (2 if paired else 1) * di or w.shape != (b, t, nj) or (
            ki_own is not None and ki_own.shape != (b, t, di)):
        raise ValueError(
            f"pool {ki_pool.shape}, qi {qi.shape}, w {w.shape}, own keys "
            f"{None if ki_own is None else ki_own.shape}")
    pb = min(INDEX_BLOCK_PAGES, mp)
    mpp = _round_up(mp, pb)
    n = pb * s
    # a tile's [J x BQ, keys] scores stay what 16 heads of INDEX_BLOCK_Q make
    bq = min(INDEX_BLOCK_Q, t, max(8, 16 * INDEX_BLOCK_Q // nj))
    tp = _round_up(t, bq)
    nt = tp // bq
    columns = n if n % INDEX_COLUMNS else INDEX_COLUMNS
    odd = jnp.asarray(layer, jnp.int32) % 2 == 1

    def lanes_of(x):
        """[.., Di] -> [.., 2 Di]: in the layer's half of a pool row."""
        if not paired:
            return x
        z = jnp.zeros_like(x)
        return jnp.where(odd, jnp.concatenate([z, x], axis=-1),
                         jnp.concatenate([x, z], axis=-1))

    def rows_of(x):  # T padded to whole tiles
        return jnp.pad(x, ((0, 0), (0, tp - t)) + ((0, 0),) * (x.ndim - 2))

    # head-major tiles: [B, NT, J * BQ, .]
    q = rows_of(lanes_of(qi.astype(ki_pool.dtype))).reshape(
        b, nt, bq, nj, lanes).transpose(0, 1, 3, 2, 4).reshape(
        b, nt, nj * bq, lanes)
    wq = rows_of(w.astype(jnp.float32)).reshape(b, nt, bq, nj).transpose(
        0, 1, 3, 2).reshape(b, nt, nj * bq, 1)

    def tile(width):
        return pl.BlockSpec(
            (1, 1, nj * bq, width), lambda bi, qi_, *_: (bi, qi_, 0, 0))

    def out_tile(width):
        return pl.BlockSpec((1, bq, width), lambda bi, qi_, *_: (bi, qi_, 0))

    own = ki_own is not None
    operands = [q, wq]
    in_specs = [tile(lanes), tile(1)]
    out_shape = [jax.ShapeDtypeStruct((b, tp, mpp * s), jnp.float32)]
    out_specs = [out_tile(mpp * s)]
    if own:
        operands.append(rows_of(lanes_of(ki_own.astype(ki_pool.dtype))))
        in_specs.append(pl.BlockSpec(
            (1, tp, lanes), lambda bi, qi_, *_: (bi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, tp, tp), jnp.float32))
        out_specs.append(out_tile(tp))
    # the pool's pages in one row of pairs x pages: the same bytes
    operands.append(ki_pool.reshape(pairs * n_p, s, lanes))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    # what the fetches walk, from the lengths alone: a row's cached pages
    # as rows of the flat pool (the pair's null page past them, and in a
    # last row of its own), and the live grid step after each
    i32 = jnp.int32
    hist = hist.astype(i32)
    held = -(-hist // s)  # pages
    null = (jnp.asarray(layer, i32) // (2 if paired else 1)) * n_p
    pages = null + jnp.pad(jnp.where(
        jnp.arange(mpp, dtype=i32)[None] < held[:, None],
        jnp.pad(tables.astype(i32), ((0, 0), (0, mpp - mp))), 0),
        ((0, 1), (0, 0)))
    steps = b * nt
    at = jnp.arange(steps, dtype=i32)
    after = jnp.pad(lax.cummin(jnp.where(
        jnp.repeat(held > 0, nt), at, steps), reverse=True),
        (0, 1), constant_values=steps)

    out = pl.pallas_call(
        functools.partial(
            _index_kernel, page_size=s, block_pages=pb, heads=nj,
            columns=columns, own=own, n_rows=b, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nt),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((INDEX_DEPTH, n, lanes), ki_pool.dtype),
                pltpu.SemaphoreType.DMA((INDEX_DEPTH,)),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="paged_index_scores_chunk" if own else "paged_index_scores",
        # the fetches run ahead across grid steps: in order, on one core;
        # a chunk's tile holds its [BQ, MPP * S] scores (9.4 MB at 18k)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
    )(pages, hist, after, *operands)
    scores = out[0][:, :t, :mp * s]
    return (scores, out[1][:, :t, :t]) if own else scores
