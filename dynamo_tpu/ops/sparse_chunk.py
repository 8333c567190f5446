"""Pallas TPU attention of a prompt chunk under a block selection
(models/minicpm_sala.py, the rule of ops/sparse_select.py): the chunk's
queries over the CACHED pages they chose and over the chunk itself, one
online softmax, by TILE of queries.

The `CHUNK_BLOCK_Q` queries of a tile mostly choose the same pages (the
first block and the window before them are forced on every one, and
neighbours score alike), so a page is read ONCE A TILE, not once a query:
`tile_lists` takes the union of what a tile's queries chose among the
cached blocks as an ascending page list (compacted with a prefix sum and
a one-hot, no sort) and, a (query, listed page), ONE BIT that says whether
that query chose it. The kernel walks the list `CHUNK_BLOCK_PAGES` pages a
turn, K and V DMA'd from the stacked pools in place (a KV head a row of a
one-row cache, `layer` a prefetched scalar, two slots), and expands a
turn's bits over a page's keys and the KV head's query heads, which fold
into the tile's rows ([G x BQ, D] against [K, D]). The chunk's own keys
are resident in VMEM and take the first turns, under the causal mask and
their blocks' bits, while the first pages land. bf16 operands reach the
MXU as they come (the queries scaled by the caller); scores, the running
maximum, denominator and accumulator are float32; the output leaves
normalised.

A masked score is finite, so a row that has met no chosen key yet carries
sums of no meaning until its first one wipes them (the correction factor
is then exactly 0); every valid query chooses its own block, which holds
itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: chunk rows of one grid cell; the KV head's query heads fold into its
#: rows (x G)
CHUNK_BLOCK_Q = 128
#: listed pages one turn takes (1,024 keys at S = 64), and the bits of one
#: mask word: 16 beat 8 by a third and 4 by 2.7 x on a 512-row chunk over
#: 12,288 tokens (0.86 / 1.30 / 2.32 ms a layer: PERF.md 6, PR 42)
CHUNK_BLOCK_PAGES = 16
#: chunk keys of one turn over the chunk itself
CHUNK_BLOCK_CUR = 256
_MASKED = -1e30  # finite: a padded query row stays NaN-free


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def chunk_blocking(t: int, page_size: int, max_pages: int):
    """The blocking of a chunk of `t` rows: (rows a tile, `t` padded to
    whole tiles and own turns, listed pages a turn, own keys a turn). A
    turn's pages are the bits of one int32 mask word. Several turns over
    the chunk take whole pages each; a single one starts on the chunk's
    first page whatever its length."""
    bq = min(CHUNK_BLOCK_Q, t)
    per = min(max(1, CHUNK_BLOCK_CUR // page_size), 32)
    cur = min(_round_up(t, bq), per * page_size)
    return (bq, _round_up(t, math.lcm(bq, cur)),
            min(CHUNK_BLOCK_PAGES, max_pages, 32), cur)


def tile_lists(selected, valid, tables, hist, page_size: int):
    """A chunk's selection as the kernel takes it. selected [B, T, NB]
    bool (`select_blocks`), valid [B, T], tables [B, NB] the rows' pages,
    hist [B] the tokens cached before the chunk (it starts on a page: the
    scheduler's invariant, so the blocks before `hist // S` are whole
    cached pages and the rest is the chunk's own). Returns

    - pages [B, NT, K] int32: a tile's list, the pages ANY of its valid
      queries chose among the cached ones, ascending, then zeros;
    - counts [B, NT] int32: how many;
    - words [B, TP, W] int32: bit p of word w says the query chose listed
      page `w * PB + p` (w < K / PB), or, from word K / PB on, page p of
      own turn `w - K / PB` of the chunk;
    - named [B] int32: the cached pages the row's queries' selections
      name, one a (query, page): what a walk a query would read.
    """
    b, t, nb = selected.shape
    s = page_size
    bq, tp, pb, cur = chunk_blocking(t, s, nb)
    nt, k = tp // bq, _round_up(nb, pb)
    i32 = jnp.int32
    if tp != t:
        selected = jnp.pad(selected, ((0, 0), (0, tp - t), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, tp - t)))
    first = hist.astype(i32) // s  # the chunk's first block
    blk = jnp.arange(nb, dtype=i32)
    hist = selected & valid[..., None] & (blk < first[:, None])[:, None]
    hist = hist.reshape(b, nt, bq, nb)
    chosen = hist.any(axis=2)  # [B, NT, NB]: by any query of the tile
    counts = chosen.sum(axis=-1, dtype=i32)
    slot = jnp.cumsum(chosen, axis=-1, dtype=i32) - 1
    place = chosen[..., None] & (
        slot[..., None] == jnp.arange(k, dtype=i32))  # [B, NT, NB, K]
    pages = jnp.sum(
        jnp.where(place, tables[:, None, :, None], 0), axis=2, dtype=i32)
    # each query's bit of each listed page: its row of `hist` through the
    # one-hot (0 / 1 products, exact in any precision)
    bits = jnp.einsum(
        "bnqj,bnjk->bnqk", hist.astype(jnp.bfloat16),
        place.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
    ).astype(i32).reshape(b, tp, k // pb, pb)

    def packed(x):  # [..., bits] 0 / 1 -> one word
        return jnp.sum(x << jnp.arange(x.shape[-1], dtype=i32), axis=-1,
                       dtype=i32)

    # the chunk's own blocks, `cur` keys (whole pages, or part of one) a
    # turn; a block past the table is past `max_context`: no valid key
    per = -(-cur // s)
    own = first[:, None] + jnp.arange((tp // cur) * per, dtype=i32)[None]
    own_bits = jnp.take_along_axis(
        selected, jnp.broadcast_to(
            jnp.minimum(own, nb - 1)[:, None, :], (b, tp, own.shape[1])),
        axis=-1,
    ) & (own < nb)[:, None, :]
    words = jnp.concatenate([
        packed(bits),
        packed(own_bits.astype(i32).reshape(b, tp, tp // cur, per)),
    ], axis=-1)
    words = jnp.pad(
        words, ((0, 0), (0, 0), (0, -words.shape[-1] % 128)))
    return pages, counts, words, jnp.sum(hist, axis=(1, 2, 3), dtype=i32)


def _chunk_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    pages_ref,  # [B * NT, K] int32: a tile's pages (rows of the pool)
    count_ref,  # [B * NT] int32: how many of them
    cur_ref,  # [B] int32: valid tokens in THIS chunk
    # inputs
    q_ref,  # [1, G, BQ, D] VMEM: a KV head's queries, scaled
    w_ref,  # [1, BQ, W] VMEM int32: the mask words of the tile's queries
    kcur_ref,  # [1, TP, D] VMEM: this chunk's keys
    vcur_ref,  # [1, TP, D]
    k_hbm,  # [L, P, S, D] ANY: the K pool, a KV head a row
    v_hbm,  # [L, P, S, D]
    # output
    o_ref,  # [1, G, BQ, D] in the queries' dtype
    # scratch
    k_scr,  # [2, PB * S, D] VMEM: a slot is a block of listed pages
    v_scr,
    m_scr,  # [G * BQ, 128] f32 running max (every lane the same)
    l_scr,  # [G * BQ, 128] f32 running denominator
    acc_scr,  # [G * BQ, D] f32
    sem,  # [2, 2] DMA semaphores: [plane, slot]
    *,
    page_size: int,
    block_pages: int,
    block_cur: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    li = layer_ref[0]
    g, bq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    tp = kcur_ref.shape[1]
    s, pb = page_size, block_pages
    rows = g * bq
    tile = b * pl.num_programs(1) + qi
    cur = cur_ref[b]
    n_blk = pl.cdiv(count_ref[tile], pb)
    hist_words = pages_ref.shape[1] // pb
    mxu = k_scr.dtype  # the pool's dtype is the model's: no cast to f32

    def copies(slot, blk):
        """The DMAs of block `blk` of the tile's list into `slot`, one a
        page and plane. Past the list's end the entries are 0: the null
        page is fetched and no query's bit is set, so every turn moves
        `pb` pages and a wait is its start's twin."""
        out = []
        for p in range(pb):
            page = pages_ref[tile, blk * pb + p]
            for pi, (src, dst) in enumerate(
                ((k_hbm, k_scr), (v_hbm, v_scr))
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

    q = q_ref[0].reshape(rows, d)
    words = w_ref[0]  # [BQ, W]
    lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    # chunk-relative index of a tile row's query
    row_rel = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def chosen(word: jax.Array, keys: int):
        """[BQ, keys] bool: key c of a turn lies in the turn's page `c //
        S`, whose bit each query's word `word` holds."""
        w = jnp.sum(jnp.where(lane == word, words, 0), axis=1, keepdims=True)
        page = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1), jnp.int32(s))
        return (jax.lax.shift_right_logical(
            jnp.broadcast_to(w, (bq, keys)),
            jnp.broadcast_to(page, (bq, keys))) & 1) == 1

    m_scr[...] = jnp.full(m_scr.shape, _MASKED, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def fold(k, v, keep):
        """One turn of the online softmax over keys `k` and values `v`
        [K, D] under `keep` [BQ, K], the same for every query head."""
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G * BQ, K]
        sc = jnp.where(
            keep[None], sc.reshape(g, bq, -1), _MASKED).reshape(rows, -1)
        m_new = jnp.maximum(m_scr[:, :1], jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_scr[:, :1] - m_new)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            p.astype(mxu), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # -- the chunk over itself: causal by position, padding masked, its
    # blocks' bits, key blocks wholly above this tile's diagonal skipped ----
    for j in range(tp // block_cur):
        def turn(j=j):
            at = pl.ds(j * block_cur, block_cur)
            key = j * block_cur + jax.lax.broadcasted_iota(
                jnp.int32, (block_cur, 1), 0
            )
            # rows past `cur` may hold anything: as values a zero weight
            # does not silence a NaN, so they go in as zeros
            v = jnp.where(key < cur, vcur_ref[0, at, :], 0).astype(mxu)
            col = j * block_cur + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_cur), 1
            )
            fold(
                kcur_ref[0, at, :].astype(mxu), v,
                chosen(hist_words + j, block_cur)
                & (col <= row_rel) & (col < cur),
            )

        pl.when(j * block_cur < (qi + 1) * bq)(turn)

    # -- the listed pages: every key lies before the chunk -------------------
    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            for cp in copies(1 - slot, i + 1):
                cp.start()

        for cp in copies(slot, i):
            cp.wait()
        fold(k_scr[slot], v_scr[slot], chosen(i, pb * s))
        return 0

    jax.lax.fori_loop(0, n_blk, body, 0)
    inv = 1.0 / jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype).reshape(g, bq, d)


def sparse_chunk_attention(
    q: jax.Array,  # [B, T, G, D] a KV head's queries, SCALED, model dtype
    k_cur: jax.Array,  # [B, T, D] this chunk's keys
    v_cur: jax.Array,  # [B, T, D]
    k_cache: jax.Array,  # [L, P, S, 1, D] the K pool (history)
    v_cache: jax.Array,  # [L, P, S, 1, D]
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, NB] int32: the rows' pages
    selected: jax.Array,  # [B, T, NB] bool: each query's blocks
    hist: jax.Array,  # [B] int32: tokens cached before the chunk, whole pages
    valid: jax.Array,  # [B, T] bool, a prefix of each row
    *,
    interpret: bool | None = None,
):
    """`softmax(q . K) . V` of each chunk query over the keys of the
    blocks `selected` names for it, up to itself: the cached ones from
    the pools' pages, the chunk's own from `k_cur` / `v_cur` (not cached
    yet). A row is one KV head of one sequence over a one-row cache.

    Returns (out [B, T, G, D] in the queries' dtype, rows past a row's
    valid prefix unspecified; (int32 [B, NT]: the pages each tile's list
    names, which is what the kernel reads, int32 [B]: the pages each
    row's queries' selections name))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, g, d = q.shape
    s = k_cache.shape[2]
    if k_cache.shape[3] != 1 or k_cache.shape[-1] != d or (
        k_cur.shape, v_cur.shape) != ((b, t, d),) * 2:
        raise ValueError(
            "a sparse chunk takes a one-row cache and rows as it caches "
            f"them; got pools {k_cache.shape} / {v_cache.shape}, rows "
            f"{k_cur.shape} / {v_cur.shape}, q {q.shape}"
        )
    bq, tp, pb, cur = chunk_blocking(t, s, tables.shape[1])
    pages, counts, words, named = tile_lists(
        selected, valid, tables.astype(jnp.int32), hist, s)
    if tp != t:  # whole tiles; `valid` masks the tail
        q, k_cur, v_cur = (
            jnp.pad(x, ((0, 0), (0, tp - t)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k_cur, v_cur)
        )
    nt = tp // bq

    def q_block(width):
        return pl.BlockSpec(
            (1, g, bq, width), lambda bi, qi, *_: (bi, 0, qi, 0))

    def chunk_block():
        return pl.BlockSpec((1, tp, d), lambda bi, qi, *_: (bi, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, page_size=s, block_pages=pb, block_cur=cur),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nt),
            in_specs=[
                q_block(d),
                pl.BlockSpec(
                    (1, bq, words.shape[-1]),
                    lambda bi, qi, *_: (bi, qi, 0)),
                chunk_block(), chunk_block(),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_block(d),
            scratch_shapes=[
                pltpu.VMEM((2, pb * s, d), k_cache.dtype),
                pltpu.VMEM((2, pb * s, d), v_cache.dtype),
                pltpu.VMEM((g * bq, 128), jnp.float32),
                pltpu.VMEM((g * bq, 128), jnp.float32),
                pltpu.VMEM((g * bq, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, tp, d), q.dtype),
        interpret=interpret,
        name="sparse_chunk_attention",
        # a [16 x 128, 512] tile holds 4 MB of scores and as much of
        # weights in float32; the limit assumes 128 MB of VMEM (v5e, v6e),
        # as `latent_prefill_attention`'s does
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        pages.reshape(b * nt, -1),
        counts.reshape(b * nt),
        jnp.sum(valid, axis=1).astype(jnp.int32),
        q.transpose(0, 2, 1, 3),  # head-major: a tile's [G, BQ, D] block
        words, k_cur, v_cur,
        # a page as [S, D] rows: the same bytes
        k_cache.reshape(*k_cache.shape[:3], d),
        v_cache.reshape(*v_cache.shape[:3], d),
    )
    return out[:, :, :t].transpose(0, 2, 1, 3), (counts, named)


# ---------------------------------------------------------------------------
# A mask bit a (query, KEY): token selection (ops/token_select.py)
# ---------------------------------------------------------------------------


def _token_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32
    pt_ref,  # [B, MPP] int32: the rows' page tables, padded to whole turns
    npages_ref,  # [B] int32: cached pages before the chunk
    cur_ref,  # [B] int32: valid tokens in THIS chunk
    # inputs
    q_ref,  # [1, Hq, BQ, D] VMEM: the tile's queries, scaled
    mh_ref,  # [1, BQ, MPP * S] VMEM int8: query x cached key
    mo_ref,  # [1, BQ, TP] VMEM int8: query x chunk key (causal inside)
    kcur_ref,  # [1, TP, Hkv, D] VMEM: this chunk's keys
    vcur_ref,  # [1, TP, Hkv, D]
    k_hbm,  # [L, P, S, Hkv, D] ANY: the K pool as the cache lays it out
    v_hbm,  # [L, P, S, Hkv, D]
    # output
    o_ref,  # [1, Hq, BQ, D]
    # scratch
    k_scr,  # [2, PB * S, Hkv, D] VMEM: a slot is a turn's pages
    v_scr,
    kh_scr,  # [Hkv, max(PB * S, cur), D] VMEM: a turn's keys by head
    vh_scr,
    m_scr,  # [Hkv, G * BQ, 128] f32 running max (every lane the same)
    l_scr,  # [Hkv, G * BQ, 128] f32 running denominator
    acc_scr,  # [Hkv, G * BQ, D] f32
    sem,  # [2, 2] DMA semaphores: [plane, slot]
    *,
    page_size: int,
    block_pages: int,
    block_cur: int,
    kv_heads: int,
):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    li = layer_ref[0]
    hq, bq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    s, pb, hkv = page_size, block_pages, kv_heads
    g = hq // hkv
    rows = g * bq
    cur = cur_ref[b]
    n_blk = pl.cdiv(npages_ref[b], pb)
    mxu = k_scr.dtype

    def copies(slot, blk):
        """A turn's pages, one DMA a page and plane; entries past the
        row's cached pages name whatever the table holds there (the
        chunk's own pages, the null page): no query's bit is set."""
        out = []
        for p in range(pb):
            page = pt_ref[b, blk * pb + p]
            for pi, (src, dst) in enumerate(
                ((k_hbm, k_scr), (v_hbm, v_scr))
            ):
                out.append(pltpu.make_async_copy(
                    src.at[li, page],
                    dst.at[slot, pl.ds(p * s, s)],
                    sem.at[pi, slot],
                ))
        return out

    @pl.when(n_blk > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    m_scr[...] = jnp.full(m_scr.shape, _MASKED, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def fold(keys: int, keep):
        """One turn of every KV head's online softmax over the first
        `keys` rows of `kh_scr` / `vh_scr` [Hkv, K, D] under `keep` [BQ,
        keys]: ONE set a query token, the same for all its heads. A loop
        over the heads, so that a turn's body is compiled once (the four
        heads unrolled in each of three turns took the program's compile
        from 18 to 35 s, and a cold run's requests past their clients'
        120 s without a byte: PERF.md 6, PR 43)."""
        def head(h, _):
            q = q_ref[0, pl.ds(h * g, g)].reshape(rows, d)
            sc = jax.lax.dot_general(
                q, kh_scr[h, :keys], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G * BQ, K]
            sc = jnp.where(
                keep[None], sc.reshape(g, bq, -1), _MASKED).reshape(rows, -1)
            m_old = m_scr[h, :, :1]
            m_new = jnp.maximum(m_old, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_old - m_new)
            l_new = corr * l_scr[h, :, :1] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = corr * acc_scr[h] + jax.lax.dot_general(
                p.astype(mxu), vh_scr[h, :keys], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
            return 0

        jax.lax.fori_loop(0, hkv, head, 0)

    # -- the chunk over itself: key blocks wholly above the tile skipped ----
    def own(j, _):
        start = j * block_cur
        if not isinstance(j, int):  # a traced turn: whole lane tiles
            start = pl.multiple_of(start, block_cur)
        at = pl.ds(start, block_cur)
        key = j * block_cur + jax.lax.broadcasted_iota(
            jnp.int32, (block_cur, 1), 0
        )
        for h in range(hkv):  # a head's rows, a head a plane
            kh_scr[h, :block_cur] = kcur_ref[0, at, h, :].astype(mxu)
            # rows past `cur` may hold anything: zeros as values
            vh_scr[h, :block_cur] = jnp.where(
                key < cur, vcur_ref[0, at, h, :], 0).astype(mxu)
        fold(block_cur, mo_ref[0, :, at].astype(jnp.int32) != 0)
        return 0

    if kcur_ref.shape[1] == block_cur:  # one turn, whatever its width
        own(0, 0)
    else:  # several: `block_cur` is whole pages and whole lane tiles
        jax.lax.fori_loop(0, pl.cdiv((qi + 1) * bq, block_cur), own, 0)

    # -- the cached pages, `pb` a turn ----------------------------------------
    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blk)
        def _():
            for cp in copies(1 - slot, i + 1):
                cp.start()

        for cp in copies(slot, i):
            cp.wait()
        at = pl.ds(pl.multiple_of(i * (pb * s), pb * s), pb * s)
        # a head's rows out of the pages' [K, Hkv, D] through float32, as
        # `paged_prefill_attention` takes them
        kp = k_scr[slot].astype(jnp.float32)
        vp = v_scr[slot].astype(jnp.float32)
        for h in range(hkv):
            kh_scr[h, :pb * s] = kp[:, h].astype(mxu)
            vh_scr[h, :pb * s] = vp[:, h].astype(mxu)
        fold(pb * s, mh_ref[0, :, at].astype(jnp.int32) != 0)
        return 0

    jax.lax.fori_loop(0, n_blk, body, 0)
    for h in range(hkv):
        inv = 1.0 / jnp.maximum(l_scr[h, :, :1], 1e-30)
        o_ref[0, h * g:(h + 1) * g] = (acc_scr[h] * inv).astype(
            o_ref.dtype).reshape(g, bq, d)


def token_chunk_attention(
    q: jax.Array,  # [B, T, Hq, D] SCALED, model dtype
    k_cur: jax.Array,  # [B, T, Hkv, D] this chunk's keys
    v_cur: jax.Array,  # [B, T, Hkv, D]
    k_cache: jax.Array,  # [L, P, S, Hkv, D] the K pool (history)
    v_cache: jax.Array,
    layer: jax.Array,  # scalar int32
    tables: jax.Array,  # [B, MP] int32
    chosen: jax.Array,  # [B, T, MP * S] bool: each query's keys, by position
    hist: jax.Array,  # [B] int32: tokens cached before the chunk, whole pages
    valid: jax.Array,  # [B, T] bool, a prefix of each row
    *,
    interpret: bool | None = None,
):
    """`softmax(q . K) . V` of each chunk query over the KEYS `chosen`
    names for it (at positions up to its own): the cached ones from the
    pools' pages, every cached page of the row read once a tile of
    `CHUNK_BLOCK_Q` queries and `CHUNK_BLOCK_PAGES` a turn, the chunk's
    own from `k_cur` / `v_cur` (not cached yet). One set a query token: the mask is shared by a tile's KV heads,
    each an online softmax of its own in the same grid cell.

    Returns [B, T, Hq, D] in the queries' dtype, rows past a row's valid
    prefix unspecified."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, hq, d = q.shape
    s, hkv = k_cache.shape[2], k_cache.shape[3]
    mp = tables.shape[1]
    if k_cache.shape[-1] != d or (k_cur.shape, v_cur.shape) != (
            (b, t, hkv, d),) * 2 or chosen.shape != (b, t, mp * s):
        raise ValueError(
            f"pools {k_cache.shape}, rows {k_cur.shape} / {v_cur.shape}, "
            f"q {q.shape}, chosen {chosen.shape}"
        )
    bq, tp, pb, cur = chunk_blocking(t, s, mp)
    mpp = _round_up(mp, pb)
    i8 = jnp.int8
    pos = jnp.arange(mp * s, dtype=jnp.int32)[None, None]
    live = chosen & valid[..., None]
    m_hist = (live & (pos < hist[:, None, None])).astype(i8)
    # the chunk's own keys: columns `hist` .. `hist + T` of the mask
    own = jax.vmap(lambda m, h: jax.lax.dynamic_slice_in_dim(
        jnp.pad(m, ((0, 0), (0, t))), h, t, axis=1))(live, hist)
    m_own = own.astype(i8)
    pad_t = ((0, 0), (0, tp - t))
    m_hist = jnp.pad(m_hist, pad_t + ((0, (mpp - mp) * s),))
    m_own = jnp.pad(m_own, pad_t + ((0, tp - t),))
    if tp != t:
        q, k_cur, v_cur = (
            jnp.pad(x, pad_t + ((0, 0),) * (x.ndim - 2))
            for x in (q, k_cur, v_cur)
        )
    nt = tp // bq
    g = hq // hkv

    def q_block():
        return pl.BlockSpec(
            (1, hq, bq, d), lambda bi, qi, *_: (bi, 0, qi, 0))

    def chunk_block():
        return pl.BlockSpec(
            (1, tp, hkv, d), lambda bi, qi, *_: (bi, 0, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _token_kernel, page_size=s, block_pages=pb, block_cur=cur,
            kv_heads=hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, nt),
            in_specs=[
                q_block(),
                pl.BlockSpec((1, bq, mpp * s),
                             lambda bi, qi, *_: (bi, qi, 0)),
                pl.BlockSpec((1, bq, tp), lambda bi, qi, *_: (bi, qi, 0)),
                chunk_block(), chunk_block(),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_block(),
            scratch_shapes=[
                pltpu.VMEM((2, pb * s, hkv, d), k_cache.dtype),
                pltpu.VMEM((2, pb * s, hkv, d), v_cache.dtype),
                pltpu.VMEM((hkv, max(pb * s, cur), d), k_cache.dtype),
                pltpu.VMEM((hkv, max(pb * s, cur), d), v_cache.dtype),
                pltpu.VMEM((hkv, g * bq, 128), jnp.float32),
                pltpu.VMEM((hkv, g * bq, 128), jnp.float32),
                pltpu.VMEM((hkv, g * bq, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, tp, d), q.dtype),
        interpret=interpret,
        name="token_chunk_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, mpp - mp))),
        (hist // s).astype(jnp.int32),
        jnp.sum(valid, axis=1).astype(jnp.int32),
        q.transpose(0, 2, 1, 3),  # head-major: a tile's [Hq, BQ, D] block
        m_hist, m_own, k_cur, v_cur, k_cache, v_cache,
    )
    return out[:, :, :t].transpose(0, 2, 1, 3)
