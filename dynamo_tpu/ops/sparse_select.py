"""Block-sparse attention that selects pages inside the page walk
(InfLLM-v2's dense-sparse switchable attention, models/minicpm_sala.py).

A query at position `t` with `n = t + 1` tokens of context attends

- over all of them while `n < dense_len`;
- from there on over `topk` BLOCKS of `block_size` keys, chosen for its KV
  head alone: the compressed keys `Kc_j = mean(k[stride j : stride j +
  kernel])` of every window that ends at or before `t` are scored by each
  query head of the KV head, `p_h = softmax_j(q_h . Kc_j * scale)`, summed
  over those heads, max-pooled onto the blocks a window touches (`B_b =
  max P_j, j in [cpb b - pad, cpb b + cpb - 1]`, `cpb = block / stride`
  windows start in a block and `pad = kernel / stride - 1` more reach into
  it from the block before); the first `init_blocks` blocks and the blocks
  that hold the last `window_size` tokens always count as chosen; ties go
  to the earlier block.

A block IS a page (`EngineConfig.page_size == block_size`), and a KV head
IS a sequence of its own here: the cache of a sparse layer is laid out
[L, P * Hkv, S, 1, D] (page `p` of KV head `h` at `p * Hkv + h`), the
layout of an MQA cache, so a row of `Hkv` KV heads is `Hkv` VIRTUAL rows of
one KV head each with `Hq / Hkv` query heads, and a virtual row's page
table `tables[b] * Hkv + h` may name ANY of its pages in any number. The
decode walk (ops/paged_attention.py) and the staged cache write
(ops/kv_update.py) take such rows as they take any other: a selected list
is a short page table whose pages are all full but the last
(`decode_lists`). A prompt chunk takes its selection as a mask: by tile of
queries over the union of their pages in ops/sparse_chunk.py, and off the
TPU as dense scores under it, `masked_attention` here.

Beside a page live its `cpb` compressed keys, in a pool [L, P * Hkv * cpb,
D] (row `(p * Hkv + h) * cpb + j % cpb` for the window `j` that STARTS in
the page; the last of a page reaches `kernel - stride` tokens into the
next). A window's key is written by the step that computes its last token
(`fresh_windows`, `land_compressed`), from the chunk's own keys and the
`kernel - 1` cached ones before them. Nothing reads a window that ends
after the query, so what a dispatch that is rolled back wrote is never
seen and is written again when the sequence comes by for good; a page that
is freed takes its compressed keys with it.

Everything here is plain `jax.numpy`. On the TPU (`attention_impl` pallas
/ hybrid) the selection (scope `attn/select`) reads the pool IN PLACE: the
kernel of ops/block_scores.py streams a sequence's compressed keys page
by page through VMEM and writes the rule's block scores, `blocks_of_scores`
picks the top `topk` of them by counting passes (ops/token_select.py
`select_tokens`: exact, ties to the earlier block, no sort) and
`decode_lists(counted=True)` lays the walk's list out by prefix sums.
`gather_compressed`, `with_fresh`, `select_blocks` and `_page_lists` (a
gathered copy of the row's compressed keys with the fresh windows put in,
one small batched matmul, a softmax and three sorts of a few hundred
blocks) are the path without kernels, and what the kernel path is judged
against (tests/test_block_scores.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.ops.token_select import select_tokens

#: a score no pooled probability reaches: blocks that always count as chosen
_FORCED = 1e30
#: bytes of float32 scores a tile of `masked_attention` holds
_SCORE_TILE_BYTES = 96 << 20


class SparseDims(NamedTuple):
    """MiniCPM4's `sparse_config`."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    @property
    def per_block(self) -> int:
        """Compressed keys whose window starts in one block."""
        return self.block_size // self.kernel_stride

    @property
    def reach(self) -> int:
        """Windows of the block before that reach into a block."""
        return self.kernel_size // self.kernel_stride - 1

    @property
    def list_pages(self) -> int:
        """The longest list a decode row walks: `topk` pages, or every
        page of a row that is still dense."""
        return max(self.topk, -(-self.dense_len // self.block_size))

    def check(self, page_size: int) -> None:
        if page_size != self.block_size:
            raise ValueError(
                f"a selection block is a page: --page-size {page_size} "
                f"must equal the model's block_size {self.block_size}"
            )
        if (self.block_size % self.kernel_stride
                or self.kernel_size % self.kernel_stride):
            raise ValueError("kernel_stride must divide kernel_size and "
                             "block_size")


# ---------------------------------------------------------------------------
# Compressed keys
# ---------------------------------------------------------------------------


def history_tail(k_cache, layer, tables, start, n: int):
    """The `n` cached keys before position `start[b]` of each (virtual)
    row, oldest first: [B, n, D] from a one-row cache [L, P, S, 1, D];
    zeros where the row has no such position."""
    s = k_cache.shape[2]
    pos = start[:, None] - n + jnp.arange(n, dtype=jnp.int32)[None]
    ok = pos >= 0
    pos = jnp.maximum(pos, 0)
    page = jnp.take_along_axis(tables, pos // s, axis=1)
    rows = k_cache[layer, page, pos % s, 0]  # one gather, no pool slice
    return jnp.where(ok[..., None], rows, 0)


def fresh_windows(k_chunk, tail, positions, valid, dims: SparseDims):
    """The compressed keys of the windows that END inside a chunk.
    k_chunk [B, T, D] the chunk's keys, tail [B, kernel - 1, D] the cached
    keys before it. Returns (kc [B, T, D]: at chunk token i the mean of
    the `kernel` keys ending there; ends [B, T]: token i ends a window; j
    [B, T]: which)."""
    kk, st = dims.kernel_size, dims.kernel_stride
    f32 = jnp.float32
    ext = jnp.concatenate([tail.astype(f32), k_chunk.astype(f32)], axis=1)
    csum = jnp.cumsum(ext, axis=1)
    csum = jnp.pad(csum, ((0, 0), (1, 0), (0, 0)))
    t = k_chunk.shape[1]
    kc = (csum[:, kk : kk + t] - csum[:, :t]) / kk
    n = positions + 1
    ends = valid & (n % st == 0) & (n >= kk)
    return kc.astype(k_chunk.dtype), ends, (n - kk) // st


def gather_compressed(kc_pool, layer, tables, dims: SparseDims,
                      heads: int = 1):
    """The (virtual) rows' compressed keys in window order: [B, MP * cpb,
    D] from the pool [L, P * cpb, D]. `heads` > 1: the rows come `heads`
    a sequence (b * heads + h, pages p * heads + h), whose compressed
    keys of one page lie side by side in the pool: one slice of `heads x
    cpb` rows a page serves them all (half the slices, twice the size)."""
    cpb = dims.per_block
    b, mp = tables.shape
    d = kc_pool.shape[-1]
    starts = (tables[::heads] * cpb).reshape(-1)
    out = jax.vmap(lambda at: lax.dynamic_slice(
        kc_pool, (layer, at, 0), (1, heads * cpb, d)))(starts)
    out = out.reshape(b // heads, mp, heads, cpb, d)
    return jnp.moveaxis(out, 2, 1).reshape(b, mp * cpb, d)


def with_fresh(kc_hist, fresh, ends, start, dims: SparseDims):
    """`kc_hist` [B, NC, D] with the windows that end inside the chunk
    (which the pool does not hold yet) put in their places."""
    kk, st = dims.kernel_size, dims.kernel_stride
    t = fresh.shape[1]
    j = jnp.arange(kc_hist.shape[1], dtype=jnp.int32)[None]
    i = j * st + (kk - 1) - start[:, None]  # the chunk token that ends j
    inside = (i >= 0) & (i < t)
    i = jnp.clip(i, 0, t - 1)
    inside &= jnp.take_along_axis(ends, i, axis=1)
    picked = jnp.take_along_axis(fresh, i[..., None], axis=1)
    return jnp.where(inside[..., None], picked, kc_hist)


def land_compressed(kc_pool, staged, tables, dims: SparseDims):
    """Write a step's fresh compressed keys, all layers at once. `staged`
    is (kc [L, B, T, D], ends [B, T], j [B, T]); what ends no window is
    dropped."""
    kc, ends, j = staged
    cpb = dims.per_block
    page = jnp.take_along_axis(
        tables, jnp.clip(j // cpb, 0, tables.shape[1] - 1), axis=1)
    rows = jnp.where(ends, page * cpb + j % cpb, kc_pool.shape[1])
    return kc_pool.at[:, rows.reshape(-1)].set(
        kc.reshape(kc.shape[0], -1, kc.shape[-1]).astype(kc_pool.dtype),
        mode="drop",
    )


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------


def pooled_scores(q, kc, positions, dims: SparseDims, scale: float):
    """The rule's block scores of each query for its KV head, before the
    forced blocks: q [B, T, G, D] the KV head's query heads, kc [B, NB *
    cpb, D] the (virtual) row's compressed keys, positions [B, T].
    float32 [B, T, NB]."""
    f32 = jnp.float32
    kk, st = dims.kernel_size, dims.kernel_stride
    cpb, reach = dims.per_block, dims.reach
    nc = kc.shape[1]
    nb = nc // cpb
    n = positions + 1
    last = jnp.where(n >= kk, (n - kk) // st, -1)  # the newest whole window
    seen = jnp.arange(nc, dtype=jnp.int32) <= last[..., None]  # [B, T, NC]
    sc = jnp.einsum("btgd,bjd->btgj", q, kc, preferred_element_type=f32)
    sc = jnp.where(seen[:, :, None], sc * scale, -1e30)
    p = jax.nn.softmax(sc, axis=-1) * seen[:, :, None]
    pj = jnp.pad(p.sum(axis=2), ((0, 0), (0, 0), (reach, 0)),
                 constant_values=-1.0)  # [B, T, reach + NC]
    score = pj[..., :nc].reshape(*pj.shape[:2], nb, cpb).max(axis=-1)
    for r in range(reach):  # the windows that start in the block before
        score = jnp.maximum(score, pj[..., cpb + r :: cpb][..., :nb])
    return score


def ranked_blocks(score, positions, dims: SparseDims):
    """The selection from the rule's block scores [B, T, NB], by two
    sorts: every block up to the query's own while its context is under
    `dense_len`, else the `topk` of the rule in this module's docstring.
    bool [B, T, NB]."""
    score, exists, dense = _candidates(score, positions, dims)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return exists & (dense | (rank < dims.topk))


def select_blocks(q, kc, positions, dims: SparseDims, scale: float):
    """Which blocks each query attends over, for its KV head:
    `ranked_blocks` of `pooled_scores`."""
    return ranked_blocks(pooled_scores(q, kc, positions, dims, scale),
                         positions, dims)


def _candidates(score, positions, dims: SparseDims):
    """(The block scores [.., NB] of queries at `positions` [..] as the
    rule ranks them: `_FORCED` where a block always counts as chosen,
    `-_FORCED` past the query's own; exists [.., NB]: the blocks up to
    the query's own; dense [.., 1]: the query's context is under
    `dense_len`)."""
    s = dims.block_size
    blk = jnp.arange(score.shape[-1], dtype=jnp.int32)
    own = (positions // s)[..., None]
    near = (jnp.maximum(positions - dims.window_size + 1, 0) // s)[..., None]
    exists = blk <= own
    forced = (blk < dims.init_blocks) | (blk >= near)
    score = jnp.where(forced, _FORCED, score)
    score = jnp.where(exists, score, -_FORCED)
    return score, exists, (positions + 1 < dims.dense_len)[..., None]


def blocks_of_scores(score, positions, dims: SparseDims):
    """`ranked_blocks`' selection from the rule's block scores [B, T, NB]
    (ops/block_scores.py), with no sort: the `topk` highest of a query's
    existing blocks, ties to the earlier, by the counting passes of
    ops/token_select.py."""
    b, t, nb = score.shape
    # a row a (virtual row, query) from here on: candidates made in three
    # axes and flattened after are a reshape XLA's TPU compiler refuses
    # beside four pieces ("Reshape should have supported layout")
    positions = positions.reshape(-1)
    score, exists, dense = _candidates(
        score.reshape(b * t, nb), positions, dims)
    context = jnp.minimum(positions // dims.block_size + 1, nb)
    top = select_tokens(score, context.astype(jnp.int32), dims.topk)
    return (exists & (dense | top)).reshape(b, t, nb)


def _page_lists(has, tables, k: int):
    """The pages of `tables` [B, MP] that `has` [B, NB] marks, in
    ascending block order, then zeros: (pages [B, k], how many)."""
    idx = jnp.argsort(~has, axis=-1, stable=True)[:, :k].astype(jnp.int32)
    count = has.sum(axis=-1).astype(jnp.int32)
    pages = jnp.take_along_axis(tables, idx, axis=1)
    if pages.shape[1] < k:
        pages = jnp.pad(pages, ((0, 0), (0, k - pages.shape[1])))
    return jnp.where(jnp.arange(k)[None] < count[:, None], pages, 0), count


def _counted_lists(has, tables, k: int):
    """`_page_lists` with no sort: the i-th marked block's page goes to
    slot `cumsum(has) - 1`, a one-hot sum a slot."""
    nb = has.shape[1]
    slot = jnp.cumsum(has, axis=-1, dtype=jnp.int32) - 1
    hit = has[..., None] & (
        slot[..., None] == jnp.arange(k, dtype=jnp.int32)[None, None])
    pages = jnp.sum(jnp.where(hit, tables[:, :nb, None], 0), axis=1)
    return pages.astype(tables.dtype), slot[:, -1] + 1


def decode_lists(selected, tables, hist, dims: SparseDims,
                 counted: bool = False):
    """A decode row's selection as what the page walk takes: (pages [B,
    K] of `tables`, the selected blocks that hold cached tokens in
    ascending order, then zeros; lens [B]: the tokens those pages hold,
    every page full but the last). selected [B, NB], hist [B] the tokens
    already cached (the query's own position). `counted`: by prefix sums
    (the TPU path), not a sort; the same lists to the bit."""
    s = dims.block_size
    blk = jnp.arange(selected.shape[1], dtype=jnp.int32)[None]
    pages, count = (_counted_lists if counted else _page_lists)(
        selected & (blk * s < hist[:, None]), tables, dims.list_pages)
    tail = hist - s * ((hist - 1) // s)  # tokens in the last cached block
    lens = jnp.where(hist > 0, s * (count - 1) + tail, 0)
    return pages, lens.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Attention under a block mask (every step off the TPU)
# ---------------------------------------------------------------------------


def dense_blocks(positions, n_blocks: int, dims: SparseDims):
    """The selection of queries that all stand under `dense_len`: every
    block up to the query's own. [B, T, NB] bool."""
    blk = jnp.arange(n_blocks, dtype=jnp.int32)
    return blk <= (positions // dims.block_size)[..., None]


def masked_attention(
    q,  # [B, T, G, D]
    k_cache, v_cache,  # [L, P, S, 1, Dc]: one-row caches
    layer,
    tables,  # [B, MP]
    q_pos,  # [B, T]
    selected,  # [B, T, MP]
    dims: SparseDims,
    scale: float,
    hist_len: Optional[jax.Array] = None,  # [B]: cached keys that count
    k_cur=None, v_cur=None,  # [B, T, D]: the chunk's own keys, not cached
    cur_pos=None,  # [B, T]; out of reach where a token is padding
):
    """Causal softmax attention of each query over the keys of its
    selected blocks: the sums of a walk over those blocks, computed as
    dense scores under a mask with one online softmax over TILES of the
    row's pages, as many tiles as the longest row has history (a loop
    with a dynamic end: a chunk early in its prompt pays for the keys it
    has, not for `max_context`), then over the chunk's own keys. With
    `hist_len` None every key is read from the cache (the scatter
    discipline wrote the chunk there first). Returns [B, T, G, D] in q's
    dtype."""
    f32 = jnp.float32
    b, t, g, d = q.shape
    s, mp = k_cache.shape[2], tables.shape[1]
    if hist_len is None:
        hist_len = jnp.max(q_pos, axis=1) + 1
    pt = 1  # pages a tile: the most under the score budget, a power of two
    while pt * 2 <= mp and b * t * g * pt * 2 * s * 4 <= _SCORE_TILE_BYTES:
        pt *= 2
    pad = -mp % pt
    tables = jnp.pad(tables, ((0, 0), (0, pad)))
    selected = jnp.pad(selected, ((0, 0), (0, 0), (0, pad)))

    def fold(carry, sc, keep, vals):
        m, l, acc = carry
        sc = jnp.where(keep[:, :, None], sc * scale, -1e30)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.where(keep[:, :, None], jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        pv = jnp.einsum("btgk,bkd->btgd", p.astype(vals.dtype), vals,
                        preferred_element_type=f32)
        return (m_new, l * corr + p.sum(axis=-1),
                acc * corr[..., None] + pv)

    def tile(i, carry):
        pages = lax.dynamic_slice(tables, (0, i * pt), (b, pt))
        rows = lambda c: c[layer, pages, :, 0].reshape(  # noqa: E731
            b, pt * s, c.shape[-1])[..., :d]
        pos = i * (pt * s) + jnp.arange(pt * s, dtype=jnp.int32)
        keep = jnp.repeat(
            lax.dynamic_slice(selected, (0, 0, i * pt), (b, t, pt)),
            s, axis=-1)
        keep &= (pos[None] < hist_len[:, None])[:, None, :]
        keep &= pos[None, None, :] <= q_pos[..., None]
        kt = rows(k_cache)
        sc = jnp.einsum("btgd,bkd->btgk", q, kt, preferred_element_type=f32)
        return fold(carry, sc, keep, rows(v_cache))

    carry = (jnp.full((b, t, g), -1e30, f32), jnp.zeros((b, t, g), f32),
             jnp.zeros((b, t, g, d), f32))
    n_tiles = (jnp.max(hist_len) + pt * s - 1) // (pt * s)
    carry = lax.fori_loop(0, n_tiles, tile, carry)
    if k_cur is not None:
        blk = jnp.clip(cur_pos // s, 0, selected.shape[-1] - 1)
        keep = jnp.take_along_axis(
            selected, jnp.broadcast_to(blk[:, None, :], (b, t, blk.shape[1])),
            axis=-1)
        keep &= cur_pos[:, None, :] <= q_pos[..., None]
        sc = jnp.einsum("btgd,bkd->btgk", q, k_cur,
                        preferred_element_type=f32)
        carry = fold(carry, sc, keep, v_cur)
    _, l, acc = carry
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
