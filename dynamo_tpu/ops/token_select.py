"""Attention over TOKENS a learned indexer chooses (DeepSeek-Sparse-
Attention's lightning indexer, models/keye_vl.py): a query at position `t`
attends the `min(topk, t + 1)` cached tokens `s <= t` of highest index score

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (float32)

over `j` index heads and ONE index key `kI[s]` a token, ties to the earlier
token, one set a query token whatever its attention heads. With `t + 1 <=
topk` that is causal dense attention.

The index keys are a page's third resident: a pool beside the K and V
pools [L, P, S, Hkv, D], a row a token at its page's slot (models/
keye_vl.py `index_pool`), staged and landed with the token's K and V,
read through the row's page table, freed and shared with the page.
Nothing reads a slot past the query's position, so what a dispatch that
is rolled back wrote is never seen and is written again when the sequence
comes by for good.

The selection is EXACT and has no sort: the k-th highest score of a row is
found by bisection over the float's bits (`kth_key`: 32 counting passes
over the row's scores, whatever `k`), equal scores at the threshold go to
the earlier positions by a second bisection over the position (skipped
where no row has a tie to break). Both kinds of step take the selection
as a mask a (query, key): a prompt chunk through ops/sparse_chunk.py
`token_chunk_attention`, a decode row through the page walk of
ops/paged_attention.py (`token_bits`). (A gather of the chosen tokens' K
and V rows, 1 KB each, in place of the walk was measured and deleted:
2.62 ms a layer at 32 rows whatever the context against 0.78 / 1.23 /
1.63 ms for the walk under bits at 8k / 13k / 18k, PERF.md 6, PR 43.)

Everything here is plain `jax.numpy` (scopes `attn/index`,
`attn/select`); off the TPU attention is dense scores under the mask
(`masked_attention`). On the TPU the scores come from the kernel of
ops/index_scores.py, which reads a row's index keys out of the pool in
place; `index_scores` over a gathered copy is the path without kernels
and what that kernel is judged against (tests/test_index_scores.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_MASKED = -1e30
#: bytes of float32 scores a tile of `masked_attention` holds
_SCORE_TILE_BYTES = 96 << 20


def index_scores(qi, w, ki):
    """I[.., t, s]: qi [B, T, J, Di] the index queries, w [B, T, J] their
    heads' weights (float32, scaled by the caller), ki [B, N, Di] the
    row's index keys in position order. float32 [B, T, N]. A head at a
    time, so that the [T, J, N] products are never held at once."""
    f32 = jnp.float32
    if qi.shape[1] == 1:  # a decode row: its heads at once
        s = jnp.einsum("bjd,bnd->bjn", qi[:, 0], ki,
                       preferred_element_type=f32)
        return jnp.sum(jnp.maximum(s, 0.0) * w[:, 0, :, None].astype(f32),
                       axis=1)[:, None]
    out = None
    for j in range(qi.shape[2]):
        s = jnp.einsum("btd,bnd->btn", qi[:, :, j], ki,
                       preferred_element_type=f32)
        s = jnp.maximum(s, 0.0) * w[:, :, j, None].astype(f32)
        out = s if out is None else out + s
    return out


def sort_key(x):
    """float32 -> uint32, order-preserving (-inf lowest); 0 is below every
    number's key and marks a position that is not a candidate."""
    x = x.astype(jnp.float32)
    x = jnp.where(x == 0, 0.0, x)  # -0.0 ties with 0.0
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    neg = (bits >> 31) == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def kth_key(keys, k):
    """The largest u with count(keys >= u) >= k, a row: keys [R, N]
    uint32, k [R] int32. Built from the top bit down, 32 counting passes.
    k <= 0 gives 2**32 - 1 and k past the row's candidates 0."""
    def bit(i, cur):
        cand = cur | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, cur)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:1], jnp.uint32))


def select_tokens(scores, context, topk: int):
    """bool [R, N]: the `min(topk, context)` highest of a row's first
    `context` scores, ties to the earlier position. scores [R, N]
    float32, context [R] int32 (0: nothing)."""
    r, n = scores.shape
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    live = pos < context[:, None]
    keys = jnp.where(live, sort_key(scores), jnp.uint32(0))
    k = jnp.minimum(context, topk).astype(jnp.int32)
    thr = kth_key(keys, k)[:, None]
    at_least = (keys >= thr) & live

    def break_ties():
        """More keys equal the threshold than places are left: the
        earliest of them, by bisection over the position."""
        above = (keys > thr) & live
        ties = (keys == thr) & live
        need = (k - jnp.sum(above, axis=1, dtype=jnp.int32))[:, None]
        bits = max(1, math.ceil(math.log2(n + 1)))

        def bit(i, cut):  # the largest cut with count(ties before it) <= need
            cand = cut | (jnp.int32(1) << (bits - 1 - i))
            took = jnp.sum(ties & (pos < cand), axis=1, keepdims=True,
                           dtype=jnp.int32)
            return jnp.where(took <= need, cand, cut)

        cut = lax.fori_loop(0, bits, bit, jnp.zeros((r, 1), jnp.int32))
        return above | (ties & (pos < cut))

    exact = jnp.all(jnp.sum(at_least, axis=1, dtype=jnp.int32) == k)
    return lax.cond(exact, lambda: at_least, break_ties)


def masked_attention(q, k, v, mask):
    """Dense float32 attention under a mask a (query, key): q [B, T, Hq,
    D] SCALED, k, v [B, N, Hkv, D], mask [B, T, N] bool. float32 [B, T,
    Hq, D]. The path without kernels, and what the kernels are judged
    against; query tiles keep the scores under `_SCORE_TILE_BYTES`."""
    b, t, hq, d = q.shape
    n, hkv = k.shape[1], k.shape[2]
    f32 = jnp.float32
    kf, vf = k.astype(f32), v.astype(f32)

    def tile(args):
        qt, mt = args  # [B, tq, Hq, D], [B, tq, N]
        qg = qt.astype(f32).reshape(b, -1, hkv, hq // hkv, d)
        sc = jnp.einsum("bthgd,bnhd->bhgtn", qg, kf)
        p = jax.nn.softmax(jnp.where(mt[:, None, None], sc, _MASKED), -1)
        return jnp.einsum("bhgtn,bnhd->bthgd", p, vf).reshape(b, -1, hq, d)

    tq = t
    while tq % 2 == 0 and b * hq * tq * n * 4 > _SCORE_TILE_BYTES:
        tq //= 2
    if tq == t:
        return tile((q, mask))
    out = lax.map(tile, (
        jnp.moveaxis(q.reshape(b, t // tq, tq, hq, d), 1, 0),
        jnp.moveaxis(mask.reshape(b, t // tq, tq, n), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, hq, d)
