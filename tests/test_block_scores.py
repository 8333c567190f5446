"""The block scores out of the compressed-key pool in place
(ops/block_scores.py `paged_block_scores`, interpreted here) against what
they replace on the TPU: `ss.pooled_scores` over the gathered copy
`ss.gather_compressed` / `ss.with_fresh` make. Every existing block's
score, the fresh windows put in, the selection made from the two the same
bits (`ss.blocks_of_scores` by counting passes against `ss.ranked_blocks`
by sorts), and the walk's lists by prefix sums against the sorted form."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import minicpm_sala as sala
from dynamo_tpu.ops import block_scores as bs
from dynamo_tpu.ops import sparse_select as ss

HKV = 2
#: (query heads a KV head, head dim, dtype, the rule): the tiny preset's,
#: and the published 16 heads x 128 with 4 compressed keys a block of 64
#: in bf16 (the rule's lengths cut so that a case is 20-40 pages)
TINY = (2, 16, jnp.float32, sala.MiniCPMSALAConfig.tiny().sparse)
PUBLISHED = (16, 128, jnp.bfloat16, ss.SparseDims(
    kernel_size=32, kernel_stride=16, block_size=64, init_blocks=1,
    window_size=256, topk=8, dense_len=1024))
WIDTHS = [pytest.param(TINY, 5e-6, id="tiny"),
          pytest.param(PUBLISHED, 2e-5, id="16-heads-of-128-bf16")]


def _setup(widths, starts, t, mp, cur=None, layers=2, seed=0):
    """Seeded operands as `sparse_mixer` hands them on: sequences that
    start a step at `starts` with `cur` valid tokens of `t`, a KV head a
    virtual row (b * Hkv + h), the fresh windows' keys random."""
    g, d, dtype, dims = widths
    b = len(starts)
    rng = np.random.default_rng(seed)
    pages = b * mp + 1
    pool = jnp.asarray(rng.standard_normal(
        (layers, pages * HKV * dims.per_block, d)), dtype)
    pt = np.stack([rng.permutation(np.arange(1, pages))[:mp]
                   for _ in range(b)]).astype(np.int32)
    tables = jnp.asarray((pt[:, None, :] * HKV + np.arange(HKV)[
        None, :, None]).reshape(b * HKV, mp))
    start = np.repeat(np.asarray(starts, np.int32), HKV)
    cur = np.repeat(np.asarray(cur if cur else [t] * b, np.int32), HKV)
    pos = jnp.asarray(start[:, None] + np.arange(t, dtype=np.int32)[None])
    valid = jnp.asarray(np.arange(t)[None] < cur[:, None])
    q = jnp.asarray(rng.standard_normal((b * HKV, t, g, d)), dtype)
    n = pos + 1
    fresh = (jnp.asarray(rng.standard_normal((b * HKV, t, d)), dtype),
             valid & (n % dims.kernel_stride == 0) & (n >= dims.kernel_size),
             (n - dims.kernel_size) // dims.kernel_stride)
    return pool, tables, pos, valid, q, fresh


def _both(widths, pool, layer, tables, pos, valid, q, fresh):
    """(XLA's block scores over the gathered copy, the kernel's out of
    the pool), float32 [B', T, NB]."""
    g, d, _, dims = widths
    scale = 1.0 / math.sqrt(d)
    layer = jnp.int32(layer)
    kc = ss.with_fresh(
        ss.gather_compressed(pool, layer, tables, dims, heads=HKV),
        fresh[0], fresh[1], pos[:, 0], dims)
    ref = ss.pooled_scores(q, kc, pos, dims, scale)
    got = bs.paged_block_scores(q, pool, layer, tables, pos, valid, fresh,
                                dims, scale, HKV)
    return np.asarray(ref), np.asarray(got)


def _judge(widths, ref, got, pos, valid, tol):
    """Every block a valid query may choose reads the same score, and the
    selections made from the two are the same bits: exactly `topk` blocks
    past `dense_len`, every block under it."""
    dims = widths[3]
    pos, valid = np.asarray(pos), np.asarray(valid)
    own = pos // dims.block_size
    live = valid[..., None] & (
        np.arange(ref.shape[-1])[None, None] <= own[..., None])
    assert live.any()
    assert np.max(np.abs(np.where(live, got - ref, 0.0))) <= tol
    mine = np.asarray(ss.blocks_of_scores(jnp.asarray(got), pos, dims))
    theirs = np.asarray(ss.ranked_blocks(jnp.asarray(ref), pos, dims))
    np.testing.assert_array_equal(mine[valid], theirs[valid])
    want = np.where(pos + 1 < dims.dense_len, own + 1,
                    np.minimum(own + 1, dims.topk))
    assert (mine.sum(axis=-1) == want)[valid].all()
    return mine


@pytest.mark.parametrize("widths,tol", WIDTHS)
@pytest.mark.parametrize("layer", [0, 1])
def test_decode_scores_are_xlas_for_rows_of_unequal_context(
        widths, tol, layer):
    """Decode rows in one call: a long one past `dense_len` whose token
    ends a window (the fresh key put in), one past it whose token ends
    none (tiny: every token ends one), a row under `dense_len` beside
    them, a row with no history, a row shorter than a window, a padding
    row."""
    dims = widths[3]
    st, s = dims.kernel_stride, dims.block_size
    mp = 3 * dims.dense_len // s
    ends = 2 * dims.dense_len - dims.dense_len % st + st - 1  # n % st == 0
    starts = [ends, ends - st // 2 - 1, dims.dense_len // 2, 0,
              dims.kernel_size - 2, mp * s - 1, 7]
    cur = [1] * (len(starts) - 1) + [0]
    pool, tables, pos, valid, q, fresh = _setup(
        widths, starts, 1, mp, cur, seed=layer)
    assert bool(fresh[1][0, 0]) and (st == 1 or not bool(fresh[1][2, 0]))
    ref, got = _both(widths, pool, layer, tables, pos, valid, q, fresh)
    _judge(widths, ref, got, pos, valid, tol)
    assert not got[-1].any() and not got[-2].any()  # the padding row


@pytest.mark.parametrize("widths,tol", WIDTHS)
@pytest.mark.parametrize("case", [
    "crosses-dense-len", "reaches-the-end-of-the-table", "ragged-pieces",
    "tail-of-two-prompts"])
def test_chunk_scores_are_xlas_with_the_fresh_windows_put_in(
        widths, tol, case, monkeypatch):
    """A prompt chunk by tiles of 8 queries (three or four grid steps a
    sequence over the keys fetched at its first): one whose queries cross
    `dense_len`, one whose padding rows run past `max_context`, two
    pieces of unequal history one of them part padding, and a tail of
    two prompts in the 32-row bucket."""
    monkeypatch.setattr(bs, "SELECT_BLOCK_Q", 8)
    dims = widths[3]
    s, dl = dims.block_size, dims.dense_len
    t = 32 if widths is TINY else 2 * s
    mp = (2 * dl + t) // s
    starts, cur = {
        "crosses-dense-len": ([dl - t // 2 - 1], None),
        "reaches-the-end-of-the-table": ([mp * s - 3], [3]),
        "ragged-pieces": ([dl + s, s, 0], [t, t - 5, t]),
        "tail-of-two-prompts": ([dl + s + 1, 2 * dl - s], [t // 2, t]),
    }[case]
    pool, tables, pos, valid, q, fresh = _setup(
        widths, starts, t, mp, cur, seed=len(case))
    ref, got = _both(widths, pool, 1, tables, pos, valid, q, fresh)
    _judge(widths, ref, got, pos, valid, tol)


def _crafted(rows, nb, levels, seed):
    """Block scores with many equal values, zeros among them."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, levels, (rows, 1, nb)) / 8.0,
                       jnp.float32)


@pytest.mark.parametrize("levels", [2, 3, 17])
def test_ties_at_the_last_rank_go_to_the_earlier_block(levels):
    """Scores of a few levels: the counting passes choose what the stable
    sorts choose, ties to the earlier block, at `topk` 64 of 288 blocks
    and at the tiny preset's 6 of 24."""
    for dims, nb, positions in (
            (ss.SparseDims(), 288, [18431, 12288, 8191, 8192, 64, 0]),
            (TINY[3], 24, [95, 40, 31, 32, 3, 0])):
        pos = jnp.asarray(positions, jnp.int32)[:, None]
        score = _crafted(len(positions), nb, levels, levels)
        mine = np.asarray(ss.blocks_of_scores(score, pos, dims))
        theirs = np.asarray(ss.ranked_blocks(score, pos, dims))
        np.testing.assert_array_equal(mine, theirs)
        # among equal scores none is chosen after one that is left out
        sc, sel = np.asarray(score)[0, 0], mine[0, 0]
        own = positions[0] // dims.block_size
        forced = np.asarray(ss._candidates(
            score, pos, dims)[0])[0, 0] >= ss._FORCED
        for level in np.unique(sc):
            at = np.flatnonzero((sc == level) & ~forced
                                & (np.arange(nb) <= own))
            chosen = sel[at]
            assert not (~chosen[:-1] & chosen[1:]).any()


@pytest.mark.parametrize("dims,nb,position", [
    pytest.param(ss.SparseDims(), 288, 18000, id="published"),
    pytest.param(ss.SparseDims(), 288, 8191, id="published-at-dense-len"),
    pytest.param(TINY[3], 24, 77, id="tiny"),
])
def test_the_forced_blocks_are_always_chosen(dims, nb, position):
    """The first block and the blocks of the last `window_size` tokens
    carry the LOWEST scores and are chosen all the same, the rest of the
    `topk` by score."""
    s = dims.block_size
    own = position // s
    near = max(position - dims.window_size + 1, 0) // s
    forced = (np.arange(nb) < dims.init_blocks) | (
        (np.arange(nb) >= near) & (np.arange(nb) <= own))
    rng = np.random.default_rng(position)
    score = np.where(forced, 0.0, 1.0 + rng.random(nb)).astype(np.float32)
    sel = np.asarray(ss.blocks_of_scores(
        jnp.asarray(score)[None, None], jnp.asarray([[position]]), dims))[0, 0]
    assert sel[forced].all() and sel.sum() == dims.topk
    rest = np.argsort(-np.where(forced | (np.arange(nb) > own), -1, score),
                      kind="stable")[:dims.topk - forced.sum()]
    assert sel[rest].all()


@pytest.mark.parametrize("positions", [
    [30], [31], [32], [33], [95, 17]])
def test_lists_by_prefix_sums_are_the_sorted_lists(positions):
    """`decode_lists(counted=True)` against the `argsort` form, for every
    case of tests/test_minicpm_sala.py's
    `test_pages_walked_counts_what_the_lists_name`: the same pages in the
    same places, zeros after, the same tokens a list."""
    cfg = sala.MiniCPMSALAConfig.tiny()
    rng = np.random.default_rng(0)
    n = len(positions) + 1  # and a padding row
    q = jnp.asarray(rng.normal(size=(n, 1, 2, 16)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(n, 32 * 4, 16)), jnp.float32)
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, 60))[:32] for _ in range(n)]), jnp.int32)
    pos = jnp.asarray([*positions, 77], jnp.int32)[:, None]
    sel, pages, lens = sala.decode_selection(q, kc, tables, pos, cfg)
    mine = ss.decode_lists(sel, tables, pos[:, 0], cfg.sparse, counted=True)
    np.testing.assert_array_equal(mine[0], pages)
    np.testing.assert_array_equal(mine[1], lens)
    assert mine[0].dtype == pages.dtype and mine[1].dtype == lens.dtype


@pytest.mark.parametrize("seed", [0, 1])
def test_lists_by_prefix_sums_hold_any_marks(seed):
    """Any marks, the published list of 128 pages: rows that mark none,
    every block, more than the list holds (the first 128 stay)."""
    rng = np.random.default_rng(seed)
    has = rng.random((6, 288)) < np.asarray(
        [0.0, 1.0, 0.6, 0.2, 0.02, 0.5])[:, None]
    tables = jnp.asarray(rng.integers(1, 9000, (6, 288)), jnp.int32)
    k = ss.SparseDims().list_pages
    want = ss._page_lists(jnp.asarray(has), tables, k)
    mine = ss._counted_lists(jnp.asarray(has), tables, k)
    np.testing.assert_array_equal(mine[0], want[0])
    np.testing.assert_array_equal(mine[1], want[1])


def _primitives(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen[eqn.primitive.name] = seen.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, seen)
    return seen


@pytest.mark.parametrize("rows,t", [
    pytest.param(64, 1, id="decode-64-virtual-rows"),
    pytest.param(2, 512, id="one-piece-of-512"),
])
def test_the_blocking_follows_the_shapes(rows, t):
    """What the kernel is handed at `sala-longctx`'s shapes: the pool as
    it lies (its layers and pages one axis, a page's 8 keys a tile: the
    same bytes), a sequence's pages, its blocks of copies and its fresh
    windows as prefetched scalars, a decode sequence's 2 x 16 heads one
    [32, 128] operand and a chunk's tile of 64 queries [2048, 128], two
    slots of a sequence's 384 page rows; a block's 32 copies start under
    NO branch, one traced copy a kernel; and the selection around it
    holds no sort."""
    dims = ss.SparseDims()
    b = rows // HKV
    args = [jax.ShapeDtypeStruct((rows, t, 16, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((4, 72000, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((rows, 288), jnp.int32),
            jax.ShapeDtypeStruct((rows, t), jnp.int32),
            jax.ShapeDtypeStruct((rows, t), jnp.bool_),
            jax.ShapeDtypeStruct((rows, t, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((rows, t), jnp.bool_),
            jax.ShapeDtypeStruct((rows, t), jnp.int32)]

    def select(q, pool, layer, tables, pos, valid, fkc, ends, fj):
        sel = ss.blocks_of_scores(bs.paged_block_scores(
            q, pool, layer, tables, pos, valid, (fkc, ends, fj), dims,
            1.0 / math.sqrt(128), HKV, interpret=False), pos, dims)
        return ss.decode_lists(sel[:, 0], tables, pos[:, 0], dims,
                               counted=True)

    jaxpr = jax.make_jaxpr(select)(*args)
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_block_scores"
    grid = call.params["grid_mapping"]
    bq = min(t, 64)
    assert grid.grid == (b, t // bq) and grid.num_index_operands == 4
    shapes = [str(v.aval) for v in call.invars]
    assert shapes == [
        f"int32[{b},384]", f"int32[{b + 2}]", f"int32[{b}]", f"int32[{b}]",
        f"bfloat16[{b},{t // bq},{2 * 16 * bq},128]",
        f"int32[{b},{t // bq},{16 * bq},1]",
        f"bfloat16[{b},8,{1 if t == 1 else 16},128]",
        "bfloat16[36000,8,128]"]
    assert [str(v.aval) for v in call.outvars] == [
        f"float32[{b},2,{t},384]"]
    body = call.params["jaxpr"]
    scratch = [str(v.aval) for v in body.invars[-grid.num_scratch_operands:]]
    assert scratch == ["Ref<vmem>{bfloat16[2,384,8,128]}",
                       "Ref<semaphore_mem>{dma_sem[2]}"]
    seen = _primitives(body, {})
    assert (seen["dma_start"], seen["dma_wait"]) == (1, 1)
    # two KV heads x four planes: a dot of the queries each, and in a
    # chunk one that puts the fresh windows in (a decode row's one key a
    # plane goes in by a select)
    assert seen["dot_general"] == (8 if t == 1 else 16)
    assert seen.get("cond", 0) == (0 if t == 1 else 1)  # a chunk's first tile
    everything = _primitives(jaxpr.jaxpr, {})
    assert "sort" not in everything
