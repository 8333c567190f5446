"""The index scores out of the pool in place (ops/index_scores.py
`paged_index_scores`, interpreted here) against what they replace on the
TPU: `ts.index_scores` over the gathered copy `keye_vl.index_keys_of`
makes. Every live position's score, the own token / own chunk put in
(`keye_vl.step_scores`), and the selection made from them bit for bit."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import keye_vl as kv_mod
from dynamo_tpu.ops import index_scores as ix
from dynamo_tpu.ops import token_select as ts

#: (index heads, index head dim, page size): the tiny preset's, and the
#: published 16 heads x 64 (bf16 as the configuration states)
TINY = (4, 8, 4, jnp.float32)
PUBLISHED = (16, 64, 16, jnp.bfloat16)


def _setup(widths, layers, pages, mp, rows, t, hists, seed=0):
    nj, di, s, dtype = widths
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal(
        (-(-layers // 2), pages, s, 2 * di)), dtype)
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, pages))[:mp] for _ in range(rows)
    ]).astype(np.int32))
    hist = jnp.asarray(hists, jnp.int32)
    qi = jnp.asarray(rng.standard_normal((rows, t, nj, di)), dtype)
    w = jnp.asarray(rng.standard_normal((rows, t, nj)), jnp.float32
                    ) / math.sqrt(nj * di)
    own = jnp.asarray(rng.standard_normal((rows, t, di)), dtype)
    positions = hist[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    return pool, tables, hist, qi, w, own, positions


def _both(pool, layer, tables, qi, w, own, positions, valid):
    """(XLA's scores over the gathered copy, the kernel's with the step's
    own keys put in), float32 [B, T, N]."""
    layer = jnp.int32(layer)
    ref = ts.index_scores(
        qi, w, kv_mod.index_keys_of(pool, layer, tables, own, positions))
    got = kv_mod.step_scores(qi, w, own, tables, positions, valid, pool,
                             layer)
    return np.asarray(ref), np.asarray(got)


def _judge(ref, got, positions, valid, topk, tol):
    """Every score a query may read (positions up to its own) agrees, and
    the selections made from the two are the same bits."""
    b, t, n = ref.shape
    context = np.where(valid, np.asarray(positions) + 1, 0)
    live = np.arange(n)[None, None] < context[..., None]
    assert live.any()
    assert np.max(np.abs(np.where(live, got - ref, 0.0))) <= tol
    ctx = jnp.asarray(context.reshape(-1), jnp.int32)
    mine, theirs = (np.asarray(ts.select_tokens(
        jnp.asarray(x).reshape(b * t, n), ctx, topk)) for x in (got, ref))
    np.testing.assert_array_equal(mine, theirs)
    assert mine.sum(axis=1).tolist() == np.minimum(
        context.reshape(-1), topk).tolist()


@pytest.mark.parametrize("widths,tol", [
    pytest.param(TINY, 5e-6, id="tiny"),
    pytest.param(PUBLISHED, 2e-5, id="16-heads-of-64-bf16"),
])
@pytest.mark.parametrize("layer", [0, 1, 2], ids=[
    "first-half", "second-half", "odd-layer-counts-last"])
def test_decode_scores_are_xlas_for_rows_of_unequal_context(
        widths, tol, layer):
    """Three layers (the last pair holds one): both halves of a pair and
    the odd one out; a row of three blocks and a part, a row with no
    history, a row shorter than a page, a row that ends on a page's last
    slot, in one call."""
    s = widths[2]
    mp = 100  # 3 blocks of 32 pages and 4 more
    hists = [mp * s - 1, 0, s - 2, 5 * s - 1, 33 * s + 1]
    pool, tables, hist, qi, w, own, pos = _setup(
        widths, 3, 140, mp, len(hists), 1, hists, seed=layer)
    valid = jnp.ones((len(hists), 1), bool)
    ref, got = _both(pool, layer, tables, qi, w, own, pos, valid)
    _judge(ref, got, pos, valid, topk=2 * s + 3, tol=tol)
    # and nothing but the cached positions and the own token is written
    for r, h in enumerate(hists):
        assert not got[r, 0, h + 1:].any()


@pytest.mark.parametrize("widths,tol", [
    pytest.param(TINY, 5e-6, id="tiny"),
    pytest.param(PUBLISHED, 2e-5, id="16-heads-of-64-bf16"),
])
@pytest.mark.parametrize("layer,t,hists", [
    pytest.param(0, 8, [40, 0], id="first-half-on-a-page"),
    pytest.param(1, 8, [37, 6], id="second-half-mid-page"),
    pytest.param(2, 8, [3], id="odd-layer-shorter-than-a-page"),
    pytest.param(1, 24, [140, 41], id="three-tiles-past-a-block"),
    pytest.param(0, 5, [2, 0, 19], id="a-ragged-chunk"),
])
def test_chunk_scores_are_xlas_with_the_chunks_own_keys_put_in(
        widths, tol, layer, t, hists, monkeypatch):
    """A prompt chunk: its queries over the cached keys out of the pool
    and over the chunk itself from what is in hand, at any start (the
    scheduler's is a page's first slot; the routine does not lean on
    it), by tiles of 8 queries so that a chunk of 24 is three grid
    steps whose fetches run ahead of one another."""
    monkeypatch.setattr(ix, "INDEX_BLOCK_Q", 8)
    s = widths[2]
    mp = 48  # a block and a half
    hists = [h * s // 4 for h in hists]  # in tokens at either page size
    pool, tables, hist, qi, w, own, pos = _setup(
        widths, 3, 70, mp, len(hists), t, hists, seed=10 + layer)
    valid = jnp.asarray(
        np.arange(t)[None] < np.asarray([t, max(1, t - 3), t])[
            :len(hists), None])
    ref, got = _both(pool, layer, tables, qi, w, own, pos, valid)
    _judge(ref, got, pos, valid, topk=s + 5, tol=tol)


def test_a_chunk_that_reaches_the_end_of_the_table_keeps_its_place():
    """The chunk's padding rows run past `max_context`: the update that
    puts its own scores in may not shift (`put_own`)."""
    nj, di, s, _ = TINY
    mp, t = 8, 8
    hists = [mp * s - 3]  # 3 real tokens, 5 rows of padding
    pool, tables, hist, qi, w, own, pos = _setup(
        TINY, 2, 20, mp, 1, t, hists)
    valid = jnp.asarray(np.arange(t)[None] < 3)
    ref, got = _both(pool, 1, tables, qi, w, own, pos, valid)
    _judge(ref, got, pos, valid, topk=7, tol=5e-6)


def test_the_blocking_follows_the_shapes():
    """What the kernel is handed at `keye-longctx`'s shapes: 288 pages in
    nine blocks of 32 through four slots, a decode row's 16 heads one
    [16, 128] operand, a chunk's tile 2,048 rows; the pool goes in as it
    lies (its pairs and pages one axis: the same bytes), the page rows,
    the lengths and the live steps as prefetched scalars; a block's 32
    copies start under NO branch (the body of a block holds no `cond`)."""
    pool = jax.ShapeDtypeStruct((4, 9000, 64, 128), jnp.bfloat16)

    def call(b, t):
        args = [jax.ShapeDtypeStruct((b, t, 16, 64), jnp.bfloat16),
                jax.ShapeDtypeStruct((b, t, 16), jnp.float32), pool,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((b, 288), jnp.int32),
                jax.ShapeDtypeStruct((b,), jnp.int32)]
        if t > 1:
            args.append(jax.ShapeDtypeStruct((b, t, 64), jnp.bfloat16))
        jaxpr = jax.make_jaxpr(ix.paged_index_scores)(*args)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns
                  if e.primitive.name == "pallas_call"]
        return eqn

    def count(jaxpr, seen):
        for e in jaxpr.eqns:
            seen[e.primitive.name] = seen.get(e.primitive.name, 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                count(sub, seen)
        return seen

    decode, chunk = call(32, 1), call(1, 512)
    assert decode.params["name"] == "paged_index_scores"
    assert chunk.params["name"] == "paged_index_scores_chunk"
    for eqn, grid, tile, outs in (
            (decode, (32, 1), 16, ["float32[32,1,18432]"]),
            (chunk, (1, 4), 2048,
             ["float32[1,512,18432]", "float32[1,512,512]"])):
        gm = eqn.params["grid_mapping"]
        steps = grid[0] * grid[1]
        assert gm.grid == grid and gm.num_index_operands == 3
        shapes = [str(v.aval) for v in eqn.invars]
        assert shapes[:3] == [f"int32[{grid[0] + 1},288]",
                              f"int32[{grid[0]}]", f"int32[{steps + 1}]"]
        assert shapes[3] == f"bfloat16[{grid[0]},{grid[1]},{tile},128]"
        assert shapes[-1] == "bfloat16[36000,64,128]"
        assert [str(v.aval) for v in eqn.outvars] == outs
        body = eqn.params["jaxpr"]
        scratch = [str(v.aval) for v in
                   body.invars[-gm.num_scratch_operands:]]
        assert scratch[0] == "Ref<vmem>{bfloat16[4,2048,128]}"
        # the branches: the first step's priming and the last step's
        # drain; the loop over a row's blocks holds none, ONE wait, and
        # its 32 copies traced once in loops that unroll where the
        # kernel is lowered (`unroll` == `length`: straight-line code)
        (loop,) = [e for e in body.eqns if e.primitive.name == "while"
                   and count(e.params["body_jaxpr"].jaxpr, {}).get(
                       "dma_start")]
        inner = loop.params["body_jaxpr"].jaxpr
        inside = count(inner, {})
        assert inside["dma_start"] == inside["dma_wait"] == 1
        assert "cond" not in inside
        assert count(body, {})["cond"] == 2

        def copies(jaxpr):  # of one pass, its loops unrolled
            total = 0
            for e in jaxpr.eqns:
                if e.primitive.name == "dma_start":
                    total += 1
                elif e.primitive.name == "scan":
                    each = copies(e.params["jaxpr"].jaxpr)
                    assert not each or (
                        e.params["unroll"] == e.params["length"])
                    total += e.params["length"] * each
            return total

        assert copies(inner) == 32
