"""A sparse prompt chunk's attention by tile of queries (ops/sparse_chunk.py,
the chunk branch of models/minicpm_sala.py `sparse_attention`): the cached
pages ANY query of a tile chose read once, a mask bit a (query, page), the
chunk's own keys in the same online softmax. Judged against
`sparse_select.masked_attention` (dense float32 scores under each query's
block mask) under the SAME `select_blocks` output, at the tiny preset's
sizes with the kernel interpreted: a block = a page = 4 tokens, 6 blocks of
a context of 32 or more, 2 query heads a KV head.

Everything is float32 here, so what separates the two is the order of sums
(2e-5 allowed, ~5e-7 observed); a page read for the wrong query, a bit
off by one or a turn skipped moves an output by 1e-2 or more.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.models import minicpm_sala as sala
from dynamo_tpu.models.llama import KVPages
from dynamo_tpu.ops import sparse_chunk as sc
from dynamo_tpu.ops import sparse_select as ss
from test_falcon_h1 import _streams

TOL = 2e-5
PAGE, PAGES, MP = 4, 64, 32


def _operands(cfg, hist, cur, t, seed=0):
    """Seeded operands of one sparse layer as `sparse_mixer` hands them
    on, a KV head a virtual row: every row of `hist` / `cur` twice."""
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    g, dc = cfg.num_heads // hkv, cfg.attn_cfg.kv_head_dim
    rng = np.random.default_rng(seed)
    b = len(hist) * hkv
    f32 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.float32)
    pt = np.stack([rng.permutation(np.arange(1, PAGES))[:MP]
                   for _ in hist]).astype(np.int32)
    hist, cur = np.repeat(hist, hkv), np.repeat(cur, hkv)
    pool = lambda: jnp.pad(  # noqa: E731: zeros past D, as the cache is
        f32(2, PAGES * hkv, PAGE, 1, d), ((0, 0),) * 4 + ((0, dc - d),))
    return dict(
        q=f32(b, t, g, d), k=f32(b, t, 1, d), v=f32(b, t, 1, d),
        kv=KVPages(k=pool(), v=pool()),
        kc_pool=jnp.pad(f32(2, PAGES * hkv * cfg.sparse.per_block, d),
                        ((0, 0), (0, 0), (0, dc - d))),
        tables=jnp.asarray((pt[:, None] * hkv + np.arange(hkv)[
            None, :, None]).reshape(b, MP)),
        pos=jnp.asarray(hist[:, None] + np.arange(t)[None], jnp.int32),
        valid=jnp.asarray(np.arange(t)[None] < cur[:, None]),
    )


def _under_the_mask(x, sel, cfg, layer=1):
    """`masked_attention` of the chunk under `sel`: the history from the
    pools, the chunk's own keys from the operands."""
    return ss.masked_attention(
        x["q"], x["kv"].k, x["kv"].v, layer, x["tables"], x["pos"], sel,
        cfg.sparse, 1.0 / math.sqrt(cfg.head_dim),
        hist_len=jnp.where(x["valid"][:, 0], x["pos"][:, 0], 0),
        k_cur=x["k"][:, :, 0], v_cur=x["v"][:, :, 0],
        cur_pos=jnp.where(x["valid"], x["pos"], 1 << 30))


def _each_query_names(x, n):
    """A selection made by hand: query i names its own block and the `n`
    cached blocks from `n * i` on (modulo the row's cached blocks): with
    one block of 16 a tile of 8 names 8 disjoint ones, with four of 8 a
    tile's union is every cached page, each named by four queries."""
    pos = np.asarray(x["pos"])
    b, t = pos.shape
    sel = np.zeros((b, t, MP), bool)
    for r in range(b):
        cached = pos[r, 0] // PAGE
        for i in range(t):
            sel[r, i, pos[r, i] // PAGE] = True
            sel[r, i, (n * i + np.arange(n)) % cached] = True
    return jnp.asarray(sel)


TINY = sala.MiniCPMSALAConfig.tiny()
CASES = {
    # positions 24-39: the rule changes at the chunk's ninth query
    "crosses-dense-len-inside": dict(hist=[24], cur=[16], t=16),
    # 8 queries a tile, each with a cached block of its own
    "a-tile-of-disjoint-blocks": dict(
        hist=[64], cur=[16], t=16, block_q=8, by_hand=1),
    # 8 cached pages, 8 queries a tile: the union is every page, half a
    # turn of 16
    "a-tile-whose-union-is-every-page": dict(
        hist=[32], cur=[16], t=16, block_q=8, by_hand=4),
    "a-dense-prompt-beside-a-sparse-one": dict(
        hist=[8, 40], cur=[16, 16], t=16),
    "padding-rows-and-a-32-row-tail": dict(
        hist=[36, 0, 64], cur=[17, 0, 32], t=32),
    # 16 cached pages, all named (block 0, the window, 6 chosen of 13 by
    # each of 16 queries): one whole turn of 16; and 32: two, the last
    # page of the table among them
    "a-history-that-ends-on-a-block-of-pages": dict(
        hist=[64, 112], cur=[16, 16], t=16),
    # every query past dense_len, every cached token written by a dense one
    "a-first-sparse-chunk": dict(hist=[32], cur=[16], t=16),
    "four-tiles-and-four-turns-over-the-chunk": dict(
        hist=[44, 20], cur=[32, 27], t=32, block_q=8, block_cur=8),
    # six virtual rows whose selection is made three at a time
    "rows-selected-in-groups": dict(
        hist=[40, 8, 64], cur=[16, 16, 12], t=16, select_bytes=4096),
    "head-width-128": dict(
        hist=[40], cur=[16], t=16, cfg=dataclasses.replace(
            TINY, head_dim=128, num_heads=4)),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_a_sparse_chunk_by_tile_is_attention_under_the_same_mask(
        case, monkeypatch):
    cfg = dataclasses.replace(
        case.get("cfg", TINY), attention_impl="pallas")
    monkeypatch.setattr(sc, "CHUNK_BLOCK_Q", case.get("block_q", 128))
    monkeypatch.setattr(sc, "CHUNK_BLOCK_CUR", case.get("block_cur", 256))
    monkeypatch.setattr(
        sala, "SELECT_BYTES", case.get("select_bytes", sala.SELECT_BYTES))
    x = _operands(cfg, case["hist"], case["cur"], case["t"])
    scale = 1.0 / math.sqrt(cfg.head_dim)
    b, t, g, d = x["q"].shape
    if "by_hand" in case:  # the kernel under a selection no rule makes
        sel = _each_query_names(x, case["by_hand"])
        lanes = lambda a: jnp.pad(  # noqa: E731
            a, ((0, 0),) * (a.ndim - 1)
            + ((0, cfg.attn_cfg.kv_head_dim - d),))
        got, (tiles, named) = sc.sparse_chunk_attention(
            lanes(x["q"] * scale), lanes(x["k"][:, :, 0]),
            lanes(x["v"][:, :, 0]), x["kv"].k, x["kv"].v, 1, x["tables"],
            sel, x["pos"][:, 0], x["valid"])
        got, read, named = got[..., :d], tiles.sum(), named.sum()
    else:  # the chunk branch itself
        attn, kv, staged, _, walk = sala.sparse_attention(
            x["q"], x["k"], x["v"], x["kv"], x["kc_pool"], 1, x["tables"],
            x["pos"], x["valid"], cfg)
        assert kv is x["kv"] and staged[0].shape == (
            b, t, 1, cfg.attn_cfg.kv_head_dim)  # read-only: staged
        got = attn.reshape(b, t, g, d)
        kc, _ = sala.compressed_keys_of(
            x["k"], x["kv"], x["kc_pool"], 1, x["tables"], x["pos"],
            x["valid"], cfg)
        sel = ss.select_blocks(x["q"], kc, x["pos"], cfg.sparse, scale)
        assert walk[:2].tolist() == [0, 0]
        read, named = walk[2:]
    want = _under_the_mask(x, sel, cfg)
    live = np.asarray(x["valid"])
    assert live.any() and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], atol=TOL)
    # the count, against the selection itself: a page of the history once
    # a (tile, page) for `read`, once a (query, page) for `named`; the
    # chunk branch counts the rows the sparse rule reached
    bq = min(case.get("block_q", 128), t)
    chose = np.asarray(sel) & live[..., None] & (
        np.arange(MP)[None, None] < np.asarray(x["pos"])[:, :1, None] // PAGE)
    if "by_hand" not in case:
        chose &= (live & (np.asarray(x["pos"]) + 1 >= 32)).any(
            axis=1)[:, None, None]
    assert int(named) == chose.sum() > 0
    assert int(read) == chose.reshape(b, t // bq, bq, MP).any(2).sum()
    if case.get("by_hand") == 1:
        assert int(read) == int(named)  # disjoint: nothing shared
    else:
        assert int(read) < int(named)


def test_a_tile_of_one_query_reads_what_its_query_names(monkeypatch):
    """`chunk_pages_read <= chunk_pages_named`, and equal where a tile is
    one query: the tile's list is then that query's own."""
    x = _operands(TINY, [40, 24], [8, 8], 8, seed=3)
    kc, _ = sala.compressed_keys_of(
        x["k"], x["kv"], x["kc_pool"], 0, x["tables"], x["pos"], x["valid"],
        TINY)
    sel = ss.select_blocks(x["q"], kc, x["pos"], TINY.sparse, 0.25)
    counts = {}
    for block_q in (1, 8):  # `named` is a row's, `n` a tile's
        monkeypatch.setattr(sc, "CHUNK_BLOCK_Q", block_q)
        pages, n, _, named = sc.tile_lists(
            sel, x["valid"], x["tables"], x["pos"][:, 0], PAGE)
        counts[block_q] = (int(n.sum()), int(named.sum()))
        # a list is ascending in block order and names the row's pages
        for r in range(pages.shape[0]):
            for tile in range(pages.shape[1]):
                got = np.asarray(pages[r, tile, : int(n[r, tile])]).tolist()
                order = [np.asarray(x["tables"][r]).tolist().index(p)
                         for p in got]
                assert order == sorted(order) and len(set(got)) == len(got)
    assert counts[1][0] == counts[1][1] == counts[8][1]
    assert 0 < counts[8][0] < counts[8][1]


def test_the_engine_counts_what_its_sparse_chunks_read():
    """A 75-token prompt over three chunks of 32 through the normal path
    with the kernels on (interpreted here): the second and third chunk
    stand past `dense_len`, their tiles read each chosen page once, and
    the count reaches the engine's metrics and the flight records the way
    the decode walks' does."""
    base = EngineConfig.for_tests(
        model="minicpm-sala-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=1, decode_buckets=(1,),
        attention_impl="pallas",
    )
    eng = JaxEngine(base)
    prompt = [int(i) for i in np.random.default_rng(2).integers(3, 250, 75)]
    _streams(eng, [("a", prompt, 4)])
    m = eng.metrics
    # 3 sparse layers x 2 KV heads: the second chunk's queries (32-63)
    # each name block 0, their window and what they chose among 8 cached
    # blocks, the third's (64-74) among 16; a tile names each once
    assert 0 < m.chunk_pages_read < m.chunk_pages_named
    assert m.chunk_pages_read <= 3 * 2 * (8 + 16)
    assert m.walk_pages_named > 0
    records = eng.flight.snapshot()
    assert sum(r.get("chunk_pages_read", 0) for r in records) == (
        m.chunk_pages_read)
    assert sum(r.get("chunk_pages_named", 0) for r in records) == (
        m.chunk_pages_named)
