"""Pallas paged-attention decode kernel + paged KV writer vs XLA paths.

Runs the real kernels in interpret mode on CPU (same lowering semantics:
scalar prefetch, async DMA, online softmax), compared against
models/llama.py:paged_attention which has its own numerics tests vs torch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import (
    KVPages,
    LlamaConfig,
    forward_hidden,
    init_kv_pages,
    init_params,
    paged_attention,
    paged_gather,
    paged_gather_kv,
)
from dynamo_tpu.ops import paged_attention as paged_attention_ops
from dynamo_tpu.ops.kv_update import paged_write
from dynamo_tpu.ops.paged_attention import (
    decode_vmem_bytes,
    paged_decode_attention,
)


def _rand_case(rng, b, hq, hkv, d, num_pages, page_size, mp, num_layers=2):
    k_cache = jnp.asarray(
        rng.normal(size=(num_layers, num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    v_cache = jnp.asarray(
        rng.normal(size=(num_layers, num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    # Distinct non-null pages per row so sequences don't alias.
    pt = np.zeros((b, mp), np.int32)
    perm = rng.permutation(np.arange(1, num_pages))[: b * mp]
    pt[:] = perm.reshape(b, mp)
    return q, k_cache, v_cache, jnp.asarray(pt)


@pytest.mark.parametrize(
    "hist_lens",
    [
        [1, 17, 64],  # fresh, mid-page, exactly-full
        [33, 5, 2],
        [64, 64, 64],
        [0, 7, 1],  # zero history: acc=0, l=0 (merge handles it)
    ],
)
def test_kernel_matches_xla_path(hist_lens):
    rng = np.random.default_rng(0)
    b, hq, hkv, d = 3, 8, 2, 128
    num_pages, page_size, mp = 16, 16, 4
    q, k_cache, v_cache, pt = _rand_case(rng, b, hq, hkv, d, num_pages, page_size, mp)
    lens = jnp.asarray(hist_lens, jnp.int32)

    # Exercise the layer-index prefetch: compare each stacked layer.
    for layer in (0, 1):
        li = jnp.asarray(layer, jnp.int32)
        acc, m, l = paged_decode_attention(
            q, k_cache, v_cache, li, pt, lens, interpret=True
        )
        for row, hist in enumerate(hist_lens):
            if hist == 0:
                assert float(np.asarray(l)[row].max()) == 0.0
                continue
            out_row = np.asarray(acc)[row] / np.asarray(l)[row][:, None]
            cfg = LlamaConfig(
                num_heads=hq, num_kv_heads=hkv, head_dim=d, dtype=jnp.float32
            )
            k_all = paged_gather(k_cache, li, pt[row : row + 1])
            v_all = paged_gather(v_cache, li, pt[row : row + 1])
            ref = paged_attention(
                q[row : row + 1, None],
                k_all,
                v_all,
                jnp.asarray([[hist - 1]], jnp.int32),
                cfg,
            )  # [1, 1, Hq*D] — attention over history tokens 0..hist-1
            np.testing.assert_allclose(
                out_row.reshape(-1), np.asarray(ref)[0, 0], rtol=2e-5,
                atol=2e-5,
            )


#: the shapes the presets hand the kernel (ISSUE 25), small enough for the
#: interpreter: query heads, kv heads, lane-padded head dim, the head dim
#: the scores are scaled by, page size, dtype of q and of the pool
KERNEL_SHAPES = {
    # qwen2-7b: G 7 on 4 kv heads, at the real page size
    "gqa7_hkv4_page64": dict(hq=28, hkv=4, d=128, s=64),
    # phi3-mini: MHA (G 1), heads of 96 padded to the 128 lanes
    "mha_padded_head": dict(hq=8, hkv=8, d=128, head_dim=96, s=16),
    # gemma-2b, or one kv head on a tp shard
    "hkv1_d256": dict(hq=8, hkv=1, d=256, s=16),
    # qwen2-0.5b: 14 query heads are not whole sublane tiles
    "gqa7_hkv2_unaligned_heads": dict(hq=14, hkv=2, d=128, s=16),
    # the serving dtypes: bf16 operands straight into the MXU
    "bf16": dict(hq=8, hkv=2, d=128, s=16, dtype=jnp.bfloat16),
    "bf16_gqa7_hkv4_page64": dict(
        hq=28, hkv=4, d=128, s=64, dtype=jnp.bfloat16
    ),
    # quantized pools: narrow rows, [Hkv, S'] f32 scale planes beside them
    "int8_scale_planes": dict(hq=8, hkv=2, d=128, s=16, kv="int8"),
    "int8_gqa7_hkv4_page64_bf16_q": dict(
        hq=28, hkv=4, d=128, s=64, kv="int8", dtype=jnp.bfloat16
    ),
    "fp8_scale_planes": dict(hq=8, hkv=4, d=128, s=16, kv="fp8"),
}


def _block_pages(shape, b):
    """Pages a block of the kernel holds at this shape."""
    itemsize = 1 if shape.get("kv") else jnp.dtype(
        shape.get("dtype", jnp.float32)
    ).itemsize
    return paged_attention_ops._block_pages(
        b, shape["hq"], shape["d"], shape["s"], shape["hkv"], itemsize,
        bool(shape.get("kv")), None,
    )


def _pool_case(rng, shape, hist_lens, num_layers=2):
    """(q, KVPages, page tables) for `hist_lens`: every row's pages
    scattered over the pool in shuffled order, none shared, page 0 (the
    null page) unused, pad lanes of a padded head zero."""
    hq, hkv, d, s = shape["hq"], shape["hkv"], shape["d"], shape["s"]
    real = shape.get("head_dim", d)
    dtype = shape.get("dtype", jnp.float32)
    b = len(hist_lens)
    mp = max(1, -(-max(hist_lens) // s)) + 1
    num_pages = 1 + b * mp + 3
    pool = (num_layers, num_pages, s, hkv, d)
    lanes = (np.arange(d) < real).astype(np.float32)

    def rows(*shape_):
        return rng.normal(size=shape_).astype(np.float32) * lanes

    kvq = shape.get("kv")
    if kvq:
        qdtype = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[kvq]
        span = 127 if kvq == "int8" else 8
        k, v = (
            jnp.asarray(np.round(rows(*pool) * span / 3).clip(-span, span))
            .astype(qdtype) for _ in range(2)
        )
        plane = (num_layers, num_pages, hkv, -(-s // 128) * 128)
        k_scale, v_scale = (
            jnp.asarray(rng.uniform(0.5, 1.5, plane) / span, jnp.float32)
            for _ in range(2)
        )
    else:
        k, v = (jnp.asarray(rows(*pool), dtype) for _ in range(2))
        k_scale = v_scale = None
    q = jnp.asarray(rows(b, hq, d), dtype)
    ids = rng.permutation(np.arange(1, num_pages))[: b * mp].reshape(b, mp)
    return q, KVPages(k, v, k_scale, v_scale), jnp.asarray(ids, jnp.int32)


def _assert_matches_gather(shape, hist_lens, seed=0, layer=1):
    """The kernel (interpreted) against the XLA gather path on one layer
    of a shuffled pool: out = acc / l per row, the empty-history state for
    rows without history."""
    rng = np.random.default_rng(seed)
    q, kv, pt = _pool_case(rng, shape, hist_lens)
    hq, hkv, d = shape["hq"], shape["hkv"], shape["d"]
    # f32 operands: the four original cases' 2e-5. bf16 queries over a
    # narrow pool feed the MXU q/sqrt(d) and the softmax weights in bf16
    # (the serving dtypes): two bf16 ulps of values of order one
    tol = 2**-7 if q.dtype == jnp.bfloat16 else 2e-5
    real = shape.get("head_dim", d)
    lens = jnp.asarray(hist_lens, jnp.int32)
    li = jnp.asarray(layer, jnp.int32)
    acc, m, l = paged_decode_attention(
        q, kv.k, kv.v, li, pt, lens, scale_dim=real, interpret=True,
        k_scale=kv.k_scale, v_scale=kv.v_scale,
    )
    acc, m, l = np.asarray(acc), np.asarray(m), np.asarray(l)
    assert acc.shape == (len(hist_lens), hq, d)
    assert m.shape == l.shape == (len(hist_lens), hq)
    cfg = LlamaConfig(
        num_heads=hq, num_kv_heads=hkv, head_dim=d, dtype=jnp.float32,
        query_pre_attn_scalar=real,
    )
    k_all, v_all = paged_gather_kv(kv, li, pt, jnp.float32)
    ref = np.asarray(paged_attention(
        q[:, None].astype(jnp.float32), k_all, v_all,
        jnp.maximum(lens - 1, 0)[:, None], cfg,
    ))[:, 0].reshape(len(hist_lens), hq, d)
    for row, hist in enumerate(hist_lens):
        if hist == 0:
            assert not acc[row].any() and not l[row].any()
            assert np.all(np.isneginf(m[row]))
            continue
        assert np.all(np.isfinite(m[row])) and np.all(l[row] > 0)
        np.testing.assert_allclose(
            acc[row] / l[row][:, None], ref[row], rtol=tol, atol=tol,
            err_msg=f"row {row} history {hist}",
        )


@pytest.mark.parametrize("name", sorted(KERNEL_SHAPES))
def test_kernel_matches_xla_gather_at_preset_shapes(name):
    """Every shape the presets give the kernel: a batch mixing no history,
    one token, a partial page, whole pages and several blocks plus a
    partial page, on shuffled non-contiguous pages."""
    shape = KERNEL_SHAPES[name]
    s, pb = shape["s"], _block_pages(shape, 5)
    _assert_matches_gather(
        shape, [0, 1, s + 3, pb * s, (2 * pb + 1) * s + s // 2 + 1]
    )


@pytest.mark.parametrize("name", [
    "gqa7_hkv4_page64", "bf16_gqa7_hkv4_page64", "int8_scale_planes",
])
@pytest.mark.parametrize("pages", ["blk-1", "blk", "blk+1", "3blk+partial"])
def test_kernel_at_block_boundaries(name, pages):
    """Histories of exactly P_blk - 1, P_blk and P_blk + 1 pages, and of
    several blocks plus a partial page, each beside its one-token
    neighbours: the last block's unfetched pages and its tail are masked,
    never read."""
    shape = KERNEL_SHAPES[name]
    s, pb = shape["s"], _block_pages(shape, 3)
    assert pb > 1, "the shape must walk several pages a block"
    tokens = {
        "blk-1": (pb - 1) * s, "blk": pb * s, "blk+1": (pb + 1) * s,
        "3blk+partial": 3 * pb * s + s // 3,
    }[pages]
    hist = [tokens - 1, tokens, tokens + 1]
    _assert_matches_gather(shape, [max(h, 0) for h in hist], seed=3)


#: shapes whose block is MORE than one sub-tile (`_tile_pages`): [Hq, a
#: block's columns] passes `_MAX_TILE_SCORES`, so the block is folded a
#: sub-tile of whole pages at a time. `scores`: that limit set for the test
WIDE_SHAPES = {
    # command-a-plus: 4 pages a block, a sub-tile one page of 512 columns
    "gqa16_hkv8_128_heads": dict(
        hq=128, hkv=8, d=128, s=64, dtype=jnp.bfloat16, pb=4, tp=1),
    # half of it: 8 pages a block in two sub-tiles of four
    "gqa16_hkv4_64_heads": dict(
        hq=64, hkv=4, d=128, s=64, dtype=jnp.bfloat16, pb=8, tp=4),
    # a tiny one forced onto sub-tiles of 128 columns: four pages of 32
    "gqa8_hkv2_forced": dict(
        hq=16, hkv=2, d=128, s=16, scores=16 * 128, pb=8, tp=4),
    # and of 64 columns, which are no lane tile: under bits one sub-tile
    "gqa8_hkv2_forced_narrow": dict(
        hq=16, hkv=2, d=128, s=16, scores=16 * 64, pb=8, tp=2),
}


def _dense_reference(q, k, v, pt, hist, bits, scale_dim):
    """Float64 attention of every row over its cached tokens (those `bits`
    names, where given): (out [B, Hq, D], m [B, Hq], l [B, Hq]); a row with
    nothing to attend is left NaN."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, hq, d = q.shape
    hkv = k.shape[2]
    out = np.full((b, hq, d), np.nan)
    m, l = np.full((b, hq), np.nan), np.full((b, hq), np.nan)
    for r in range(b):
        keep = np.arange(len(pt[r]) * k.shape[1]) < hist[r]
        if bits is not None:
            keep &= np.asarray(bits[r])[: len(keep)]
        if not keep.any():
            continue
        kr = k[pt[r]].reshape(-1, hkv, d)[keep]  # [K, Hkv, D]
        vr = v[pt[r]].reshape(-1, hkv, d)[keep]
        for h in range(hq):
            sc = kr[:, h // (hq // hkv)] @ q[r, h] / np.sqrt(scale_dim)
            m[r, h] = sc.max()
            w = np.exp(sc - m[r, h])
            l[r, h] = w.sum()
            out[r, h] = w @ vr[:, h // (hq // hkv)] / l[r, h]
    return out, m, l


def _wide_histories(kind, s, pb, tp):
    """Histories (tokens a row) around a wide block's edges."""
    blk, tile = pb * s, tp * s
    return {
        # the last block short by 1 .. pb-1 pages, each beside a whole one
        "short-last-block": [
            2 * blk - short * s for short in range(1, pb)] + [2 * blk],
        # a last page part filled: one token, half a page, all but one
        "part-filled-last-page": [
            blk + tile + 1, 2 * blk + s // 2, 3 * blk - 1],
        # a row of no history between two that have some
        "zero-history-between": [blk + 3, 0, tile + s + 5],
        # a row ends where a sub-tile ends, where a block does, and one
        # token and one page past each
        "tile-and-block-boundaries": [
            tile, tile + 1, blk, blk + 1, blk + tile, 2 * blk + s],
    }[kind]


@pytest.mark.parametrize("bits", [False, True], ids=["plain", "token-bits"])
@pytest.mark.parametrize("kind", [
    "short-last-block", "part-filled-last-page", "zero-history-between",
    "tile-and-block-boundaries",
])
@pytest.mark.parametrize("name", sorted(WIDE_SHAPES))
def test_wide_blocks_fold_by_sub_tile(monkeypatch, name, kind, bits):
    """A block wider than `_MAX_TILE_SCORES` is folded a sub-tile at a
    time: against dense float64 attention over the same cached tokens (and,
    under `token_bits`, the same chosen ones), m and l included."""
    shape = WIDE_SHAPES[name]
    if "scores" in shape:
        monkeypatch.setattr(
            paged_attention_ops, "_MAX_TILE_SCORES", shape["scores"])
    hq, hkv, s = shape["hq"], shape["hkv"], shape["s"]
    pb = _block_pages(shape, 8)
    tp = paged_attention_ops._tile_pages(pb, hq, s * hkv, bits)
    whole = bits and (shape["tp"] * s * hkv) % 128  # no lane tile: one tile
    assert (pb, tp) == (shape["pb"], pb if whole else shape["tp"])
    hist = _wide_histories(kind, s, shape["pb"], shape["tp"])
    rng = np.random.default_rng(5)
    q, kv, pt = _pool_case(rng, shape, hist, num_layers=1)
    chosen = None
    if bits:  # about half of a row's tokens, the row's last among them
        chosen = rng.random((len(hist), pt.shape[1] * s)) < 0.5
        for r, h in enumerate(hist):
            chosen[r, max(h - 1, 0)] = True
    acc, m, l = paged_decode_attention(
        q, kv.k, kv.v, jnp.int32(0), pt, jnp.asarray(hist, jnp.int32),
        interpret=True,
        token_bits=None if chosen is None else jnp.asarray(chosen),
    )
    acc, m, l = np.asarray(acc), np.asarray(m), np.asarray(l)
    want, want_m, want_l = _dense_reference(
        q.astype(jnp.float32), kv.k[0].astype(jnp.float32),
        kv.v[0].astype(jnp.float32), np.asarray(pt), hist, chosen,
        shape["d"])
    # bf16 operands into the MXU (the serving dtypes): two bf16 ulps of
    # values of order one; f32 operands to rounding
    tol = 2**-7 if q.dtype == jnp.bfloat16 else 2e-5
    for r, h in enumerate(hist):
        if h == 0:
            assert not acc[r].any() and not l[r].any()
            assert np.all(np.isneginf(m[r]))
            continue
        np.testing.assert_allclose(
            acc[r] / l[r][:, None], want[r], rtol=tol, atol=tol,
            err_msg=f"row {r} history {h}")
        # the caller folds the row's own token in by m and l themselves
        np.testing.assert_allclose(m[r], want_m[r], atol=8 * tol)
        np.testing.assert_allclose(
            l[r] * np.exp(m[r] - want_m[r]), want_l[r], rtol=8 * tol)


def test_block_rule_follows_the_shapes_and_the_budget():
    """Pages a block: from page bytes and columns alone, halved under a
    budget before the call would overflow it; never under one page."""
    rule = paged_attention_ops._block_pages
    # qwen2-7b bf16: 128 KiB a page of K+V, 256 columns a page
    assert rule(64, 28, 128, 64, 4, 2, False, None) == 8
    # phi3-mini: a page alone is 1 MiB and 2048 columns
    assert rule(16, 32, 128, 64, 32, 2, False, None) == 1
    # llama3-8b: 512 columns a page
    assert rule(32, 32, 128, 64, 8, 2, False, None) == 4
    # a budget the whole-batch blocks nearly fill shrinks the block first
    roomy = decode_vmem_bytes(64, 28, 128, 64, 4, 2)
    tight = decode_vmem_bytes(64, 28, 128, 64, 4, 2, budget=roomy - 1)
    assert tight < roomy
    assert rule(64, 28, 128, 64, 4, 2, False, roomy - 1) == 4
    assert rule(64, 28, 128, 64, 4, 2, False, 1) == 1
    # command-a-plus, 32 rows x 128 / 8 heads: 256 KiB and 512 columns a
    # page, 4 pages a block folded ONE page at a time (128 x 512 scores),
    # so the budget pays for a sub-tile's temporaries, not a block's. The
    # whole-batch blocks take 10 of models/llama.py's 12 MiB: 2 pages
    # there (1 before the sub-tiles), 4 under the family's own 16
    cmda = (32, 128, 128, 64, 8, 2, False)
    tile = paged_attention_ops._tile_pages
    assert rule(*cmda, None) == 4 and tile(4, 128, 512) == 1
    assert rule(*cmda, 12 << 20) == 2 and rule(*cmda, 16 << 20) == 4
    assert decode_vmem_bytes(*cmda[:6], budget=16 << 20) == 13 << 20
    # a sub-tile is whole pages that divide the block; at 32 heads or
    # fewer it is the whole 2048-column block
    assert tile(8, 28, 256) == 8 and tile(4, 32, 512) == 4
    assert tile(8, 64, 256) == 4 and tile(6, 64, 256) == 3
    assert tile(1, 128, 2048) == 1
    # under bits a sub-tile is whole lane tiles, else the block is one
    assert tile(8, 1024, 64) == 1 and tile(8, 1024, 64, True) == 8
    assert tile(8, 256, 64, True) == 4


#: pages a block (no budget, models/llama.py's 12 MiB) at every preset of
#: scripts/paged_decode_bench.py, as PR 25 to PR 54 had them
BENCH_BLOCKS = {
    "qwen2-7b": (8, 8), "qwen2-7b-kv8": (8, 8), "phi3-mini": (1, 1),
    "llama3-8b": (4, 4), "deepseek-v2-lite": (8, 8),
}


def test_every_bench_preset_keeps_its_block_and_is_one_sub_tile():
    """The sub-tile rule reads Hq: the shapes the accepted cells walk keep
    the block they had and fold it in one piece (their programs lower to
    the same text); Command A+'s two walks are the wide ones."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parent.parent / "scripts" / "paged_decode_bench.py"
    spec = importlib.util.spec_from_file_location("paged_decode_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    wide = set()
    for name, shape in bench.SHAPES.items():
        args = (
            shape["b"], shape["hq"], shape["d"], bench.PAGE, shape["hkv"],
            1 if shape.get("kv") else 2, bool(shape.get("kv")),
        )
        rope = shape.get("rope", 0)
        blocks = tuple(
            paged_attention_ops._block_pages(*args, budget, rope)
            for budget in (None, 12 << 20)
        )
        tiles = paged_attention_ops._tile_pages(
            blocks[0], shape["hq"], bench.PAGE * shape["hkv"])
        if tiles < blocks[0]:
            wide.add(name)
        else:
            assert blocks == BENCH_BLOCKS[name], name
    assert wide == {"command-a-plus-full", "command-a-plus-ring"}


def _allocated_vmem_bytes(monkeypatch, b, hq, hkv, d, s, dtype, quantized,
                          budget=12 << 20):
    """Bytes of the blocks and scratch one call hands Mosaic, each padded
    to its dtype's (sublane, 128) tile, read off the pallas_call itself."""
    seen = {}

    def fake_pallas_call(kernel, *, out_shape, grid_spec, **_kw):
        seen.update(out_shape=out_shape, grid_spec=grid_spec)
        return lambda *a: [jnp.zeros(o.shape, o.dtype) for o in out_shape]

    monkeypatch.setattr(
        paged_attention_ops.pl, "pallas_call", fake_pallas_call
    )
    mp, pages, layers = 128, 8, 1
    sds = jax.ShapeDtypeStruct
    pool = sds((layers, pages, s, hkv, d), dtype)
    plane = sds((layers, pages, hkv, 128), jnp.float32)
    scales = dict(k_scale=plane, v_scale=plane) if quantized else {}
    jax.eval_shape(
        lambda q, k, v, pt, hist, **kw: paged_decode_attention(
            q, k, v, jnp.int32(0), pt, hist, interpret=True,
            vmem_budget=budget, **kw
        ),
        sds((b, hq, d), jnp.bfloat16), pool, pool,
        sds((b, mp), jnp.int32), sds((b,), jnp.int32), **scales,
    )

    def padded(shape, dt):
        item = jnp.dtype(dt).itemsize
        sub = 8 * 4 // item
        *lead, rows, lanes = shape
        return (
            int(np.prod(lead, dtype=np.int64)) * (-(-rows // sub) * sub)
            * (-(-lanes // 128) * 128) * item
        )

    spec = seen["grid_spec"]
    total = padded((b, -(-hq // 8) * 8, d), jnp.bfloat16)  # the q block
    total += sum(padded(o.shape, o.dtype) for o in seen["out_shape"])
    total += sum(
        padded(sc.shape, sc.dtype) for sc in spec.scratch_shapes
        if str(sc.memory_space) == "vmem"  # not the DMA semaphores
    )
    return total


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_vmem_estimate_covers_what_the_call_allocates(monkeypatch, quantized):
    """decode_vmem_bytes is what attention_block routes by: at the cell's
    shape (qwen2-7b, B 64) and at the largest batch it keeps on the
    kernel it is not below the blocks and scratch the call allocates."""
    budget = 12 << 20
    hq, hkv, d, s = 28, 4, 128, 64
    dtype = jnp.int8 if quantized else jnp.bfloat16
    item = jnp.dtype(dtype).itemsize

    def estimate(b):
        return decode_vmem_bytes(
            b, hq, d, s, hkv, item, quantized=quantized, budget=budget
        )

    largest = max(b for b in range(8, 1025, 8) if estimate(b) <= budget)
    assert largest >= 128  # fitted before this kernel (ISSUE 25): still does
    assert estimate(largest + 8) > budget
    for b in (64, largest):
        allocated = _allocated_vmem_bytes(
            monkeypatch, b, hq, hkv, d, s, dtype, quantized
        )
        assert allocated <= estimate(b) <= budget, (b, allocated)


@pytest.mark.parametrize("budget", [12 << 20, 16 << 20], ids=["12", "16"])
def test_vmem_estimate_covers_the_wide_form(monkeypatch, budget):
    """Command A+'s walk (32 rows x 128 / 8 heads, bf16): more than one
    page a block under either budget, and the estimate, which counts a
    sub-tile's temporaries, still covers what the call allocates."""
    b, hq, hkv, d, s = 32, 128, 8, 128, 64
    estimate = decode_vmem_bytes(b, hq, d, s, hkv, 2, budget=budget)
    allocated = _allocated_vmem_bytes(
        monkeypatch, b, hq, hkv, d, s, jnp.bfloat16, False, budget=budget)
    assert paged_attention_ops._block_pages(
        b, hq, d, s, hkv, 2, False, budget) > 1
    assert allocated <= estimate <= budget, (allocated, estimate)


@pytest.mark.parametrize("t", [1, 4, 8])
def test_paged_write_kernel_matches_scatter(t):
    """The DMA writer (interpret) == the XLA scatter fallback, for decode
    runs (t=1), sub-page chunks (t=4=S), and multi-page chunks (t=8)."""
    rng = np.random.default_rng(2)
    L, P, S, hkv, d = 3, 8, 4, 2, 128
    b, mp = 2, 4
    k_cache = jnp.asarray(rng.normal(size=(L, P, S, hkv, d)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(L, P, S, hkv, d)), jnp.float32)
    k_stage = jnp.asarray(rng.normal(size=(L, b, t, hkv, d)), jnp.float32)
    v_stage = jnp.asarray(rng.normal(size=(L, b, t, hkv, d)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    # Page-aligned starts (scheduler invariant when t > 1).
    starts = np.array([0, 4]) if t > 1 else np.array([2, 5])
    positions = jnp.asarray(
        starts[:, None] + np.arange(t)[None, :], jnp.int32
    )
    n_valid = max(1, t - 2)
    valid = jnp.asarray(
        np.array([[True] * t, [True] * n_valid + [False] * (t - n_valid)]),
        bool,
    )

    got_k, got_v = paged_write(
        k_cache, v_cache, k_stage, v_stage, pt, positions, valid,
        use_kernel=True,
    )
    want_k, want_v = paged_write(
        k_cache, v_cache, k_stage, v_stage, pt, positions, valid,
        use_kernel=False,
    )
    # The DMA path writes whole runs (garbage past the valid tail lands in
    # never-read slots); compare only slots the fallback wrote, plus check
    # valid-token slots match exactly.
    pos = np.asarray(positions)
    val = np.asarray(valid)
    for row in range(b):
        for j in range(t):
            if not val[row, j]:
                continue
            page = int(np.asarray(pt)[row, pos[row, j] // S])
            slot = int(pos[row, j] % S)
            np.testing.assert_allclose(
                np.asarray(got_k)[:, page, slot],
                np.asarray(want_k)[:, page, slot],
            )
            np.testing.assert_allclose(
                np.asarray(got_v)[:, page, slot],
                np.asarray(want_v)[:, page, slot],
            )


def test_full_model_decode_pallas_vs_xla():
    """forward_hidden with attention_impl=pallas == xla on a decode step."""
    from dataclasses import replace

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    page_size, num_pages, mp = 4, 32, 6

    pt = jnp.asarray(np.array([[1, 2, 3, 0, 0, 0], [4, 5, 6, 0, 0, 0]], np.int32))
    # Prefill 9 tokens into the cache (positions 0..8), then decode pos 9.
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)), jnp.int32)
    positions = jnp.tile(jnp.arange(9, dtype=jnp.int32)[None], (2, 1))
    dec_tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)), jnp.int32)
    dec_pos = jnp.full((2, 1), 9, jnp.int32)
    dec_valid = jnp.ones((2, 1), bool)

    # Each impl builds its own cache: the pallas cache is lane-padded
    # (cfg.kv_head_dim 128 vs head_dim 16), exercising the padded path.
    cfg_p = replace(cfg, attention_impl="pallas")
    assert cfg_p.kv_head_dim == 128 and cfg.kv_head_dim == cfg.head_dim
    results = {}
    for c in (cfg, cfg_p):
        kv = init_kv_pages(c, num_pages, page_size)
        _, kv = forward_hidden(
            params, c, toks, positions, jnp.ones((2, 9), bool), kv, pt
        )
        h, _ = forward_hidden(params, c, dec_tok, dec_pos, dec_valid, kv, pt)
        results[c.attention_impl] = np.asarray(h)
    np.testing.assert_allclose(
        results["pallas"], results["xla"], rtol=1e-5, atol=1e-5
    )


def test_full_model_chunked_prefill_pallas_vs_xla():
    """Chunked prefill under the pallas write discipline (staged writes,
    history+current-chunk attention) matches the xla scatter path."""
    from dataclasses import replace

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    page_size, num_pages = 4, 32
    pt = jnp.asarray(np.array([[1, 2, 3, 4, 0, 0], [5, 6, 7, 8, 0, 0]], np.int32))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)

    cfg_p = replace(cfg, attention_impl="pallas")
    results = {}
    for c in (cfg, cfg_p):
        kv = init_kv_pages(c, num_pages, page_size)
        hs = []
        for start in (0, 8):  # two page-aligned chunks: 8 then 4 tokens
            t = 8 if start == 0 else 4
            chunk = toks[:, start : start + t]
            positions = jnp.tile(
                jnp.arange(t, dtype=jnp.int32)[None] + start, (2, 1)
            )
            h, kv = forward_hidden(
                params, c, chunk, positions, jnp.ones((2, t), bool), kv, pt
            )
            hs.append(np.asarray(h))
        results[c.attention_impl] = hs
    for h_x, h_p in zip(results["xla"], results["pallas"]):
        np.testing.assert_allclose(h_p, h_x, rtol=1e-5, atol=1e-5)


def test_paged_write_kernel_under_tp_mesh():
    """The shard_mapped DMA writer (use_kernel=True, interpret on CPU)
    matches the replicated fallback under a tp=2 mesh."""
    import jax
    import pytest as _pytest

    if len(jax.devices()) < 2:
        _pytest.skip("needs the virtual multi-device CPU mesh")
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    rng = np.random.default_rng(5)
    L, P, S, hkv, d = 2, 8, 4, 2, 128
    b = 2
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    k_cache = jnp.asarray(rng.normal(size=(L, P, S, hkv, d)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(L, P, S, hkv, d)), jnp.float32)
    k_st = jnp.asarray(rng.normal(size=(L, b, 1, hkv, d)), jnp.float32)
    v_st = jnp.asarray(rng.normal(size=(L, b, 1, hkv, d)), jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([[2], [5]], jnp.int32)
    val = jnp.ones((b, 1), bool)

    got_k, got_v = paged_write(
        k_cache, v_cache, k_st, v_st, pt, pos, val,
        use_kernel=True, mesh=mesh,
    )
    want_k, want_v = paged_write(
        k_cache, v_cache, k_st, v_st, pt, pos, val, use_kernel=False
    )
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v))


def test_full_model_decode_hybrid_matches_xla_both_sides_of_threshold():
    """attention_impl=hybrid: decode == xla whether the bucket lands on
    the pallas page-walk side (b <= pallas_decode_max_batch) or the
    XLA-gather side (b > threshold). Same staged write discipline both
    ways — only the decode attention read path switches."""
    from dataclasses import replace

    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(5)
    page_size, num_pages = 4, 32

    pt = jnp.asarray(
        np.array([[1, 2, 3, 0, 0, 0], [4, 5, 6, 0, 0, 0]], np.int32)
    )
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)), jnp.int32)
    positions = jnp.tile(jnp.arange(9, dtype=jnp.int32)[None], (2, 1))
    dec_tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)), jnp.int32)
    dec_pos = jnp.full((2, 1), 9, jnp.int32)
    dec_valid = jnp.ones((2, 1), bool)

    variants = {
        "xla": cfg,
        # b=2 > 1: hybrid decodes via the XLA gather (kernel-free path)
        "hybrid_gather": replace(
            cfg, attention_impl="hybrid", pallas_decode_max_batch=1
        ),
        # b=2 <= 8: hybrid decodes via the pallas page-walk kernel
        "hybrid_kernel": replace(
            cfg, attention_impl="hybrid", pallas_decode_max_batch=8
        ),
    }
    assert variants["hybrid_gather"].kv_head_dim == 128  # padded cache
    results = {}
    for name, c in variants.items():
        kv = init_kv_pages(c, num_pages, page_size)
        _, kv = forward_hidden(
            params, c, toks, positions, jnp.ones((2, 9), bool), kv, pt
        )
        h, _ = forward_hidden(params, c, dec_tok, dec_pos, dec_valid, kv, pt)
        results[name] = np.asarray(h)
    np.testing.assert_allclose(
        results["hybrid_gather"], results["xla"], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        results["hybrid_kernel"], results["xla"], rtol=1e-5, atol=1e-5
    )


def test_hybrid_serves_under_tp_mesh(cpu_mesh_devices):
    """hybrid impl on a tp=2 mesh, with the decode bucket ABOVE the
    pallas threshold so the XLA-gather branch runs against the sharded
    (lane-padded) cache; tokens must match the single-chip xla engine."""
    from dataclasses import replace as _replace

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.models.registry import _LLAMA_PRESETS

    _LLAMA_PRESETS["hybrid-test-tiny"] = lambda: _replace(
        LlamaConfig.tiny(), pallas_decode_max_batch=1
    )
    try:
        kw = dict(
            model="hybrid-test-tiny", num_pages=32, page_size=4,
            max_pages_per_seq=8, decode_buckets=(2,), prefill_chunk=8,
            max_seqs=2, dtype="float32",
        )
        outs = {}
        for name, extra in (
            ("xla", dict(attention_impl="xla")),
            ("hybrid_tp", dict(attention_impl="hybrid", tp=2)),
        ):
            eng = JaxEngine(EngineConfig(**kw, **extra))
            rng = np.random.default_rng(9)
            for i in range(2):
                eng.add_request(
                    f"r{i}", [int(x) for x in rng.integers(1, 250, 6 + i)],
                    SamplingParams(temperature=0.0, max_tokens=4),
                )
            outs[name] = eng.run_to_completion()
        assert outs["hybrid_tp"] == outs["xla"], outs
    finally:
        _LLAMA_PRESETS.pop("hybrid-test-tiny", None)
