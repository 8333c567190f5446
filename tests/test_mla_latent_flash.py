"""A latent model's prefill chunk under the kernels' discipline: ONE kernel
over the paged history and the chunk itself (`ops/flash_prefill.py`
`latent_prefill_attention`, reached through `mla._latent_prefill_attention`),
against dense float64 attention in numpy over the operands as the kernel
rounds them, and against `_attend_xla`'s scatter-then-gather form.

On the CPU the kernel is interpreted at `mla-tiny-moe`'s widths (float32,
pages of 4). The same file runs ON THE CHIP (`DYNTPU_TEST_ON_TPU=1 python
-m pytest tests/test_mla_latent_flash.py` through the chip tool; conftest
then leaves the platform alone) at DeepSeek-V2-Lite's widths (16 heads, a
512-wide latent, a rope key of 64 cached as 128 lanes, bfloat16, pages of
64), compiled by Mosaic; the engine case, whose preset is the tiny one, is
the CPU's alone.

Tolerances. float32, interpreted: both sides differ in the order of their
sums, a few 1e-7; the limit is 2e-5. bfloat16 on the chip: the kernel
rounds the softmax's weights to bfloat16 before the value sum (as the loop
it replaced did), 2^-9 of a weight, against values of N(0, 1): the limit
against numpy is 2e-2, and against `_attend_xla`, whose float32 einsums the
TPU runs in bfloat16 passes of its own, 5e-2. A missing page, a key a row
too far or a dropped mask moves a row by 0.1 and more.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import mla
from dynamo_tpu.models.llama import StepGroup
from dynamo_tpu.ops import flash_prefill

LAYERS, LAYER = 3, 1


@pytest.fixture(scope="module")
def widths():
    """(config under the kernels, page size, (against numpy, against
    `_attend_xla`) limits) of this backend."""
    if jax.default_backend() == "tpu":
        cfg = replace(mla.MlaConfig.deepseek_v2_lite(LAYERS),
                      attention_impl="pallas")
        return cfg, 64, (2e-2, 5e-2)
    cfg = replace(mla.MlaConfig.tiny_moe(), attention_impl="pallas")
    return cfg, 4, (2e-5, 2e-5)


def make_rows(cfg, page, t, hist, cur, seed=0):
    """Seeded operands of one prefill group: `hist[b]` tokens of history
    in pages of their own, `cur[b]` valid rows of `t`; the rows past them
    hold NaN, in the queries and in the staged keys."""
    b = len(hist)
    pages = 8 + sum(-(-(h + n) // page) for h, n in zip(hist, cur))
    hn, c, r = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    keys = jax.random.split(jax.random.key(seed), 6)
    k = jax.random.normal(keys[0], (LAYERS, pages, page, 1, c), cfg.dtype)
    v = mla._pad_last(
        jax.random.normal(keys[1], (LAYERS, pages, page, 1, r), cfg.dtype),
        cfg.kv_rope_dim)
    mp = -(-(max(hist) + t) // page)
    rng = np.random.default_rng(seed)
    pt = np.zeros((b, mp), np.int32)
    free = rng.permutation(np.arange(1, pages))
    for i in range(b):  # the history's pages and the chunk's own
        n = -(-(hist[i] + cur[i]) // page)
        pt[i, :n], free = free[:n], free[n:]
    hist, cur = np.asarray(hist, np.int32), np.asarray(cur, np.int32)
    valid = np.arange(t)[None, :] < cur[:, None]
    positions = np.where(valid, hist[:, None] + np.arange(t)[None, :], 0)

    def rows(key, shape, dtype):
        x = jax.random.normal(key, (b, t, *shape), dtype)
        live = valid.reshape(b, t, *(1,) * len(shape))
        return x, jnp.where(live, x, jnp.nan)

    q_lat, q_lat_nan = rows(keys[2], (hn, c), jnp.float32)
    q_pe, q_pe_nan = rows(keys[3], (hn, r), cfg.dtype)
    c_kv, c_kv_nan = rows(keys[4], (c,), cfg.dtype)
    k_pe, k_pe_nan = rows(keys[5], (r,), cfg.dtype)
    return dict(
        k=k, v=v, pt=jnp.asarray(pt), hist=hist, cur=cur,
        valid=jnp.asarray(valid), positions=jnp.asarray(positions, jnp.int32),
        clean=(q_lat, q_pe, c_kv, k_pe),
        dirty=(q_lat_nan, q_pe_nan, c_kv_nan, k_pe_nan),
    )


def dense_numpy(cfg, case):
    """float64 softmax attention of every valid row over its history in
    the pool and the chunk's rows up to itself, the operands as the
    kernel rounds them: [B, T, H, c]."""
    dt, r = cfg.dtype, cfg.qk_rope_head_dim
    q_lat, q_pe, c_kv, k_pe = case["clean"]
    f64 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)  # noqa: E731
    ql = f64((q_lat * cfg.softmax_scale).astype(dt))
    qp = f64((q_pe.astype(jnp.float32) * cfg.softmax_scale).astype(dt))
    out = np.zeros(ql.shape, np.float64)
    for b, (h, n) in enumerate(zip(case["hist"], case["cur"])):
        pages = np.asarray(case["pt"])[b]
        lat = np.concatenate([
            f64(case["k"][LAYER])[pages].reshape(-1, ql.shape[-1])[:h],
            f64(c_kv[b])[:n]])
        rope = np.concatenate([
            f64(case["v"][LAYER])[pages].reshape(
                -1, case["v"].shape[-1])[:h, :r],
            f64(k_pe[b])[:n]])
        for i in range(n):
            s = ql[b, i] @ lat[:h + i + 1].T + qp[b, i] @ rope[:h + i + 1].T
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, i] = (p / p.sum(-1, keepdims=True)) @ lat[:h + i + 1]
    return out


def through_the_kernel(cfg, case, first_chunk=False, mesh=None):
    q_lat, q_pe, c_kv, k_pe = case["dirty"]
    return np.asarray(jax.jit(
        lambda *a: mla._latent_prefill_attention(
            *a, cfg, first_chunk, mesh)
    )(q_lat, q_pe, c_kv, mla._pad_last(k_pe, cfg.kv_rope_dim), case["k"],
      case["v"], jnp.int32(LAYER), case["pt"], case["positions"],
      case["valid"]))


def through_attend_xla(cfg, case):
    """The xla discipline on the same rows: its cache holds the rope key
    unpadded, and its padding rows zeros (it lands them on the null page,
    whose rows it then gathers under a zero weight)."""
    xla = replace(cfg, attention_impl="xla")
    r = cfg.qk_rope_head_dim
    q_lat, q_pe, c_kv, k_pe = case["clean"]
    g = StepGroup(None, case["positions"], case["valid"], case["pt"], False)
    o_lat, _kv, _ = jax.jit(
        lambda ql, qp, ck, kp, k, v: mla._attend_xla(
            ql, qp, ck, kp, xla, (k, v), jnp.int32(LAYER), g, None, None)
    )(q_lat, q_pe, c_kv, k_pe, case["k"], case["v"][..., :r])
    return np.asarray(o_lat)


def pages_of(page):
    """Histories in tokens: none, one page, two pages and a partial
    third, nine pages (more than one block of eight)."""
    return {"none": 0, "one": page, "partial": 2 * page + page // 2 + 1,
            "nine": 9 * page}


ROWS = {
    # name: (histories by `pages_of`'s names, valid rows as a share of T)
    "one-prompt-no-history": (["none"], [1.0]),
    "one-prompt-one-page": (["one"], [0.8]),
    "two-prompts-partial-page-and-nine": (["partial", "nine"], [1.0, 0.6]),
    "four-prompts-one-all-padding": (["nine", "none", "one", "none"],
                                     [1.0, 0.5, 1.0, 0.0]),
}


@pytest.mark.parametrize("t", [16, 32, 512])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_chunk_attention_matches_dense_and_the_xla_form(widths, rows, t):
    cfg, page, (lim_numpy, lim_xla) = widths
    names, shares = ROWS[rows]
    hist = [pages_of(page)[n] for n in names]
    cur = [int(round(share * t)) for share in shares]
    case = make_rows(cfg, page, t, hist, cur, seed=t)
    got = through_the_kernel(cfg, case)
    live = np.asarray(case["valid"])
    assert np.isfinite(got[live]).all()  # what padding held reached no row
    want = dense_numpy(cfg, case)
    assert np.abs(got - want)[live].max() < lim_numpy
    assert np.abs(want[live]).max() > 0.5  # there is something to miss
    xla = through_attend_xla(cfg, case)
    assert np.abs(got - xla)[live].max() < lim_xla


@pytest.mark.parametrize("first_chunk", [True, False])
def test_a_first_chunk_walks_no_history(widths, first_chunk):
    """`first_chunk` runs no turn over the cache whatever the page table
    says; a chunk that starts at 0 and is not called first gives the same."""
    cfg, page, (lim, _) = widths
    case = make_rows(cfg, page, 32, [0, 0], [32, 20], seed=5)
    case["pt"] = case["pt"].at[:, :].set(7)  # live pages it must not read
    got = through_the_kernel(cfg, case, first_chunk)
    live = np.asarray(case["valid"])
    assert np.abs(got - dense_numpy(cfg, case))[live].max() < lim


def test_heads_shard_over_a_tp_mesh_and_the_cache_replicates(widths):
    """tp = 2: each shard runs the kernel on its half of the heads over
    the whole one-row cache (`shard_map`); the rows are the mesh-less
    kernel's (a tile of half the rows sums in another order: a few 1e-7)
    and dense attention's."""
    if jax.default_backend() == "tpu":
        pytest.skip("one chip there; the mesh is the CPU's virtual one")
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, page, (lim, _) = widths
    mesh = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    assert cfg.num_heads % 2 == 0
    case = make_rows(cfg, page, 16, [2 * page + 3, 9 * page, 0],
                     [16, 10, 0], seed=11)
    got = through_the_kernel(cfg, case, mesh=mesh)
    live = np.asarray(case["valid"])
    assert np.isfinite(got[live]).all()
    assert np.abs(got - through_the_kernel(cfg, case))[live].max() < lim
    assert np.abs(got - dense_numpy(cfg, case))[live].max() < lim


def test_the_blocking_is_constants_and_rows_come_as_cached(widths):
    """No option chooses the blocking (8 history pages a turn, as the loop
    it replaced took), and the chunk's rope keys come as the pool holds
    them: whole lane tiles."""
    assert flash_prefill.LATENT_BLOCK_PAGES == 8
    cfg, page, _ = widths
    case = make_rows(cfg, page, 16, [9 * page], [16])
    q_lat, q_pe, c_kv, k_pe = case["clean"]
    with pytest.raises(ValueError, match="one-row cache"):
        flash_prefill.latent_prefill_attention(
            q_lat.astype(cfg.dtype), q_pe, c_kv, k_pe, case["k"], case["v"],
            jnp.int32(0), case["pt"], case["hist"], case["cur"])


def test_engine_streams_equal_the_xla_disciplines():
    """The normal path (scheduler, page allocator, step programs): prompts
    prefilled in chunks of 8 over their own latent history, one of them
    beside the other's decode, then fused decode; greedy streams under
    the kernels are the xla discipline's token for token."""
    if jax.default_backend() == "tpu":
        pytest.skip("the tiny preset's widths are the interpreter's")
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    rng = np.random.default_rng(2)
    prompts = {f"r{i}": [int(x) for x in rng.integers(1, 250, n)]
               for i, n in enumerate((29, 13))}
    streams = {}
    for impl in ("xla", "pallas"):
        eng = JaxEngine(EngineConfig(
            model="mla-tiny-moe", attention_impl=impl, num_pages=64,
            page_size=4, max_pages_per_seq=16, decode_buckets=(2,),
            prefill_chunk=8, max_seqs=2, dtype="float32", decode_steps=4))
        for rid, p in prompts.items():
            eng.add_request(rid, p, SamplingParams(temperature=0.0,
                                                   max_tokens=10))
        streams[impl] = eng.run_to_completion()
    assert all(len(s) == 10 for s in streams["xla"].values())
    assert streams["pallas"] == streams["xla"]
