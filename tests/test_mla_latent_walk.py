"""DeepSeek-V2-Lite's two mechanisms at `mla-tiny-moe` widths, float32,
seeded weights, on the CPU: the latent page walk (the kernel interpreted)
against `mla_attention`'s XLA form and against the plain reference, and
the dropless expert dispatch against a per-token loop.

Tolerances: both sides are float32 and differ only in the order of
accumulation (blocks of pages and an online softmax against one dense
softmax; sorted groups against a per-token sum), so logits agree to a
few 1e-6 and the limits below are 1e-4 / 2e-4: forty times that, and a
hundredth of what a missing page, a missing expert or a float32 -> bf16
rounding moves (1e-2 and up, asserted where it is cheap)."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import deepseek_v2_lite as ref
from dynamo_tpu.models import mla
from dynamo_tpu.ops.paged_attention import (_block_pages, decode_vmem_bytes,
                                            paged_decode_attention)

PAGE = 4
HF = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "vocab_size": 256, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "first_k_dense_replace": 1,
    "routed_scaling_factor": 1.0, "norm_topk_prob": False,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
}


def tiny(impl: str) -> mla.MlaConfig:
    """mla-tiny-moe with DeepSeek-V2-Lite's YaRN fields (HF above)."""
    return replace(
        mla.MlaConfig.tiny_moe(), attention_impl=impl,
        rope_scaling_factor=40.0, rope_mscale=0.707,
        rope_mscale_all_dim=0.707, rope_original_max_position=16)


# -- the kernel alone ----------------------------------------------------------


def dense_latent_attention(q, k, v, layer, pt, hist, scale):
    """softmax(q . [latent | rope key]) . latent over the first `hist`
    tokens of each row, densely, in float32: (out, running max)."""
    b, mp = pt.shape
    c = k.shape[-1]
    ck = k[layer][pt].reshape(b, mp * k.shape[2], c)
    rk = v[layer][pt].reshape(b, mp * k.shape[2], -1)
    s = scale * (jnp.einsum("bhc,bkc->bhk", q[..., :c], ck)
                 + jnp.einsum("bhr,bkr->bhk", q[..., c:], rk))
    live = jnp.arange(s.shape[-1])[None, None] < hist[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    return (jnp.einsum("bhk,bkc->bhc", p, ck),
            jnp.max(jnp.where(live, s, -jnp.inf), axis=-1))


@pytest.mark.parametrize("hist", [
    pytest.param([3, 4, 17, 36], id="ragged-and-mid-page"),
    pytest.param([1, 2, 4, 3], id="one-page"),
    # 8 pages a block at these shapes: 32 tokens end a block, 33 start one
    pytest.param([32, 33, 31, 64], id="block-boundary"),
    pytest.param([0, 36, 0, 5], id="rows-without-history"),
])
def test_latent_walk_matches_dense_attention(hist):
    layers, pages, c, r, heads, mp = 2, 40, 32, 128, 4, 16
    k = jax.random.normal(jax.random.key(0), (layers, pages, PAGE, 1, c))
    v = jax.random.normal(jax.random.key(1), (layers, pages, PAGE, 1, r))
    q = jax.random.normal(jax.random.key(2), (len(hist), heads, c + r))
    pt = jnp.asarray(np.random.default_rng(0).integers(
        1, pages, (len(hist), mp)), jnp.int32)
    hist = jnp.asarray(hist, jnp.int32)
    assert _block_pages(len(hist), heads, c, PAGE, 1, 4, False, None, r) == 8
    for layer in range(layers):
        acc, m, l = paged_decode_attention(
            q, k, v, jnp.int32(layer), pt, hist, scale=0.17, latent=True)
        want, want_m = dense_latent_attention(q, k, v, layer, pt, hist, 0.17)
        for b, n in enumerate(np.asarray(hist)):
            if n == 0:  # the empty state the caller's merge expects
                assert float(l[b].sum()) == 0 and bool(jnp.isinf(m[b]).all())
                continue
            np.testing.assert_allclose(acc[b] / l[b][:, None], want[b],
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(m[b], want_m[b], rtol=1e-5)


def test_latent_walk_refuses_what_it_cannot_walk():
    k = jnp.zeros((1, 4, PAGE, 2, 32))
    with pytest.raises(ValueError, match="latent walk"):
        paged_decode_attention(
            jnp.zeros((1, 4, 160)), k, jnp.zeros((1, 4, PAGE, 2, 128)),
            jnp.int32(0), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), latent=True)


def test_block_rule_and_footprint_at_deepseek_v2_lite_shapes():
    """One rule for both kinds of cache: 8 pages a block (512 key
    columns, 640 KiB a slot) for a 512 + 128 latent page at 64 rows, and
    the dense caches' numbers as PR 25 left them."""
    assert _block_pages(64, 16, 512, 64, 1, 2, False, 12 << 20, 128) == 8
    assert decode_vmem_bytes(64, 16, 512, 64, 1, 2, budget=12 << 20,
                             rope_dim=128) < 12 << 20
    assert _block_pages(64, 28, 128, 64, 4, 2, False, 12 << 20) == 8  # qwen2
    assert _block_pages(16, 32, 128, 64, 32, 2, False, 12 << 20) == 1  # phi3


# -- the model through the walk ------------------------------------------------


def run_paged(cfg, params, toks, chunks):
    """Prefill in `chunks` (first_chunk where a chunk starts at 0), then
    whatever follows token by token: logits [B, T, V]."""
    b, t = toks.shape
    n_pages = -(-t // PAGE)
    kv = mla.init_kv_pages(cfg, 1 + b * n_pages, PAGE)
    pt = jnp.asarray(1 + np.arange(b * n_pages).reshape(b, n_pages),
                     jnp.int32)
    outs = []
    for start, end in chunks:
        pos = jnp.broadcast_to(jnp.arange(start, end, dtype=jnp.int32),
                               (b, end - start))
        h, kv = mla.forward_hidden(
            params, cfg, jnp.asarray(toks[:, start:end]), pos,
            jnp.ones((b, end - start), bool), kv, pt,
            first_chunk=start == 0)
        outs.append(np.asarray(mla.compute_logits(params, cfg, h)))
    return np.concatenate(outs, axis=1)


CHUNKS = [(0, 8), (8, 16), (16, 20)] + [(i, i + 1) for i in range(20, 27)]


@pytest.fixture(scope="module")
def seeded():
    params = mla.init_params(jax.random.key(3), tiny("xla"))
    toks = np.random.default_rng(0).integers(1, 256, (2, 27))
    return params, toks


def test_kernel_discipline_matches_the_xla_form(seeded):
    """Chunked prefill over a latent history (the XLA loop over live
    pages), then decode through the interpreted kernel with the staged
    write, against scatter-then-gather."""
    params, toks = seeded
    want = run_paged(tiny("xla"), params, toks, CHUNKS)
    got = run_paged(tiny("pallas"), params, toks, CHUNKS)
    assert np.abs(got - want).max() < 1e-4
    # and the cache it leaves behind pads the rope key to a lane tile
    kv = mla.init_kv_pages(tiny("pallas"), 4, PAGE)
    assert kv.k.shape[-1] == 32 and kv.v.shape[-1] == 128
    assert mla.init_kv_pages(tiny("xla"), 4, PAGE).v.shape[-1] == 8


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_agrees_with_the_reference(seeded, impl):
    params, toks = seeded
    got = jax.nn.log_softmax(run_paged(tiny(impl), params, toks, CHUNKS))
    for b in range(toks.shape[0]):
        want = ref.log_probs(params, HF, toks[b].tolist(), np.arange(27))
        assert np.abs(np.asarray(got[b]) - want).max() < 2e-4
    # tight enough to see a page go missing: the reference over a
    # sequence whose first page is other tokens
    other = toks[0].copy()
    other[:PAGE] = (other[:PAGE] + 1) % 256
    moved = ref.log_probs(params, HF, other.tolist(), np.arange(27))
    assert np.abs(np.asarray(got[0][PAGE:]) - moved[PAGE:]).max() > 1e-2


def test_a_large_batch_walks_in_pieces(seeded, monkeypatch):
    params, toks = seeded
    want = run_paged(tiny("pallas"), params, toks, CHUNKS)
    monkeypatch.setattr(mla, "_DECODE_VMEM_BUDGET", 40_000)  # one row fits
    got = run_paged(tiny("pallas"), params, toks, CHUNKS)
    assert np.abs(got - want).max() < 1e-5


def test_engine_serves_through_the_walk_and_the_reference_agrees():
    """The normal path (scheduler, page allocator, step programs) with
    `attention_impl="pallas"`: chunked prefill, fused decode, and every
    greedy token is the reference's own best or a near-tie of it."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    eng = JaxEngine(EngineConfig(
        model="mla-tiny-moe", attention_impl="pallas", num_pages=64,
        page_size=PAGE, max_pages_per_seq=16, decode_buckets=(2,),
        prefill_chunk=8, max_seqs=2, dtype="float32", decode_steps=4))
    assert eng.adapter.config.attention_impl == "pallas"
    assert eng.kv.v.shape[-1] == 128
    rng = np.random.default_rng(1)
    prompts = {f"r{i}": [int(x) for x in rng.integers(1, 250, n)]
               for i, n in enumerate((19, 11))}
    for rid, p in prompts.items():
        eng.add_request(rid, p, SamplingParams(temperature=0.0,
                                               max_tokens=12))
    done = eng.run_to_completion()
    hf = {k: v for k, v in HF.items() if k != "rope_scaling"}  # the preset
    for rid, out in done.items():
        assert len(out) == 12
        seq = prompts[rid] + out
        lp = ref.log_probs(eng.params, hf, seq,
                           len(prompts[rid]) - 1 + np.arange(12))
        gap = lp.max(-1) - lp[np.arange(12), out]
        assert gap.max() < 1e-3, gap


# -- dropless experts ----------------------------------------------------------


def per_token_experts(xf, topw, topi, lp):
    """Each token through each of its experts, one at a time."""
    out = np.zeros(xf.shape, np.float64)
    w = {n: np.asarray(lp[n], np.float64) * (
        np.asarray(lp[n + "_scale"], np.float64) if n + "_scale" in lp else 1)
        for n in ("we_gate", "we_up", "we_down")}
    x = np.asarray(xf, np.float64)
    for t in range(x.shape[0]):
        for j in range(topi.shape[1]):
            e = int(topi[t, j])
            g = x[t] @ w["we_gate"][e]
            h = g / (1 + np.exp(-g)) * (x[t] @ w["we_up"][e])
            out[t] += float(topw[t, j]) * (h @ w["we_down"][e])
    return out


@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("routing", ["gate", "one-expert-takes-all",
                                     "two-experts-only"])
def test_dropless_dispatch_matches_a_per_token_loop(routing, quantize):
    """Every assignment is computed, whatever the routing: with one
    expert taking every token the capacity dispatch (factor 2: room for
    k*N/E*2 = 16 of 32 rows) zeroed half of them."""
    cfg = tiny("xla")
    params = mla.init_params(jax.random.key(0), cfg)
    if quantize:
        params = mla.quantize_params_int8(params)
    lp = jax.tree.map(lambda a: a[1], params["moe_layers"])
    n = 32
    xf = jax.random.normal(jax.random.key(5), (n, cfg.hidden_size))
    topw, topi = mla._gate(xf, lp, cfg)
    if routing == "one-expert-takes-all":
        topi = jnp.stack([jnp.full((n,), 2), (jnp.arange(n) % 3 + 3) % 4], 1)
    elif routing == "two-experts-only":
        topi = jnp.broadcast_to(jnp.asarray([3, 0]), (n, 2))
    got, _ = mla._routed_experts(xf, topw, topi, lp, cfg)
    want = per_token_experts(xf, np.asarray(topw), np.asarray(topi), lp)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
    assert np.abs(want).max() > 1e-2  # there is something to drop


def test_no_capacity_and_no_float32_expert_copy_are_left():
    for gone in ("capacity_factor", "moe_expert_chunk"):
        assert not hasattr(mla.MlaConfig(), gone)
    for gone in ("_auto_expert_chunk", "_MOE_CHUNK_BYTES",
                 "_routed_expert_ffn"):
        assert not hasattr(mla, gone)
    # a bf16 step multiplies bf16 expert matrices: no f32 convert of one
    cfg = replace(mla.MlaConfig.tiny_moe(), dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: mla.init_params(jax.random.key(0), cfg))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      params["moe_layers"])
    text = str(jax.make_jaxpr(
        lambda x, lp: mla._deepseek_moe_ffn(x, lp, cfg))(
            jax.ShapeDtypeStruct((2, 4, 64), jnp.bfloat16), lp))
    assert text.count("ragged_dot_general[") == 3  # gate, up, down
    for shape in ("f32[4,64,32]", "f32[4,32,64]"):
        assert shape not in text


# -- the grouped matmul and the one-row cache write ----------------------------


@pytest.mark.parametrize("sizes", [
    pytest.param([5, 0, 4, 3], id="an-empty-group"),
    pytest.param([0, 0, 150, 0], id="one-group-over-two-row-tiles"),
    pytest.param([1, 1, 1, 1], id="one-row-each"),
])
def test_grouped_matmul_kernel_is_ragged_dot(sizes):
    """The TPU's kernel (megablox, interpreted here) against the
    contract it stands in for, rows not a multiple of the 128-row tile."""
    from dynamo_tpu.ops.grouped_matmul import _tiling, grouped_matmul

    m = sum(sizes)
    x = jax.random.normal(jax.random.key(0), (m, 64))
    w = jax.random.normal(jax.random.key(1), (len(sizes), 64, 32))
    gs = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(x, w, gs)
    np.testing.assert_allclose(grouped_matmul(x, w, gs), want)  # off the TPU
    got = grouped_matmul(x, w, gs, use_kernel=True, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # a layer of a stack, read in place: L*G groups, this layer's non-zero
    stack = jnp.stack([w * 0 + 7.0, w, w * 0 - 3.0])
    for kernel in (False, True):
        got = grouped_matmul(x, stack, gs, layer=jnp.int32(1),
                             use_kernel=kernel, interpret=kernel)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # DeepSeek-V2-Lite's projections: all of K and N in one weight tile
    assert _tiling(2048, 1408, 2) == (128, 2048, 1408)
    assert _tiling(1408, 2048, 2) == (128, 1408, 2048)
    assert _tiling(7168, 2048, 2) == (128, 7168, 384)  # a wider model splits N
    # Nemotron-H's two projections (1856 stored as 1920) under the same
    # rule: two tiles each, the second hanging 384 columns over the edge
    assert _tiling(2688, 1920, 2) == (128, 2688, 1152)
    assert _tiling(1920, 2688, 2) == (128, 1920, 1536)


def test_one_row_cache_writes_land_where_the_scatter_puts_them():
    """A latent cache has one row a token, so its slots lie in the tiled
    dimensions: a decode step's rows go in by `_write_single_rows`, a
    prefill chunk's whole pages through the DMA kernel (interpreted
    here); both against the token-granular scatter."""
    from dynamo_tpu.ops import kv_update

    layers, pages, page, b = 3, 12, 8, 5  # a page is one f32 sublane tile
    k = jax.random.normal(jax.random.key(0), (layers, pages, page, 1, 32))
    v = jax.random.normal(jax.random.key(1), (layers, pages, page, 1, 128))
    pt = jnp.asarray(np.arange(1, 1 + b * 2).reshape(b, 2), jnp.int32)
    # decode: one row each, one of them frozen (lands on the null page)
    ks = jax.random.normal(jax.random.key(2), (layers, b, 1, 1, 32))
    vs = jax.random.normal(jax.random.key(3), (layers, b, 1, 1, 128))
    pos = jnp.asarray([[0], [3], [4], [15], [9]], jnp.int32)
    valid = jnp.asarray([[True], [True], [False], [True], [True]])
    want = kv_update.paged_write(k, v, ks, vs, pt, pos, valid,
                                 use_kernel=False)
    got = kv_update.paged_write(k, v, ks, vs, pt, pos, valid,
                                use_kernel=True)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w_)[:, 1:])
    assert not np.array_equal(np.asarray(got[0]), np.asarray(k))
    # prefill: page-aligned chunks of two pages, through the kernel
    t = 2 * page
    ks = jax.random.normal(jax.random.key(4), (layers, b, t, 1, 32))
    vs = jax.random.normal(jax.random.key(5), (layers, b, t, 1, 128))
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    valid = jnp.ones((b, t), bool)
    want = kv_update.paged_write(k, v, ks, vs, pt, pos, valid,
                                 use_kernel=False)
    got = kv_update.paged_write(k, v, ks, vs, pt, pos, valid,
                                use_kernel=True)
    for g, w_ in zip(got, want):
        assert g.shape == w_.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
    # a chunk shorter than a page, in whole tiles of slots (a T bucket of
    # 32 under pages of 64 on the chip): still the kernel
    page2 = 16
    k2 = jax.random.normal(jax.random.key(6), (layers, pages, page2, 1, 32))
    v2 = jax.random.normal(jax.random.key(7), (layers, pages, page2, 1, 128))
    args = (ks[:, :, :8], vs[:, :, :8], pt, pos[:, :8], valid[:, :8])
    got = kv_update.paged_write(k2, v2, *args, use_kernel=True)
    want = kv_update.paged_write(k2, v2, *args, use_kernel=False)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
    # and one that is not whole tiles goes through the scatter
    args = (ks[:, :, :2], vs[:, :, :2], pt, pos[:, :2], valid[:, :2])
    got = kv_update.paged_write(k, v, *args, use_kernel=True)
    want = kv_update.paged_write(k, v, *args, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
