"""Quantized KV-cache pages (EngineConfig.kv_quantize, ISSUE 2).

Pages store int8 (or fp8) rows with per-(page, kv-head, slot) f32 scale
planes; the Pallas page writer quantizes on write and both page-walk
readers (decode + paged-history prefill) dequantize in VMEM, with the
XLA gather fallback matching. These tests pin:

- the quantize/dequantize round-trip error bound per row,
- write-kernel vs XLA-scatter cache agreement (same quantized bytes),
- kernel outputs against the dense fp reference within the gate budget,
- page/byte accounting (~2x capacity at a fixed HBM budget; KVBM tier
  entries ship quantized bytes),
- the engine-level greedy A/B on the tiny CPU model (streams pinned),
- refusals (MLA, bad mode strings).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.models.llama import (
    LlamaConfig,
    dequantize_kv_rows,
    forward,
    init_kv_pages,
    init_params,
    kv_page_bytes,
    quantize_kv_rows,
)

PAGE_SIZE = 4


# -- row quantization ------------------------------------------------------


def test_quantize_roundtrip_bounds():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (5, 7, 2, 16)), jnp.float32)
    q, scale = quantize_kv_rows(x, "int8")
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    deq = dequantize_kv_rows(q, scale, jnp.float32)
    # symmetric round-to-nearest: |err| <= scale/2 per element
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # a zero row must survive (scale floor, no NaN/inf)
    qz, sz = quantize_kv_rows(jnp.zeros((3, 16)), "int8")
    assert np.asarray(dequantize_kv_rows(qz, sz, jnp.float32)).sum() == 0.0


def test_quantize_fp8_when_available():
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no fp8 dtype in this jax")
    x = jnp.asarray(np.random.default_rng(1).normal(0, 2, (4, 16)))
    q, scale = quantize_kv_rows(x, "fp8")
    deq = np.asarray(dequantize_kv_rows(q, scale, jnp.float32))
    rel = np.abs(deq - np.asarray(x)).max() / (np.abs(np.asarray(x)).max())
    assert rel < 0.08, rel  # e4m3: ~2^-3 relative worst case near amax


# -- write kernel ----------------------------------------------------------


def test_paged_write_quantized_kernel_matches_fallback():
    """The Pallas DMA writer (interpret mode) and the XLA scatter must
    land BYTE-IDENTICAL quantized pages + scale planes."""
    from dynamo_tpu.ops.kv_update import paged_write

    cfg = LlamaConfig.tiny()
    rng = np.random.default_rng(2)
    L, B, T, Hkv, D = cfg.num_layers, 2, PAGE_SIZE, cfg.num_kv_heads, 16
    k_st = jnp.asarray(rng.normal(0, 1, (L, B, T, Hkv, D)), jnp.float32)
    v_st = jnp.asarray(rng.normal(0, 1, (L, B, T, Hkv, D)), jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    valid = jnp.asarray([[True] * T, [True, True, True, False]])

    outs = {}
    for use_kernel in (True, False):
        kv = init_kv_pages(cfg, 8, PAGE_SIZE, kv_quantize="int8")
        outs[use_kernel] = paged_write(
            kv.k, kv.v, k_st, v_st, pt, positions, valid,
            use_kernel=use_kernel, k_scale=kv.k_scale, v_scale=kv.v_scale,
        )
    for i, (a, b) in enumerate(zip(outs[True], outs[False])):
        # compare READABLE slots only: the kernel's whole-run DMA also
        # lands the prompt-tail garbage row (seq 1 slot 3 — contractually
        # unreadable, overwritten before decode exposes it) which the
        # token-granular scatter drops; page 0 is the null page
        a, b = np.asarray(a), np.asarray(b)
        if i >= 2:  # scale planes [L, P, Hkv, S] -> slot-major like rows
            a, b = a.swapaxes(2, 3), b.swapaxes(2, 3)
        assert np.array_equal(a[:, 1], b[:, 1])  # seq 0's full page
        assert np.array_equal(a[:, 3, :3], b[:, 3, :3])  # seq 1 valid rows

    # dequantized cache rows ≈ the staged fp values within scale/2
    kq, vq, ks, vs = outs[False]
    page1_scales = ks[:, 1].swapaxes(1, 2)  # [L, S, Hkv]
    got = np.asarray(
        dequantize_kv_rows(kq[:, 1], page1_scales, jnp.float32)
    )  # page 1 = seq 0's tokens
    want = np.asarray(k_st[:, 0])
    bound = np.asarray(page1_scales)[..., None] * 0.5 + 1e-6
    assert (np.abs(got - want) <= bound).all()


# -- kernel readers vs dense fp reference ----------------------------------


def _chunked_forward(cfg, params, toks, kvq):
    """first chunk -> history chunk -> decode steps; returns the logits
    trace (exercises flash prefill, paged-history prefill, decode walk)."""
    B, T = 2, 8
    kv = init_kv_pages(cfg, 32, PAGE_SIZE, kv_quantize=kvq)
    pt = jnp.asarray(
        np.stack([np.arange(1, 9), np.arange(9, 17)]).astype(np.int32)
    )
    pos1 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    valid = jnp.ones((B, T), bool)
    _, kv = forward(
        params, cfg, toks[:, :T], pos1, valid, kv, pt, first_chunk=True
    )
    logits, kv = forward(params, cfg, toks[:, T:], pos1 + T, valid, kv, pt)
    trace = [np.asarray(logits[:, -1])]
    for i in range(4):
        logits, kv = forward(
            params, cfg,
            jnp.asarray([[3], [4]], jnp.int32),
            jnp.full((B, 1), 2 * T + i, jnp.int32),
            jnp.ones((B, 1), bool), kv, pt,
        )
        trace.append(np.asarray(logits[:, 0]))
    return trace


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = LlamaConfig.tiny()
    params = init_params(jax.random.key(0), cfg)
    toks = jnp.asarray(
        np.random.default_rng(3).integers(1, 200, (2, 16)), jnp.int32
    )
    return cfg, params, toks


def test_kernels_match_dense_fp_reference(tiny_setup):
    cfg, params, toks = tiny_setup
    ref = _chunked_forward(
        dataclasses.replace(cfg, attention_impl="xla"), params, toks, None
    )
    for impl in ("xla", "pallas"):
        got = _chunked_forward(
            dataclasses.replace(cfg, attention_impl=impl), params, toks,
            "int8",
        )
        for i, (a, b) in enumerate(zip(got, ref)):
            d = float(np.abs(a - b).max())
            # the serve gate's budget; measured ~0.03 on this setup
            assert d < 0.25, (impl, i, d)


def test_pallas_and_xla_read_identical_quantized_bytes(tiny_setup):
    """Both impls dequantize the SAME stored history rows; the residual
    gap is the CURRENT token's handling — the pallas merge folds the
    exact fp row in while the xla scatter-then-gather reads it back
    quantized (strictly less accurate) — plus accumulation order. Both
    are one-token effects, an order of magnitude under the gate budget."""
    cfg, params, toks = tiny_setup
    a = _chunked_forward(
        dataclasses.replace(cfg, attention_impl="xla"), params, toks, "int8"
    )
    b = _chunked_forward(
        dataclasses.replace(cfg, attention_impl="pallas"), params, toks,
        "int8",
    )
    for i, (x, y) in enumerate(zip(a, b)):
        assert float(np.abs(x - y).max()) < 6e-2, i


def test_default_off_is_bit_identical(tiny_setup):
    """kv_quantize=None must not change a single bit of today's outputs
    (the acceptance criterion's default-path guarantee)."""
    cfg, params, toks = tiny_setup
    for impl in ("xla", "pallas"):
        c = dataclasses.replace(cfg, attention_impl=impl)
        a = _chunked_forward(c, params, toks, None)
        b = _chunked_forward(c, params, toks, None)
        for x, y in zip(a, b):
            assert np.array_equal(x, y)


# -- byte accounting -------------------------------------------------------


def test_page_capacity_doubles_at_fixed_budget():
    cfg = LlamaConfig.llama3_8b()  # D=128: the scale overhead is ~3%
    budget = 8 << 30
    dense = kv_page_bytes(cfg, 64, dtype=jnp.bfloat16)
    quant = kv_page_bytes(cfg, 64, "int8")
    assert budget // quant >= 1.9 * (budget // dense)
    # scale planes are accounted: strictly more than plain int8 rows
    assert quant > dense // 2


def test_engine_pool_byte_gauges():
    base = EngineConfig.for_tests()
    eng_q = JaxEngine(dataclasses.replace(base, kv_quantize="int8"))
    m = eng_q.metrics
    assert m.kv_pool_bytes > 0
    assert m.kv_pool_bytes_dense_equiv > m.kv_pool_bytes
    # tiny config is f32/D=16: int8+scale = (16+4)/64 of dense
    assert m.kv_pool_bytes / m.kv_pool_bytes_dense_equiv == pytest.approx(
        20 / 64
    )
    assert m.kv_free_pages == eng_q.allocator.num_free


def test_kvbm_tier_entries_ship_quantized_bytes():
    def host_entry(kvq):
        cfg = dataclasses.replace(
            EngineConfig.for_tests(), kv_quantize=kvq,
            host_kv_cache_bytes=1 << 20,
        )
        eng = JaxEngine(cfg)
        eng.add_request(
            "a", list(range(1, 13)),
            SamplingParams(temperature=0.0, max_tokens=4),
        )
        out = eng.run_to_completion()["a"]
        alloc = eng.allocator
        metas = dict(alloc._page_meta)
        alloc._offload_pages(list(metas))
        alloc.flush_offloads()
        return out, alloc.host.get(next(iter(metas.values()))[0])

    out_q, eq = host_entry("int8")
    out_f, ef = host_entry(None)
    assert out_q == out_f  # tiny-model greedy stream pinned across modes
    assert eq.k.dtype == np.int8
    # wire rows carry D+4 bytes (packed f32 scale) vs D*4 f32 dense
    assert eq.nbytes / ef.nbytes == pytest.approx(20 / 64)


# -- engine A/B ------------------------------------------------------------


def test_engine_greedy_ab_pins_streams():
    """Greedy token streams on the tiny CPU model: int8 pages vs fp
    pages. With random near-uniform weights a near-tie argmax can flip
    under ~0.4% row noise, so the pin is a TOLERANCE: per request the
    first 4 tokens match exactly and at most one token of 6 diverges
    (measured: 17/18 agree, one last-token flip). The int8 engine itself
    must be exactly deterministic run to run."""
    prompts = [
        [5, 17, 42, 99, 3, 8, 21, 60, 11, 2],
        [9, 1, 33, 7, 52, 4, 18, 73, 6, 12],
        list(range(2, 14)),
    ]

    def run(kvq):
        cfg = dataclasses.replace(
            EngineConfig.for_tests(), kv_quantize=kvq
        )
        eng = JaxEngine(cfg)
        for i, p in enumerate(prompts):
            eng.add_request(
                f"r{i}", p, SamplingParams(temperature=0.0, max_tokens=6)
            )
        return eng.run_to_completion()

    fp = run(None)
    q8 = run("int8")
    q8b = run("int8")
    assert q8 == q8b, "int8 engine must be deterministic"
    for rid in fp:
        assert fp[rid][:4] == q8[rid][:4], (rid, fp[rid], q8[rid])
        agree = sum(a == b for a, b in zip(fp[rid], q8[rid]))
        assert agree >= len(fp[rid]) - 1, (rid, fp[rid], q8[rid])


def test_extract_inject_roundtrip_byte_identity():
    cfg = dataclasses.replace(EngineConfig.for_tests(), kv_quantize="int8")
    pre = JaxEngine(cfg)
    prompt = [5, 17, 42, 99, 3, 8, 21, 60, 11, 2]
    req = pre.add_request(
        "d1", prompt,
        SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True),
    )
    req.hold_pages = True
    pre.run_to_completion()
    held = pre.scheduler.held["d1"]
    k, v = pre.extract_pages(held)
    assert k.dtype == np.int8
    # wire width = D + 4 packed scale lanes
    assert k.shape[-1] == pre.adapter.config.head_dim + 4

    dec = JaxEngine(cfg)
    rd = dec.allocate_for_remote_prefill(
        "d1", prompt, SamplingParams(temperature=0.0, max_tokens=4)
    )
    dec.inject_pages(rd.pages, k, v)
    k2, v2 = dec.extract_pages(rd.pages)
    assert np.array_equal(k, k2) and np.array_equal(v, v2)


def test_quantized_under_tp_mesh_both_impls(cpu_mesh_devices):
    """shard_map paths: scale planes shard on the kv-head axis with their
    pools, for the xla scatter AND all three Pallas kernels."""
    from dynamo_tpu.parallel import MeshConfig

    base = EngineConfig.for_tests()
    outs = {}
    for impl in ("xla", "pallas"):
        cfg = dataclasses.replace(
            base, kv_quantize="int8", tp=2, attention_impl=impl
        )
        eng = JaxEngine(cfg, mesh_config=MeshConfig(dp=1, tp=2, sp=1))
        eng.add_request(
            "m", [1, 2, 3, 4, 5, 6],
            SamplingParams(temperature=0.0, max_tokens=4),
        )
        outs[impl] = eng.run_to_completion()["m"]
        assert len(outs[impl]) == 4
    # single-chip quantized engine must produce the identical tokens
    eng1 = JaxEngine(dataclasses.replace(base, kv_quantize="int8"))
    eng1.add_request(
        "s", [1, 2, 3, 4, 5, 6],
        SamplingParams(temperature=0.0, max_tokens=4),
    )
    single = eng1.run_to_completion()["s"]
    assert outs["xla"] == single and outs["pallas"] == single


# -- refusals --------------------------------------------------------------


def test_config_validates_kv_quantize():
    with pytest.raises(ValueError, match="kv_quantize"):
        dataclasses.replace(EngineConfig.for_tests(), kv_quantize="int4")


def test_mla_rejects_kv_quantize():
    from dynamo_tpu.models.registry import get_model

    adapter = get_model("mla-tiny")
    with pytest.raises(ValueError, match="MLA"):
        adapter.init_kv(8, 4, kv_quantize="int8")
