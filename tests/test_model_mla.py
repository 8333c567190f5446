"""DeepSeek-V2-style MLA + DeepSeek MoE vs HuggingFace
DeepseekV2ForCausalLM, through the compressed-latent paged cache.

The cache stores (c_kv, k_pe) per token and attention runs in the
absorbed form — mathematically identical to HF's decompress-then-attend
eager path, so logits must match to float tolerance.
"""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.disagg import device_transfer
from dynamo_tpu.models.mla import (
    MlaConfig,
    forward,
    init_kv_pages,
    init_params,
    params_from_torch_state_dict,
)

PAGE_SIZE = 4


def _hf_model(cfg: MlaConfig, seed: int = 3):
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        head_dim=cfg.qk_rope_head_dim,  # HF uses this for rotary dims
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_norm_eps,
        tie_word_embeddings=cfg.tie_word_embeddings,
        n_routed_experts=cfg.n_routed_experts or None,
        n_shared_experts=cfg.n_shared_experts or None,
        moe_intermediate_size=cfg.moe_intermediate_size or 1407,
        num_experts_per_tok=(
            cfg.num_experts_per_tok if cfg.n_routed_experts else None
        ),
        first_k_dense_replace=(
            cfg.first_k_dense_replace
            if cfg.n_routed_experts
            else cfg.num_layers
        ),
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
        topk_method="greedy",
        rope_scaling=None,
        attn_implementation="eager",
    )
    torch.manual_seed(seed)
    model = DeepseekV2ForCausalLM(hf_cfg).eval()
    return torch, model


def _run_paged(cfg, params, toks, chunks=None):
    b, t = toks.shape
    kv = init_kv_pages(cfg, 64, PAGE_SIZE)
    n_pages = -(-t // PAGE_SIZE)
    pts = np.zeros((b, n_pages), np.int32)
    for i in range(b):
        pts[i] = np.arange(1 + i * n_pages, 1 + (i + 1) * n_pages)
    outs = []
    for start, end in chunks or [(0, t)]:
        positions = np.tile(np.arange(start, end, dtype=np.int32), (b, 1))
        logits, kv = forward(
            params, cfg, jnp.asarray(toks[:, start:end]),
            jnp.asarray(positions),
            jnp.ones((b, end - start), bool), kv, jnp.asarray(pts),
        )
        outs.append(np.asarray(logits))
    return np.concatenate(outs, axis=1)


def test_mla_dense_against_hf():
    """MLA attention isolated: all layers dense (no MoE)."""
    cfg = MlaConfig.tiny()
    torch, model = _hf_model(cfg)
    params = params_from_torch_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 11)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.95

    # chunked prefill + decode continuation through the paged latent cache
    chunked = _run_paged(cfg, params, toks, chunks=[(0, 8), (8, 11)])
    np.testing.assert_allclose(chunked, ours, rtol=1e-4, atol=1e-4)


def test_mla_q_lora_against_hf():
    """Low-rank q (q_a/q_b with q_a_layernorm — the full V2 shape)."""
    cfg = replace(MlaConfig.tiny(), q_lora_rank=24)
    torch, model = _hf_model(cfg, seed=11)
    params = params_from_torch_state_dict(model.state_dict(), cfg)
    assert "wq_a" in params["dense_layers"]

    rng = np.random.default_rng(9)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 9)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)


def test_mla_moe_against_hf():
    """Dense prefix + DeepSeek MoE layers (greedy top-k, un-normalized
    softmax weights, shared experts)."""
    cfg = MlaConfig.tiny_moe()
    torch, model = _hf_model(cfg, seed=13)
    params = params_from_torch_state_dict(model.state_dict(), cfg)
    assert "we_gate" in params["moe_layers"]
    assert "ws_gate" in params["moe_layers"]

    rng = np.random.default_rng(17)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.9


def test_mla_cache_is_compressed():
    cfg = MlaConfig.tiny()
    kv = init_kv_pages(cfg, 8, PAGE_SIZE)
    assert kv.k.shape[-1] == cfg.kv_lora_rank
    assert kv.v.shape[-1] == cfg.qk_rope_head_dim
    # per-token cache cost = latent + rope key, NOT heads x head_dim x 2
    assert cfg.cache_dim < 2 * cfg.num_heads * (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    )


def test_mla_serves_through_engine():
    """mla-tiny end to end in the real engine: continuous batching,
    prefix caching, greedy decode over the compressed cache."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    eng = JaxEngine(
        EngineConfig(
            model="mla-tiny", num_pages=32, page_size=4,
            max_pages_per_seq=8, decode_buckets=(2,), prefill_chunk=8,
            max_seqs=2, dtype="float32",
        )
    )
    rng = np.random.default_rng(23)
    for i in range(2):
        eng.add_request(
            f"r{i}",
            [int(x) for x in rng.integers(1, 250, 7 + 3 * i)],
            SamplingParams(temperature=0.0, max_tokens=5),
        )
    done = eng.run_to_completion()
    assert all(len(v) == 5 for v in done.values()), done


def test_mla_yarn_config_resolves(tmp_path):
    """YaRN rope-scaling configs (the released R1/V2 shape) load; other
    rope_scaling types stay refused by name."""
    import json

    from dynamo_tpu.models.registry import get_model

    d = tmp_path / "ds"
    d.mkdir()
    (d / "config.json").write_text(json.dumps({
        "architectures": ["DeepseekV2ForCausalLM"],
        "model_type": "deepseek_v2",
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_scaling": {"type": "yarn", "factor": 40, "mscale": 1.0,
                         "mscale_all_dim": 1.0,
                         "original_max_position_embeddings": 4096},
    }))
    c = get_model(str(d), dtype="float32").config
    assert c.rope_scaling_factor == 40.0
    assert c.rope_original_max_position == 4096

    d2 = tmp_path / "ds2"
    d2.mkdir()
    (d2 / "config.json").write_text(json.dumps({
        "architectures": ["DeepseekV2ForCausalLM"],
        "model_type": "deepseek_v2",
        "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_scaling": {"type": "linear", "factor": 4},
    }))
    with pytest.raises(ValueError, match="rope_scaling"):
        get_model(str(d2))


def test_mla_yarn_against_hf():
    """YaRN-scaled rope (interp/extrap ramp + mscale-scaled cos/sin) vs
    HF with an original_max_position SMALLER than the sequence, so the
    scaling demonstrably bites."""
    cfg = replace(
        MlaConfig.tiny(),
        rope_scaling_factor=4.0,
        rope_beta_fast=32.0,
        rope_beta_slow=1.0,
        rope_mscale=1.0,
        rope_mscale_all_dim=0.8,
        rope_original_max_position=8,
    )
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        n_routed_experts=None, first_k_dense_replace=cfg.num_layers,
        tie_word_embeddings=False, attn_implementation="eager",
        max_position_embeddings=64,
        rope_scaling={
            "rope_type": "yarn", "factor": 4.0, "beta_fast": 32,
            "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 0.8,
            "original_max_position_embeddings": 8, "truncate": True,
        },
    )
    torch.manual_seed(41)
    model = DeepseekV2ForCausalLM(hf_cfg).eval()
    params = params_from_torch_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(43)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.95

    # yarn genuinely differs from plain rope on this sequence
    plain = _run_paged(
        replace(cfg, rope_scaling_factor=None), params, toks
    )
    assert not np.allclose(plain, ours)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_mla_serves_under_tp_mesh(cpu_mesh_devices, quantize):
    """tp=2: q heads shard, the latent cache replicates (the engine's
    kv-divisibility check must not refuse the MQA-shaped cache) — both
    the fp and int8 layouts' PartitionSpecs must serve."""
    import numpy as np

    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    eng = JaxEngine(
        EngineConfig(
            model="mla-tiny", tp=2, num_pages=32, page_size=4,
            max_pages_per_seq=8, decode_buckets=(2,), prefill_chunk=8,
            max_seqs=2, dtype="float32", quantize=quantize,
        )
    )
    rng = np.random.default_rng(1)
    for i in range(2):
        eng.add_request(
            f"r{i}", [int(x) for x in rng.integers(1, 250, 6)],
            SamplingParams(temperature=0.0, max_tokens=4),
        )
    done = eng.run_to_completion()
    assert all(len(v) == 4 for v in done.values()), done


def test_mla_int8_quantized_serving_close_to_fp():
    """Weight-only int8 over the full MLA+MoE layout: engine serves, and
    the quantized forward stays close to fp32 (per-channel scales)."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.models.mla import quantize_params_int8

    cfg = MlaConfig.tiny_moe()
    params = init_params(jax.random.key(2), cfg)
    qparams = quantize_params_int8(params)
    assert qparams["moe_layers"]["we_gate"].dtype == jnp.int8
    assert "we_gate_scale" in qparams["moe_layers"]
    with pytest.raises(ValueError, match="already int8"):
        quantize_params_int8(qparams)

    rng = np.random.default_rng(31)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    fp = _run_paged(cfg, params, toks)
    q8 = _run_paged(cfg, qparams, toks)
    # loose: int8 quantization noise, but same model
    assert (fp.argmax(-1) == q8.argmax(-1)).mean() > 0.7

    eng = JaxEngine(
        EngineConfig(
            model="mla-tiny-moe", num_pages=32, page_size=4,
            max_pages_per_seq=8, decode_buckets=(2,), prefill_chunk=8,
            max_seqs=2, dtype="float32", quantize="int8",
        )
    )
    eng.add_request(
        "r0", [int(x) for x in rng.integers(1, 250, 6)],
        SamplingParams(temperature=0.0, max_tokens=4),
    )
    done = eng.run_to_completion()
    assert len(done["r0"]) == 4


def test_mla_moe_group_limited_greedy_against_hf():
    """Full-V2 gating: top groups by max member score, then top-k within
    the winning groups only."""
    cfg = replace(
        MlaConfig.tiny_moe(),
        topk_method="group_limited_greedy", n_group=2, topk_group=1,
    )
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    hf_cfg = DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=None, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        n_routed_experts=cfg.n_routed_experts,
        n_shared_experts=cfg.n_shared_experts,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense_replace,
        topk_method="group_limited_greedy", n_group=2, topk_group=1,
        rope_scaling=None, attn_implementation="eager",
        tie_word_embeddings=False,
    )
    torch.manual_seed(19)
    model = DeepseekV2ForCausalLM(hf_cfg).eval()
    params = params_from_torch_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(21)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.9


def test_mla_v3_noaux_gate_against_hf():
    """DeepSeek-V3/R1 routing: sigmoid scores, bias-corrected top-2-sum
    group ranking, weights from uncorrected scores, normalized + scaled."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    cfg = replace(
        MlaConfig.tiny_moe(),
        q_lora_rank=24,
        topk_method="noaux_tc", n_group=2, topk_group=2,
        norm_topk_prob=True, routed_scaling_factor=2.5,
    )
    hf_cfg = DeepseekV3Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        n_routed_experts=cfg.n_routed_experts,
        n_shared_experts=cfg.n_shared_experts,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense_replace,
        n_group=2, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5,
        rope_scaling=None, rope_interleave=True,
        attn_implementation="eager", tie_word_embeddings=False,
    )
    torch.manual_seed(29)
    model = DeepseekV3ForCausalLM(hf_cfg).eval()
    # give the correction bias real values (zeros would under-test it)
    with torch.no_grad():
        for layer in model.model.layers[cfg.first_k_dense_replace:]:
            layer.mlp.gate.e_score_correction_bias.uniform_(-0.5, 0.5)
    params = params_from_torch_state_dict(model.state_dict(), cfg)
    assert "router_bias" in params["moe_layers"]

    rng = np.random.default_rng(33)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.9


def test_mla_param_specs_cover_every_leaf():
    """Sharded init does jax.device_put(params, tree_map(specs)) — a spec
    pytree missing any param leaf (e.g. router_bias) crashes engine init
    on a mesh. Assert structural match for every config variant."""
    import jax

    from dynamo_tpu.models.mla import mla_param_specs, quantize_params_int8

    for cfg in (
        MlaConfig.tiny(),
        MlaConfig.tiny_moe(),
        replace(
            MlaConfig.tiny_moe(), q_lora_rank=24, topk_method="noaux_tc",
            n_group=2, topk_group=2, norm_topk_prob=True,
        ),
    ):
        params = init_params(jax.random.key(0), cfg)
        for quantized, tree in (
            (False, params),
            (True, quantize_params_int8(params)),
        ):
            specs = mla_param_specs(cfg, quantized=quantized)
            ts_p = jax.tree.structure(tree)
            ts_s = jax.tree.structure(
                specs, is_leaf=lambda x: not isinstance(x, dict)
            )
            assert ts_p == ts_s, (
                f"specs/params mismatch for {cfg.topk_method} "
                f"quantized={quantized}:\n{ts_p}\nvs\n{ts_s}"
            )


def test_mla_v3_yarn_mscale_softmax_against_hf():
    """V3/R1 YaRN: HF's DeepseekV3Attention multiplies the softmax scale
    by yarn_mscale(factor, mscale_all_dim)^2 (the V2 integrated port does
    not) — with mscale == mscale_all_dim the rotary attention factor is
    1.0, so ONLY the softmax adjustment distinguishes right from wrong."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    cfg = replace(
        MlaConfig.tiny(),
        q_lora_rank=24,
        rope_scaling_factor=40.0,
        rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
        rope_original_max_position=8,
        rope_mscale_softmax=True,
    )
    assert abs(cfg.softmax_scale * (cfg.qk_head_dim ** 0.5) - 1.869) < 0.01
    hf_cfg = DeepseekV3Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, head_dim=cfg.qk_rope_head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        n_routed_experts=8, n_shared_experts=1,
        moe_intermediate_size=32, num_experts_per_tok=2,
        n_group=2, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5,
        first_k_dense_replace=cfg.num_layers,  # all dense: isolate rope
        tie_word_embeddings=False, attn_implementation="eager",
        max_position_embeddings=64, rope_interleave=True,
        rope_scaling={
            "rope_type": "yarn", "factor": 40.0, "beta_fast": 32,
            "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 8, "truncate": True,
        },
    )
    torch.manual_seed(47)
    model = DeepseekV3ForCausalLM(hf_cfg).eval()
    params = params_from_torch_state_dict(model.state_dict(), cfg)

    rng = np.random.default_rng(51)
    toks = rng.integers(0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(toks.astype(np.int64))).logits.numpy()
    ours = _run_paged(cfg, params, toks)
    np.testing.assert_allclose(ours, ref, rtol=5e-2, atol=5e-2)
    assert (ours.argmax(-1) == ref.argmax(-1)).mean() > 0.95

    # without the softmax adjustment the logits demonstrably diverge
    wrong = _run_paged(replace(cfg, rope_mscale_softmax=False), params, toks)
    assert not np.allclose(wrong, ours, atol=1e-3)


def test_mla_spec_decode_byte_identical():
    """Prompt-lookup speculative decoding rides the family-agnostic
    spec_verify path: over the compressed MLA cache it must stay
    byte-identical to plain greedy decoding."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    def run(spec_ngram):
        eng = JaxEngine(
            EngineConfig(
                model="mla-tiny", num_pages=64, page_size=4,
                max_pages_per_seq=16, decode_buckets=(2,),
                prefill_chunk=16, max_seqs=2, dtype="float32",
                spec_ngram=spec_ngram,
            )
        )
        rng = np.random.default_rng(7)
        base = [int(x) for x in rng.integers(1, 250, 8)]
        eng.add_request(  # repetitive prompt: lookup actually proposes
            "r0", base * 3, SamplingParams(temperature=0.0, max_tokens=12)
        )
        return eng.run_to_completion()["r0"]

    assert run(0) == run(4)


def test_mla_tier_evict_onboard_byte_exact():
    """KVBM host tier over the ASYMMETRIC MLA cache (k latent 32-wide,
    v rope-key 8-wide): evict a prefix, re-serve it, outputs must be
    byte-identical (extract/inject must not assume k/v share a width)."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    eng = JaxEngine(
        EngineConfig(
            model="mla-tiny", num_pages=10, page_size=4,
            max_pages_per_seq=8, decode_buckets=(1,), prefill_chunk=8,
            max_seqs=1, dtype="float32",
            host_kv_cache_bytes=1 << 20,
        )
    )
    rng = np.random.default_rng(61)
    prompt = [int(x) for x in rng.integers(1, 250, 12)]

    def serve(rid, toks):
        eng.add_request(rid, toks, SamplingParams(temperature=0.0,
                                                  max_tokens=4))
        return eng.run_to_completion()[rid]

    first = serve("a", prompt)
    # churn the tiny pool so the prompt's pages evict into the host tier
    for i in range(3):
        serve(f"churn{i}", [int(x) for x in rng.integers(1, 250, 12)])
    # re-serve: prefix onboards from the tier; output must match exactly
    again = serve("b", prompt)
    assert first == again, (first, again)
    assert eng.allocator.stats.onboarded_blocks > 0  # tier really used


@pytest.mark.skipif(
    not device_transfer.available(),
    reason="jax.experimental.transfer absent from this jax build "
           "(device KV transfer plane unavailable)",
)
def test_mla_disagg_device_path_in_process(monkeypatch):
    """Disagg KV transfer of the asymmetric MLA cache over the DEVICE
    plane in-process: staged (k latent, v rope) arrays pull with their
    OWN shapes and decode continues byte-identically."""
    import asyncio

    from dynamo_tpu.disagg.device_transfer import DevicePlane
    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    DevicePlane.reset_for_tests()
    monkeypatch.setenv("DYN_KV_TRANSFER", "device")
    cfg = EngineConfig(
        model="mla-tiny", num_pages=32, page_size=4, max_pages_per_seq=8,
        decode_buckets=(1,), prefill_chunk=8, max_seqs=1, dtype="float32",
    )
    rng = np.random.default_rng(71)
    prompt = [int(x) for x in rng.integers(1, 250, 9)]
    n_out = 5

    ref = JaxEngine(cfg)
    ref.add_request("ref", prompt,
                    SamplingParams(temperature=0.0, max_tokens=n_out))
    ref_tokens = ref.run_to_completion()["ref"]

    pre = JaxEngine(cfg, params=ref.params)
    req_p = pre.add_request(
        "d1", prompt,
        SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True),
    )
    req_p.hold_pages = True
    first = pre.run_to_completion()["d1"]
    held = pre.scheduler.held["d1"]
    k_dev, v_dev = pre.extract_pages_async(held)
    assert k_dev.shape[-1] != v_dev.shape[-1]  # genuinely asymmetric

    dec = JaxEngine(cfg, params=ref.params)
    req_d = dec.allocate_for_remote_prefill(
        "d1", prompt, SamplingParams(temperature=0.0, max_tokens=n_out)
    )
    assert req_d is not None

    async def main():
        async def device_write_fn(page_ids, k, v):
            dec.inject_pages_device(page_ids, k, v)

        async def write_fn(page_ids, k, v):  # must not run
            raise AssertionError("host path used")

        server = KvTransferServer(write_fn, device_write_fn=device_write_fn)
        await server.start()
        waiter = server.expect("d1")
        client = KvTransferClient()
        try:
            ok = await client.send(
                *server.address, "d1", req_d.pages, k_dev, v_dev, first[0]
            )
            assert ok
            await asyncio.wait_for(waiter, 10)
            assert server.transfers == {"device": 1, "host": 0, "shm": 0, "bulk": 0}
        finally:
            client.close()
            await server.stop()

    asyncio.run(main())
    pre.scheduler.release_held("d1")
    outputs = dec.add_prefilled(req_d, first[0])
    got = [t for o in outputs for t in o.new_token_ids]
    got += dec.run_to_completion().get("d1", [])
    assert got == ref_tokens


def test_mla_disagg_host_path(monkeypatch):
    """Host-path transfer of the asymmetric MLA cache (the default
    transport off-TPU and the device-path fallback): separate k/v widths
    must ride the write frame and decode must continue byte-identically."""
    import asyncio

    from dynamo_tpu.disagg.transfer import KvTransferClient, KvTransferServer
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    monkeypatch.setenv("DYN_KV_TRANSFER", "host")
    cfg = EngineConfig(
        model="mla-tiny", num_pages=32, page_size=4, max_pages_per_seq=8,
        decode_buckets=(1,), prefill_chunk=8, max_seqs=1, dtype="float32",
    )
    rng = np.random.default_rng(81)
    prompt = [int(x) for x in rng.integers(1, 250, 9)]
    n_out = 4

    ref = JaxEngine(cfg)
    ref.add_request("ref", prompt,
                    SamplingParams(temperature=0.0, max_tokens=n_out))
    ref_tokens = ref.run_to_completion()["ref"]

    pre = JaxEngine(cfg, params=ref.params)
    req_p = pre.add_request(
        "d1", prompt,
        SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True),
    )
    req_p.hold_pages = True
    first = pre.run_to_completion()["d1"]
    held = pre.scheduler.held["d1"]
    k, v = pre.extract_pages(held)
    assert k.shape[-1] != v.shape[-1]

    dec = JaxEngine(cfg, params=ref.params)
    req_d = dec.allocate_for_remote_prefill(
        "d1", prompt, SamplingParams(temperature=0.0, max_tokens=n_out)
    )

    async def main():
        async def write_fn(page_ids, kk, vv):
            dec.inject_pages(page_ids, kk, vv)

        server = KvTransferServer(write_fn)
        await server.start()
        waiter = server.expect("d1")
        client = KvTransferClient()
        try:
            ok = await client.send(
                *server.address, "d1", req_d.pages, k, v, first[0]
            )
            assert ok
            await asyncio.wait_for(waiter, 10)
            assert server.transfers == {"device": 0, "host": 0, "shm": 1, "bulk": 0}
        finally:
            client.close()
            await server.stop()

    asyncio.run(main())
    pre.scheduler.release_held("d1")
    outputs = dec.add_prefilled(req_d, first[0])
    got = [t for o in outputs for t in o.new_token_ids]
    got += dec.run_to_completion().get("d1", [])
    assert got == ref_tokens
