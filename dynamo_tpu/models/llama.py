"""Llama-family decoder in pure functional JAX with a paged KV cache.

This is the flagship engine model (the reference serves Llama via external
GPU engines — vLLM/TRT-LLM; here the engine is first-class, SURVEY.md §2.9).
Design choices are TPU-first:

- One `forward` covers prefill AND decode: T is just the chunk length (1 for
  decode). Attention always runs against the paged KV cache gathered through
  the page table, so chunked prefill, prefix-cache continuation, and decode
  are the same compiled program shape-family.
- Layers are scanned (`lax.scan` over stacked layer params), so compile time
  is O(1) in depth and XLA sees one fused layer body.
- Weights live in bf16; softmax/norm accumulate in f32 (MXU-friendly).
- All shapes are static: (B, T, MAX_PAGES) come from the scheduler's bucket,
  padding is masked. No data-dependent control flow under jit.

Parity notes: replaces the model execution the reference delegates to
vLLM/SGLang/TRT-LLM subprocesses (/root/reference launch/dynamo-run/src/
subprocess/vllm_inc.py etc.); paged-KV semantics match the vLLM-style paged
attention contract (page table per sequence, block == token-block of the
router, so KV routing hashes align with engine pages).
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # Llama-3.1-style NTK rope scaling (None disables).
    rope_scaling_factor: Optional[float] = None
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    dtype: Any = jnp.bfloat16
    #: "xla" (gather path, any T) | "pallas" (flash kernels: page-walk DMA
    #: decode for T=1, VMEM-tiled causal flash for first-chunk prefill;
    #: history-chunk prefill still takes the XLA gather path) | "hybrid"
    #: (pallas write discipline + flash prefill, but decode attention
    #: switches to the XLA gather past pallas_decode_max_batch — the
    #: page-walk kernel issues O(B x pages) DMA descriptors per layer,
    #: which is latency-optimal at small B and descriptor-bound at large)
    attention_impl: str = "xla"
    #: "hybrid" decode: largest batch bucket still served by the pallas
    #: page-walk kernel (bigger buckets use the XLA gather)
    pallas_decode_max_batch: int = 32
    #: q/k/v projection bias — the Qwen2 family's one architectural delta
    attention_bias: bool = False
    #: False: no rotary embedding on any layer (Nemotron-H's attention
    #: layers: position reaches the model through its state-space layers;
    #: models/nemotron_h.py). The kernels need nothing for it: q and k go
    #: in unrotated
    use_rope: bool = True
    #: a prefill chunk over paged history walks the pages in the flash
    #: kernel (ops/flash_prefill.py). False: gather the history and attend
    #: in plain XLA instead. Worth it where the history is small whatever
    #: the context (Nemotron-H: 4 attention layers of 2 KV heads, 2 MB a
    #: layer at 4,096 tokens) and the kernel's compile time is not: a
    #: third of a mixed program's (16.0 -> 9.9 s through the TPU compiler
    #: here; PERF.md 6, PR 31)
    prefill_history_kernel: bool = True
    #: Qwen3: per-head RMSNorm on q and k (head_dim-wide), applied after
    #: the projections, before rope
    qk_norm: bool = False
    #: MLP activation: "silu" (Llama/Qwen GLU) or "gelu_tanh" (Gemma GeGLU)
    hidden_act: str = "silu"
    #: Gemma-style RMSNorm: scale by (1 + weight) instead of weight
    rms_norm_unit_offset: bool = False
    #: Gemma scales token embeddings by sqrt(hidden_size)
    scale_embeddings: bool = False
    #: Gemma2: attention scores pass cap*tanh(s/cap) before masking
    attn_logit_softcap: Optional[float] = None
    #: Gemma2: final lm_head logits pass cap*tanh(l/cap)
    final_logit_softcap: Optional[float] = None
    #: Local attention: affected layers attend only the last
    #: `sliding_window` positions; 0 disables
    sliding_window: int = 0
    #: which layers are local: layer_idx % every == 0. 2 = Gemma2's
    #: local/global alternation; 1 = every layer (Mistral)
    sliding_window_every: int = 2
    #: Gemma2: query scale is query_pre_attn_scalar**-0.5 (None: head_dim)
    query_pre_attn_scalar: Optional[float] = None
    #: Gemma2 block: extra post-attention / post-feedforward RMSNorms
    post_block_norms: bool = False
    #: Gemma3: layer is GLOBAL iff (layer+1) % this == 0, all others are
    #: local (the 5:1 pattern with 6). 0 = use sliding_window_every's
    #: "every Nth layer is local" semantics instead (Gemma2/Mistral).
    sliding_global_every: int = 0
    #: Gemma3: LOCAL-attention layers rope with this theta (10k) while
    #: global layers use rope_theta (1M). None = one theta everywhere.
    rope_local_theta: Optional[float] = None
    #: Gemma3 4B+: linear rope position scaling on GLOBAL layers only
    #: (positions effectively divided by this factor)
    rope_linear_factor: Optional[float] = None
    #: Llama-4: rope rotates interleaved pairs (x0,x1),(x2,x3)… (the
    #: complex freqs_cis convention) instead of the HF half-split
    rope_interleaved: bool = False
    #: Llama-4 NoPE: every Nth layer ((layer+1) % N == 0) skips rope and
    #: attends globally; 0 = rope everywhere
    nope_every: int = 0
    #: Llama-4: weightless L2 q/k norm after rope (rope layers only)
    qk_l2_norm: bool = False
    #: Llama-4: scale NoPE-layer queries by
    #: log1p(floor((pos+1)/floor_scale)) * attn_scale + 1
    attn_temperature_tuning: bool = False
    attn_floor_scale: float = 8192.0
    attn_scale_coef: float = 0.1
    #: Llama-4 chunked attention on rope layers: token attends only
    #: within its `attention_chunk`-sized block (0 = off). Equivalent to
    #: a per-query window of (pos % chunk) + 1.
    attention_chunk: int = 0
    #: YaRN rope scaling (GPT-OSS): interpolation factor; None = off.
    #: Uses rope_original_max_position as the pretraining context and
    #: scales cos/sin by the paper's 0.1·ln(factor)+1 attention factor.
    rope_yarn_factor: Optional[float] = None
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_truncate: bool = True
    #: explicit cos/sin scale override (HF rope_scaling.attention_factor);
    #: None = the paper's 0.1·ln(factor)+1
    rope_yarn_attention_factor: Optional[float] = None
    #: GPT-OSS attention sinks: a learned per-head logit joins every
    #: softmax (params key "sinks" [Hq] per layer)
    attn_sinks: bool = False
    #: GPT-OSS: the o projection carries a bias too (params key "bo")
    attention_out_bias: bool = False
    #: Qwen2-VL m-RoPE: head_dim/2 frequency slots partitioned into
    #: (temporal, height, width) sections — e.g. (16, 24, 24) for D=128.
    #: Rope positions may then be [3, B, T] (one stream per axis); plain
    #: [B, T] positions still work and equal the (p, p, p) case exactly,
    #: which is why text-only serving needs no special path.
    mrope_section: Optional[tuple[int, ...]] = None

    def __post_init__(self):
        if self.rope_local_theta is not None and not self.sliding_global_every:
            # the per-layer theta selection keys off the global-layer
            # period; without it the modulus is by zero (undefined under
            # XLA) and every layer's theta would be silently arbitrary
            raise ValueError(
                "rope_local_theta requires sliding_global_every > 0 "
                "(the dual-theta selection follows the Gemma3 "
                "local/global layer pattern)"
            )

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def kv_head_dim(self) -> int:
        """head_dim as stored in the paged KV cache. The Pallas decode
        kernel DMAs one [page_size, D] block per page, and Mosaic requires
        DMA slice shapes aligned to the (8,128) lane tile — so for
        head_dim-64 models (Llama-3.2-1B, Qwen2.5-0.5B) the cache keeps D
        padded up to 128 zero lanes when the kernel is active. Padding is
        invisible outside the cache: q·k over zero lanes adds nothing and
        the attention output is sliced back to head_dim."""
        if (
            self.attention_impl in ("pallas", "hybrid")
            and self.head_dim % 128 != 0
        ):
            return -(-self.head_dim // 128) * 128
        return self.head_dim

    def kv_scale_slots(self, page_size: int) -> int:
        """Slots per page in a quantized pool's scale planes
        ([L, P, Hkv, slots]). The Pallas readers DMA one page's whole
        [Hkv, slots] plane, and Mosaic requires the minor dim of a DMA
        slice aligned to the 128-lane tile — so with the kernels active
        the planes keep `page_size` padded up to a 128 multiple (the pad
        slots are never read)."""
        if self.attention_impl in ("pallas", "hybrid"):
            return -(-page_size // 128) * 128
        return page_size

    # -- canned configs ----------------------------------------------------

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()  # defaults above are Llama-3-8B

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(
            hidden_size=8192, intermediate_size=28672, num_layers=80,
            num_heads=64, num_kv_heads=8,
        )

    @staticmethod
    def llama3_1b() -> "LlamaConfig":
        """Llama-3.2-1B-shaped config — fits a single v5e chip with headroom."""
        return LlamaConfig(
            hidden_size=2048, intermediate_size=8192, num_layers=16,
            num_heads=32, num_kv_heads=8, head_dim=64,
            tie_word_embeddings=True,
            rope_scaling_factor=32.0,
        )

    @staticmethod
    def llama3_draft() -> "LlamaConfig":
        """Draft-sized Llama sharing the Llama-3 vocabulary (128256):
        ~8% of llama3-1b's non-embedding FLOPs — the speculation draft
        (`EngineConfig.spec_draft_model="llama3-draft"`) for the 1B/8B
        targets. Random-init unless a distilled checkpoint is loaded
        via spec_draft_checkpoint; a random draft accepts at chance and
        the engine's acceptance cooldown keeps it out of the hot path."""
        return LlamaConfig(
            hidden_size=512, intermediate_size=2048, num_layers=4,
            num_heads=8, num_kv_heads=4, head_dim=64,
            tie_word_embeddings=True,
            rope_scaling_factor=32.0,
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        """For unit tests (CPU) — small enough to compare against torch."""
        return LlamaConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            rope_theta=10000.0, dtype=jnp.float32,
        )

    @staticmethod
    def qwen2_7b() -> "LlamaConfig":
        """Qwen2/2.5-7B: Llama architecture + qkv bias."""
        return LlamaConfig(
            vocab_size=152064, hidden_size=3584, intermediate_size=18944,
            num_layers=28, num_heads=28, num_kv_heads=4, head_dim=128,
            rope_theta=1000000.0, rms_norm_eps=1e-6, attention_bias=True,
        )

    @staticmethod
    def qwen2_05b() -> "LlamaConfig":
        """Qwen2.5-0.5B — single-chip smoke size for the family."""
        return LlamaConfig(
            vocab_size=151936, hidden_size=896, intermediate_size=4864,
            num_layers=24, num_heads=14, num_kv_heads=2, head_dim=64,
            rope_theta=1000000.0, rms_norm_eps=1e-6, attention_bias=True,
            tie_word_embeddings=True,
        )

    @staticmethod
    def gemma_2b() -> "LlamaConfig":
        """Gemma-2B: GeGLU MLP, (1+w) RMSNorm, sqrt(H)-scaled embeddings,
        tied lm_head, MQA (1 kv head), head_dim 256."""
        return LlamaConfig(
            vocab_size=256000, hidden_size=2048, intermediate_size=16384,
            num_layers=18, num_heads=8, num_kv_heads=1, head_dim=256,
            rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=True,
            hidden_act="gelu_tanh", rms_norm_unit_offset=True,
            scale_embeddings=True,
        )

    @staticmethod
    def gemma_7b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=256000, hidden_size=3072, intermediate_size=24576,
            num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
            rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=True,
            hidden_act="gelu_tanh", rms_norm_unit_offset=True,
            scale_embeddings=True,
        )

    @staticmethod
    def qwen3_8b() -> "LlamaConfig":
        """Qwen3-8B: Llama architecture + per-head q/k RMSNorm, no bias."""
        return LlamaConfig(
            vocab_size=151936, hidden_size=4096, intermediate_size=12288,
            num_layers=36, num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=1000000.0, rms_norm_eps=1e-6, qk_norm=True,
        )

    @staticmethod
    def phi3_mini() -> "LlamaConfig":
        """Phi-3-mini-4k: Llama architecture with fused qkv/gate_up
        projections in the checkpoint (split at load); 128k longrope
        variants are refused."""
        return LlamaConfig(
            vocab_size=32064, hidden_size=3072, intermediate_size=8192,
            num_layers=32, num_heads=32, num_kv_heads=32, head_dim=96,
            rope_theta=10000.0, rms_norm_eps=1e-5,
        )

    @staticmethod
    def phi4() -> "LlamaConfig":
        """Phi-4 (14B): the same phi3 architecture (fused qkv/gate_up
        split at load, tests/test_model_phi3.py) at 40 layers with GQA
        and a 250k rope base."""
        return LlamaConfig(
            vocab_size=100352, hidden_size=5120, intermediate_size=17920,
            num_layers=40, num_heads=40, num_kv_heads=10, head_dim=128,
            rope_theta=250000.0, rms_norm_eps=1e-5,
        )

    @staticmethod
    def mistral_7b() -> "LlamaConfig":
        """Mistral-7B-v0.1: Llama architecture + sliding-window attention
        on every layer (window 4096)."""
        return LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=10000.0, rms_norm_eps=1e-5,
            sliding_window=4096, sliding_window_every=1,
        )

    @staticmethod
    def gemma2_2b() -> "LlamaConfig":
        """Gemma-2-2B: Gemma base + sliding/global layer alternation,
        attn+final logit soft-capping, post-block norms."""
        return LlamaConfig(
            vocab_size=256000, hidden_size=2304, intermediate_size=9216,
            num_layers=26, num_heads=8, num_kv_heads=4, head_dim=256,
            rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=True,
            hidden_act="gelu_tanh", rms_norm_unit_offset=True,
            scale_embeddings=True, attn_logit_softcap=50.0,
            final_logit_softcap=30.0, sliding_window=4096,
            query_pre_attn_scalar=256.0, post_block_norms=True,
        )

    @staticmethod
    def gemma3_1b() -> "LlamaConfig":
        """Gemma-3-1B: Gemma2 block structure minus the soft-caps, plus
        qk-norm, 5:1 local/global layer pattern, and dual rope theta
        (1M global / 10k local)."""
        return LlamaConfig(
            vocab_size=262144, hidden_size=1152, intermediate_size=6912,
            num_layers=26, num_heads=4, num_kv_heads=1, head_dim=256,
            rope_theta=1_000_000.0, rope_local_theta=10_000.0,
            rms_norm_eps=1e-6, tie_word_embeddings=True,
            hidden_act="gelu_tanh", rms_norm_unit_offset=True,
            scale_embeddings=True, qk_norm=True, sliding_window=512,
            sliding_global_every=6, query_pre_attn_scalar=256.0,
            post_block_norms=True,
        )

    @staticmethod
    def gemma3_4b_text() -> "LlamaConfig":
        """Gemma-3-4B language model (text weights of the multimodal
        checkpoint): 1B recipe + linear rope scaling x8 on global
        layers."""
        return LlamaConfig(
            vocab_size=262208, hidden_size=2560, intermediate_size=10240,
            num_layers=34, num_heads=8, num_kv_heads=4, head_dim=256,
            rope_theta=1_000_000.0, rope_local_theta=10_000.0,
            rope_linear_factor=8.0, rms_norm_eps=1e-6,
            tie_word_embeddings=True, hidden_act="gelu_tanh",
            rms_norm_unit_offset=True, scale_embeddings=True, qk_norm=True,
            sliding_window=1024, sliding_global_every=6,
            query_pre_attn_scalar=256.0, post_block_norms=True,
        )

    @staticmethod
    def from_hf_config(hf: dict) -> "LlamaConfig":
        """Map a HuggingFace `config.json` dict onto LlamaConfig (covers the
        Llama, Qwen2 (= Llama + qkv bias), Gemma, Gemma2, and Gemma3-text
        families)."""
        arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
        gemma3 = (
            hf.get("model_type") == "gemma3_text"
            or arch == "Gemma3ForCausalLM"
        )
        rope_scaling = hf.get("rope_scaling") or {}
        factor = None
        linear_factor = None
        yarn = {}
        rs_type = rope_scaling.get("rope_type", rope_scaling.get("type"))
        if rs_type == "llama3":
            factor = float(rope_scaling["factor"])
        elif gemma3 and rs_type == "linear":
            linear_factor = float(rope_scaling["factor"])
        elif rs_type == "yarn":
            if rope_scaling.get("mscale") or rope_scaling.get(
                "mscale_all_dim"
            ):
                # DeepSeek-style mscale yarn lives in models/mla.py;
                # refuse rather than scale attention silently wrong here
                raise ValueError(
                    "yarn mscale/mscale_all_dim is only implemented for "
                    "the DeepSeek MLA family"
                )
            att = rope_scaling.get("attention_factor")
            yarn = dict(
                rope_yarn_factor=float(rope_scaling["factor"]),
                rope_yarn_beta_fast=float(
                    rope_scaling.get("beta_fast") or 32.0
                ),
                rope_yarn_beta_slow=float(
                    rope_scaling.get("beta_slow") or 1.0
                ),
                rope_yarn_truncate=bool(rope_scaling.get("truncate", True)),
                rope_yarn_attention_factor=(
                    float(att) if att is not None else None
                ),
            )
        elif rope_scaling:
            # refuse rather than run long-context positions unscaled
            raise ValueError(
                f"unsupported rope_scaling type {rs_type!r} for this "
                "family (llama3 NTK, Gemma3 linear, and yarn are "
                "implemented)"
            )
        head_dim = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
        global_every = 0
        if gemma3:
            lt = hf.get("layer_types") or []
            global_every = (
                lt.index("full_attention") + 1
                if "full_attention" in lt
                else 6
            )
            want = [
                "full_attention"
                if (i + 1) % global_every == 0
                else "sliding_attention"
                for i in range(len(lt))
            ]
            if lt and lt != want:
                # refuse rather than run a non-periodic pattern silently
                # wrong (only the every-Nth-global layout is implemented)
                raise ValueError(
                    f"unsupported gemma3 layer_types pattern {lt!r}: only "
                    f"periodic every-{global_every}th-global is implemented"
                )
        gemma2 = hf.get("model_type") == "gemma2" or arch == "Gemma2ForCausalLM"
        gemma = (
            hf.get("model_type") == "gemma"
            or arch == "GemmaForCausalLM"
            or gemma2
            or gemma3
        )
        llama4 = (
            hf.get("model_type") == "llama4_text"
            or arch == "Llama4ForCausalLM"
        )
        gpt_oss = (
            hf.get("model_type") == "gpt_oss" or arch == "GptOssForCausalLM"
        )
        nope_every = 0
        if llama4:
            nrl = hf.get("no_rope_layers") or []
            if not nrl:
                # HF serializes an empty list to mean "the default
                # pattern" (every no_rope_layer_interval-th layer NoPE)
                nope_every = int(hf.get("no_rope_layer_interval") or 4)
            elif 0 in nrl:
                nope_every = nrl.index(0) + 1
                want = [
                    0 if (i + 1) % nope_every == 0 else 1
                    for i in range(len(nrl))
                ]
                if nrl != want:
                    raise ValueError(
                        f"unsupported llama4 no_rope_layers pattern "
                        f"{nrl!r}: only periodic every-{nope_every}th-NoPE "
                        "is implemented"
                    )
        mistral = (
            hf.get("model_type") == "mistral" or arch == "MistralForCausalLM"
        )
        qwen3 = hf.get("model_type") in ("qwen3", "qwen3_moe") or arch in (
            "Qwen3ForCausalLM",
            "Qwen3MoeForCausalLM",
        )

        hidden_act = hf.get("hidden_activation") or hf.get("hidden_act", "silu")
        if hidden_act in ("gelu_pytorch_tanh", "gelu_tanh", "gelu"):
            hidden_act = "gelu_tanh"
        elif hidden_act == "silu":
            hidden_act = "silu"
        else:
            # refuse rather than run a numerically wrong model
            raise ValueError(
                f"unsupported hidden_act {hidden_act!r} in HF config"
            )
        return LlamaConfig(
            attention_bias=bool(
                hf.get("attention_bias", arch == "Qwen2ForCausalLM")
            ),
            qk_norm=qwen3 or gemma3,
            hidden_act=hidden_act,
            rms_norm_unit_offset=gemma,
            scale_embeddings=gemma,
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            head_dim=head_dim,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", gemma)),
            rope_scaling_factor=factor,
            rope_low_freq_factor=float(rope_scaling.get("low_freq_factor", 1.0)),
            rope_high_freq_factor=float(rope_scaling.get("high_freq_factor", 4.0)),
            rope_original_max_position=int(
                rope_scaling.get("original_max_position_embeddings")
                # HF's yarn falls back to the model's max positions, NOT
                # a fixed constant — the correction range depends on it
                or (hf.get("max_position_embeddings") if rs_type == "yarn"
                    else None)
                or 8192
            ),
            attn_logit_softcap=(
                hf.get("attn_logit_softcapping") if gemma2 else None
            ),
            final_logit_softcap=(
                hf.get("final_logit_softcapping") if gemma2 else None
            ),
            sliding_window=(
                int(hf.get("sliding_window") or 0)
                if (gemma2 or gemma3 or mistral or gpt_oss)
                else 0
            ),
            sliding_window_every=2 if (gemma2 or gpt_oss) else 1,
            sliding_global_every=global_every,
            rope_local_theta=(
                float(hf.get("rope_local_base_freq", 10_000.0))
                if gemma3
                else None
            ),
            rope_linear_factor=linear_factor,
            query_pre_attn_scalar=(
                float(hf["query_pre_attn_scalar"])
                if (gemma2 or gemma3) and hf.get("query_pre_attn_scalar")
                else None
            ),
            post_block_norms=gemma2 or gemma3,
            rope_interleaved=llama4,
            nope_every=nope_every,
            qk_l2_norm=bool(llama4 and hf.get("use_qk_norm", True)),
            attn_temperature_tuning=bool(
                llama4 and hf.get("attn_temperature_tuning", True)
            ),
            attn_floor_scale=float(hf.get("floor_scale", 8192.0)),
            attn_scale_coef=float(hf.get("attn_scale", 0.1)),
            attention_chunk=(
                int(hf.get("attention_chunk_size") or 0) if llama4 else 0
            ),
            attn_sinks=gpt_oss,
            attention_out_bias=gpt_oss,
            **yarn,
        )


class KVPages(NamedTuple):
    """Paged KV cache: one page pool shared by all sequences of a worker.

    k, v: [num_layers, num_pages, page_size, num_kv_heads, head_dim]
    Page-major: one (layer, page) slice is a contiguous [S, Hkv, D] block —
    a single dense DMA descriptor covering every kv head (the Pallas decode
    kernel reads one page per DMA and computes all heads from it), and a
    token's row [Hkv, D] is contiguous so the Pallas write kernel can land
    it with one descriptor; writes for one sequence across ALL layers are a
    single strided DMA (stride = the page axis). tp shards the kv-heads
    axis. Page 0 is the null page: padding writes land there and no real
    page table ever references it.

    Quantized pages (`kv_quantize="int8"|"fp8"`): k/v hold the narrow
    dtype and k_scale/v_scale carry per-(page, kv-head, slot) f32 scale
    planes of shape [L, P, Hkv, S'] — each page travels with its own
    [Hkv, S'] scale plane, slot-minor so a page's plane is one
    lane-aligned DMA (S' = LlamaConfig.kv_scale_slots(S): S, or S padded
    to a 128 multiple when the kernels are active). A token's row [D]
    quantizes symmetrically
    against its own amax on write, so pages filling incrementally never
    need re-scaling, and readers dequantize in VMEM right after the page
    DMA lands — no fp copy of the cache ever exists in HBM.
    """

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # [L, P, Hkv, S'] f32, quantized only
    v_scale: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


#: kv_quantize mode -> (storage dtype, symmetric max representable)
def kv_quant_spec(mode: str):
    if mode == "int8":
        return jnp.int8, 127.0
    if mode == "fp8":
        return jnp.float8_e4m3fn, 448.0
    raise ValueError(f"unknown kv_quantize mode {mode!r}; use int8|fp8")


def quantize_kv_rows(x: jax.Array, mode: str = "int8"):
    """Per-token, per-kv-head symmetric quantization of KV rows:
    x [..., D] -> (q [..., D] narrow dtype, scale [...] f32). The scale is
    each row's amax/qmax — decode writes one token at a time, so row-local
    scales are exact under incremental page fill (a page-wide scale would
    clip tokens written after it was fixed)."""
    dtype, qmax = kv_quant_spec(mode)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / qmax, 1e-8)
    q = xf / scale[..., None]
    if dtype == jnp.int8:
        q = jnp.round(q)
    return q.astype(dtype), scale


def dequantize_kv_rows(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Inverse of quantize_kv_rows: q [..., D] x scale [...] -> dtype."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def init_kv_pages(
    cfg: LlamaConfig,
    num_pages: int,
    page_size: int,
    dtype=None,
    kv_quantize: Optional[str] = None,
) -> KVPages:
    shape = (
        cfg.num_layers, num_pages, page_size, cfg.num_kv_heads, cfg.kv_head_dim
    )
    if kv_quantize:
        qdtype, _ = kv_quant_spec(kv_quantize)
        scale_shape = (
            cfg.num_layers, num_pages, cfg.num_kv_heads,
            cfg.kv_scale_slots(page_size),
        )
        return KVPages(
            k=jnp.zeros(shape, qdtype),
            v=jnp.zeros(shape, qdtype),
            k_scale=jnp.zeros(scale_shape, jnp.float32),
            v_scale=jnp.zeros(scale_shape, jnp.float32),
        )
    dtype = dtype or cfg.dtype
    return KVPages(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def kv_page_bytes(
    cfg, page_size: int, kv_quantize: Optional[str] = None, dtype=None
) -> int:
    """Bytes ONE page costs across all layers (k + v + scale planes) —
    the capacity-planning arithmetic for sizing num_pages against an HBM
    budget before an engine exists (the live gauges, kv_pool_bytes /
    kv_pool_bytes_dense_equiv, are computed from the actual pool arrays
    at engine init instead — that also covers MLA's asymmetric caches).
    `cfg` is a LlamaConfig (MoE passes cfg.base); quantized pages pay
    1 byte/elem + 4-byte f32 row scales (plus the scale planes' lane
    padding when the kernels are active), i.e. ~(1 + 4/D)/itemsize of
    the dense cost. Pinned by tests/test_kv_quant.py."""
    d = cfg.kv_head_dim
    heads = cfg.num_layers * cfg.num_kv_heads
    if kv_quantize:
        qdtype, _ = kv_quant_spec(kv_quantize)
        per_head = (
            page_size * d * jnp.dtype(qdtype).itemsize  # rows
            + cfg.kv_scale_slots(page_size) * 4  # f32 scale plane
        )
    else:
        per_head = page_size * d * jnp.dtype(dtype or cfg.dtype).itemsize
    return 2 * heads * per_head  # k and v


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Random-init params, layer-stacked for lax.scan."""
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    L = cfg.num_layers
    keys = jax.random.split(key, 10)

    def norm_init(shape):
        return jnp.ones(shape, cfg.dtype)

    def dense(key, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(cfg.dtype)

    params = {
        "embed": dense(keys[0], (v, h), h),
        "layers": {
            "attn_norm": norm_init((L, h)),
            "wq": dense(keys[1], (L, h, qd), h),
            "wk": dense(keys[2], (L, h, kvd), h),
            "wv": dense(keys[3], (L, h, kvd), h),
            "wo": dense(keys[4], (L, qd, h), qd),
            "mlp_norm": norm_init((L, h)),
            "w_gate": dense(keys[5], (L, h, i), h),
            "w_up": dense(keys[6], (L, h, i), h),
            "w_down": dense(keys[7], (L, i, h), i),
        },
        "final_norm": norm_init((h,)),
    }
    if cfg.attention_bias:
        params["layers"]["bq"] = jnp.zeros((L, qd), cfg.dtype)
        params["layers"]["bk"] = jnp.zeros((L, kvd), cfg.dtype)
        params["layers"]["bv"] = jnp.zeros((L, kvd), cfg.dtype)
    if cfg.qk_norm:
        params["layers"]["q_norm"] = norm_init((L, cfg.head_dim))
        params["layers"]["k_norm"] = norm_init((L, cfg.head_dim))
    if cfg.post_block_norms:
        params["layers"]["post_attn_norm"] = norm_init((L, h))
        params["layers"]["post_mlp_norm"] = norm_init((L, h))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(keys[8], (h, v), h)
    return params


def llama_logical_axes(cfg: LlamaConfig, quantized: bool = False) -> dict:
    """Logical axis names for every param, declared ONCE beside the
    shapes `init_params` builds (parallel/logical.py resolves them to
    PartitionSpecs through the one rule table):

    - "heads"/"kv_heads": packed q/kv head output dims of wq/wk/wv;
      wo's INPUT dim carries "heads" so the following matmul produces
      partial sums and XLA inserts the one per-layer psum,
    - "mlp": ffn intermediate dim (w_down's input, like wo),
    - "embed": the embedding table's hidden dim; the vocab dim of the
      TABLE stays unnamed (lookup is a gather — sharding the hidden dim
      is the cheap one), while an untied lm_head names its output dim
      "vocab",
    - "layers": the lax.scan stack dim, never sharded,
    - int8 scales [L, 1, out] ride their weight's OUTPUT dim
      (contraction-sharded wo/w_down keep unsharded scales, which
      commute with the partial-sum).
    """
    from dynamo_tpu.parallel.logical import L

    axes = {
        "embed": L(None, "embed"),
        "layers": {
            "attn_norm": L("layers", None),
            "wq": L("layers", None, "heads"),
            "wk": L("layers", None, "kv_heads"),
            "wv": L("layers", None, "kv_heads"),
            "wo": L("layers", "heads", None),
            "mlp_norm": L("layers", None),
            "w_gate": L("layers", None, "mlp"),
            "w_up": L("layers", None, "mlp"),
            "w_down": L("layers", "mlp", None),
        },
        "final_norm": L(None),
    }
    if cfg.attention_bias:
        # biases shard with their projection's output dim
        axes["layers"]["bq"] = L("layers", "heads")
        axes["layers"]["bk"] = L("layers", "kv_heads")
        axes["layers"]["bv"] = L("layers", "kv_heads")
    if getattr(cfg, "qk_norm", False):
        # per-head-dim norms apply identically on every sharded head
        axes["layers"]["q_norm"] = L("layers", None)
        axes["layers"]["k_norm"] = L("layers", None)
    if getattr(cfg, "post_block_norms", False):
        # Gemma2 post-sublayer norms act on the replicated hidden dim
        axes["layers"]["post_attn_norm"] = L("layers", None)
        axes["layers"]["post_mlp_norm"] = L("layers", None)
    if quantized:
        axes["layers"]["wq_scale"] = L("layers", None, "heads")
        axes["layers"]["wk_scale"] = L("layers", None, "kv_heads")
        axes["layers"]["wv_scale"] = L("layers", None, "kv_heads")
        axes["layers"]["w_gate_scale"] = L("layers", None, "mlp")
        axes["layers"]["w_up_scale"] = L("layers", None, "mlp")
        axes["layers"]["wo_scale"] = L("layers", None, None)
        axes["layers"]["w_down_scale"] = L("layers", None, None)
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = L(None, "vocab")
    return axes


def params_from_torch_state_dict(state_dict, cfg: LlamaConfig) -> dict:
    """Convert a HuggingFace Llama state_dict (torch tensors) to our pytree.

    HF stores projections as [out, in]; we use [in, out] so matmuls read
    x @ W. Layer tensors are stacked along a leading L axis for lax.scan.
    """
    import numpy as np

    def t(name):
        w = state_dict[name]
        return np.asarray(w.to("cpu").float().numpy())

    L = cfg.num_layers

    if "model.layers.0.self_attn.qkv_proj.weight" in state_dict:
        # Phi-3 fuses qkv and gate_up; split into the canonical leaves so
        # one forward serves the family (HF Phi3Attention chunks in
        # q/k/v order, Phi3MLP in gate/up order).
        qd = cfg.num_heads * cfg.head_dim
        kvd = cfg.num_kv_heads * cfg.head_dim
        for l in range(L):
            qkv = state_dict[f"model.layers.{l}.self_attn.qkv_proj.weight"]
            state_dict[f"model.layers.{l}.self_attn.q_proj.weight"] = qkv[:qd]
            state_dict[f"model.layers.{l}.self_attn.k_proj.weight"] = (
                qkv[qd : qd + kvd]
            )
            state_dict[f"model.layers.{l}.self_attn.v_proj.weight"] = (
                qkv[qd + kvd :]
            )
            gu = state_dict[f"model.layers.{l}.mlp.gate_up_proj.weight"]
            half = gu.shape[0] // 2
            state_dict[f"model.layers.{l}.mlp.gate_proj.weight"] = gu[:half]
            state_dict[f"model.layers.{l}.mlp.up_proj.weight"] = gu[half:]

    def stack(fmt, transpose=True):
        ws = [t(fmt.format(l)) for l in range(L)]
        ws = [w.T if transpose else w for w in ws]
        return jnp.asarray(np.stack(ws), cfg.dtype)

    # Gemma2 renames the pre-MLP norm: post_attention_layernorm becomes a
    # POST-attention branch norm and pre_feedforward_layernorm takes the
    # pre-MLP role the Llama name implies.
    mlp_norm_name = (
        "model.layers.{}.pre_feedforward_layernorm.weight"
        if cfg.post_block_norms
        else "model.layers.{}.post_attention_layernorm.weight"
    )
    params = {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), cfg.dtype),
        "layers": {
            "attn_norm": stack("model.layers.{}.input_layernorm.weight", transpose=False),
            "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
            "mlp_norm": stack(mlp_norm_name, transpose=False),
            "w_gate": stack("model.layers.{}.mlp.gate_proj.weight"),
            "w_up": stack("model.layers.{}.mlp.up_proj.weight"),
            "w_down": stack("model.layers.{}.mlp.down_proj.weight"),
        },
        "final_norm": jnp.asarray(t("model.norm.weight"), cfg.dtype),
    }
    if cfg.qk_norm:
        params["layers"]["q_norm"] = stack(
            "model.layers.{}.self_attn.q_norm.weight", transpose=False
        )
        params["layers"]["k_norm"] = stack(
            "model.layers.{}.self_attn.k_norm.weight", transpose=False
        )
    if cfg.post_block_norms:
        params["layers"]["post_attn_norm"] = stack(
            "model.layers.{}.post_attention_layernorm.weight", transpose=False
        )
        params["layers"]["post_mlp_norm"] = stack(
            "model.layers.{}.post_feedforward_layernorm.weight",
            transpose=False,
        )
    if cfg.attention_bias:
        params["layers"]["bq"] = stack(
            "model.layers.{}.self_attn.q_proj.bias", transpose=False
        )
        params["layers"]["bk"] = stack(
            "model.layers.{}.self_attn.k_proj.bias", transpose=False
        )
        params["layers"]["bv"] = stack(
            "model.layers.{}.self_attn.v_proj.bias", transpose=False
        )
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(t("lm_head.weight").T, cfg.dtype)
    return params


def params_from_gguf(gguf_file, cfg: LlamaConfig) -> dict:
    """Load unquantized GGUF tensors into our layer-stacked pytree.

    GGUF (llama.cpp) names: token_embd, blk.{l}.{attn_norm, attn_q, attn_k,
    attn_v, attn_output, ffn_norm, ffn_gate, ffn_up, ffn_down},
    output_norm, output. Projections stored [out, in] -> transposed to
    [in, out] like params_from_torch_state_dict.

    llama-arch GGUFs carry q/k projections in llama.cpp's interleaved rope
    row order (the HF->GGUF converter permutes them); apply_rope here uses
    the HF half-split pairing, so those rows are permuted back on load.
    qwen2-arch GGUFs are not permuted by the converter.
    """
    import numpy as np

    g = gguf_file
    L = cfg.num_layers
    permute_qk = g.architecture() == "llama"

    def unpermute_rows(w: np.ndarray, n_head: int) -> np.ndarray:
        # inverse of convert_hf_to_gguf's permute():
        #   reshape(h, 2, d/2, in).swapaxes(1, 2)
        out, inn = w.shape
        d = out // n_head
        return (
            w.reshape(n_head, d // 2, 2, inn)
            .swapaxes(1, 2)
            .reshape(out, inn)
        )

    def t(name, transpose=True, rope_heads: Optional[int] = None):
        w = np.asarray(g.load_tensor(name), np.float32)
        if rope_heads is not None and permute_qk:
            w = unpermute_rows(w, rope_heads)
        return w.T if transpose else w

    def stack(fmt, transpose=True, rope_heads: Optional[int] = None):
        return jnp.asarray(
            np.stack(
                [t(fmt.format(l), transpose, rope_heads) for l in range(L)]
            ),
            cfg.dtype,
        )

    params = {
        "embed": jnp.asarray(t("token_embd.weight", transpose=False), cfg.dtype),
        "layers": {
            "attn_norm": stack("blk.{}.attn_norm.weight", transpose=False),
            "wq": stack("blk.{}.attn_q.weight", rope_heads=cfg.num_heads),
            "wk": stack("blk.{}.attn_k.weight", rope_heads=cfg.num_kv_heads),
            "wv": stack("blk.{}.attn_v.weight"),
            "wo": stack("blk.{}.attn_output.weight"),
            "mlp_norm": stack("blk.{}.ffn_norm.weight", transpose=False),
            "w_gate": stack("blk.{}.ffn_gate.weight"),
            "w_up": stack("blk.{}.ffn_up.weight"),
            "w_down": stack("blk.{}.ffn_down.weight"),
        },
        "final_norm": jnp.asarray(
            t("output_norm.weight", transpose=False), cfg.dtype
        ),
    }
    if cfg.attention_bias:  # qwen2-family GGUFs carry qkv biases
        params["layers"]["bq"] = stack("blk.{}.attn_q.bias", transpose=False)
        params["layers"]["bk"] = stack("blk.{}.attn_k.bias", transpose=False)
        params["layers"]["bv"] = stack("blk.{}.attn_v.bias", transpose=False)
    if cfg.qk_norm:  # qwen3/gemma3-family GGUFs carry per-head q/k norms
        params["layers"]["q_norm"] = stack(
            "blk.{}.attn_q_norm.weight", transpose=False
        )
        params["layers"]["k_norm"] = stack(
            "blk.{}.attn_k_norm.weight", transpose=False
        )
    if cfg.post_block_norms:  # gemma2/3 sandwich norms
        params["layers"]["post_attn_norm"] = stack(
            "blk.{}.post_attention_norm.weight", transpose=False
        )
        params["layers"]["post_mlp_norm"] = stack(
            "blk.{}.post_ffw_norm.weight", transpose=False
        )
    if "output.weight" in g.tensors:
        params["lm_head"] = jnp.asarray(t("output.weight"), cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


#: the per-layer dense weights weight-only quantization covers
QUANTIZED_DENSE_NAMES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def init_params_int8(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Random-init directly into the int8 weight-only layout.

    `init_params` + `quantize_params_int8` materializes the full
    model-dtype weights first — 16GB for an 8B config, more than one
    v5e chip's HBM. Here every quantized dense weight is generated and
    quantized one layer at a time under lax.map, so peak transient
    memory is a single fp32 layer (~235MB for 8B); embeddings, norms and
    biases keep the base init. Output layout == quantize_params_int8's.
    """
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    L = cfg.num_layers
    keys = jax.random.split(key, 10)

    def qdense(k, in_dim, out_dim):
        def one(kl):
            w = jax.random.normal(
                kl, (in_dim, out_dim), jnp.float32
            ) / math.sqrt(in_dim)
            return quantize_channelwise_int8(w)

        return jax.lax.map(one, jax.random.split(k, L))

    def dense(k, shape, fan_in):
        scale = 1.0 / math.sqrt(fan_in)
        return (
            jax.random.normal(k, shape, jnp.float32) * scale
        ).astype(cfg.dtype)

    layers: dict = {
        "attn_norm": jnp.ones((L, h), cfg.dtype),
        "mlp_norm": jnp.ones((L, h), cfg.dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, cfg.head_dim), cfg.dtype)
        layers["k_norm"] = jnp.ones((L, cfg.head_dim), cfg.dtype)
    for name, k, din, dout in (
        ("wq", keys[1], h, qd), ("wk", keys[2], h, kvd),
        ("wv", keys[3], h, kvd), ("wo", keys[4], qd, h),
        ("w_gate", keys[5], h, i), ("w_up", keys[6], h, i),
        ("w_down", keys[7], i, h),
    ):
        q, s = qdense(k, din, dout)
        layers[name] = q
        layers[name + "_scale"] = s
    if cfg.attention_bias:
        layers["bq"] = jnp.zeros((L, qd), cfg.dtype)
        layers["bk"] = jnp.zeros((L, kvd), cfg.dtype)
        layers["bv"] = jnp.zeros((L, kvd), cfg.dtype)
    params = {
        "embed": dense(keys[0], (v, h), h),
        "layers": layers,
        "final_norm": jnp.ones((h,), cfg.dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(keys[8], (h, v), h)
    return params


def quantize_params_int8(params: dict) -> dict:
    """Weight-only int8 quantization with per-output-channel symmetric
    scales, applied to the seven layer matmul weights (embed / lm_head /
    norms / biases stay in the model dtype). Decode on TPU is
    HBM-bandwidth-bound on weight reads; int8 halves that traffic vs
    bf16 — XLA streams the int8->bf16 convert + scale into the dot's
    operand read. Matmul helpers (`_mm`) dequantize transparently, so the
    same forward serves both layouts."""
    quant_one = quantize_channelwise_int8

    out = dict(params)
    layers = dict(params["layers"])
    if any(layers[n].dtype == jnp.int8 for n in QUANTIZED_DENSE_NAMES):
        raise ValueError(
            "params are already int8-quantized; re-quantizing would "
            "recompute scales from quantized values and corrupt the model"
        )
    for name in QUANTIZED_DENSE_NAMES:
        # lax.map over the stacked layer axis keeps the fp32 temporary at
        # one layer's size (a whole-tensor astype would briefly double the
        # biggest weight on one device before sharding).
        q, scale = jax.lax.map(quant_one, layers[name])
        layers[name] = q
        layers[name + "_scale"] = scale
    out["layers"] = layers
    return out


def _mm(x: jax.Array, lp: dict, name: str, dtype) -> jax.Array:
    """x @ lp[name], dequantizing int8 weights on the fly."""
    w = lp[name]
    if w.dtype == jnp.int8:
        return (x @ w.astype(dtype)) * lp[name + "_scale"][0].astype(dtype)
    return x @ w


def _w(lp: dict, name: str, dtype) -> jax.Array:
    """lp[name], dequantized when int8 — for weights consumed by einsum
    (the scale varies over non-factorable axes, so dequant first; XLA
    fuses the convert+scale into the consumer's operand read). Shared by
    every family (mla/moe expert stacks, wkv_b)."""
    w = lp[name]
    if w.dtype == jnp.int8:
        return w.astype(dtype) * lp[name + "_scale"].astype(dtype)
    return w.astype(dtype)


def quantize_channelwise_int8(w: jax.Array):
    """THE int8 scheme, shared by every family's quantize/init path:
    per-output-channel symmetric max-abs scales over a [in, out] weight.
    Returns (int8 weight, [1, out] f32 scale)."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(wf), axis=0, keepdims=True) / 127.0, 1e-8
    )
    return jnp.round(wf / scale).astype(jnp.int8), scale


def _l2_norm(x: jax.Array, eps: float) -> jax.Array:
    """Weightless RMS normalization (Llama-4's q/k norm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype)


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, unit_offset: bool = False
) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if unit_offset:  # Gemma stores norm weights as deltas around 1
        w = w + 1.0
    return (out * w).astype(x.dtype)


def _rope_inv_freq(
    cfg: LlamaConfig,
    theta: Optional[float] = None,
    linear_factor: Optional[float] = None,
) -> jax.Array:
    """`theta` overrides cfg.rope_theta (Gemma3 local layers — the NTK
    path below never applies to an override); `linear_factor` divides
    every frequency, i.e. linear position scaling."""
    d = cfg.head_dim
    base = cfg.rope_theta if theta is None else theta
    inv_freq = 1.0 / (
        base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    if linear_factor is not None:
        inv_freq = inv_freq / linear_factor
    if theta is not None:
        return inv_freq
    if cfg.rope_yarn_factor is not None:
        # YaRN (2309.00071): interpolate low-frequency slots by `factor`,
        # keep high-frequency slots, ramp between the correction bounds.
        f = cfg.rope_yarn_factor
        orig = cfg.rope_original_max_position

        def corr_dim(rot):
            return (
                d * math.log(orig / (rot * 2 * math.pi))
            ) / (2 * math.log(base))

        low = corr_dim(cfg.rope_yarn_beta_fast)
        high = corr_dim(cfg.rope_yarn_beta_slow)
        if cfg.rope_yarn_truncate:
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low),
            0.0, 1.0,
        )
        extrapolation_w = 1.0 - ramp
        return (inv_freq / f) * (1.0 - extrapolation_w) + (
            inv_freq * extrapolation_w
        )
    if cfg.rope_scaling_factor is not None:
        # Llama-3.1 NTK-by-parts scaling.
        low = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        wavelen = 2.0 * jnp.pi / inv_freq
        smooth = (cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor) / (
            cfg.rope_high_freq_factor - cfg.rope_low_freq_factor
        )
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / cfg.rope_scaling_factor
        blended = (1.0 - smooth) * scaled + smooth * inv_freq
        inv_freq = jnp.where(wavelen > low, scaled, jnp.where(wavelen < high, inv_freq, blended))
    return inv_freq


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    cfg: LlamaConfig,
    inv_freq: Optional[jax.Array] = None,
) -> jax.Array:
    """x: [B, T, H, D]; positions: [B, T] absolute positions — or
    [3, B, T] m-RoPE streams (temporal, height, width) when
    cfg.mrope_section is set (Qwen2-VL; reference reaches this family
    only through vLLM — /root/reference examples/multimodal).
    `inv_freq` overrides the frequency table (Gemma3's per-layer-type
    selection, attention_block)."""
    default_table = inv_freq is None
    if inv_freq is None:
        inv_freq = _rope_inv_freq(cfg)
    if positions.ndim == 3:
        if not cfg.mrope_section:
            raise ValueError("[3,B,T] rope positions need cfg.mrope_section")
        # Each frequency section takes its angles from one position
        # stream; equal streams reduce to standard rope exactly.
        angles3 = positions[..., None].astype(jnp.float32) * inv_freq
        parts, off = [], 0
        for j, sec in enumerate(cfg.mrope_section):
            parts.append(angles3[j, ..., off : off + sec])
            off += sec
        angles = jnp.concatenate(parts, axis=-1)  # [B,T,D/2]
    else:
        angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,T,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    if default_table and cfg.rope_yarn_factor is not None:
        # YaRN attention factor scales the rotated vectors (HF convention:
        # cos/sin multiplied, so q·k scores scale by the factor squared)
        s = (
            cfg.rope_yarn_attention_factor
            if cfg.rope_yarn_attention_factor is not None
            else 0.1 * math.log(cfg.rope_yarn_factor) + 1.0
        )
        cos = cos * s
        sin = sin * s
    xf = x.astype(jnp.float32)
    if cfg.rope_interleaved:
        # Llama-4 / original-Llama pairing: (x[2i], x[2i+1]) rotate by
        # angle i (torch.view_as_complex semantics)
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        out = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape)
        return out.astype(x.dtype)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def paged_scatter(
    cache: jax.Array,  # [L, P, S, ...] — the FULL stacked cache
    layer: jax.Array,  # scalar int32 layer index
    new: jax.Array,  # [B, T, ...] (KV rows [B,T,Hkv,D])
    page_tables: jax.Array,  # [B, MP] int32
    positions: jax.Array,  # [B, T] int32
    valid: jax.Array,  # [B, T] bool
) -> jax.Array:
    """Write new KV for absolute `positions` into cache[layer]'s pages
    (the XLA fallback path; the Pallas impl stages writes and lands them
    with one DMA kernel per step instead — ops/kv_update.py). Quantized
    pools land their scale planes with _scale_scatter.

    Invalid (padding) slots are redirected to the null page 0 slot 0.

    The full cache goes in and comes out so the layer loop can carry it
    through `lax.scan`: a carried buffer is updated in place by the XLA
    while loop, so per-step HBM traffic is proportional to the tokens
    written — NOT to the cache size. (Emitting per-layer caches as scan
    outputs instead forces XLA to rewrite the entire pool every step —
    measured 2.6× slower at 512 pages and linear in num_pages. The
    slice-layer → 4D scatter → dynamic_update structure below keeps the
    carry aliasable; a direct 5D advanced-index scatter with the scalar
    layer index broke XLA's in-place update.)
    """
    page_size = cache.shape[2]
    page_of = positions // page_size  # [B,T] index into page table
    slot_of = positions % page_size
    page_ids = jnp.take_along_axis(page_tables, page_of, axis=1)  # [B,T]
    page_ids = jnp.where(valid, page_ids, 0)
    slot_of = jnp.where(valid, slot_of, 0)
    flat_pages = page_ids.reshape(-1)
    flat_slots = slot_of.reshape(-1)
    flat_new = new.reshape(-1, *new.shape[2:])  # [N, ...]
    layer_cache = lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    layer_cache = layer_cache.at[flat_pages, flat_slots].set(
        flat_new, mode="drop"
    )
    return lax.dynamic_update_index_in_dim(cache, layer_cache, layer, 0)


def paged_scatter_kv(
    kv: KVPages,
    layer: jax.Array,
    k_new: jax.Array,  # [B, T, Hkv, D] model-dtype rows
    v_new: jax.Array,
    page_tables: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
) -> KVPages:
    """paged_scatter over the whole pool, quantizing on write when the
    pool is quantized (scales land next to their rows)."""
    if not kv.quantized:
        return kv._replace(
            k=paged_scatter(
                kv.k, layer, k_new.astype(kv.k.dtype), page_tables,
                positions, valid,
            ),
            v=paged_scatter(
                kv.v, layer, v_new.astype(kv.v.dtype), page_tables,
                positions, valid,
            ),
        )
    mode = "int8" if kv.k.dtype == jnp.int8 else "fp8"
    kq, ks = quantize_kv_rows(k_new, mode)
    vq, vs = quantize_kv_rows(v_new, mode)
    args = (page_tables, positions, valid)
    return KVPages(
        k=paged_scatter(kv.k, layer, kq, *args),
        v=paged_scatter(kv.v, layer, vq, *args),
        k_scale=_scale_scatter(kv.k_scale, layer, ks, kv.page_size, *args),
        v_scale=_scale_scatter(kv.v_scale, layer, vs, kv.page_size, *args),
    )


def _scale_scatter(
    planes: jax.Array,  # [L, P, Hkv, S'] — the FULL stacked scale planes
    layer: jax.Array,
    new: jax.Array,  # [B, T, Hkv] f32 row scales
    page_size: int,
    page_tables: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
) -> jax.Array:
    """paged_scatter for the slot-minor scale planes (same null-page
    redirect, same slice-layer -> scatter -> dynamic_update structure)."""
    page_ids = jnp.take_along_axis(
        page_tables, positions // page_size, axis=1
    )
    flat_pages = jnp.where(valid, page_ids, 0).reshape(-1)
    flat_slots = jnp.where(valid, positions % page_size, 0).reshape(-1)
    plane = lax.dynamic_index_in_dim(planes, layer, 0, keepdims=False)
    plane = plane.at[flat_pages, :, flat_slots].set(
        new.reshape(-1, new.shape[-1]), mode="drop"
    )
    return lax.dynamic_update_index_in_dim(planes, plane, layer, 0)


def _scale_gather(
    planes: jax.Array, layer: jax.Array, page_tables: jax.Array,
    page_size: int,
) -> jax.Array:
    """[L, P, Hkv, S'] × [B, MP] -> [B, MP*S, Hkv], position-ordered row
    scales (pad slots stripped)."""
    g = lax.dynamic_index_in_dim(planes, layer, 0, keepdims=False)[
        page_tables
    ][..., :page_size]  # [B, MP, Hkv, S]
    b, mp, hkv, s = g.shape
    return g.transpose(0, 1, 3, 2).reshape(b, mp * s, hkv)


def paged_gather(
    cache: jax.Array, layer: jax.Array, page_tables: jax.Array
) -> jax.Array:
    """[L, P, S, ...] × [B, MP] -> [B, MP*S, ...], position-ordered."""
    g = jax.lax.dynamic_index_in_dim(
        cache, layer, axis=0, keepdims=False
    )[page_tables]  # [B, MP, S, ...]
    b, mp, s = g.shape[:3]
    return g.reshape(b, mp * s, *g.shape[3:])


def paged_gather_kv(
    kv: KVPages, layer: jax.Array, page_tables: jax.Array, dtype
) -> tuple[jax.Array, jax.Array]:
    """Gather + dequantize the paged history densely (the XLA fallback
    read path): returns (k, v) [B, MP*S, Hkv, D] in `dtype`. Quantized
    pools dequantize row-by-row against their gathered scale planes, so
    the xla/hybrid impls see exactly the values the flash kernels see."""
    k = paged_gather(kv.k, layer, page_tables)
    v = paged_gather(kv.v, layer, page_tables)
    if kv.quantized:
        ks = _scale_gather(kv.k_scale, layer, page_tables, kv.page_size)
        vs = _scale_gather(kv.v_scale, layer, page_tables, kv.page_size)
        return (
            dequantize_kv_rows(k, ks, dtype),
            dequantize_kv_rows(v, vs, dtype),
        )
    return k.astype(dtype), v.astype(dtype)


def paged_attention(
    q: jax.Array,  # [B, T, Hq, D] (post-rope)
    k_pages: jax.Array,  # [B, K, Hkv, D] gathered, position-ordered
    v_pages: jax.Array,  # [B, K, Hkv, D]
    q_positions: jax.Array,  # [B, T]
    cfg: LlamaConfig,
    key_positions: Optional[jax.Array] = None,  # [B, K]; default arange(K)
    window: Optional[jax.Array] = None,  # scalar: keys within (q_pos-w, q_pos]
    sinks: Optional[jax.Array] = None,  # [Hq] per-head sink logits
) -> jax.Array:
    """Reference paged attention (XLA path; the Pallas decode kernel in
    dynamo_tpu.ops replaces this for T=1 when cfg.attention_impl="pallas").

    Causality over the whole paged history: key at gathered index i has
    absolute position i (or key_positions when given), so the mask is
    simply key_pos <= q_pos. Unallocated page-table slots sit at positions
    >= seq_len and are masked by the same comparison. `window` (a traced
    scalar — Gemma2's per-layer local attention) additionally drops keys
    older than q_pos - window + 1.
    """
    b, t, hq, d = q.shape
    kk = k_pages.shape[1]
    g = cfg.q_per_kv
    qg = q.reshape(b, t, cfg.num_kv_heads, g, d)
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or d)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg.astype(jnp.float32), k_pages.astype(jnp.float32)
    ) * scale
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    if key_positions is None:
        key_pos = jnp.arange(kk)[None, None, None, None, :]
    else:
        key_pos = key_positions[:, None, None, None, :]
    q_pos = q_positions[:, None, None, :, None]
    mask = key_pos <= q_pos
    if window is not None:
        if getattr(window, "ndim", 0) == 2:
            # per-query window [B, T] (Llama-4 chunked attention)
            window = window[:, None, None, :, None]
        mask = mask & (key_pos > q_pos - window)
    scores = jnp.where(mask, scores, -1e30)
    if sinks is not None:
        # GPT-OSS attention sinks: a learned per-head logit joins the
        # softmax denominator (equivalently: softmax over [scores, sink]
        # with the sink column dropped)
        sk = sinks.astype(jnp.float32).reshape(cfg.num_kv_heads, g)[
            None, :, :, None, None
        ]
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sk)
        e = jnp.exp(scores - m)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sk - m))
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v_pages.astype(jnp.float32))
    # (as wide as the values: models/mimo_v2.py's are narrower than its keys)
    return out.reshape(b, t, hq * v_pages.shape[-1]).astype(q.dtype)


def _chunk_only_attention(q, k, v, positions, valid, cfg, dpad, mesh=None,
                          window=None, sinks=None):
    """First-chunk fast path: no history exists, so attend over the
    in-register chunk only — skips the O(MP·S) page gather and the
    attention over its padding. Invalid (padding) keys are pushed past
    every query position.

    Long-context: under a mesh with an sp axis, the chunk's causal
    attention runs as ring attention over the sequence shards (ICI
    ppermute of K/V blocks — parallel/context.py), so a prompt too long
    for one chip's attention memory prefills across the sp group. Valid
    first-chunk positions are contiguous from 0, so index-causal masking
    equals position masking; padding sits past every valid query.

    Under attention_impl="pallas" (and no sp ring), the chunk runs the
    flash kernel (ops/flash_prefill.py): online softmax in VMEM instead
    of materializing [B, H, T, T] fp32 scores in HBM."""
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    t = q.shape[1]
    if sp > 1 and t % sp == 0 and t > 1:
        if window is not None or sinks is not None:
            raise ValueError(
                "sliding-window / sink attention (Gemma2, GPT-OSS) is not "
                "implemented for the sp ring-attention path — run with sp=1"
            )
        if dpad:
            k = k[..., : cfg.head_dim]
            v = v[..., : cfg.head_dim]
        from dynamo_tpu.parallel.context import ring_attention

        out = ring_attention(
            q, k, v, mesh=mesh, causal=True,
            batch_axis="dp" if mesh.shape.get("dp", 1) > 1 else None,
            head_axis="tp" if mesh.shape.get("tp", 1) > 1 else None,
        )
        b, _, hq, d = q.shape
        return out.reshape(b, t, hq * d)
    if cfg.attention_impl in ("pallas", "hybrid"):
        from dynamo_tpu.ops.flash_prefill import flash_prefill_attention

        qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dpad))) if dpad else q
        valid_len = jnp.sum(valid, axis=1).astype(jnp.int32)
        out = flash_prefill_attention(
            qp, k, v, valid_len, scale_dim=cfg.head_dim, mesh=mesh
        )
        if dpad:
            out = out[..., : cfg.head_dim]
        b, _, hq, d = q.shape
        return out.reshape(b, t, hq * cfg.head_dim).astype(q.dtype)
    if dpad:
        k = k[..., : cfg.head_dim]
        v = v[..., : cfg.head_dim]
    cur_pos = jnp.where(valid, positions, jnp.int32(1 << 30))
    return paged_attention(
        q, k, v, positions, cfg, key_positions=cur_pos, window=window,
        sinks=sinks,
    )


#: route decode to the XLA gather past this kernel VMEM estimate rather
#: than letting Mosaic fail allocation (v5e VMEM is 16 MiB; leave head-
#: room for Mosaic's own buffers)
_PALLAS_DECODE_VMEM_BUDGET = 12 << 20
#: shapes whose explicit-pallas VMEM reroute was already warned about
_warned_vmem_reroute: set = set()


def maybe_decode_work(cfg, tokens, positions, kv, page_tables):
    """The decode kernel's work list (its rows with history) is LAYER-INVARIANT:
    build it once per step, outside the layer scan (XLA won't reliably
    hoist the sort out of the loop). Shared by the Llama and MoE forward
    passes; None whenever the step can't take the kernel path."""
    if tokens.shape[1] != 1 or cfg.attention_impl not in (
        "pallas", "hybrid"
    ):
        return None
    from dynamo_tpu.ops.paged_attention import decode_work_list

    return decode_work_list(page_tables, positions[:, 0])


def attention_block(
    q: jax.Array,  # [B, T, Hq, D] pre-rope
    k: jax.Array,  # [B, T, Hkv, D] pre-rope
    v: jax.Array,  # [B, T, Hkv, D]
    kv: KVPages,  # full stacked cache (+ scale planes when quantized)
    layer: jax.Array,  # scalar int32
    page_tables: jax.Array,  # [B, MP] int32
    positions: jax.Array,  # [B, T] int32
    valid: jax.Array,  # [B, T] bool
    cfg: LlamaConfig,
    first_chunk: bool = False,
    mesh=None,
    decode_work=None,  # precomputed ops.paged_attention.decode_work_list
    rope_positions=None,  # [3,B,T] m-RoPE streams; None = positions
    sinks=None,  # [Hq] GPT-OSS per-head sink logits
    decode_vmem_budget: int = _PALLAS_DECODE_VMEM_BUDGET,
):
    """rope → paged attention, in one of two write disciplines:

    - "xla": scatter this layer's KV into the cache, then gather + dense
      attention. Works on any backend and under any mesh.
    - "pallas": the cache is READ-ONLY here (history); this layer's KV is
      returned as `staged` for the layer scan to stack, and the engine step
      lands all layers with one DMA kernel (ops/kv_update.paged_write).
      Decode (T==1) runs the flash kernel + exact current-token merge;
      prefill attends to history pages + the in-register current chunk.

    Quantized pools (kv.quantized): the xla discipline quantizes on
    scatter and dequantizes on gather; the pallas discipline stages
    model-dtype KV (the write kernel quantizes) and the flash kernels
    dequantize each page in VMEM right after its DMA lands.

    Returns (attn [B,T,Hq*head_dim], kv, staged) where staged is None
    (xla) or ([B,T,Hkv,Dpad], [B,T,Hkv,Dpad]).
    Handles the cache's lane padding (cfg.kv_head_dim) transparently.
    `decode_vmem_budget`: what the decode walk may plan for and past which
    a decode step takes the XLA gather; a family whose shapes are known to
    fit the chip under another number says so (models/cohere2_moe.py).
    """
    b, t = q.shape[0], q.shape[1]
    rp = positions if rope_positions is None else rope_positions
    # Gemma3's every-Nth-layer-global predicate, shared by the rope theta
    # selection and the window selection below (`layer` is a traced scan
    # carry, so this is a traced scalar bool)
    is_global = (
        (layer + 1) % cfg.sliding_global_every == 0
        if cfg.sliding_global_every
        else None
    )
    # Llama-4 NoPE: every Nth layer skips rope entirely (traced bool)
    use_rope = (
        (layer + 1) % cfg.nope_every != 0 if cfg.nope_every else None
    )
    if not cfg.use_rope:
        pass
    elif cfg.rope_local_theta is not None:
        # Gemma3: global layers rope at rope_theta (with optional linear
        # scaling), local layers at rope_local_theta — select between the
        # two tiny [D/2] frequency tables, one rope application each.
        inv_freq = jnp.where(
            is_global,
            _rope_inv_freq(cfg, linear_factor=cfg.rope_linear_factor),
            _rope_inv_freq(cfg, theta=cfg.rope_local_theta),
        )
        q = apply_rope(q, rp, cfg, inv_freq=inv_freq)
        k = apply_rope(k, rp, cfg, inv_freq=inv_freq)
    else:
        rq = apply_rope(q, rp, cfg)
        rk = apply_rope(k, rp, cfg)
        if cfg.qk_l2_norm:
            # Llama-4: weightless L2 norm AFTER rope, rope layers only
            rq = _l2_norm(rq, cfg.rms_norm_eps)
            rk = _l2_norm(rk, cfg.rms_norm_eps)
        if use_rope is None:
            q, k = rq, rk
        else:
            q = jnp.where(use_rope, rq, q)
            k = jnp.where(use_rope, rk, k)
            if cfg.attn_temperature_tuning:
                # arXiv 2501.19399 temperature tuning on NoPE layers
                scales = (
                    jnp.log1p(
                        jnp.floor(
                            (positions.astype(jnp.float32) + 1.0)
                            / cfg.attn_floor_scale
                        )
                    )
                    * cfg.attn_scale_coef
                    + 1.0
                )  # [B, T]
                q = jnp.where(
                    use_rope,
                    q,
                    (q.astype(jnp.float32) * scales[..., None, None]).astype(
                        q.dtype
                    ),
                )
    dpad = cfg.kv_head_dim - cfg.head_dim
    if dpad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dpad)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dpad)))

    # Local attention (Gemma2 alternation / Mistral all-layers): affected
    # layers see only the trailing window. A traced scalar per scan step —
    # the mask comparison absorbs it with no extra program variants.
    window = None
    if cfg.sliding_window:
        if is_global is not None:
            # Gemma3: every Nth layer is GLOBAL, the rest are local
            window = jnp.where(
                is_global,
                jnp.int32(1 << 30), jnp.int32(cfg.sliding_window),
            )
        else:
            window = jnp.where(
                layer % cfg.sliding_window_every == 0,
                jnp.int32(cfg.sliding_window), jnp.int32(1 << 30),
            )
    elif cfg.attention_chunk:
        # Llama-4 chunked attention ≡ a PER-QUERY window of
        # (pos % chunk) + 1 on rope layers; NoPE layers attend globally
        wq = positions % cfg.attention_chunk + 1  # [B, T]
        if use_rope is not None:
            wq = jnp.where(use_rope, wq, jnp.int32(1 << 30))
        window = wq
    if cfg.attention_impl in ("pallas", "hybrid") and (
        cfg.sliding_window
        or cfg.attn_logit_softcap
        or cfg.attention_chunk
        or cfg.nope_every
        or cfg.attn_sinks
        or (
            cfg.query_pre_attn_scalar is not None
            and cfg.query_pre_attn_scalar != cfg.head_dim
        )
    ):
        raise ValueError(
            "sliding-window / softcap / rescaled / chunked / NoPE / "
            "sink attention (Gemma2, Llama-4, GPT-OSS) requires "
            "attention_impl='xla' — the flash kernels don't implement them"
        )

    # scopes (under the caller's `attn`): `kv_update` the cache write,
    # `paged` attention that reads cache pages, `flash` attention over
    # the in-register chunk alone or with paged history (prefill)
    if cfg.attention_impl not in ("pallas", "hybrid"):
        with jax.named_scope("kv_update"):
            kv = paged_scatter_kv(
                kv, layer, k, v, page_tables, positions, valid
            )
        if first_chunk and t > 1:
            with jax.named_scope("flash"):
                attn = _chunk_only_attention(
                    q, k, v, positions, valid, cfg, dpad, mesh=mesh,
                    window=window, sinks=sinks,
                )
            return attn, kv, None
        with jax.named_scope("paged"):
            k_all, v_all = paged_gather_kv(
                kv, layer, page_tables, cfg.dtype
            )
            if dpad:
                k_all = k_all[..., : cfg.head_dim]
                v_all = v_all[..., : cfg.head_dim]
            attn = paged_attention(
                q, k_all, v_all, positions, cfg, window=window, sinks=sinks
            )
        return attn, kv, None

    from dynamo_tpu.ops.paged_attention import (
        decode_vmem_bytes,
        paged_decode_attention,
    )

    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    kernel_vmem = decode_vmem_bytes(
        b, cfg.num_heads // tp, cfg.kv_head_dim, kv.k.shape[2],
        cfg.num_kv_heads // tp or 1, jnp.dtype(kv.k.dtype).itemsize,
        quantized=kv.quantized, budget=decode_vmem_budget,
    )
    if t == 1 and (
        (cfg.attention_impl == "hybrid" and b > cfg.pallas_decode_max_batch)
        or kernel_vmem > decode_vmem_budget
    ):
        # Two routes to the dense gather: (a) hybrid's large-batch policy
        # (the gather reads ~the same HBM bytes in a handful of fused XLA
        # ops), (b) the flattened kernel's whole-batch VMEM blocks would
        # overflow — route instead of letting Mosaic fail allocation.
        if (
            cfg.attention_impl == "pallas"
            and kernel_vmem > decode_vmem_budget
            and (key := (b, cfg.num_heads // tp, kv.k.shape[2]))
            not in _warned_vmem_reroute
        ):
            # An explicit pallas request silently running the XLA gather
            # is the measured-the-wrong-kernel hazard: say so at trace
            # time (same severity as the registry coercions). Once per
            # shape, not once per layer per retrace.
            _warned_vmem_reroute.add(key)
            logging.getLogger(__name__).warning(
                "attention_impl='pallas' rerouted to the XLA gather: "
                "decode kernel needs ~%.1f MiB VMEM (budget %.0f MiB) at "
                "b=%d heads=%d S=%d — shrink batch, page size, or "
                "heads-per-chip (tp) to keep the Pallas path",
                kernel_vmem / 2**20, decode_vmem_budget / 2**20,
                b, cfg.num_heads // tp, kv.k.shape[2],
            )
        with jax.named_scope("paged"):
            attn = _xla_history_attention(
                q, k, v, kv, layer, page_tables, positions, valid, cfg,
                dpad,
            )
    elif t == 1:
        with jax.named_scope("paged"):
            hist = positions[:, 0]  # tokens already in the cache
            qd = q[:, 0]
            if dpad:
                qd = jnp.pad(qd, ((0, 0), (0, 0), (0, dpad)))
            acc, m, l = paged_decode_attention(
                qd, kv.k, kv.v, layer, page_tables, hist,
                scale_dim=cfg.head_dim, mesh=mesh, work_list=decode_work,
                k_scale=kv.k_scale, v_scale=kv.v_scale,
                vmem_budget=decode_vmem_budget,
            )  # acc [B,Hq,Dpad] unnormalized, m/l [B,Hq]
            # Exact merge of the current (unwritten) token: self-attention
            # score s = q·k_cur/√d folded into the flash running state.
            g = cfg.q_per_kv
            kv_of = jnp.arange(cfg.num_heads) // g  # [Hq]
            k_sel = k[:, 0, kv_of]  # [B, Hq, Dpad]
            v_sel = v[:, 0, kv_of].astype(jnp.float32)
            scale = 1.0 / math.sqrt(cfg.head_dim)
            s_self = jnp.sum(
                qd.astype(jnp.float32) * k_sel.astype(jnp.float32), axis=-1
            ) * scale  # [B, Hq]
            m_star = jnp.maximum(m, s_self)
            alpha = jnp.exp(m - m_star)
            beta = jnp.exp(s_self - m_star)
            out = (alpha[..., None] * acc + beta[..., None] * v_sel) / (
                alpha * l + beta
            )[..., None]
            out = out.astype(cfg.dtype)
            if dpad:
                out = out[..., : cfg.head_dim]
            attn = out.reshape(b, cfg.num_heads * cfg.head_dim)[:, None, :]
    elif first_chunk:
        with jax.named_scope("flash"):
            attn = _chunk_only_attention(
                q, k, v, positions, valid, cfg, dpad, mesh=mesh
            )
    elif t <= 1024 and cfg.prefill_history_kernel:
        # Prefill chunk with history: paged pages (positions < chunk
        # start) + the current chunk, one online softmax — the flash
        # kernel walks pages with double-buffered DMA instead of
        # materializing the gathered history densely in HBM. The kernel
        # holds the whole current chunk's K/V in VMEM per grid cell, so
        # very large chunks (t > 1024) take the XLA path below instead of
        # oversubscribing VMEM.
        from dynamo_tpu.ops.flash_prefill import paged_prefill_attention

        with jax.named_scope("flash"):
            qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dpad))) if dpad else q
            start = positions[:, 0]
            hist_lens = jnp.where(valid[:, 0], start, 0).astype(jnp.int32)
            cur_lens = jnp.sum(valid, axis=1).astype(jnp.int32)
            out = paged_prefill_attention(
                qp, k, v, kv.k, kv.v, layer, page_tables,
                hist_lens, cur_lens, scale_dim=cfg.head_dim, mesh=mesh,
                k_scale=kv.k_scale, v_scale=kv.v_scale,
            )
            if dpad:
                out = out[..., : cfg.head_dim]
            attn = out.reshape(b, t, cfg.num_heads * cfg.head_dim).astype(q.dtype)
    else:
        with jax.named_scope("paged"):
            attn = _xla_history_attention(
                q, k, v, kv, layer, page_tables, positions, valid, cfg,
                dpad,
            )
    return attn, kv, (k, v)


def _xla_history_attention(
    q, k, v, kv, layer, page_tables, positions, valid, cfg, dpad
):
    """Gather-then-attend fallback for history chunks too large for the
    flash kernel's VMEM budget (dequantizes quantized pools on gather)."""
    k_hist, v_hist = paged_gather_kv(kv, layer, page_tables, k.dtype)
    kk = k_hist.shape[1]
    start = positions[:, 0]
    hist_pos = jnp.arange(kk, dtype=jnp.int32)[None, :]
    # Mask unwritten (>= chunk start) gathered slots outright.
    hist_pos = jnp.where(
        hist_pos < start[:, None], hist_pos, jnp.int32(1 << 30)
    )
    cur_pos = jnp.where(valid, positions, jnp.int32(1 << 30))
    keys = jnp.concatenate([k_hist, k], axis=1)
    vals = jnp.concatenate([v_hist, v], axis=1)
    key_positions = jnp.concatenate([hist_pos, cur_pos], axis=1)
    if dpad:
        keys = keys[..., : cfg.head_dim]
        vals = vals[..., : cfg.head_dim]
    return paged_attention(
        q, keys, vals, positions, cfg, key_positions=key_positions
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


class StepGroup(NamedTuple):
    """One group of rows of a model step: requests that go through
    attention together, with their own positions, validity and page
    tables. Every program kind but `mixed` runs one group; the fused mixed
    step runs two (a prompt chunk [B_pre, T], then the decode rows
    [B_dec, 1]) so that the dense work of a layer reads its weights once
    for both (`forward_groups`)."""

    tokens: jax.Array  # [B, T] int32
    positions: jax.Array  # [B, T] int32 absolute positions (padding: any)
    valid: jax.Array  # [B, T] bool — which (b,t) are real tokens
    page_tables: jax.Array  # [B, MP] int32
    first_chunk: bool = False  # static: every row starts at position 0
    rope_positions: Optional[jax.Array] = None  # [3,B,T] m-RoPE streams
    mm_embeds: Optional[jax.Array] = None  # [B, T, H] multimodal embeds
    mm_mask: Optional[jax.Array] = None  # [B, T] bool — use mm_embeds here
    #: [B, 2] int32 — where each row's recurrent state is read and where it
    #: is written (entries of the slot pool; models/nemotron_h.py)
    state_rows: Optional[jax.Array] = None


def join_rows(xs: list) -> jax.Array:
    """The groups' activations [B_g, T_g, ...] as what a layer's dense
    work runs on: one group goes through as it is; several are flattened
    to rows and concatenated, [sum(B_g * T_g), ...]."""
    if len(xs) == 1:
        return xs[0]
    return jnp.concatenate([x.reshape(-1, *x.shape[2:]) for x in xs])


def split_rows(x: jax.Array, groups) -> list:
    """`join_rows` undone: each group's [B_g, T_g, ...] out of the rows."""
    if len(groups) == 1:
        return [x]
    out, lo = [], 0
    for g in groups:
        b, t = g.tokens.shape
        out.append(x[lo : lo + b * t].reshape(b, t, *x.shape[1:]))
        lo += b * t
    return out


def forward_groups(
    params: dict,
    cfg: LlamaConfig,
    groups: Sequence[StepGroup],
    kv: KVPages,
    mesh=None,  # tp mesh: the Pallas kernels shard_map over it
) -> tuple[list, KVPages]:
    """One model step over the groups' token chunks: ONE layer scan, in
    which the norms, the projections and the FFN run on every group's
    rows together (each weight is read once) and attention runs per group
    (`attention_block`: its own rope, page walk or flash chunk, staging).
    Returns ([hidden [B_g, T_g, H] post final norm per group], new kv).
    Groups hold disjoint requests, so no group reads what another
    writes in the step.

    Covers prefill (T = chunk), decode (T = 1), and prefix-cache continuation
    (positions start past 0) uniformly. Multimodal (llava-style) prompts
    pass projected image embeddings in mm_embeds; where mm_mask is True
    they replace the token-id embedding lookup (the placeholder ids under
    the mask are ignored).

    The fused decode scan (`decode_multi`) calls this inside a lax.scan:
    padding rows keep the same [B, 1] shapes and their paged_write lanes
    redirect to the null page (valid=False contract in ops/kv_update).
    """
    # The named scopes below (embed, attn[/qkv, /kv_update, /paged or
    # /flash, /out], mlp, final_norm; lm_head in compute_logits) only
    # name things: a device trace carries them in each operation's
    # metadata, so device time falls under a part of the model
    # (docs/observability.md).
    with jax.named_scope("embed"):
        hs = []
        for g in groups:
            h = params["embed"][g.tokens].astype(cfg.dtype)  # [B,T,H]
            if g.mm_embeds is not None:
                h = jnp.where(
                    g.mm_mask[..., None], g.mm_embeds.astype(cfg.dtype), h
                )
            hs.append(h)
        h = join_rows(hs)
        if cfg.scale_embeddings:  # Gemma: normalizer cast to model dtype
            h = h * jnp.asarray(math.sqrt(cfg.hidden_size), cfg.dtype)
    off = cfg.rms_norm_unit_offset
    if cfg.hidden_act == "silu":
        act = jax.nn.silu
    elif cfg.hidden_act == "gelu_tanh":
        act = partial(jax.nn.gelu, approximate=True)
    else:
        raise ValueError(f"unknown hidden_act {cfg.hidden_act!r}")

    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(cfg, g.tokens, g.positions, kv, g.page_tables)
            for g in groups
        ]

    def layer(carry, xs):
        h, kvc = carry
        lp, li = xs
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, off)
                q = _mm(x, lp, "wq", cfg.dtype)
                k = _mm(x, lp, "wk", cfg.dtype)
                v = _mm(x, lp, "wv", cfg.dtype)
                if cfg.attention_bias:
                    q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
                lead = x.shape[:-1]
                q = q.reshape(*lead, cfg.num_heads, cfg.head_dim)
                k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
                v = v.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
                if cfg.qk_norm:  # Qwen3: head_dim-wide RMSNorm pre-rope
                    q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, off)
                    k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, off)
            attns, staged = [], []
            for g, work, qg, kg, vg in zip(
                groups, works, *(split_rows(a, groups) for a in (q, k, v))
            ):
                attn, kvc, st = attention_block(
                    qg, kg, vg, kvc, li, g.page_tables, g.positions, g.valid,
                    cfg, first_chunk=g.first_chunk, mesh=mesh,
                    decode_work=work, rope_positions=g.rope_positions,
                )
                attns.append(attn)
                staged.append(st)
            with jax.named_scope("out"):
                attn_out = _mm(join_rows(attns), lp, "wo", cfg.dtype)
                if cfg.post_block_norms:  # Gemma2: norm, then residual
                    attn_out = rms_norm(
                        attn_out, lp["post_attn_norm"], cfg.rms_norm_eps,
                        off,
                    )
                h = h + attn_out
        with jax.named_scope("mlp"):
            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps, off)
            gate = act(_mm(x, lp, "w_gate", cfg.dtype).astype(jnp.float32))
            up = _mm(x, lp, "w_up", cfg.dtype).astype(jnp.float32)
            mlp_out = _mm(
                (gate * up).astype(cfg.dtype), lp, "w_down", cfg.dtype
            )
            if cfg.post_block_norms:
                mlp_out = rms_norm(
                    mlp_out, lp["post_mlp_norm"], cfg.rms_norm_eps, off
                )
            h = h + mlp_out
        return (h, kvc), tuple(staged)

    (h, kv_new), staged = lax.scan(
        layer,
        (h, kv),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    with jax.named_scope("attn"), jax.named_scope("kv_update"):
        for g, st in zip(groups, staged):
            kv_new = land_staged_kv(
                kv_new, st, g.page_tables, g.positions, g.valid, mesh=mesh
            )
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, off)
    return split_rows(h, groups), kv_new


def forward_hidden(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # [B, T] int32
    positions: jax.Array,  # [B, T] int32 absolute positions (padding: any)
    valid: jax.Array,  # [B, T] bool — which (b,t) are real tokens
    kv: KVPages,
    page_tables: jax.Array,  # [B, MP] int32
    mm_embeds: Optional[jax.Array] = None,  # [B, T, H] multimodal embeds
    mm_mask: Optional[jax.Array] = None,  # [B, T] bool — use mm_embeds here
    first_chunk: bool = False,  # static: every row starts at position 0
    mesh=None,  # tp mesh: the Pallas kernels shard_map over it
    rope_positions: Optional[jax.Array] = None,  # [3,B,T] m-RoPE streams
) -> tuple[jax.Array, KVPages]:
    """One model step over a token chunk, the one-group `forward_groups`;
    returns (hidden [B,T,H] post final norm, new kv). The engine applies
    `compute_logits` only at the positions it samples from — for a
    512-token prefill chunk the full-chunk lm_head matmul would otherwise
    dominate the step's FLOPs."""
    (h,), kv = forward_groups(
        params, cfg,
        [StepGroup(
            tokens, positions, valid, page_tables, first_chunk,
            rope_positions, mm_embeds, mm_mask,
        )],
        kv, mesh=mesh,
    )
    return h, kv


def land_staged_kv(
    kv: KVPages, staged, page_tables, positions, valid, mesh=None
) -> KVPages:
    """Land a layer scan's staged KV (pallas write discipline) in one DMA
    kernel call; no-op under the xla scatter discipline (staged is None).
    Quantized pools quantize inside the page writer (the staged arrays
    are model-dtype). Shared by the Llama and MoE forward passes."""
    if staged is None:
        return kv
    from dynamo_tpu.ops.kv_update import paged_write

    out = paged_write(
        kv.k, kv.v, staged[0], staged[1], page_tables, positions,
        valid, mesh=mesh, k_scale=kv.k_scale, v_scale=kv.v_scale,
    )
    if kv.quantized:
        return KVPages(*out)
    return kv._replace(k=out[0], v=out[1])


def compute_logits(params: dict, cfg: LlamaConfig, hidden: jax.Array) -> jax.Array:
    """Project hidden states [..., H] to vocab logits [..., V] in f32."""
    with jax.named_scope("lm_head"):
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        logits = (hidden @ lm_head).astype(jnp.float32)
        if cfg.final_logit_softcap:  # Gemma2
            c = cfg.final_logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


def forward(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
    kv: KVPages,
    page_tables: jax.Array,
    **kw,
) -> tuple[jax.Array, KVPages]:
    """forward_hidden + full-chunk logits (tests/tools; engine uses the
    split form to avoid the all-positions lm_head matmul)."""
    h, kv = forward_hidden(
        params, cfg, tokens, positions, valid, kv, page_tables, **kw
    )
    return compute_logits(params, cfg, h), kv
