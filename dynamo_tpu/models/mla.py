"""DeepSeek-V2-style MLA (multi-head latent attention) + DeepSeek MoE.

The reference's flagship scale example serves DeepSeek models through
SGLang with DeepEP (examples/sglang/dsr1-wideep.md); here the
architecture is first-class TPU, built on the same paged-cache contract
as the Llama family — with the cache holding the COMPRESSED latent:

  cache.k: [L, P, S, 1, kv_lora_rank]      c_kv  (latent KV, pre-norm'd)
  cache.v: [L, P, S, 1, qk_rope_head_dim]  k_pe  (shared rope key)

Per token the cache costs kv_lora+rope floats (576 for V2 shapes) —
~9x smaller than the equivalent MHA cache — and every generic subsystem
(page allocator, prefix caching, tiering, disagg transfer) carries it
unchanged because they treat KVPages as opaque pages. Under
attention_impl="pallas" the rope key is cached in whole lane tiles
(`kv_rope_dim`: 64 -> 128 columns, zeros past the key): a 64-wide minor
dimension fills half a tile in HBM either way, so a token costs
(512 + 128) x 2 B = 1280 B a layer there in any layout, and the two
arrays keep the engine's (k, v) contract and the wire format (512, 64)
as they are. One 640-wide array would cost the same bytes and a second
meaning for `KVPages.v`.

Two write disciplines, as models/llama.py's attention_block:

- "xla": scatter the chunk's latent and rope key, then gather the page
  table's whole width in float32 and attend. Any backend, any mesh; what
  the CPU tests and the golden tests run.
- "pallas": the cache is read-only inside the layer scan; the chunk's
  rows are staged and land once a step (ops/kv_update.paged_write).
  Decode (T = 1) walks the live pages in ops/paged_attention.py's kernel
  (`latent=True`: one page is key and value at once, 16 heads in one
  dot) and folds the current token in exactly; a prefill chunk attends
  over its live history pages, a block of them a turn, and over itself
  in one kernel with one online softmax, the 16 heads folded into the
  tile's rows (ops/flash_prefill.latent_prefill_attention): each row
  stops at its own history, and no score leaves VMEM.

Attention runs in the ABSORBED form (the deployment form from the
DeepSeek-V2 paper): q_nope is projected by W_UK^T into the latent space
so scores dot directly with the cached latent, and the value projection
W_UV is applied AFTER the probability-weighted latent sum — no per-token
decompression of the history, FLOPs independent of kv_b:

  q_lat  = q_nope @ W_UK          [B,T,H,c]
  score  = q_lat . c_hist + q_pe . k_pe_hist    (scale 1/sqrt(nope+rope))
  o_lat  = softmax(score) . c_hist
  attn   = (o_lat @ W_UV) reshaped @ W_O

RoPE here is the DeepSeek complex-interleaved pairing (adjacent elements
(x[2j], x[2j+1]) rotate together — modeling_deepseek_v2.apply_rotary_emb)
— NOT the Llama half-split. DeepSeek-YaRN rope scaling is implemented
(interp/extrap frequency ramp; the attention factor scales the rotary
cos/sin, and V3/R1 configs additionally scale the softmax by
yarn_mscale(factor, mscale_all_dim)^2 — both matching HF).

MoE layers follow HF DeepseekV2MoE semantics: softmax gate -> greedy
top-k (weights NOT renormalized unless norm_topk_prob) scaled by
routed_scaling_factor, plus always-on shared experts. Routed experts are
DROPLESS: the N x k assignments are sorted by expert and each projection
is one grouped matmul over the contiguous groups (ops/grouped_matmul.py:
`jax.lax.ragged_dot`'s contract, a Pallas kernel on the TPU; operands
in the model dtype, float32 accumulation), then un-sorted and
summed with the gate's weights. Every assignment is computed: there is no
capacity. A chip that holds a SHARE of a layer's experts (`held`:
models/nemotron_h.py, models/keye_vl.py, models/dots3.py) moves only the
assignments that fall on its share, a bounded number of rows a pass and as
many passes as a step's count needs (`_routed_experts`, `share_rows`): the
bound is no capacity either. The expert axis is sharded over the mesh's
"ep" axis. The first
`first_k_dense_replace` layers use a dense MLP (V2-Lite: layer 0) — the
layer stack is two lax.scans (dense prefix, MoE suffix), keeping params
scan-stacked without per-layer Python unrolling.
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.llama import (
    _PALLAS_DECODE_VMEM_BUDGET as _DECODE_VMEM_BUDGET,
    KVPages,
    StepGroup,
    _mm,
    _w,
    join_rows,
    maybe_decode_work,
    paged_gather,
    paged_scatter,
    quantize_channelwise_int8,
    rms_norm,
    split_rows,
)

#: weight names quantized by quantize_params_int8 / init_params_int8
#: (w_router stays UNquantized in the base dtype — the gate matmul
#: upcasts it to f32; norms/embeds keep the base dtype too)
_QUANT_2D = (
    "wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
    "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
)
_QUANT_EXPERTS = ("we_gate", "we_up", "we_down")  # [L, E, in, out]


@dataclass(frozen=True)
class MlaConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128  # dense layers' MLP width
    num_layers: int = 2
    num_heads: int = 4
    q_lora_rank: Optional[int] = None  # None: direct q projection (V2-Lite)
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    #: DeepSeek-YaRN rope scaling (None disables): matches HF's
    #: _compute_yarn_parameters + the V2/V3 practice of scaling the
    #: rotary cos/sin by the attention factor
    rope_scaling_factor: Optional[float] = None
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: Optional[float] = None
    rope_mscale_all_dim: Optional[float] = None
    rope_original_max_position: int = 4096
    #: V3/R1: softmax scale additionally multiplies by
    #: yarn_mscale(factor, mscale_all_dim)^2 (DeepseekV3Attention); the
    #: integrated HF V2 port does NOT, so V2 configs default False — but
    #: deepseek-ai's ORIGINAL remote code applies it for V2 too; set True
    #: to match such a checkpoint's training-time semantics
    rope_mscale_softmax: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    #: "xla", or "pallas" / "hybrid": the latent page walk in a kernel and
    #: the staged cache write (module text)
    attention_impl: str = "xla"
    # -- MoE (None/0 experts = dense model) --------------------------------
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    num_experts_per_tok: int = 2
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    #: "greedy" (V2-Lite), "group_limited_greedy" (V2/V2-Chat),
    #: "noaux_tc" (V3/R1: sigmoid scores + aux-loss-free bias-corrected
    #: group routing) or "sigmoid" (the same with no correction bias).
    #: Groups rank by max member (V2) / top-2 sum (V3) of
    #: the (bias-corrected, V3) scores; top-k selects within the winning
    #: groups; V3 weights come from the UNcorrected sigmoid scores
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        s = 1.0 / math.sqrt(self.qk_head_dim)
        if (
            self.rope_mscale_softmax
            and self.rope_scaling_factor
            and self.rope_scaling_factor > 1
            and self.rope_mscale_all_dim
        ):
            m = (
                0.1 * self.rope_mscale_all_dim
                * math.log(self.rope_scaling_factor) + 1.0
            )
            s *= m * m
        return s

    @property
    def cache_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    @property
    def kv_rope_dim(self) -> int:
        """Columns of the cached rope key: whole 128-lane tiles under the
        kernels (Mosaic DMA slices are lane-aligned), zeros past the key."""
        r = self.qk_rope_head_dim
        return -(-r // 128) * 128 if self.kernels else r

    @property
    def num_kv_heads(self) -> int:
        """MLA stores ONE shared latent per token (MQA-shaped cache)."""
        return 1

    @property
    def mqa_latent_cache(self) -> bool:
        """The cache REPLICATES over tp (kv_cache_spec(shard_heads=False))
        — the engine skips its kv-head tp-divisibility check for us."""
        return True

    @property
    def num_dense_layers(self) -> int:
        if not self.n_routed_experts:
            return self.num_layers
        return min(self.first_k_dense_replace, self.num_layers)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @staticmethod
    def deepseek_v2_lite(num_layers: int = 27) -> "MlaConfig":
        """DeepSeek-V2-Lite (15.7B total / 2.4B active) as its config.json
        publishes it: MLA with direct q, layer 0 dense, 26 MoE layers of
        64 routed (top-6, greedy) + 2 shared experts, YaRN rope (factor
        40, mscale = mscale_all_dim = 0.707, original 4096). The softmax
        scale follows the HF port (`rope_mscale_softmax` False).
        `num_layers` cuts depth only (the one-chip preset keeps layer 0
        dense and 7 expert layers); every width stays."""
        return MlaConfig(
            vocab_size=102400, hidden_size=2048, intermediate_size=10944,
            num_layers=num_layers, num_heads=16, q_lora_rank=None,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, rope_theta=10000.0,
            rope_scaling_factor=40.0, rope_beta_fast=32.0,
            rope_beta_slow=1.0, rope_mscale=0.707,
            rope_mscale_all_dim=0.707, rope_original_max_position=4096,
            n_routed_experts=64, n_shared_experts=2,
            moe_intermediate_size=1408, num_experts_per_tok=6,
            first_k_dense_replace=1,
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MlaConfig":
        return MlaConfig(vocab_size=vocab_size, dtype=jnp.float32)

    @staticmethod
    def tiny_moe(vocab_size: int = 256) -> "MlaConfig":
        return MlaConfig(
            vocab_size=vocab_size, dtype=jnp.float32, num_layers=3,
            n_routed_experts=4, n_shared_experts=1,
            moe_intermediate_size=32, num_experts_per_tok=2,
            first_k_dense_replace=1,
        )

    @staticmethod
    def from_hf_config(hf: dict) -> "MlaConfig":
        rs = hf.get("rope_scaling") or {}
        if rs and rs.get("rope_type", rs.get("type")) != "yarn":
            raise ValueError(
                f"unsupported rope_scaling {rs!r} for DeepSeek (only "
                "yarn is implemented)"
            )
        if rs and rs.get("factor") is None:
            raise ValueError(
                "yarn rope_scaling needs an explicit 'factor'"
            )
        v3 = (
            hf.get("model_type") == "deepseek_v3"
            or "DeepseekV3ForCausalLM" in (hf.get("architectures") or [])
        )
        topk_method = hf.get("topk_method") or (
            "noaux_tc" if v3 else "greedy"
        )
        if topk_method not in (
            "greedy", "group_limited_greedy", "noaux_tc"
        ):
            raise ValueError(f"unsupported topk_method {topk_method!r}")
        if topk_method in ("group_limited_greedy", "noaux_tc"):
            ng = int(hf.get("n_group") or 1)
            tg = int(hf.get("topk_group") or 1)
            ne = int(hf.get("n_routed_experts") or 0)
            # fail at load with a named error, not at trace with a shape one
            if ne % max(ng, 1) or tg > ng:
                raise ValueError(
                    f"{topk_method} needs n_group ({ng}) dividing "
                    f"n_routed_experts ({ne}) and topk_group ({tg}) <= "
                    f"n_group"
                )
        return MlaConfig(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            q_lora_rank=hf.get("q_lora_rank"),
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rope_scaling_factor=(
                float(rs["factor"]) if rs else None
            ),
            rope_beta_fast=float(rs.get("beta_fast") or 32.0),
            rope_beta_slow=float(rs.get("beta_slow") or 1.0),
            rope_mscale=rs.get("mscale"),
            rope_mscale_all_dim=rs.get("mscale_all_dim"),
            rope_original_max_position=int(
                rs.get("original_max_position_embeddings")
                or hf.get("max_position_embeddings", 4096)
            ),
            # V3 applies the yarn mscale^2 term inside the softmax scale;
            # the integrated HF port of V2 does NOT (our golden tests match
            # that port), but V2 yarn checkpoints (factor=40,
            # mscale_all_dim=0.707) were TRAINED with it, so expose an
            # operator override: DYN_MLA_MSCALE_SOFTMAX=1 forces it on.
            # See docs/models.md "DeepSeek V2 yarn softmax scale".
            rope_mscale_softmax=(
                v3
                or os.environ.get("DYN_MLA_MSCALE_SOFTMAX", "") == "1"
            ),
            rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
            n_routed_experts=int(hf.get("n_routed_experts") or 0),
            n_shared_experts=int(hf.get("n_shared_experts") or 0),
            moe_intermediate_size=int(hf.get("moe_intermediate_size") or 0),
            num_experts_per_tok=int(hf.get("num_experts_per_tok") or 2),
            first_k_dense_replace=int(hf.get("first_k_dense_replace", 1)),
            routed_scaling_factor=float(
                hf.get("routed_scaling_factor", 1.0)
            ),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            topk_method=topk_method,
            n_group=int(hf.get("n_group") or 1),
            topk_group=int(hf.get("topk_group") or 1),
        )


def init_kv_pages(cfg: MlaConfig, num_pages: int, page_size: int) -> KVPages:
    """k holds the latent c_kv, v the shared rope key (`kv_rope_dim`
    columns) — see module doc."""
    return KVPages(
        k=jnp.zeros(
            (cfg.num_layers, num_pages, page_size, 1, cfg.kv_lora_rank),
            cfg.dtype,
        ),
        v=jnp.zeros(
            (cfg.num_layers, num_pages, page_size, 1, cfg.kv_rope_dim),
            cfg.dtype,
        ),
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _attn_layer_shapes(cfg: MlaConfig) -> dict:
    h = cfg.hidden_size
    shapes = {
        "attn_norm": (h,),
        "wkv_a": (h, cfg.cache_dim),
        "kv_a_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (cfg.num_heads * cfg.v_head_dim, h),
        "mlp_norm": (h,),
    }
    if cfg.q_lora_rank:
        shapes["wq_a"] = (h, cfg.q_lora_rank)
        shapes["q_a_norm"] = (cfg.q_lora_rank,)
        shapes["wq_b"] = (cfg.q_lora_rank, cfg.num_heads * cfg.qk_head_dim)
    else:
        shapes["wq"] = (h, cfg.num_heads * cfg.qk_head_dim)
    return shapes


def init_params(key: jax.Array, cfg: MlaConfig) -> dict:
    h, v = cfg.hidden_size, cfg.vocab_size
    counter = iter(range(1 << 30))

    def dense(shape):
        # fold_in per tensor: no fixed key pool to exhaust (deepseek-v2-
        # lite alone has thousands of expert tensors)
        k = jax.random.fold_in(key, next(counter))
        scale = 1.0 / math.sqrt(shape[0])
        return (
            jax.random.normal(k, shape, jnp.float32) * scale
        ).astype(cfg.dtype)

    def norm(shape):
        return jnp.ones(shape, cfg.dtype)

    def group(n_layers: int, moe: bool) -> dict:
        if n_layers == 0:
            return {}
        lp = {}
        for name, shape in _attn_layer_shapes(cfg).items():
            init = norm if "norm" in name else dense
            lp[name] = jnp.stack([init(shape) for _ in range(n_layers)])
        if not moe:
            i = cfg.intermediate_size
            for nm, shape in (
                ("w_gate", (h, i)), ("w_up", (h, i)), ("w_down", (i, h)),
            ):
                lp[nm] = jnp.stack([dense(shape) for _ in range(n_layers)])
        else:
            e, mi = cfg.n_routed_experts, cfg.moe_intermediate_size
            si = mi * cfg.n_shared_experts
            lp["w_router"] = jnp.stack(
                [dense((h, e)) for _ in range(n_layers)]
            )
            if cfg.topk_method == "noaux_tc":
                lp["router_bias"] = jnp.zeros((n_layers, e), jnp.float32)
            for nm, shape in (
                ("we_gate", (e, h, mi)), ("we_up", (e, h, mi)),
                ("we_down", (e, mi, h)),
            ):
                lp[nm] = jnp.stack(
                    [
                        jnp.stack([dense(shape[1:]) for _ in range(e)])
                        for _ in range(n_layers)
                    ]
                )
            for nm, shape in (
                ("ws_gate", (h, si)), ("ws_up", (h, si)), ("ws_down", (si, h)),
            ):
                lp[nm] = jnp.stack([dense(shape) for _ in range(n_layers)])
        return lp

    params = {
        "embed": dense((v, h)),
        "dense_layers": group(cfg.num_dense_layers, moe=False),
        "moe_layers": group(cfg.num_moe_layers, moe=True),
        "final_norm": norm((h,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense((h, v))
    return params


def params_from_torch_state_dict(state_dict, cfg: MlaConfig) -> dict:
    """HF DeepseekV2ForCausalLM state_dict -> our two-scan pytree."""
    import numpy as np

    def t(name):
        return np.asarray(state_dict[name].to("cpu").float().numpy())

    def stack(layers, fmt, transpose=True):
        ws = [t(fmt.format(l)) for l in layers]
        return jnp.asarray(
            np.stack([w.T if transpose else w for w in ws]), cfg.dtype
        )

    def attn_group(layers) -> dict:
        lp = {
            "attn_norm": stack(
                layers, "model.layers.{}.input_layernorm.weight", False
            ),
            "wkv_a": stack(
                layers, "model.layers.{}.self_attn.kv_a_proj_with_mqa.weight"
            ),
            "kv_a_norm": stack(
                layers, "model.layers.{}.self_attn.kv_a_layernorm.weight",
                False,
            ),
            "wkv_b": stack(
                layers, "model.layers.{}.self_attn.kv_b_proj.weight"
            ),
            "wo": stack(layers, "model.layers.{}.self_attn.o_proj.weight"),
            "mlp_norm": stack(
                layers, "model.layers.{}.post_attention_layernorm.weight",
                False,
            ),
        }
        if cfg.q_lora_rank:
            lp["wq_a"] = stack(
                layers, "model.layers.{}.self_attn.q_a_proj.weight"
            )
            lp["q_a_norm"] = stack(
                layers, "model.layers.{}.self_attn.q_a_layernorm.weight",
                False,
            )
            lp["wq_b"] = stack(
                layers, "model.layers.{}.self_attn.q_b_proj.weight"
            )
        else:
            lp["wq"] = stack(
                layers, "model.layers.{}.self_attn.q_proj.weight"
            )
        return lp

    dense_idx = list(range(cfg.num_dense_layers))
    moe_idx = list(range(cfg.num_dense_layers, cfg.num_layers))

    dense_lp = attn_group(dense_idx) if dense_idx else {}
    if dense_idx:
        for nm, hf_nm in (
            ("w_gate", "gate_proj"), ("w_up", "up_proj"),
            ("w_down", "down_proj"),
        ):
            dense_lp[nm] = stack(
                dense_idx, "model.layers.{}.mlp." + hf_nm + ".weight"
            )

    moe_lp = attn_group(moe_idx) if moe_idx else {}
    if moe_idx:
        import numpy as np

        moe_lp["w_router"] = stack(
            moe_idx, "model.layers.{}.mlp.gate.weight"
        )  # HF gate.weight is [E, h]; transposed to [h, E]
        if cfg.topk_method == "noaux_tc":
            # keep FULL f32 precision: stack() would round-trip through
            # cfg.dtype (bf16) and lose the tie-breaking bias bits that
            # govern V3 expert selection
            moe_lp["router_bias"] = jnp.asarray(
                np.stack([
                    t(f"model.layers.{l}.mlp.gate.e_score_correction_bias")
                    for l in moe_idx
                ]),
                jnp.float32,
            )
        for nm, hf_nm in (
            ("we_gate", "gate_proj"), ("we_up", "up_proj"),
            ("we_down", "down_proj"),
        ):
            moe_lp[nm] = jnp.asarray(
                np.stack(
                    [
                        np.stack(
                            [
                                t(
                                    f"model.layers.{l}.mlp.experts.{e}."
                                    f"{hf_nm}.weight"
                                ).T
                                for e in range(cfg.n_routed_experts)
                            ]
                        )
                        for l in moe_idx
                    ]
                ),
                cfg.dtype,
            )
        for nm, hf_nm in (
            ("ws_gate", "gate_proj"), ("ws_up", "up_proj"),
            ("ws_down", "down_proj"),
        ):
            moe_lp[nm] = stack(
                moe_idx, "model.layers.{}.mlp.shared_experts." + hf_nm
                + ".weight"
            )

    params = {
        "embed": jnp.asarray(t("model.embed_tokens.weight"), cfg.dtype),
        "dense_layers": dense_lp,
        "moe_layers": moe_lp,
        "final_norm": jnp.asarray(t("model.norm.weight"), cfg.dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(t("lm_head.weight").T, cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _yarn_inv_freq_and_factor(cfg: MlaConfig, d: int):
    """HF _compute_yarn_parameters: blend interpolated/extrapolated
    inverse frequencies with a linear ramp between the beta_fast/slow
    correction dims; the attention factor (mscale ratio, or
    0.1*ln(factor)+1) scales the rotary cos/sin — exactly how the HF
    DeepSeek rotary applies it (freqs_cis * attention_scaling)."""
    import numpy as np

    base, factor = cfg.rope_theta, cfg.rope_scaling_factor
    pos_freqs = base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extrap = 1.0 / pos_freqs
    interp = 1.0 / (factor * pos_freqs)

    def corr_dim(rot):
        return (
            d
            * math.log(cfg.rope_original_max_position / (rot * 2 * math.pi))
        ) / (2 * math.log(base))

    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1
    )
    extrap_factor = 1.0 - ramp
    inv = interp * (1 - extrap_factor) + extrap * extrap_factor

    def get_mscale(scale, m=1.0):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    if cfg.rope_mscale and cfg.rope_mscale_all_dim:
        att = get_mscale(factor, cfg.rope_mscale) / get_mscale(
            factor, cfg.rope_mscale_all_dim
        )
    else:
        att = get_mscale(factor)
    return jnp.asarray(inv, jnp.float32), float(att)


def _interleaved_rope(x: jax.Array, positions: jax.Array, cfg: MlaConfig):
    """DeepSeek rope: adjacent pairs (x[2j], x[2j+1]) rotate as complex
    numbers (modeling_deepseek_v2.apply_rotary_emb) — unlike Llama's
    half-split pairing. x: [B, T, ..., D], positions [B, T]."""
    d = x.shape[-1]
    if cfg.rope_scaling_factor:
        inv, att = _yarn_inv_freq_and_factor(cfg, d)
    else:
        inv = 1.0 / (
            cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        )
        att = 1.0
    freqs = positions.astype(jnp.float32)[..., None] * inv  # [B,T,d/2]
    cos, sin = jnp.cos(freqs) * att, jnp.sin(freqs) * att
    extra = x.ndim - 3  # broadcast over any head axes between T and D
    for _ in range(extra):
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32)
    x_even, x_odd = xf[..., 0::2], xf[..., 1::2]
    out_even = x_even * cos - x_odd * sin
    out_odd = x_even * sin + x_odd * cos
    return jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape).astype(
        x.dtype
    )


#: masked scores; finite so that a padded query row stays NaN-free
_MASKED = -1e30
#: the decode walk's VMEM budget under token bits: that call raises the
#: kernel's limit to 64 MiB (ops/paged_attention.py), so a whole batch of
#: 32 rows x 128 heads (34 MB of q, acc and m|l) walks in ONE call
_BITS_VMEM_BUDGET = 48 << 20


def _pad_last(x: jax.Array, width: int) -> jax.Array:
    return jnp.pad(
        x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),)
    )


def _latent_decode(qd, c_cur, pe_cur, k_cache, v_cache, layer, page_tables,
                   hist, cfg: MlaConfig, decode_work, mesh, chosen=None):
    """o_lat [B, H, c] f32 of one decode step: the kernel's walk over the
    history pages, then the current (staged, unwritten) token folded in
    exactly. qd [B, H, c+R] is the absorbed query, then its rope part;
    c_cur [B, c] and pe_cur [B, R] the current token's rows. `chosen`
    [B, MP * S] bool names the tokens a row attends (models/dots3.py: the
    walk under a bit a cached token, and the row's own token folded in
    only where it is chosen)."""
    from dynamo_tpu.ops.paged_attention import (
        decode_vmem_bytes,
        paged_decode_attention,
    )

    b, hn, _ = qd.shape
    c, scale = cfg.kv_lora_rank, cfg.softmax_scale
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    budget = _DECODE_VMEM_BUDGET if chosen is None else _BITS_VMEM_BUDGET

    def walk(rows, work):
        return paged_decode_attention(
            qd[rows], k_cache, v_cache, layer, page_tables[rows],
            hist[rows], scale=scale, latent=True, mesh=mesh,
            work_list=work, vmem_budget=budget,
            token_bits=None if chosen is None else chosen[rows],
        )

    # the kernel keeps the whole batch's q and acc in VMEM: a batch too
    # large for the budget walks in halves (a second work list, built here)
    piece = b
    while piece > 1 and decode_vmem_bytes(
        piece, hn // tp, c, k_cache.shape[2], 1,
        jnp.dtype(k_cache.dtype).itemsize, budget=budget,
        rope_dim=cfg.kv_rope_dim,
    ) > budget:
        piece = -(-piece // 2)
    if piece == b:
        acc, m, l = walk(slice(None), decode_work)
    else:
        parts = [walk(slice(i, i + piece), None) for i in range(0, b, piece)]
        acc, m, l = (jnp.concatenate(x) for x in zip(*parts))
    qf = qd.astype(jnp.float32)
    s_self = scale * (
        jnp.einsum("bhc,bc->bh", qf[..., :c], c_cur.astype(jnp.float32))
        + jnp.einsum("bhr,br->bh", qf[..., c:], pe_cur.astype(jnp.float32))
    )
    if chosen is not None:
        own = jnp.take_along_axis(chosen, hist[:, None], axis=1)  # [B, 1]
        s_self = jnp.where(own, s_self, _MASKED)
    m_star = jnp.maximum(m, s_self)
    alpha, beta = jnp.exp(m - m_star), jnp.exp(s_self - m_star)
    return (
        alpha[..., None] * acc
        + beta[..., None] * c_cur.astype(jnp.float32)[:, None, :]
    ) / (alpha * l + beta)[..., None]


def _latent_prefill_attention(
    q_lat, q_pe, c_kv, pe_rows, k_cache, v_cache, layer, page_tables,
    positions, valid, cfg: MlaConfig, first_chunk: bool, mesh=None,
    chosen=None,
):
    """o_lat [B, T, H, c] (model dtype) of a prefill chunk in the absorbed
    form: the chunk over its history in the cache and over itself (causal by
    position) in ONE kernel with one online softmax
    (ops/flash_prefill.latent_prefill_attention). Operands go to the MXU
    in the model dtype, the queries scaled before they are rounded to
    it; scores, softmax and sums are float32. The history is what lies
    before the chunk's first position (chunks start page-aligned; the
    chunk's own rows are staged, not yet in the cache: `c_kv` and
    `pe_rows`, the rope key as cached), none for a `first_chunk`. `chosen`
    [B, T, MP * S] bool names each query's keys by position
    (models/dots3.py)."""
    from dynamo_tpu.ops.flash_prefill import latent_prefill_attention

    dt, f32 = cfg.dtype, jnp.float32
    start = jnp.where(valid[:, 0], positions[:, 0], 0)  # [B] history
    qp = (q_pe.astype(f32) * cfg.softmax_scale).astype(dt)
    return latent_prefill_attention(
        (q_lat * cfg.softmax_scale).astype(dt),
        _pad_last(qp, cfg.kv_rope_dim), c_kv.astype(dt), pe_rows, k_cache,
        v_cache, layer, page_tables,
        jnp.zeros_like(start) if first_chunk else start,
        jnp.sum(valid, axis=1), mesh=mesh, chosen=chosen,
    )


def latent_projections(x, lp, cfg: MlaConfig, rescale=None):
    """A layer's latent projections of the normed input x [.., H], scope
    `qkv`: (q [.., heads, nope + rope] before the rotary embedding, the
    latent c_kv [.., c] normed, `kv_a` [.., c + r] whose last r columns
    are the rope key before the rotary embedding, the query latent c_q
    [.., q_lora_rank] normed, or None where q is projected directly).
    `rescale` = (a_q, a_kv) multiplies the normed latents (models/dots3.py;
    the rope key is left alone)."""
    hn, c = cfg.num_heads, cfg.kv_lora_rank
    with jax.named_scope("qkv"):
        qa = None
        if cfg.q_lora_rank:
            qa = rms_norm(
                _mm(x, lp, "wq_a", cfg.dtype).astype(cfg.dtype),
                lp["q_a_norm"], cfg.rms_norm_eps,
            )
            if rescale:
                qa = (qa * rescale[0]).astype(cfg.dtype)
            q = _mm(qa, lp, "wq_b", cfg.dtype)
        else:
            q = _mm(x, lp, "wq", cfg.dtype)
        q = q.reshape(*x.shape[:-1], hn, cfg.qk_head_dim)
        kv_a = _mm(x, lp, "wkv_a", cfg.dtype)  # [..., c+r]
        c_kv = rms_norm(
            kv_a[..., :c].astype(cfg.dtype), lp["kv_a_norm"],
            cfg.rms_norm_eps,
        )
        if rescale:
            c_kv = (c_kv * rescale[1]).astype(cfg.dtype)
    return q, c_kv, kv_a, qa


def absorbed_query(q, lp, cfg: MlaConfig):
    """(q_lat [.., heads, c] float32, W_UV [c, heads, v]): the nope part
    of the queries q [.., heads, nope + rope] through W_UK into the latent
    space (scope `absorb`), and the
    value half of `wkv_b` for `latent_output`. Operands in the model dtype
    under the kernels, float32 under xla; float32 accumulation in both."""
    hn, c = cfg.num_heads, cfg.kv_lora_rank
    n, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    wdt = cfg.dtype if cfg.kernels else jnp.float32
    wkv_b = _w(lp, "wkv_b", wdt).reshape(c, hn, n + vd)
    w_uk, w_uv = wkv_b[..., :n], wkv_b[..., n:]
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum(
            "...hn,chn->...hc", q[..., :n].astype(wdt), w_uk,
            preferred_element_type=jnp.float32,
        )
    return q_lat, w_uv


def latent_output(o_lat, w_uv, lp, cfg: MlaConfig, gate=None):
    """The attention block's output from o_lat [.., heads, c] (in W_UV's
    dtype): the value up-projection, then `wo`; `gate` [.., heads]
    multiplies each head's output before `wo` (models/dots3.py). The
    caller names the scope."""
    return heads_output(jnp.einsum(
        "...hc,chv->...hv", o_lat, w_uv,
        preferred_element_type=jnp.float32,
    ), lp, cfg, gate)


def heads_output(out, lp, cfg: MlaConfig, gate=None):
    """`wo` over the heads' outputs out [.., heads, v] (`latent_output`'s
    second half: models/dots3.py has rows that come out of attention up-
    projected already)."""
    if gate is not None:
        out = out * gate[..., None].astype(jnp.float32)
    out = out.reshape(*out.shape[:-2], -1).astype(cfg.dtype)
    return _mm(out, lp, "wo", cfg.dtype)


def mla_attention(
    x: jax.Array,  # the groups' rows (llama.join_rows), post-attn-norm
    lp: dict,
    cfg: MlaConfig,
    kv: tuple,  # (k_cache, v_cache) full stacked
    layer: jax.Array,
    groups,  # llama.StepGroup, one or two
    works,  # per group: ops.paged_attention.decode_work_list or None
    mesh=None,
):
    """Returns (attn, k_cache, v_cache, staged), `attn` shaped like `x`.
    Every projection (q, the latent, the absorbed query, the value
    up-projection, `wo`) runs on all the groups' rows at once; rope, the
    cache write or staging and attention itself run per group, each with
    its own positions and page tables. `staged` holds, per group, None
    under the xla discipline (the caches come back written) and the
    chunk's (latent, rope key) rows under the kernels (the caches come
    back as they went in). Scopes, under the caller's `attn`: `qkv`,
    `kv_update`, `absorb`, `paged` (reads cache pages: the decode walk,
    and the xla discipline's gather), `flash` (a prefill chunk under the
    kernels, with or without history), `out`."""
    n, c = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q, c_kv, kv_a, _ = latent_projections(x, lp, cfg)
    q_lat, w_uv = absorbed_query(q, lp, cfg)

    attend = _attend_kernels if cfg.kernels else _attend_xla
    o_lats, staged = [], []
    parts = (q_lat, q[..., n:], c_kv, kv_a[..., c:])
    for g, work, ql, qp, ck, kp in zip(
        groups, works, *(split_rows(a, groups) for a in parts)
    ):
        with jax.named_scope("qkv"):
            qp = _interleaved_rope(qp, g.positions, cfg)
            kp = _interleaved_rope(kp, g.positions, cfg).astype(cfg.dtype)
        o_lat, kv, st = attend(ql, qp, ck, kp, cfg, kv, layer, g, work, mesh)
        o_lats.append(o_lat.astype(w_uv.dtype))
        staged.append(st)

    with jax.named_scope("out"):
        return (latent_output(join_rows(o_lats), w_uv, lp, cfg), *kv,
                tuple(staged))


def _attend_xla(q_lat, q_pe, c_kv, k_pe, cfg: MlaConfig, kv, layer, g,
                work, mesh, keep=None):
    """One group under the xla discipline: land the chunk's latent and
    rope key, then attend over the gathered (history + current) cache:
    the same scatter-then-gather as the Llama XLA path, so causality is
    pure position masking; `keep` [B, T, K] bool masks further (the
    tokens a query's indexer chose, models/dots3.py). Returns (o_lat [B, T,
    H, c] f32, the caches written, None)."""
    k_cache, v_cache = kv
    with jax.named_scope("kv_update"):
        k_cache = paged_scatter(
            k_cache, layer, c_kv[:, :, None, :], g.page_tables, g.positions,
            g.valid,
        )
        v_cache = paged_scatter(
            v_cache, layer, k_pe[:, :, None, :], g.page_tables, g.positions,
            g.valid,
        )
    with jax.named_scope("paged"):
        # [B, K, c] and [B, K, r]
        c_hist = paged_gather(k_cache, layer, g.page_tables)[:, :, 0]
        pe_hist = paged_gather(v_cache, layer, g.page_tables)[:, :, 0]
        scores = (
            jnp.einsum("bthc,bkc->bhtk", q_lat, c_hist.astype(jnp.float32))
            + jnp.einsum(
                "bthr,bkr->bhtk", q_pe.astype(jnp.float32),
                pe_hist.astype(jnp.float32),
            )
        ) * cfg.softmax_scale
        kk = c_hist.shape[1]
        key_pos = jnp.arange(kk)[None, None, None, :]
        mask = key_pos <= g.positions[:, None, :, None]
        if keep is not None:
            mask = mask & keep[:, None]
        scores = jnp.where(mask, scores, _MASKED)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum(
            "bhtk,bkc->bthc", probs, c_hist.astype(jnp.float32)
        )
    return o_lat, (k_cache, v_cache), None


def _attend_kernels(q_lat, q_pe, c_kv, k_pe, cfg: MlaConfig, kv, layer, g,
                    work, mesh, chosen=None):
    """One group under the kernels' discipline (module text): the cache
    is read, never written here; the chunk's rows come back as `staged`.
    Returns (o_lat [B, T, H, c], float32 from the decode walk and the
    model dtype from a prefill chunk's kernel, the caches as they came,
    staged). `chosen` [B, T, MP * S] bool names each query's keys by
    position (models/dots3.py's indexer; None: every key up to its own)."""
    k_cache, v_cache = kv
    pe_rows = _pad_last(k_pe, cfg.kv_rope_dim)  # the rope key as cached
    if q_lat.shape[1] == 1:
        with jax.named_scope("paged"):
            qd = jnp.concatenate(
                [q_lat.astype(cfg.dtype), _pad_last(q_pe, cfg.kv_rope_dim)],
                axis=-1,
            )[:, 0]
            o_lat = _latent_decode(
                qd, c_kv[:, 0], pe_rows[:, 0], k_cache, v_cache, layer,
                g.page_tables, g.positions[:, 0], cfg, work, mesh,
                None if chosen is None else chosen[:, 0],
            )[:, None]
    else:
        with jax.named_scope("flash"):
            o_lat = _latent_prefill_attention(
                q_lat, q_pe, c_kv, pe_rows, k_cache, v_cache, layer,
                g.page_tables, g.positions, g.valid, cfg, g.first_chunk,
                mesh, chosen,
            )
    return o_lat, kv, (c_kv[:, :, None, :], pe_rows[:, :, None, :])


# ---------------------------------------------------------------------------
# MoE FFN (DeepSeek semantics, dropless sort-by-expert dispatch)
# ---------------------------------------------------------------------------


def _grouped_ffn(
    xs: jax.Array,  # [M, H] rows sorted by expert, model dtype
    expert_of_row: jax.Array,  # [M] int32, ascending
    group_sizes: jax.Array,  # [E] int32, sums to M
    lp: dict,
    cfg: MlaConfig,
    mesh=None,
    stack=None,
) -> jax.Array:
    """The routed experts' gated FFN over contiguous groups of rows: one
    grouped matmul a projection, operands in the model dtype, float32
    accumulation. Int8 expert weights go in as the exact integers they
    are and their per-output-channel scales multiply the product's rows.
    `stack` is (every expert layer's matrices [L, E, ., .] by name, this
    layer's index in them): a layer scan hands the matrices in whole, so
    that the kernel reads this layer's in place (ops/grouped_matmul.py)."""
    from dynamo_tpu.ops.grouped_matmul import grouped_matmul

    def grouped(x, name):
        if stack is not None and name in stack[0]:
            w, layer = stack[0][name], stack[1]
        else:  # int8: this layer's integers as the model dtype holds them
            w, layer = lp[name].astype(cfg.dtype), None
        out = grouped_matmul(
            x, w, group_sizes, layer=layer,
            use_kernel=None if mesh is None else False,
        )
        if name + "_scale" in lp:  # int8: scale [E, 1, out]
            out = out * lp[name + "_scale"][:, 0][expert_of_row]
        return out

    if getattr(cfg, "expert_mlp", "swiglu") == "relu2":
        # Nemotron-H: an ungated expert of two matrices, down(relu(up x)^2)
        up = jnp.maximum(grouped(xs, "we_up"), 0.0)
        return grouped((up * up).astype(cfg.dtype), "we_down")
    gate = jax.nn.silu(grouped(xs, "we_gate"))
    return grouped((gate * grouped(xs, "we_up")).astype(cfg.dtype), "we_down")


def _gate(xf: jax.Array, lp: dict, cfg: MlaConfig, precision=None):
    """(topw [N, k] f32, topi [N, k]): router logits and scores in
    float32, top-k by the configuration's method, weights scaled by
    `routed_scaling_factor`. `precision` is the logits matmul's: on a TPU
    a float32 product rounds its operands to bfloat16 unless told
    otherwise, which is a router in bfloat16 weights (models/nemotron_h.py
    asks for the highest: where a chip holds a share of the experts, a
    flipped sixth expert adds or removes a whole expert)."""
    nt = xf.shape[0]
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    logits = jnp.matmul(
        xf.astype(jnp.float32), lp["w_router"].astype(jnp.float32),
        precision=precision,
    )

    def _group_mask(choice, rank_fn):
        g = cfg.n_group
        group_scores = rank_fn(choice.reshape(nt, g, e // g))
        _, gidx = lax.top_k(group_scores, cfg.topk_group)  # [N, tg]
        gmask = jnp.sum(
            jax.nn.one_hot(gidx, g, dtype=jnp.float32), axis=1
        )  # [N, g]
        return jnp.repeat(gmask, e // g, axis=-1)  # [N, E]

    if cfg.topk_method in ("noaux_tc", "sigmoid"):
        # HF DeepseekV3TopkRouter: sigmoid scores; groups rank by the SUM
        # of their top-2 bias-corrected scores; selection uses corrected
        # scores, weights use the uncorrected ones. "sigmoid" is the rule
        # with NO correction bias (models/cohere2_moe.py): the k highest
        # scores themselves, ties to the lower index.
        scores = jax.nn.sigmoid(logits)
        choice = scores
        if cfg.topk_method == "noaux_tc":
            choice = scores + lp["router_bias"][None, :]
        if cfg.n_group > 1:  # one group: every expert stands (two top-k
            # less to compile and to run)
            choice = choice * _group_mask(
                choice,
                lambda gc: jnp.sum(
                    lax.top_k(gc, min(2, e // cfg.n_group))[0], axis=-1),
            )
        _, topi = lax.top_k(choice, k)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.norm_topk_prob:
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20)
    else:
        scores = jax.nn.softmax(logits, axis=-1)  # [N, E]
        if cfg.topk_method == "group_limited_greedy":
            # HF DeepseekV2MoEGate: groups rank by their max member score
            scores = scores * _group_mask(
                scores, lambda gc: jnp.max(gc, axis=-1)
            )
        topw, topi = lax.top_k(scores, k)
        if cfg.norm_topk_prob:
            topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    topw = topw * cfg.routed_scaling_factor
    return topw, topi


#: an expert layer's scopes from the top, for a caller that stands under
#: none (`_routed_experts`' `scope`)
MOE_SCOPE = "mlp/moe/"
#: a pass over a share's assignments holds this many times what even
#: routing sends the share (`share_rows`)
_SHARE_ROOM = 2
#: how a pass's rows are added into their tokens' rows: a product of [N, C]
#: zeros and ones with the rows on the MXU, float32 at the highest
#: precision, grows with N x C; a gather of every token's k places reads N
#: x k rows. The product where a pass holds at most this many rows a slot
#: of the top-k, the gather above it. scripts/expert_block_bench.py, PR 51,
#: ms a layer: 384 rows of 5,120 under top 8 (a 512-token piece at 8 of
#: 256 held) 0.041 against 0.149, 1,152 of 2,048 (16 of 128) 0.043 against
#: 0.085; the product quadruples with a second piece where the gather
#: doubles, so they cross at 175-270 rows a slot. A scatter-add took the
#: TPU 2.5 us A ROW (0.97 ms at 384).
_ONE_HOT_ROWS = 160


def share_rows(nt: int, k: int, count: int, width: int) -> int:
    """C, the rows one pass over a share's assignments holds: `_SHARE_ROOM`
    times what `nt` tokens' top `k` of a router `width` wide send `count`
    held experts under even routing, in whole row tiles of the grouped
    matmul, and never more than there are assignments (a share that is
    everything, a handful of rows: one pass of all of them, which is the
    whole-batch path). A bound on a pass, not a capacity: what a step sends
    the share beyond it takes another pass (`_routed_experts`)."""
    from dynamo_tpu.ops.grouped_matmul import _TILE_ROWS as tile

    mean = nt * k * count / width
    return min(nt * k, -(-math.ceil(_SHARE_ROOM * mean) // tile) * tile)


def _routed_experts(
    xf: jax.Array,  # [N, H]
    topw: jax.Array,  # [N, k] f32
    topi: jax.Array,  # [N, k] expert ids
    lp: dict,
    cfg: MlaConfig,
    mesh=None,
    stack=None,
    held=None,
    scope: str = "",
):
    """([N, H] f32, passes beyond the first): every assignment to an
    expert `lp` holds computed, whatever the routing. The assignments are
    sorted by expert (row j of the sorted batch is token order[j] // k
    under expert expert_of_row[j]), go through the grouped FFN, and come
    back in token order weighted by the gate.

    `held` = (first, count) says which experts `lp` holds where that is a
    share of them (an expert-parallel deployment's chip,
    models/nemotron_h.py). Only the share's own assignments move: they are
    ordered by COUNTING (a running count a group: exact and stable, for
    the few groups of a share; XLA's stable sort of 16,640 assignments
    takes the TPU's compiler 15 s a program, against 1.5 s for 4,352: the
    compile for the described v5e, PR 48; models/dots3.py), and the head of
    that order is gathered, multiplied and added into its tokens' rows
    `share_rows` at a time, in a loop of as many passes as the step's
    count needs: one where routing is anywhere near even, more where it is
    not, NONE dropped at any count; an assignment to an expert held
    elsewhere is never materialised. The second result counts the passes
    beyond the first (0 without `held`), which each read the held experts'
    matrices again. A token's held assignments are summed in float32 in
    the order of their experts, not of the gate's top-k.

    `scope` is the path the three parts are named under (`route`,
    `experts`) where the caller stands OUTSIDE it: an operation in a loop's
    body is named `<the loop's own path>/while/body/<its scopes>`, and a
    trace's readers take the first scope they know, so a share's caller
    binds the loop under no scope and passes "mlp/moe/"."""
    nt, h = xf.shape
    e, k = cfg.n_routed_experts, topi.shape[1]
    flat_e = topi.reshape(nt * k).astype(jnp.int32)
    if held is not None:
        return _held_experts(xf, topw, flat_e, lp, cfg, mesh, stack, held,
                             scope)
    with jax.named_scope(scope + "route"):
        order = jnp.argsort(flat_e, stable=True)
        # (an index past the groups is dropped: a scatter's default)
        group_sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
        expert_of_row = flat_e[order]
        xs = xf[order // k].astype(cfg.dtype)
    with jax.named_scope(scope + "experts"):
        ys = _grouped_ffn(
            xs, expert_of_row, group_sizes, lp, cfg, mesh, stack
        )
    with jax.named_scope(scope + "route"):
        back = jnp.zeros((nt * k,), jnp.int32).at[order].set(
            jnp.arange(nt * k, dtype=jnp.int32)
        )
        return jnp.sum(
            ys[back].reshape(nt, k, h) * topw[..., None], axis=1
        ), jnp.int32(0)


def _held_experts(xf, topw, flat_e, lp, cfg, mesh, stack, held, scope):
    """`_routed_experts` on a chip that holds the experts `held`."""
    nt, h = xf.shape
    k = topw.shape[1]
    first, e = held
    c = share_rows(nt, k, e, cfg.n_routed_experts)
    room = -(-nt * k // c) * c  # whole passes: a slice never runs off
    with jax.named_scope(scope + "route"):
        flat_e = flat_e - first
        flat_e = jnp.where((flat_e >= 0) & (flat_e < e), flat_e, e)
        one = (flat_e[:, None] == jnp.arange(e)[None]).astype(
            jnp.float32)  # [N * k, groups]: no column for "elsewhere"
        # the running count in blocks of 128 assignments: inside a
        # block one triangular product (exact: counts below 2**24),
        # across blocks a short cumulative sum
        pad = -(nt * k) % 128
        blocks = jnp.pad(one, ((0, pad), (0, 0))).reshape(-1, 128, e)
        before = jnp.tril(jnp.ones((128, 128), jnp.float32), -1)
        inside = jnp.einsum("ij,bjg->big", before, blocks,
                            precision=lax.Precision.HIGHEST)
        sums = jnp.sum(blocks, axis=1)  # [blocks, groups]
        rank = (inside + (jnp.cumsum(sums, axis=0) - sums)[:, None]
                ).reshape(-1, e)[:nt * k]
        sizes = jnp.sum(sums, axis=0)
        ends = jnp.cumsum(sizes)
        # an assignment's place: its group's start + its rank there; one
        # held elsewhere has none (a place past the end is dropped)
        place = jnp.where(
            flat_e < e, jnp.sum(one * (rank + (ends - sizes)[None]), axis=1),
            room).astype(jnp.int32)
        order = jnp.zeros((room,), jnp.int32).at[place].set(
            jnp.arange(nt * k, dtype=jnp.int32), mode="drop")
        sizes, ends = sizes.astype(jnp.int32), ends.astype(jnp.int32)
        total = ends[-1]
        gate = topw.reshape(nt * k)

    def one_pass(p, acc):
        lo = p * c
        with jax.named_scope(scope + "route"):
            mine = lax.dynamic_slice(order, (lo,), (c,))  # assignments
            token = mine // k
            # the groups as this slice of the order cuts them
            group_sizes = (jnp.clip(ends, lo, lo + c)
                           - jnp.clip(ends - sizes, lo, lo + c))
            xs = xf[token].astype(cfg.dtype)
        with jax.named_scope(scope + "experts"):
            ys = _grouped_ffn(
                xs, flat_e[mine], group_sizes, lp, cfg, mesh, stack)
        with jax.named_scope(scope + "route"):
            weighted = ys * gate[mine][:, None]
            if c <= _ONE_HOT_ROWS * k:
                # each token's rows summed by a product with 0 / 1 on the
                # MXU, float32 throughout (rows past the step's count hold
                # whatever was there: to no token)
                live = lo + jnp.arange(c, dtype=jnp.int32) < total
                sel = (token[None, :] == jnp.arange(nt)[:, None]) & live[None]
                return acc + jnp.matmul(
                    sel.astype(jnp.float32),
                    jnp.where(live[:, None], weighted, 0.0),
                    precision=lax.Precision.HIGHEST)
            # each token's k places, where they lie in this pass
            at = place.reshape(nt, k) - lo
            here = (at >= 0) & (at < c)
            return acc + jnp.sum(jnp.where(
                here[..., None], weighted[jnp.where(here, at, 0)], 0.0),
                axis=1)

    passes = (total + c - 1) // c
    out = lax.fori_loop(0, passes, one_pass, jnp.zeros((nt, h), jnp.float32))
    return out, jnp.maximum(passes - 1, 0)


def _dense_ffn(x: jax.Array, lp: dict, cfg: MlaConfig) -> jax.Array:
    """A dense layer's SwiGLU MLP (`w_gate`, `w_up`, `w_down`)."""
    gate = jax.nn.silu(_mm(x, lp, "w_gate", cfg.dtype).astype(jnp.float32))
    up = _mm(x, lp, "w_up", cfg.dtype).astype(jnp.float32)
    return _mm((gate * up).astype(cfg.dtype), lp, "w_down", cfg.dtype)


def _shared_expert(xf: jax.Array, lp: dict, cfg: MlaConfig) -> jax.Array:
    """The shared expert's SwiGLU (`ws_gate`, `ws_up`, `ws_down`) on [N, H]."""
    shared_gate = jax.nn.silu(
        _mm(xf, lp, "ws_gate", cfg.dtype).astype(jnp.float32)
    )
    return _mm(
        (shared_gate
         * _mm(xf, lp, "ws_up", cfg.dtype).astype(jnp.float32))
        .astype(cfg.dtype),
        lp, "ws_down", cfg.dtype,
    )


def _deepseek_moe_ffn(
    x: jax.Array, lp: dict, cfg: MlaConfig, mesh=None, stack=None
) -> jax.Array:
    """Scopes (under the caller's `mlp`): `moe/route` (gate, top-k, the
    sort by expert and the weighted un-sort), `moe/experts` (the grouped
    matmuls), `moe/shared`."""
    h = x.shape[-1]
    xf = x.reshape(-1, h)  # [B, T, H] of one group or the joined rows
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            topw, topi = _gate(xf, lp, cfg)
        routed, _ = _routed_experts(xf, topw, topi, lp, cfg, mesh, stack)
        with jax.named_scope("shared"):
            shared = _shared_expert(xf, lp, cfg)
        return (routed.astype(cfg.dtype) + shared).reshape(x.shape)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_groups(
    params: dict,
    cfg: MlaConfig,
    groups,  # llama.StepGroup, one or two
    kv: KVPages,
    mesh=None,
) -> tuple[list, KVPages]:
    """models/llama.py's `forward_groups` for this family: one pass over
    the layers in which the projections, the gate, the dropless dispatch,
    the grouped expert matmuls and the shared experts run on every group's
    rows together, attention per group (`mla_attention`). Returns ([hidden
    [B_g, T_g, H] post final norm per group], new kv)."""
    if any(g.mm_embeds is not None for g in groups):
        raise ValueError("multimodal prompts are not supported for MLA yet")
    # named scopes as models/llama.py's (embed, attn[/qkv, /kv_update,
    # /absorb, /paged or /flash, /out], mlp[/moe/route, /moe/experts,
    # /moe/shared], final_norm; lm_head in compute_logits): a device
    # trace carries them in each operation's metadata
    # (docs/observability.md)
    with jax.named_scope("embed"):
        h = join_rows(
            [params["embed"][g.tokens].astype(cfg.dtype) for g in groups]
        )
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(cfg, g.tokens, g.positions, kv, g.page_tables)
            for g in groups
        ]

    def layer_of(ffn):
        def layer(carry, xs):
            h, kc, vc = carry
            lp, li = xs
            with jax.named_scope("attn"):
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                attn, kc, vc, staged = mla_attention(
                    x, lp, cfg, (kc, vc), li, groups, works, mesh=mesh
                )
                h = h + attn
            with jax.named_scope("mlp"):
                x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
                h = h + ffn(x, lp, li)
            return (h, kc, vc), staged

        return layer

    nd = cfg.num_dense_layers
    # The expert matrices stay out of the scan's per-layer slices where
    # the grouped matmul can read a layer of the whole stack in place.
    experts = {
        n: w for n, w in (params.get("moe_layers") or {}).items()
        if n in _QUANT_EXPERTS and w.dtype == cfg.dtype
    }

    def moe_ffn(x, lp, li):
        return _deepseek_moe_ffn(
            x, lp, cfg, mesh, (experts, li - nd) if experts else None
        )

    carry = (h, kv.k, kv.v)
    staged = []
    for group, ffn, lo, hi in (
        ("dense_layers", lambda x, lp, li: _dense_ffn(x, lp, cfg), 0, nd),
        ("moe_layers", moe_ffn, nd, cfg.num_layers),
    ):
        if hi > lo:
            scanned = {
                n: w for n, w in params[group].items() if n not in experts
            } if group == "moe_layers" else params[group]
            carry, st = lax.scan(
                layer_of(ffn), carry,
                (scanned, jnp.arange(lo, hi, dtype=jnp.int32)),
            )
            staged.append(st)
    h, k_cache, v_cache = carry
    if cfg.kernels:
        # each group's rows of the whole step, every layer, in one write
        # (the cache is one shared row a token: under a tp mesh it
        # replicates, and the head-sharded DMA kernel gives way to the XLA
        # scatter)
        from dynamo_tpu.ops.kv_update import paged_write

        with jax.named_scope("attn"), jax.named_scope("kv_update"):
            for i, g in enumerate(groups):
                k_cache, v_cache = paged_write(
                    k_cache, v_cache,
                    jnp.concatenate([st[i][0] for st in staged]),
                    jnp.concatenate([st[i][1] for st in staged]),
                    g.page_tables, g.positions, g.valid,
                    use_kernel=None if mesh is None else False,
                )
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
    return split_rows(h, groups), KVPages(k=k_cache, v=v_cache)


def forward_hidden(
    params: dict,
    cfg: MlaConfig,
    tokens: jax.Array,
    positions: jax.Array,
    valid: jax.Array,
    kv: KVPages,
    page_tables: jax.Array,
    mm_embeds: Optional[jax.Array] = None,
    mm_mask: Optional[jax.Array] = None,
    first_chunk: bool = False,
    mesh=None,
) -> tuple[jax.Array, KVPages]:
    (h,), kv = forward_groups(
        params, cfg,
        [StepGroup(
            tokens, positions, valid, page_tables, first_chunk,
            mm_embeds=mm_embeds, mm_mask=mm_mask,
        )],
        kv, mesh=mesh,
    )
    return h, kv


def compute_logits(params: dict, cfg: MlaConfig, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        lm_head = params.get("lm_head")
        if lm_head is None:
            lm_head = params["embed"].T
        return (hidden @ lm_head).astype(jnp.float32)


def forward(params, cfg: MlaConfig, tokens, positions, valid, kv, page_tables):
    h, kv = forward_hidden(
        params, cfg, tokens, positions, valid, kv, page_tables
    )
    return compute_logits(params, cfg, h), kv


def mla_logical_axes(cfg: MlaConfig, quantized: bool = False) -> dict:
    """Logical axis names (parallel/logical.py): attention heads carry
    "heads" (the packed head output axes of wq/wkv_b, wo's input),
    routed experts carry "expert" with DELIBERATELY unnamed
    intermediate dims — DeepSeek's many small experts shard on ep
    alone, tp-splitting a 1408-wide expert mlp would fragment the
    matmuls below MXU tile size. The latent projections and cache
    replicate (one shared latent — MQA-shaped). Quantized scale leaves
    ride their weight's OUTPUT dim (contraction-sharded wo/w_down keep
    replicated scales, which commute with the partial-sum)."""
    from dynamo_tpu.parallel.logical import L

    def attn_axes(moe: bool) -> dict:
        axes = {
            "attn_norm": L(),
            "wkv_a": L(),
            "kv_a_norm": L(),
            "wkv_b": L("layers", None, "heads"),
            "wo": L("layers", "heads", None),
            "mlp_norm": L(),
        }
        if cfg.q_lora_rank:
            axes.update(
                wq_a=L(), q_a_norm=L(), wq_b=L("layers", None, "heads")
            )
        else:
            axes["wq"] = L("layers", None, "heads")
        if not moe:
            axes.update(
                w_gate=L("layers", None, "mlp"),
                w_up=L("layers", None, "mlp"),
                w_down=L("layers", "mlp", None),
            )
        else:
            axes.update(
                w_router=L(),
                **(
                    {"router_bias": L()}
                    if cfg.topk_method == "noaux_tc"
                    else {}
                ),
                we_gate=L("layers", "expert", None, None),
                we_up=L("layers", "expert", None, None),
                we_down=L("layers", "expert", None, None),
                ws_gate=L("layers", None, "mlp"),
                ws_up=L("layers", None, "mlp"),
                ws_down=L("layers", "mlp", None),
            )
        if quantized:
            for name in list(axes):
                if name not in _QUANT_2D + _QUANT_EXPERTS:
                    continue
                waxes = tuple(axes[name])
                if name in _QUANT_EXPERTS:
                    # [L, E, 1, out]: scale rides the expert shard
                    axes[name + "_scale"] = L(
                        "layers", "expert", None, None
                    )
                elif waxes and waxes[-1] is not None:  # output-dim named
                    axes[name + "_scale"] = L("layers", None, waxes[-1])
                else:  # replicated or contraction-sharded: scale replicates
                    axes[name + "_scale"] = L()
        return axes

    axes = {
        "embed": L(),
        "dense_layers": attn_axes(moe=False) if cfg.num_dense_layers else {},
        "moe_layers": attn_axes(moe=True) if cfg.num_moe_layers else {},
        "final_norm": L(),
    }
    if not cfg.tie_word_embeddings:
        axes["lm_head"] = L(None, "vocab")
    return axes


def mla_param_specs(cfg: MlaConfig, quantized: bool = False, rules=None):
    """PartitionSpecs for MLA params: `mla_logical_axes` resolved
    through the logical-axis rule table (default table when `rules` is
    None)."""
    from dynamo_tpu.parallel.logical import resolve

    return resolve(mla_logical_axes(cfg, quantized=quantized), rules)


# ---------------------------------------------------------------------------
# Weight-only int8
# ---------------------------------------------------------------------------


def quantize_params_int8(params: dict) -> dict:
    """Per-output-channel symmetric int8 for every dense matmul weight
    (same scheme as llama.quantize_params_int8; w_router / norms / embed
    stay in the base dtype). Makes deepseek-v2-lite's 15.7B weights
    ~16GB — servable on one v5e chip."""

    quant_one = quantize_channelwise_int8

    out = dict(params)
    for gname in ("dense_layers", "moe_layers"):
        group = dict(params.get(gname) or {})
        if not group:
            continue
        if any(
            group.get(n) is not None and group[n].dtype == jnp.int8
            for n in _QUANT_2D + _QUANT_EXPERTS
        ):
            raise ValueError("params are already int8-quantized")
        for name in _QUANT_2D:
            if name in group:
                q, s = jax.lax.map(quant_one, group[name])
                group[name] = q
                group[name + "_scale"] = s
        for name in _QUANT_EXPERTS:
            if name in group:
                q, s = jax.lax.map(
                    lambda we: jax.lax.map(quant_one, we), group[name]
                )
                group[name] = q
                group[name + "_scale"] = s
        out[gname] = group
    return out


def init_params_int8(key: jax.Array, cfg: MlaConfig) -> dict:
    """Random-init straight into the int8 layout, one (layer, expert)
    tensor at a time — full-dtype init of deepseek-v2-lite (~31GB bf16)
    would blow a single chip's HBM before quantization could run."""
    counter = iter(range(1 << 30))

    def qdense(shape):
        k = jax.random.fold_in(key, next(counter))
        w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[0])
        return quantize_channelwise_int8(w)

    def dense(shape):
        k = jax.random.fold_in(key, next(counter))
        return (
            jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[0])
        ).astype(cfg.dtype)

    def norm(shape):
        return jnp.ones(shape, cfg.dtype)

    h = cfg.hidden_size

    def group(n_layers: int, moe: bool) -> dict:
        if n_layers == 0:
            return {}
        lp: dict = {}
        for name, shape in _attn_layer_shapes(cfg).items():
            if "norm" in name:
                lp[name] = jnp.stack([norm(shape)] * n_layers)
            elif name in _QUANT_2D:
                qs = [qdense(shape) for _ in range(n_layers)]
                lp[name] = jnp.stack([q for q, _ in qs])
                lp[name + "_scale"] = jnp.stack([s for _, s in qs])
            else:
                lp[name] = jnp.stack([dense(shape) for _ in range(n_layers)])
        if not moe:
            i = cfg.intermediate_size
            for nm, shape in (
                ("w_gate", (h, i)), ("w_up", (h, i)), ("w_down", (i, h)),
            ):
                qs = [qdense(shape) for _ in range(n_layers)]
                lp[nm] = jnp.stack([q for q, _ in qs])
                lp[nm + "_scale"] = jnp.stack([s for _, s in qs])
        else:
            e, mi = cfg.n_routed_experts, cfg.moe_intermediate_size
            si = mi * cfg.n_shared_experts
            lp["w_router"] = jnp.stack(
                [dense((h, e)) for _ in range(n_layers)]
            )
            if cfg.topk_method == "noaux_tc":
                lp["router_bias"] = jnp.zeros((n_layers, e), jnp.float32)
            for nm, shape in (
                ("we_gate", (e, h, mi)), ("we_up", (e, h, mi)),
                ("we_down", (e, mi, h)),
            ):
                # one compiled map over all (layer, expert) tensors —
                # eager per-expert dispatch would mean thousands of
                # round-trips and list-then-stack copies at v2-lite scale
                base = next(counter)

                def one(idx, _shape=shape[1:], _base=base):
                    k = jax.random.fold_in(key, _base + idx)
                    w = jax.random.normal(
                        k, _shape, jnp.float32
                    ) / math.sqrt(_shape[0])
                    return quantize_channelwise_int8(w)

                q, s = jax.lax.map(
                    one, jnp.arange(n_layers * e, dtype=jnp.int32)
                )
                for _ in range(n_layers * e - 1):
                    next(counter)  # keep the fold_in stream unique
                lp[nm] = q.reshape(n_layers, e, *shape[1:])
                lp[nm + "_scale"] = s.reshape(n_layers, e, 1, shape[2])
            for nm, shape in (
                ("ws_gate", (h, si)), ("ws_up", (h, si)),
                ("ws_down", (si, h)),
            ):
                qs = [qdense(shape) for _ in range(n_layers)]
                lp[nm] = jnp.stack([q for q, _ in qs])
                lp[nm + "_scale"] = jnp.stack([s for _, s in qs])
        return lp

    params = {
        "embed": dense((cfg.vocab_size, h)),
        "dense_layers": group(cfg.num_dense_layers, moe=False),
        "moe_layers": group(cfg.num_moe_layers, moe=True),
        "final_norm": norm((h,)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense((h, cfg.vocab_size))
    return params
