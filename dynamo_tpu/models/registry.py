"""Model registry: name -> ModelAdapter the engine can drive.

The engine is model-family-agnostic (same role as the reference being
engine-agnostic at a higher level): an adapter exposes init/forward/kv-init
over the paged cache contract. New families (Qwen2, Mixtral/MoE) register
here.
"""

from __future__ import annotations

import json
import logging
import os
from dataclasses import dataclass, replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama as llama_mod
from dynamo_tpu.models import qwen2vl as qwen2vl_mod
from dynamo_tpu.models.llama import KVPages, LlamaConfig, StepGroup

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class ModelAdapter:
    name: str
    config: Any
    vocab_size: int
    init_params: Callable[[jax.Array], Any]
    forward: Callable[..., tuple[jax.Array, KVPages]]  # (params, tokens, positions, valid, kv, pt) -> (logits, kv)
    forward_hidden: Callable[..., tuple[jax.Array, KVPages]]  # same in, (hidden, kv) out
    #: the fused mixed step's model pass: (params, prompt, decode, kv,
    #: first_chunk=False) -> (hidden_p, hidden_d, kv), `prompt` and
    #: `decode` each (tokens, positions, valid, page_tables). llama and
    #: mla read every weight once for both (`_one_pass_mixed`); moe runs
    #: its two `forward_hidden` passes (`_two_pass_mixed`)
    forward_hidden_mixed: Callable[..., tuple]
    compute_logits: Callable[[Any, jax.Array], jax.Array]  # (params, hidden) -> logits
    #: (num_pages, page_size, kv_quantize=None) -> KVPages; families
    #: without quantized pages raise on kv_quantize != None
    init_kv: Callable[..., KVPages]
    param_specs: Callable[[], Any]
    kv_spec: Callable[[], Any]
    #: (quantized=False) -> the same tree as param_specs but with
    #: logical AxisNames leaves (parallel/logical.py) — the model's
    #: single layout declaration; param_specs is this resolved through
    #: the rule table. /v1/debug/mesh groups params by these names.
    logical_axes: Optional[Callable[[], Any]] = None
    load_params: Optional[Callable[[str], Any]] = None  # from a checkpoint dir
    #: where weights live when the model name itself identifies them
    #: (an HF checkpoint dir or a .gguf file); engines load from here when
    #: no explicit checkpoint_path is given
    default_checkpoint: Optional[str] = None
    #: weight-only quantization transform for this family's param layout
    #: (None = family doesn't support it); the engine calls it for
    #: EngineConfig.quantize="int8"
    quantize_params: Optional[Callable[[Any], Any]] = None
    #: random-init straight into the quantized layout, one layer at a
    #: time — init_params + quantize_params peaks at full-model dtype
    #: size, which for 8B+ configs exceeds a single chip's HBM
    init_params_quantized: Optional[Callable[[jax.Array], Any]] = None
    #: layers that keep a state of FIXED size a sequence in the slot pool (a
    #: recurrent state: Mamba-2, lightning attention; or the last tokens of
    #: a window layer as a ring, models/dots3.py): 0 for every
    #: family whose only per-sequence state is pages. Where it is not 0 the
    #: engine hands `init_kv` a `state_slots` count, passes each row's
    #: (read, write) slot entries beside its page table (`pt` is then the
    #: pair (page tables, state rows [B, 2])), and refuses what would move
    #: or share half of a sequence's state (docs/models.md)
    state_layers: int = 0
    #: bytes of one sequence's state, one generation (state_layers > 0)
    state_slot_bytes: int = 0
    #: True where the slot holds KV WRITTEN BY POSITION (a window layer's
    #: ring): what a rolled-back dispatch wrote is written again before it
    #: is read, so the pool keeps ONE generation, a row reads and writes
    #: the same entry and a commit flips nothing. False for a recurrent
    #: state, which is not benign in place (docs/engine.md)
    state_in_place: bool = False
    #: a model whose decode walk reads a chosen part of a row's pages:
    #: cache -> the device's running count, int32 [4], of (pages the lists
    #: given to the walks named, pages those rows held, pages its sparse
    #: prompt chunks' tiles read, pages their queries named), or [6] with
    #: the held experts its rows chose and the passes over a share's
    #: assignments beyond a layer's first; the engine reads it beside each
    #: dispatch's ids (`EngineMetrics.walk_pages_named` / `walk_pages_live`
    #: / `chunk_pages_read` / `chunk_pages_named` / `moe_experts_touched`
    #: / `moe_extra_passes`)
    walk_pages: Optional[Callable] = None
    #: False for a model whose step programs are dear to load: the engine
    #: then keeps ONE prefill-carrying program a shape where it would keep
    #: twins, a history-free one for chunks that all start a prompt
    #: (`first_chunk`) and one that samples nothing for chunks none of
    #: which ends one (every chunk then samples, the token unread)
    step_twins: bool = True
    #: what this family cannot serve, (feature, why) pairs the engine
    #: refuses with the sentence: "kv_tiers" (KVBM offload),
    #: "speculation", "page_transfer" (disaggregated prefill, handover). A
    #: family whose page holds more than K and V rows names all three:
    #: what moves or rewinds K and V alone would leave the rest behind
    refuses: tuple = ()


def _kv_pages_spec(kv_quantize=None, shard_heads: bool = True):
    """Partition specs matching init_kv_pages' pytree: head-sharded KV
    pools, scale planes (when quantized) sharded on the same Hkv axis —
    both resolved through the logical-axis rule table."""
    from dynamo_tpu.parallel.logical import L, resolve
    from dynamo_tpu.parallel.shardings import kv_cache_spec

    scale = (
        resolve(L(
            "layers", "kv_pages",
            "kv_heads" if shard_heads else None, "kv_seq",
        ))
        if kv_quantize
        else None
    )
    return KVPages(
        k=kv_cache_spec(shard_heads),
        v=kv_cache_spec(shard_heads),
        k_scale=scale,
        v_scale=scale,
    )


_LLAMA_PRESETS: dict[str, Callable[[], LlamaConfig]] = {
    "tiny": LlamaConfig.tiny,
    "llama3-1b": LlamaConfig.llama3_1b,
    # speculation draft for the llama3 family (same 128256 vocab);
    # serveable standalone but meant for EngineConfig.spec_draft_model
    "llama3-draft": LlamaConfig.llama3_draft,
    "llama3-8b": LlamaConfig.llama3_8b,
    "llama3-70b": LlamaConfig.llama3_70b,
    # DeepSeek-R1-Distill-Llama-8B is architecturally Llama-3-8B.
    "deepseek-r1-distill-llama-8b": LlamaConfig.llama3_8b,
    # Qwen2 family = Llama + qkv bias (models/llama.py attention_bias).
    "qwen2-7b": LlamaConfig.qwen2_7b,
    "qwen2-0.5b": LlamaConfig.qwen2_05b,
    # Gemma family = GeGLU + (1+w) RMSNorm + scaled embeddings + tied head.
    "gemma-2b": LlamaConfig.gemma_2b,
    "gemma-7b": LlamaConfig.gemma_7b,
    # Gemma2 adds sliding/global alternation, logit softcaps, post-norms.
    "gemma2-2b": LlamaConfig.gemma2_2b,
    # Gemma3: 5:1 local/global pattern, dual rope theta, qk-norm,
    # no softcaps (text model; the 4B+ vision tower is not served).
    "gemma3-1b": LlamaConfig.gemma3_1b,
    "gemma3-4b-text": LlamaConfig.gemma3_4b_text,
    # Mistral = Llama + sliding-window attention on every layer.
    "mistral-7b": LlamaConfig.mistral_7b,
    # Qwen3 = Llama + per-head q/k RMSNorm (no attention bias).
    "qwen3-8b": LlamaConfig.qwen3_8b,
    # Phi-3/Phi-4 = Llama with fused qkv/gate_up in the checkpoint.
    "phi3-mini": LlamaConfig.phi3_mini,
    "phi4": LlamaConfig.phi4,
}


# Qwen2-VL language models (Qwen2 + m-RoPE; the vision tower rides the
# multimodal encode worker, models/qwen2vl.vision_forward).
_LLAMA_PRESETS.update(
    {
        "qwen2-vl-tiny": qwen2vl_mod.text_tiny,
        "qwen2-vl-2b": qwen2vl_mod.text_2b,
        "qwen2-vl-7b": qwen2vl_mod.text_7b,
        "qwen2.5-vl-3b": qwen2vl_mod.text_25_3b,
        "qwen2.5-vl-7b": qwen2vl_mod.text_25_7b,
    }
)


def _two_pass_mixed(forward_hidden):
    """`ModelAdapter.forward_hidden_mixed` of a family with no two-group
    layer body: two `forward_hidden` passes back to back, prompt first.
    models/moe.py keeps it for a reason of its own: its expert dispatch
    has a capacity that follows the row count, so which rows overflow an
    expert would change if the groups shared a pass."""

    def forward_hidden_mixed(params, prompt, decode, kv, first_chunk=False):
        h_p, kv = forward_hidden(
            params, *prompt[:3], kv, prompt[3], first_chunk=first_chunk
        )
        h_d, kv = forward_hidden(params, *decode[:3], kv, decode[3])
        return h_p, h_d, kv

    return forward_hidden_mixed


def _one_pass_mixed(forward_groups, cfg, mesh):
    """`ModelAdapter.forward_hidden_mixed` of a family whose layer body
    takes groups of rows (`forward_groups` of models/llama.py, models/
    mla.py): the prompt chunk and the decode rows through ONE layer scan,
    prompt first."""

    def forward_hidden_mixed(params, prompt, decode, kv, first_chunk=False):
        (h_p, h_d), kv = forward_groups(
            params, cfg,
            [StepGroup(*prompt, first_chunk=first_chunk), StepGroup(*decode)],
            kv, mesh=mesh,
        )
        return h_p, h_d, kv

    return forward_hidden_mixed


def _llama_adapter(
    name: str, cfg: LlamaConfig, mesh=None
) -> ModelAdapter:
    from dynamo_tpu.parallel.shardings import llama_param_specs

    def forward(params, tokens, positions, valid, kv, page_tables):
        return llama_mod.forward(params, cfg, tokens, positions, valid, kv, page_tables)

    def forward_hidden(
        params, tokens, positions, valid, kv, page_tables, **mm
    ):
        return llama_mod.forward_hidden(
            params, cfg, tokens, positions, valid, kv, page_tables,
            mesh=mesh, **mm
        )

    return ModelAdapter(
        name=name,
        config=cfg,
        vocab_size=cfg.vocab_size,
        init_params=lambda key: llama_mod.init_params(key, cfg),
        forward=forward,
        forward_hidden=forward_hidden,
        compute_logits=lambda params, h: llama_mod.compute_logits(params, cfg, h),
        init_kv=lambda num_pages, page_size, kv_quantize=None: (
            llama_mod.init_kv_pages(
                cfg, num_pages, page_size, kv_quantize=kv_quantize
            )
        ),
        param_specs=lambda quantized=False: llama_param_specs(
            cfg, quantized=quantized
        ),
        kv_spec=lambda kv_quantize=None: _kv_pages_spec(kv_quantize),
        logical_axes=lambda quantized=False: llama_mod.llama_logical_axes(
            cfg, quantized=quantized
        ),
        load_params=lambda path: _load_llama_checkpoint(path, cfg),
        quantize_params=llama_mod.quantize_params_int8,
        init_params_quantized=lambda key: llama_mod.init_params_int8(
            key, cfg
        ),
        forward_hidden_mixed=_one_pass_mixed(
            llama_mod.forward_groups, cfg, mesh
        ),
    )


def _load_llama_checkpoint(path: str, cfg: LlamaConfig):
    """Load HF-format weights (safetensors/bin) from a local dir."""
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        path, torch_dtype=torch.float32, low_cpu_mem_usage=True
    )
    return llama_mod.params_from_torch_state_dict(model.state_dict(), cfg)


def _mla_init_kv(cfg, num_pages: int, page_size: int, kv_quantize):
    from dynamo_tpu.models import mla as mla_mod

    if kv_quantize:
        # The shared-latent cache IS the attention input (no per-head
        # rows to scale); refuse rather than serve silently degraded.
        raise ValueError(
            "kv_quantize is not supported for MLA (shared-latent cache) "
            "models — run with kv_quantize=None"
        )
    return mla_mod.init_kv_pages(cfg, num_pages, page_size)


def _mla_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    from dynamo_tpu.models import mla as mla_mod

    def fwd(params, tokens, positions, valid, kv, pt):
        return mla_mod.forward(params, cfg, tokens, positions, valid, kv, pt)

    def fwd_hidden(params, tokens, positions, valid, kv, pt, **mm):
        return mla_mod.forward_hidden(
            params, cfg, tokens, positions, valid, kv, pt, mesh=mesh, **mm
        )

    def load(path):
        import torch
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            path, torch_dtype=torch.float32, low_cpu_mem_usage=True,
            trust_remote_code=False,
        )
        return mla_mod.params_from_torch_state_dict(model.state_dict(), cfg)

    return ModelAdapter(
        name=name,
        config=cfg,
        vocab_size=cfg.vocab_size,
        init_params=lambda key: mla_mod.init_params(key, cfg),
        forward=fwd,
        forward_hidden=fwd_hidden,
        compute_logits=lambda params, h: mla_mod.compute_logits(
            params, cfg, h
        ),
        init_kv=lambda num_pages, page_size, kv_quantize=None: (
            _mla_init_kv(cfg, num_pages, page_size, kv_quantize)
        ),
        param_specs=lambda quantized=False: mla_mod.mla_param_specs(
            cfg, quantized=quantized
        ),
        # one shared latent per token: the cache replicates over tp (MQA
        # shape) — reuse the generic spec with no head axis to shard
        kv_spec=lambda kv_quantize=None: _kv_pages_spec(
            kv_quantize, shard_heads=False
        ),
        logical_axes=lambda quantized=False: mla_mod.mla_logical_axes(
            cfg, quantized=quantized
        ),
        load_params=load,
        quantize_params=mla_mod.quantize_params_int8,
        init_params_quantized=lambda key: mla_mod.init_params_int8(
            key, cfg
        ),
        forward_hidden_mixed=_one_pass_mixed(
            mla_mod.forward_groups, cfg, mesh
        ),
    )


def _moe_adapter(name: str, moe_cfg, mesh=None) -> ModelAdapter:
    from dynamo_tpu.models import moe as moe_mod

    cfg = moe_cfg

    def fwd(params, tokens, positions, valid, kv, pt):
        return moe_mod.forward(params, cfg, tokens, positions, valid, kv, pt)

    def fwd_hidden(params, tokens, positions, valid, kv, pt, **mm):
        return moe_mod.forward_hidden(
            params, cfg, tokens, positions, valid, kv, pt, mesh=mesh, **mm
        )

    def load(path):
        import torch
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            path, torch_dtype=torch.float32, low_cpu_mem_usage=True
        )
        return moe_mod.params_from_torch_state_dict(model.state_dict(), cfg)

    return ModelAdapter(
        name=name,
        config=cfg,
        vocab_size=cfg.base.vocab_size,
        init_params=lambda key: moe_mod.init_params(key, cfg),
        forward=fwd,
        forward_hidden=fwd_hidden,
        forward_hidden_mixed=_two_pass_mixed(fwd_hidden),
        compute_logits=lambda params, h: llama_mod.compute_logits(
            params, cfg.base, h
        ),
        init_kv=lambda num_pages, page_size, kv_quantize=None: (
            llama_mod.init_kv_pages(
                cfg.base, num_pages, page_size, kv_quantize=kv_quantize
            )
        ),
        param_specs=lambda quantized=False: moe_mod.moe_param_specs(
            cfg, quantized=quantized
        ),
        kv_spec=lambda kv_quantize=None: _kv_pages_spec(kv_quantize),
        logical_axes=lambda quantized=False: moe_mod.moe_logical_axes(
            cfg, quantized=quantized
        ),
        load_params=load,
        quantize_params=moe_mod.quantize_params_int8,
    )


def _hybrid_adapter(name: str, cfg, mod, family: str, axes,
                    mesh=None) -> ModelAdapter:
    """A family with Mamba-2 layers (`mod`: models/nemotron_h.py or
    models/falcon_h1.py): pages for its attention and a state slot a
    sequence for its state-space layers, in one `HybridCache`. `pt` of
    every step function is the pair (page tables, state rows)."""
    from dynamo_tpu.models import nemotron_h as nh

    if mesh is not None:
        raise ValueError(
            f"{name}: {family} runs on one chip (its layers over a mesh "
            "are not implemented): use tp=dp=ep=sp=1"
        )

    def fwd_hidden(params, tokens, positions, valid, kv, pt, **kw):
        if kw.pop("mm_embeds", None) is not None:
            raise ValueError("multimodal prompts are not supported for "
                             f"{family}")
        kw.pop("mm_mask", None)
        return mod.forward_hidden(
            params, cfg, tokens, positions, valid, kv, *pt, **kw
        )

    def fwd(params, tokens, positions, valid, kv, pt):
        h, kv = fwd_hidden(params, tokens, positions, valid, kv, pt)
        return mod.compute_logits(params, cfg, h), kv

    def fwd_mixed(params, prompt, decode, kv, first_chunk=False):
        (h_p, h_d), kv = mod.forward_groups(
            params, cfg,
            [
                StepGroup(*prompt[:3], prompt[3][0], first_chunk,
                          state_rows=prompt[3][1]),
                StepGroup(*decode[:3], decode[3][0],
                          state_rows=decode[3][1]),
            ],
            kv,
        )
        return h_p, h_d, kv

    def init_kv(num_pages, page_size, kv_quantize=None, state_slots=0):
        if kv_quantize:
            raise ValueError(
                "kv_quantize is not supported for a model with state-space "
                f"layers ({family}): a sequence is its pages AND a float32 "
                "recurrent state that stays float32, and narrowing the "
                "pages alone has no tested path beside the state pool; run "
                "with kv_quantize=None"
            )
        return getattr(mod, "init_cache", nh.init_cache)(
            cfg, num_pages, page_size, state_slots)

    def no_mesh_specs(*_a, **_k):
        from dynamo_tpu.parallel.logical import resolve

        return resolve(axes(cfg))

    return ModelAdapter(
        name=name,
        config=cfg,
        vocab_size=cfg.vocab_size,
        init_params=lambda key: mod.init_params(key, cfg),
        forward=fwd,
        forward_hidden=fwd_hidden,
        forward_hidden_mixed=fwd_mixed,
        compute_logits=lambda params, h: mod.compute_logits(params, cfg, h),
        init_kv=init_kv,
        param_specs=no_mesh_specs,
        kv_spec=lambda kv_quantize=None: None,
        logical_axes=lambda quantized=False: axes(cfg),
        state_layers=cfg.state_layers,
        state_slot_bytes=getattr(
            mod, "state_bytes_per_slot", nh.state_bytes_per_slot)(cfg),
        walk_pages=getattr(mod, "walk_count", None),
        step_twins=getattr(mod, "STEP_TWINS", True),
    )


def _nemotron_h_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    from dynamo_tpu.models import nemotron_h as nh

    return _hybrid_adapter(name, cfg, nh, "Nemotron-H",
                           nh.nemotron_h_logical_axes, mesh)


def _falcon_h1_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    from dynamo_tpu.models import falcon_h1 as fh

    return _hybrid_adapter(name, cfg, fh, "Falcon-H1",
                           fh.falcon_h1_logical_axes, mesh)


def _minicpm_sala_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    from dynamo_tpu.models import minicpm_sala as sala

    return _hybrid_adapter(name, cfg, sala, "MiniCPM-SALA",
                           sala.minicpm_sala_logical_axes, mesh)


def _keye_vl_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    """Keye-VL's language model (models/keye_vl.py): pages that hold K, V
    and the indexer's keys in one `KeyeCache`, no state slot: a prefix hit
    shares all three with the page and is accepted."""
    from dynamo_tpu.models import keye_vl as kv_mod

    if mesh is not None:
        raise ValueError(
            f"{name}: Keye-VL runs on one chip (its layers over a mesh are "
            "not implemented): use tp=dp=ep=sp=1"
        )

    def fwd_hidden(params, tokens, positions, valid, kv, pt, **kw):
        if kw.pop("mm_embeds", None) is not None:
            raise ValueError(
                "multimodal prompts are not supported for Keye-VL: the "
                "vision tower is not built (its sizes are not published in "
                "the language model's config)")
        kw.pop("mm_mask", None)
        return kv_mod.forward_hidden(
            params, cfg, tokens, positions, valid, kv, pt, **kw)

    def fwd(params, tokens, positions, valid, kv, pt):
        h, kv = fwd_hidden(params, tokens, positions, valid, kv, pt)
        return kv_mod.compute_logits(params, cfg, h), kv

    def init_kv(num_pages, page_size, kv_quantize=None):
        if kv_quantize:
            raise ValueError(
                "kv_quantize is not supported for Keye-VL: the indexer "
                "scores every cached token against index keys kept in "
                "bfloat16 beside the pages, and narrowing K and V alone has "
                "no tested path beside them; run with kv_quantize=None"
            )
        return kv_mod.init_cache(cfg, num_pages, page_size)

    def no_mesh_specs(*_a, **_k):
        from dynamo_tpu.parallel.logical import resolve

        return resolve(kv_mod.keye_vl_logical_axes(cfg))

    why = ("a page of this family holds the indexer's keys beside its K "
           "and V rows, and this would move or rewind K and V alone")
    return ModelAdapter(
        name=name,
        config=cfg,
        vocab_size=cfg.vocab_size,
        init_params=lambda key: kv_mod.init_params(key, cfg),
        forward=fwd,
        forward_hidden=fwd_hidden,
        forward_hidden_mixed=_one_pass_mixed(
            kv_mod.forward_groups, cfg, mesh),
        compute_logits=lambda params, h: kv_mod.compute_logits(
            params, cfg, h),
        init_kv=init_kv,
        param_specs=no_mesh_specs,
        kv_spec=lambda kv_quantize=None: None,
        logical_axes=lambda quantized=False: kv_mod.keye_vl_logical_axes(
            cfg),
        walk_pages=kv_mod.walk_count,
        step_twins=kv_mod.STEP_TWINS,
        refuses=tuple((what, why) for what in (
            "kv_tiers", "speculation", "page_transfer")),
    )


def _dots3_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    """dots3-note-prev's language model (models/dots3.py) through
    `_hybrid_adapter`: pages for its full layers (latent, rope key and the
    indexer's keys) and a slot a sequence for its window layers' rings, ONE
    generation (`state_in_place`). What the hybrid adapter cannot say is
    replaced here: the refusals' sentences."""
    from dynamo_tpu.models import dots3

    base = _hybrid_adapter(
        name, cfg, dots3, "dots3-note-prev", dots3.dots3_logical_axes, mesh)

    def init_kv(num_pages, page_size, kv_quantize=None, state_slots=0):
        if kv_quantize:
            raise ValueError(
                "kv_quantize is not supported for dots3-note-prev: its "
                "cache IS the attention input (a shared latent a token, no "
                "per-head rows to scale), its indexer scores index keys "
                "kept in bfloat16 beside the pages and its window layers "
                "read a ring in the slot pool; run with kv_quantize=None")
        return dots3.init_cache(cfg, num_pages, page_size, state_slots)

    why = ("a sequence of this family is its pages (latent, rope key and "
           "the indexer's keys), and the rings of its window layers in the "
           "slot pool, and this would move or rewind the latent and the "
           "rope key alone")
    return replace(
        base, init_kv=init_kv, state_in_place=dots3.STATE_IN_PLACE,
        refuses=tuple((what, why) for what in (
            "kv_tiers", "speculation", "page_transfer")))


def _gqa_ring_adapter(name: str, cfg, mod, family: str, axes, no_quantize: str,
                      mesh=None) -> ModelAdapter:
    """A family whose full layers keep K and V pages and whose window
    layers keep a ring of K and V a sequence in the slot pool, ONE
    generation (`state_in_place`), through `_hybrid_adapter`. What the
    hybrid adapter cannot say is replaced here: why `kv_quantize` is
    refused (`no_quantize`) and the refusals' sentence."""
    base = _hybrid_adapter(name, cfg, mod, family, axes, mesh)

    def init_kv(num_pages, page_size, kv_quantize=None, state_slots=0):
        if kv_quantize:
            raise ValueError(
                f"kv_quantize is not supported for {family}: {no_quantize}; "
                "run with kv_quantize=None")
        return mod.init_cache(cfg, num_pages, page_size, state_slots)

    why = ("a sequence of this family is its pages (the full layers' K and "
           "V) and the rings of its window layers in the slot pool, and "
           "this would move or rewind the pages alone")
    return replace(
        base, init_kv=init_kv, state_in_place=mod.STATE_IN_PLACE,
        refuses=tuple((what, why) for what in (
            "kv_tiers", "speculation", "page_transfer")))


def _cohere2_moe_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    """Command A+'s language model (models/cohere2_moe.py)."""
    from dynamo_tpu.models import cohere2_moe as c2

    return _gqa_ring_adapter(
        name, cfg, c2, "Command A+", c2.cohere2_moe_logical_axes,
        "three layers of four keep their K and V in a ring in the slot "
        "pool, in the model dtype, and narrowing the full layers' pages "
        "alone has no tested path beside it", mesh)


def _mimo_v2_adapter(name: str, cfg, mesh=None) -> ModelAdapter:
    """MiMo-V2.5's language model (models/mimo_v2.py): pages and rings of
    two KV geometries, both in lane parts."""
    from dynamo_tpu.models import mimo_v2 as mm

    return _gqa_ring_adapter(
        name, cfg, mm, "MiMo-V2.5", mm.mimo_v2_logical_axes,
        "five layers of six keep their K and V in a ring in the slot pool, "
        "in the model dtype, and narrowing the full layers' pages alone "
        "has no tested path beside it (nor has the walk of a cache in lane "
        "parts one for scale planes)", mesh)


def _mimo_v2_presets() -> dict:
    from dynamo_tpu.models.mimo_v2 import MimoV2Config

    return {
        # the language model of MiMo-V2.5 as published: 48 layers, 256
        # experts, 152,576 ids (618 GB in bf16: shape tests and a later
        # multi-chip issue)
        "mimo-v2.5": MimoV2Config.mimo_v2_5,
        # one chip of its deployment: layers 0 and 6-11, 16 of the 256
        # experts, an eighth of the vocabulary (chipbench/configs/
        # mimo-v2.5-1chip.json)
        "mimo-v2.5-7l-16e": MimoV2Config.mimo_v2_5_1chip,
        "mimo-v2.5-tiny": MimoV2Config.tiny,
    }


def _cohere2_moe_presets() -> dict:
    from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig

    return {
        # the language model of command-a-plus-05-2026 as published: 32
        # layers, 128 experts, 262,144 ids (436 GB in bf16: shape tests and
        # a later multi-chip issue)
        "command-a-plus": Cohere2MoeConfig.command_a_plus,
        # one chip of its deployment: layers 0-3, 16 of the 128 experts, an
        # eighth of the vocabulary (chipbench/configs/
        # command-a-plus-1chip.json)
        "command-a-plus-4l-16e": Cohere2MoeConfig.command_a_plus_1chip,
        "command-a-plus-tiny": Cohere2MoeConfig.tiny,
    }


def _dots3_presets() -> dict:
    from dynamo_tpu.models.dots3 import Dots3Config

    return {
        # the language model of dots3-note-prev as published: 46 layers,
        # 256 experts, 152,064 ids (shape tests and a later multi-chip issue)
        "dots3-note-prev": Dots3Config.dots3_note_prev,
        # one chip of its deployment: layers 0-8, 8 of the 256 experts, an
        # eighth of the vocabulary (chipbench/configs/
        # dots3-note-prev-1chip.json)
        "dots3-note-prev-9l-8e": Dots3Config.dots3_1chip,
        "dots3-tiny": Dots3Config.tiny,
    }


def _keye_vl_presets() -> dict:
    from dynamo_tpu.models.keye_vl import KeyeVLConfig

    return {
        # the language model of Keye-VL-2.0-30B-A3B as published: 48
        # layers, 128 experts (61 GB in bf16: shape tests and a later
        # multi-chip issue)
        "keye-vl2-30b-a3b": KeyeVLConfig.keye_vl2_30b_a3b,
        # one chip of its deployment: a pipeline stage of 8 layers, 16 of
        # the 128 experts (chipbench/configs/keye-vl2-30b-a3b-1chip.json)
        "keye-vl2-30b-a3b-8l-16e": KeyeVLConfig.keye_vl2_1chip,
        "keye-vl2-tiny": KeyeVLConfig.tiny,
    }


def _minicpm_sala_presets() -> dict:
    from dynamo_tpu.models.minicpm_sala import MiniCPMSALAConfig

    return {
        # MiniCPM-SALA as published: 32 layers (19 GB in bf16: shape tests
        # and a later multi-chip issue)
        "minicpm-sala-9b": MiniCPMSALAConfig.minicpm_sala_9b,
        # one stage of a two-stage pipeline over depth: the published
        # layers 9-24 (4 sparse, 12 lightning), with the embedding and the
        # head (chipbench/configs/minicpm-sala-9b-1chip.json)
        "minicpm-sala-9b-16l": lambda: MiniCPMSALAConfig.minicpm_sala_9b(
            range(9, 25)),
        "minicpm-sala-tiny": MiniCPMSALAConfig.tiny,
    }


def _falcon_h1_presets() -> dict:
    from dynamo_tpu.models.falcon_h1 import FalconH1Config

    return {
        # Falcon-H1-34B-Instruct as published: 72 layers (67 GB in bf16:
        # shape tests and a later multi-chip issue)
        "falcon-h1-34b": FalconH1Config.falcon_h1_34b,
        # one stage of a pipeline over depth: 6 of the 72 layers, with the
        # embedding and the head (chipbench/configs/falcon-h1-34b-1chip.json)
        "falcon-h1-34b-6l": lambda: FalconH1Config.falcon_h1_34b(6),
        "falcon-h1-tiny": FalconH1Config.tiny,
    }


def _nemotron_h_presets() -> dict:
    from dynamo_tpu.models.nemotron_h import NemotronHConfig

    return {
        # NVIDIA-Nemotron-3-Nano-30B-A3B as published: 52 layers, 128
        # experts (63 GB in bf16: shape tests and a later multi-chip issue)
        "nemotron3-nano": NemotronHConfig.nemotron3_nano,
        # one chip of its 8-chip deployment: 28 layers, 16 experts held
        "nemotron3-nano-28l-16e": NemotronHConfig.nemotron3_nano_1chip,
        "nemotron-h-tiny": NemotronHConfig.tiny,
    }


def _moe_presets() -> dict:
    from dynamo_tpu.models.moe import MoeConfig

    return {
        "mixtral-8x7b": MoeConfig.mixtral_8x7b,
        "moe-tiny": MoeConfig.tiny,
        "qwen3-moe-30b": MoeConfig.qwen3_moe_30b,
        "llama4-scout-text": MoeConfig.llama4_scout_text,
        "llama4-tiny": MoeConfig.llama4_tiny,
        "gpt-oss-20b": MoeConfig.gpt_oss_20b,
        "gpt-oss-tiny": MoeConfig.gpt_oss_tiny,
    }


def _mla_presets() -> dict:
    from dynamo_tpu.models.mla import MlaConfig

    return {
        "deepseek-v2-lite": MlaConfig.deepseek_v2_lite,
        # the depth cut that fits one v5e chip in bf16: layer 0 dense + 7
        # expert layers, every width and all 64 experts as published
        "deepseek-v2-lite-8l": lambda: MlaConfig.deepseek_v2_lite(8),
        "mla-tiny": MlaConfig.tiny,
        "mla-tiny-moe": MlaConfig.tiny_moe,
    }


#: the families whose cache is more than K and V pages, each with an
#: adapter of its own: a recurrent state a sequence beside its pages, or
#: (Keye-VL) a third resident of the page itself
_STATE_FAMILIES = (
    (_nemotron_h_presets, _nemotron_h_adapter),
    (_falcon_h1_presets, _falcon_h1_adapter),
    (_minicpm_sala_presets, _minicpm_sala_adapter),
    (_keye_vl_presets, _keye_vl_adapter),
    (_dots3_presets, _dots3_adapter),
    (_cohere2_moe_presets, _cohere2_moe_adapter),
    (_mimo_v2_presets, _mimo_v2_adapter),
)


def list_presets() -> list[str]:
    """Every serveable preset id (llama + MoE + MLA families) — the
    iteration surface for `scripts/dryrun_70b.py --check-rules`, which
    dry-resolves each one's logical axes through the rule table."""
    return sorted(_LLAMA_PRESETS) + sorted(_moe_presets()) + sorted(
        _mla_presets()
    ) + [name for presets, _ in _STATE_FAMILIES for name in sorted(presets())]


def get_model(
    name: str,
    dtype: Optional[str] = None,
    attention_impl: Optional[str] = None,
    mesh=None,
) -> ModelAdapter:
    """Resolve a model name: preset id, or a local HF checkpoint dir."""
    from dynamo_tpu.models.mla import MlaConfig
    from dynamo_tpu.models.moe import MoeConfig

    key = name.lower()
    moe_presets = _moe_presets()
    mla_presets = _mla_presets()
    moe_cfg = None
    mla_cfg = None
    gguf_path = None
    qwen2vl_dir = False
    if key in _LLAMA_PRESETS:
        cfg = _LLAMA_PRESETS[key]()
    elif key.endswith(".gguf") and os.path.isfile(name):
        from dynamo_tpu.gguf import read_gguf

        g = read_gguf(name)
        arch = g.architecture()
        if arch not in ("llama", "qwen2", "qwen3", "gemma", "gemma2",
                        "gemma3"):
            raise ValueError(
                f"unsupported GGUF architecture {arch!r} for {name}"
            )
        cfg = g.to_llama_config()
        gguf_path = name
    elif key in moe_presets:
        moe_cfg = moe_presets[key]()
    elif key in mla_presets:
        mla_cfg = mla_presets[key]()
    elif any(key in presets() for presets, _ in _STATE_FAMILIES):
        # a family with an adapter of its own
        presets, adapter = next(
            (presets(), adapter) for presets, adapter in _STATE_FAMILIES
            if key in presets()
        )
        hy_cfg = presets[key]()
        if dtype is not None:
            hy_cfg = _with_dtype(hy_cfg, dtype)
        if attention_impl is not None:
            hy_cfg = replace(hy_cfg, attention_impl=attention_impl)
        return adapter(name, hy_cfg, mesh=mesh)
    elif os.path.isdir(name) and os.path.exists(os.path.join(name, "config.json")):
        with open(os.path.join(name, "config.json")) as f:
            hf = json.load(f)
        arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
        if (
            "mixtral" in arch.lower()
            or arch in (
                "Qwen3MoeForCausalLM", "Llama4ForCausalLM",
                "GptOssForCausalLM",
            )
            or hf.get("model_type") in ("qwen3_moe", "llama4_text", "gpt_oss")
        ):
            moe_cfg = MoeConfig.from_hf_config(hf)
        elif (
            arch in ("DeepseekV2ForCausalLM", "DeepseekV3ForCausalLM")
            or hf.get("model_type") in ("deepseek_v2", "deepseek_v3")
        ):
            mla_cfg = MlaConfig.from_hf_config(hf)
        elif (
            arch in (
                "Qwen2VLForConditionalGeneration",
                "Qwen2_5_VLForConditionalGeneration",
            )
            or hf.get("model_type") in ("qwen2_vl", "qwen2_5_vl")
        ):
            from dynamo_tpu.models import qwen2vl

            cfg = qwen2vl.config_from_hf(hf)
            qwen2vl_dir = True
        elif (
            "llama" in arch.lower()
            or "qwen2" in arch.lower()
            or arch in (
                "GemmaForCausalLM", "Gemma2ForCausalLM",
                "Gemma3ForCausalLM", "MistralForCausalLM",
                "Qwen3ForCausalLM", "Phi3ForCausalLM",
            )
            or hf.get("model_type") in (
                "gemma", "gemma2", "gemma3_text", "mistral", "qwen3",
                "phi3",
            )
            # Multimodal Gemma3 dumps (model_type "gemma3") and
            # RecurrentGemma remain refused rather than served
            # silently wrong (text-only Gemma3ForCausalLM is covered).
            # A recurrent state as such is served (models/nemotron_h.py,
            # by preset); RecurrentGemma's RG-LRU block and a nemotron_h
            # checkpoint directory have no loader here.
        ):
            cfg = LlamaConfig.from_hf_config(hf)
        else:
            raise ValueError(f"unsupported architecture {arch} for {name}")
    else:
        raise ValueError(
            f"unknown model {name!r}; presets: "
            f"{list_presets()} "
            "or a local HF checkpoint directory"
        )
    if mla_cfg is not None:
        if dtype is not None:
            mla_cfg = _with_dtype(mla_cfg, dtype)
        if attention_impl is not None:
            mla_cfg = replace(mla_cfg, attention_impl=attention_impl)
        mla_adapter = _mla_adapter(name, mla_cfg, mesh=mesh)
        if os.path.isdir(name):
            mla_adapter = replace(mla_adapter, default_checkpoint=name)
        return mla_adapter
    if moe_cfg is not None:
        if dtype is not None:
            moe_cfg = replace(moe_cfg, base=_with_dtype(moe_cfg.base, dtype))
        if attention_impl is not None:
            moe_cfg = replace(
                moe_cfg,
                base=replace(moe_cfg.base, attention_impl=attention_impl),
            )
        moe_adapter = _moe_adapter(name, moe_cfg, mesh=mesh)
        if os.path.isdir(name):
            moe_adapter = replace(moe_adapter, default_checkpoint=name)
        return moe_adapter
    if dtype is not None:
        cfg = _with_dtype(cfg, dtype)
    if attention_impl is not None:
        cfg = replace(cfg, attention_impl=attention_impl)
    if cfg.attention_impl in ("pallas", "hybrid") and (
        cfg.sliding_window
        or cfg.attn_logit_softcap
        or (
            cfg.query_pre_attn_scalar is not None
            and cfg.query_pre_attn_scalar != cfg.head_dim
        )
    ):
        # Gemma2's sliding-window / softcapped / rescaled attention isn't
        # implemented in the flash kernels (they scale by 1/sqrt(head_dim))
        # — serve it on the XLA path rather than fail ("auto" on TPU would
        # otherwise pick pallas and raise at trace). Explicit requests get
        # a WARNING (see the MLA coercion above). This is the models/llama.py
        # and models/moe.py presets' window alone (a mask over pages that
        # keep growing: Gemma2/3, Mistral); a family whose window layers
        # keep a ring in the slot pool (models/dots3.py,
        # models/cohere2_moe.py) runs its windows under the kernels.
        log = (
            logger.warning
            if attention_impl in ("pallas", "hybrid")
            else logger.info
        )
        log(
            "%s: the sliding-window/softcap/rescaled attention of the "
            "models/llama.py presets (Gemma2/3, Mistral: a mask over pages "
            "that keep growing) has no flash kernel -> serving with "
            "attention_impl=xla (the families with a ring a window layer, "
            "dots3-note-prev and Command A+, keep their kernels)",
            name,
        )
        cfg = replace(cfg, attention_impl="xla")
    adapter = _llama_adapter(name, cfg, mesh=mesh)
    if gguf_path is not None:
        from dynamo_tpu.gguf import read_gguf

        def load_from_gguf(path=gguf_path, cfg=cfg):
            return llama_mod.params_from_gguf(read_gguf(path), cfg)

        adapter = replace(
            adapter, load_params=load_from_gguf, default_checkpoint=gguf_path
        )
    elif os.path.isdir(name):
        adapter = replace(adapter, default_checkpoint=name)
        if qwen2vl_dir:
            # Qwen2-VL dirs hold a conditional-generation model;
            # AutoModelForCausalLM refuses them, and the language weights
            # live under `model.language_model.*`.
            adapter = replace(
                adapter,
                load_params=lambda path: _load_qwen2vl_checkpoint(path, cfg),
            )
    return adapter


def _load_qwen2vl_checkpoint(path: str, cfg: LlamaConfig):
    import torch

    from dynamo_tpu.models.qwen2vl import remap_language_state_dict

    with open(os.path.join(path, "config.json")) as f:
        mt = json.load(f).get("model_type")
    if mt == "qwen2_5_vl":
        from transformers import Qwen2_5_VLForConditionalGeneration as cls
    else:
        from transformers import Qwen2VLForConditionalGeneration as cls
    model = cls.from_pretrained(
        path, torch_dtype=torch.float32, low_cpu_mem_usage=True
    )
    return llama_mod.params_from_torch_state_dict(
        remap_language_state_dict(model.state_dict()), cfg
    )


def _with_dtype(cfg: LlamaConfig, dtype) -> LlamaConfig:
    if isinstance(dtype, str):
        table = {
            "bfloat16": jnp.bfloat16,
            "float32": jnp.float32,
            "float64": jnp.float64,
        }
        if dtype not in table:
            raise ValueError(
                f"unsupported dtype {dtype!r}; use one of {sorted(table)}"
            )
        dtype = table[dtype]
    return replace(cfg, dtype=dtype)
