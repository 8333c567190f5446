"""A plain reference for an MLA + MoE decoder (the DeepSeek-V2 layer
equations), as a configuration of that architecture brings it: the
`reference_module` of tests/chipbench/test_chipbench_config_seam.py's
configuration, and a pattern for chipbench/references/<name>.py.

float32, `jax.default_matmul_precision("highest")`, no cache, no
absorbed latent, no capacity: queries project directly (`q_lora_rank`
null), keys and values are up-projected from the normalised latent
`c_kv` per head, the rope part of the key is one vector shared by all
heads, rope pairs adjacent elements; an MoE layer adds the softmax-
scored top-k routed experts (weights not renormalised, times
`routed_scaling_factor`) to the shared experts; the first
`first_k_dense_replace` layers are dense SwiGLU. Fed the served engine's
own parameter tree (`dense_layers`, `moe_layers`), one layer at a time.

The comparison's arithmetic is chipbench.reference.compare: only the
forward pass is this module's.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import reference as dense


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rope(z, positions, theta):
    """Adjacent pairs (z[2j], z[2j+1]) rotate; z [T, ..., D]."""
    import jax.numpy as jnp

    d = z.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]  # [T, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    for _ in range(z.ndim - 2):
        cos, sin = cos[:, None], sin[:, None]
    even, odd = z[..., 0::2], z[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(z.shape)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def block(x, lp: dict, hf: dict, positions):
    """One decoder layer over x [T, H] (f32), causal over the T rows;
    MoE where the layer's tree has a router."""
    import jax
    import jax.numpy as jnp

    heads, c = hf["num_attention_heads"], hf["kv_lora_rank"]
    n, r, vd = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                hf["v_head_dim"])
    eps, t = hf["rms_norm_eps"], x.shape[0]

    h = dense._rms(x, lp["attn_norm"], eps)
    q = (h @ _f32(lp["wq"])).reshape(t, heads, n + r)
    q_nope, q_pe = q[..., :n], _rope(q[..., n:], positions, hf["rope_theta"])
    kv_a = h @ _f32(lp["wkv_a"])
    c_kv = dense._rms(kv_a[:, :c], lp["kv_a_norm"], eps)
    k_pe = _rope(kv_a[:, c:], positions, hf["rope_theta"])  # [T, r]
    kv = (c_kv @ _f32(lp["wkv_b"])).reshape(t, heads, n + vd)
    k_nope, v = kv[..., :n], kv[..., n:]
    s = (jnp.einsum("thn,khn->htk", q_nope, k_nope)
         + jnp.einsum("thr,kr->htk", q_pe, k_pe)) / math.sqrt(n + r)
    causal = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("htk,khv->thv", p, v).reshape(t, heads * vd)
    x = x + a @ _f32(lp["wo"])

    h = dense._rms(x, lp["mlp_norm"], eps)
    if "w_router" not in lp:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    scores = jax.nn.softmax(h @ _f32(lp["w_router"]), axis=-1)  # [T, E]
    topw, topi = jax.lax.top_k(scores, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob"):
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(t)[:, None], topi].set(topw)  # [T, E], 0 off the top k
    routed = sum(
        weight[:, e:e + 1] * _swiglu(h, lp["we_gate"][e], lp["we_up"][e],
                                     lp["we_down"][e])
        for e in range(hf["n_routed_experts"]))
    return x + routed + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def log_probs(params: dict, hf: dict, ids, at) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids])
        for group in ("dense_layers", "moe_layers"):
            stack = params.get(group) or {}
            for i in range(len(stack.get("wq", ()))):
                x = block(x, jax.tree.map(lambda a: a[i], stack), hf, pos)
        h = dense._rms(x[jnp.asarray(at)], params["final_norm"],
                       hf["rms_norm_eps"])
        w = params["lm_head"] if "lm_head" in params else params["embed"].T
        out = jax.nn.log_softmax(h @ _f32(w), axis=-1)
    return np.asarray(out)


def compare(params: dict, hf: dict, streams: list[dict]) -> dict:
    return dense.compare(params, hf, streams, forward=log_probs)


def served_widths(cfg) -> dict:
    """An MlaConfig's sizes under the published file's keys: every one
    of them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "vocab_size": cfg.vocab_size,
        "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim,
        "n_routed_experts": cfg.n_routed_experts,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "first_k_dense_replace": cfg.first_k_dense_replace,
    }
