"""The default plain reference (a configuration whose file names no
`reference_module` is compared with this one; another architecture
brings its own module with the same `compare`, see
`manifest.module_of`): a decoder-only transformer block in jax.numpy,
float32, `jax.default_matmul_precision("highest")`, no cache, no
kernels, no batching. It follows the published Llama/Qwen2/Phi-3 layer
equations from the configuration file's own keys: RMSNorm, q/k/v
projections (with bias where the config says so), rotary embedding in
the half-split convention, grouped-query causal attention, SwiGLU, and
an untied output head. It is fed the served engine's own parameter tree
one layer at a time; int8 weights are dequantised as the configuration
states (per-output-channel scales, w = q * scale).

Departures from the published models: Phi-3 checkpoints fuse qkv and
gate_up; the engine holds them split, and so does this reference.
"""

from __future__ import annotations

import math

import numpy as np


def _dense(lp: dict, name: str):
    import jax.numpy as jnp

    w = lp[name]
    if w.dtype == jnp.int8:
        return w.astype(jnp.float32) * lp[name + "_scale"].astype(jnp.float32)
    return w.astype(jnp.float32)


def _rms(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def block(x, lp: dict, hf: dict, positions):
    """One decoder layer over x [T, H] (f32), causal over the T rows."""
    import jax
    import jax.numpy as jnp

    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    d = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    eps = hf["rms_norm_eps"]
    t = x.shape[0]

    h = _rms(x, lp["attn_norm"], eps)
    q, k, v = (h @ _dense(lp, n) for n in ("wq", "wk", "wv"))
    if "bq" in lp:
        q = q + lp["bq"].astype(jnp.float32)
        k = k + lp["bk"].astype(jnp.float32)
        v = v + lp["bv"].astype(jnp.float32)
    q = q.reshape(t, heads, d)
    k = k.reshape(t, kv_heads, d)
    v = v.reshape(t, kv_heads, d)

    inv = 1.0 / (hf["rope_theta"] ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(z):
        z1, z2 = z[..., : d // 2], z[..., d // 2:]
        return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], -1)

    q, k = rope(q), rope(k)
    g = heads // kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,khd->htk", q, k) / math.sqrt(d)
    causal = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("htk,khd->thd", p, v).reshape(t, heads * d)
    x = x + a @ _dense(lp, "wo")

    h = _rms(x, lp["mlp_norm"], eps)
    gate = h @ _dense(lp, "w_gate")
    x = x + (jax.nn.silu(gate) * (h @ _dense(lp, "w_up"))) @ _dense(lp, "w_down")
    return x


def log_probs(params: dict, hf: dict, ids, at) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp, pos: block(x, lp, hf, pos))
        x = params["embed"][ids].astype(jnp.float32)
        n_layers = params["layers"]["wq"].shape[0]
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = layer(x, lp, pos)

        @jax.jit
        def head(x, norm, w):
            h = _rms(x, norm, hf["rms_norm_eps"])
            return jax.nn.log_softmax(h @ w.astype(jnp.float32), axis=-1)

        w = params["lm_head"] if "lm_head" in params else params["embed"].T
        out = head(x[jnp.asarray(at)], params["final_norm"], w)
    return np.asarray(out)


def compare(params: dict, hf: dict, streams: list[dict],
            forward=None) -> dict:
    """Teacher-force each served greedy stream {prompt, out, logprobs}
    through the reference: argmax agreement, the largest |difference| of
    the served token's log-prob, and how far below the reference's own
    best the served token sits (an argmax flip is harmless where that is
    ~0: seeded random weights make many near-ties), and the mean
    |difference| over every token. `forward` is another
    architecture's `log_probs`, so that its module keeps this arithmetic."""
    forward = forward or log_probs
    agree = total = 0
    drift = gap = drift_sum = 0.0
    for s in streams:
        seq = list(s["prompt"]) + list(s["out"])
        n = len(s["out"])
        at = len(s["prompt"]) - 1 + np.arange(n)
        lp = forward(params, hf, seq, at)
        served = np.asarray(s["out"])
        of_served = lp[np.arange(n), served]
        agree += int((lp.argmax(-1) == served).sum())
        total += n
        off = np.abs(of_served - np.asarray(s["logprobs"], np.float32))
        drift = max(drift, float(off.max()))
        drift_sum += float(off.sum())
        gap = max(gap, float((lp.max(-1) - of_served).max()))
    return {"tokens": total, "argmax_agreement": agree / max(total, 1),
            "max_logprob_drift": drift, "max_gap_to_reference_best": gap,
            # the mean over every token: steadier from stream to stream
            # than the largest of 128, which is an extreme value
            "mean_logprob_drift": drift_sum / max(total, 1)}
