"""The plain reference of DeepSeek-V2-Lite (and of any DeepSeek-V2 block
with a direct query projection), as the configuration
`deepseek-v2-lite-1chip` brings it (`reference_module` in its file):
float32, `jax.default_matmul_precision("highest")`, no cache, no
absorbed latent, no capacity, no kernels.

The layer, from the published description (DeepSeek-V2, arXiv 2405.04434,
section 2.1 and appendix C; `modeling_deepseek.py` beside the
checkpoint): RMSNorm; queries projected directly (`q_lora_rank` null)
into `qk_nope_head_dim` + `qk_rope_head_dim` a head; the latent
`c_kv = RMSNorm(x W_DKV)` up-projected PER HEAD into keys and values
(`kv_b_proj`), the rope part of the key one vector shared by all heads;
rope pairs adjacent elements and takes YaRN frequencies (the
interpolated and the extrapolated inverse frequencies blended by a
linear ramp between the `beta_fast` and `beta_slow` correction
dimensions of `original_max_position_embeddings`), its cos and sin
scaled by mscale(factor, `mscale`) / mscale(factor, `mscale_all_dim`);
causal softmax attention at 1 / sqrt(nope + rope). An expert layer adds
the softmax-scored top-k routed experts (greedy, weights not
renormalised unless `norm_topk_prob`, times `routed_scaling_factor`),
every expert computed for every token and weighted by a [T, E] matrix
that is zero off the top k, to the shared experts; the first
`first_k_dense_replace` layers are dense SwiGLU. Fed the served
engine's own parameter tree (`dense_layers`, `moe_layers`), one layer
at a time.

Departures from the published description: (1) the softmax scale has no
mscale(factor, mscale_all_dim)^2 term. deepseek-ai's own remote code
multiplies it in; the transformers port (DeepseekV2Attention) does not,
the repository's golden test follows the port, and so do the program
(`rope_mscale_softmax` False) and this reference. With seeded weights
either is a constant factor on the scores of both sides. (2) The routed
experts are computed densely (every expert for every token, times a
weight that is 0 off the top k): the same sum in another order.

The comparison's arithmetic is chipbench.reference.compare; only the
forward pass is this module's. `compare` also probes how the served
weights route one decode step's worth of rows (`routing_probe`: distinct
experts touched, and `expert_load_max_over_mean`, the busiest expert's
assignments over the mean), outside every timing. `python -m chipbench.references.deepseek_v2_lite` is this
configuration's control (chipbench/control.py covers the default
reference only): see `main`.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import reference as dense


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _dense(lp: dict, name: str):
    """A matrix of the tree in float32 (int8: w = q * scale)."""
    import jax.numpy as jnp

    w = _f32(lp[name])
    if lp[name].dtype == jnp.int8:
        w = w * _f32(lp[name + "_scale"])
    return w


def rope_table(hf: dict, d: int):
    """(inverse frequencies [d/2] float64, factor on cos and sin)."""
    inv = 1.0 / (hf["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d))
    rs = hf.get("rope_scaling")
    if not rs:
        return inv, 1.0
    factor = rs["factor"]
    original = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(hf["rope_theta"])))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    if rs.get("mscale") and rs.get("mscale_all_dim"):
        return inv, mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    return inv, mscale(1.0)


def _rope(z, positions, hf):
    """Adjacent pairs (z[2j], z[2j+1]) rotate; z [T, ..., D]."""
    import jax.numpy as jnp

    inv, att = rope_table(hf, z.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]  # [T, D/2]
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    for _ in range(z.ndim - 2):
        cos, sin = cos[:, None], sin[:, None]
    even, odd = z[..., 0::2], z[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(z.shape)


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def block(x, lp: dict, hf: dict, positions, router_dtype=None):
    """One decoder layer over x [T, H] (f32), causal over the T rows;
    an expert layer where the layer's tree has a router. Returns (x,
    top-k expert ids [T, k] or None). `router_dtype` is the control's:
    the gate's operands rounded to it."""
    import jax
    import jax.numpy as jnp

    heads, c = hf["num_attention_heads"], hf["kv_lora_rank"]
    n, r, vd = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                hf["v_head_dim"])
    eps, t = hf["rms_norm_eps"], x.shape[0]

    h = dense._rms(x, lp["attn_norm"], eps)
    q = (h @ _dense(lp, "wq")).reshape(t, heads, n + r)
    q_nope, q_pe = q[..., :n], _rope(q[..., n:], positions, hf)
    kv_a = h @ _dense(lp, "wkv_a")
    c_kv = dense._rms(kv_a[:, :c], lp["kv_a_norm"], eps)
    k_pe = _rope(kv_a[:, c:], positions, hf)  # [T, r]
    kv = (c_kv @ _dense(lp, "wkv_b")).reshape(t, heads, n + vd)
    k_nope, v = kv[..., :n], kv[..., n:]
    s = (jnp.einsum("thn,khn->htk", q_nope, k_nope)
         + jnp.einsum("thr,kr->htk", q_pe, k_pe)) / math.sqrt(n + r)
    causal = positions[:, None] >= positions[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("htk,khv->thv", p, v).reshape(t, heads * vd)
    x = x + a @ _dense(lp, "wo")

    h = dense._rms(x, lp["mlp_norm"], eps)
    if "w_router" not in lp:
        return x + _swiglu(h, _dense(lp, "w_gate"), _dense(lp, "w_up"),
                           _dense(lp, "w_down")), None
    gate_in, gate_w = h, _f32(lp["w_router"])
    if router_dtype is not None:
        gate_in = _f32(h.astype(router_dtype))
        gate_w = _f32(gate_w.astype(router_dtype))
    scores = jax.nn.softmax(gate_in @ gate_w, axis=-1)  # [T, E]
    topw, topi = jax.lax.top_k(scores, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob"):
        topw = topw / topw.sum(-1, keepdims=True)
    topw = topw * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(t)[:, None], topi].set(topw)  # [T, E], 0 off the top k
    hidden = (jax.nn.silu(jnp.einsum("th,ehi->eti", h, _dense(lp, "we_gate")))
              * jnp.einsum("th,ehi->eti", h, _dense(lp, "we_up")))
    routed = jnp.einsum("eti,eih,te->th", hidden, _dense(lp, "we_down"),
                        weight)
    shared = _swiglu(h, _dense(lp, "ws_gate"), _dense(lp, "ws_up"),
                     _dense(lp, "ws_down"))
    return x + routed + shared, topi


def log_probs(params: dict, hf: dict, ids, at, lower=None,
              router_dtype=None) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32. `lower` (the control's)
    maps a layer's tree to the one computed with."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp, pos: block(
            x, lower(lp) if lower else lp, hf, pos, router_dtype))
        x = _f32(params["embed"][ids])
        for group in ("dense_layers", "moe_layers"):
            stack = params.get(group) or {}
            for i in range(len(stack.get("wq", ()))):
                x, _topi = layer(x, jax.tree.map(lambda a: a[i], stack), pos)

        @jax.jit
        def head(x, norm, w):
            h = dense._rms(x, norm, hf["rms_norm_eps"])
            return jax.nn.log_softmax(h @ _f32(w), axis=-1)

        w = params["lm_head"] if "lm_head" in params else params["embed"].T
        out = head(x[jnp.asarray(at)], params["final_norm"], w)
    return np.asarray(out)


#: where `compare` leaves what its routing probe read, for the reader of
#: `moe_experts_hbm_share` (chipbench/layer_metrics/): one run, one process
PROBE_FILE = "deepseek_v2_lite_routing_probe.json"
#: the control switches the probe off: it compares many streams
PROBE = True


def routing_probe(params: dict, hf: dict, rows: int = 64, tokens: int = 32,
                  seed: int = 1234) -> dict:
    """How the served weights route one decode step's worth of rows: the
    last tokens of `rows` random sequences (a step holds one token of each
    of its sequences) through these layers at the default matmul precision
    (only the top-k choice is read), and per expert layer the number of
    distinct experts they pick and the busiest expert's load over the
    mean. A value read back outside every step and every timing."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic

    ids = jnp.asarray(np.random.default_rng(seed).integers(
        traffic.FIRST_ID, hf["vocab_size"], (rows, tokens)), jnp.int32)
    pos = jnp.arange(tokens, dtype=jnp.int32)
    layer = jax.jit(jax.vmap(lambda x, lp: block(x, lp, hf, pos),
                             in_axes=(0, None)))
    x = _f32(params["embed"][ids])
    touched, load = [], []
    for group in ("dense_layers", "moe_layers"):
        stack = params.get(group) or {}
        for i in range(len(stack.get("wq", ()))):
            x, topi = layer(x, jax.tree.map(lambda a: a[i], stack))
            if topi is not None:
                counts = np.bincount(np.asarray(topi[:, -1]).ravel(),
                                     minlength=hf["n_routed_experts"])
                touched.append(int((counts > 0).sum()))
                load.append(float(counts.max() / counts.mean()))
    return {"rows": rows, "experts_touched": float(np.mean(touched)),
            "expert_load_max_over_mean": float(np.mean(load)),
            "per_layer_touched": touched}


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    res = dense.compare(
        params, hf, streams,
        forward=lambda p, c, ids, at: log_probs(p, c, ids, at, **how))
    if PROBE and hf.get("n_routed_experts"):
        import json
        import os

        from chipbench import manifest

        probe = routing_probe(params, hf)
        res["expert_load_max_over_mean"] = probe["expert_load_max_over_mean"]
        res["experts_touched_at_64_rows"] = probe["experts_touched"]
        manifest.RUN_DIR.mkdir(parents=True, exist_ok=True)
        with open(manifest.RUN_DIR / PROBE_FILE, "w") as f:
            json.dump({"pid": os.getpid(), **probe}, f)
    return res


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


# -- the control --------------------------------------------------------------

MATRICES = ("wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
            "ws_gate", "ws_up", "ws_down", "we_gate", "we_up", "we_down")


def to_int8(lp: dict) -> dict:
    """The layer's matrices one precision below bf16: int8, symmetric per
    output channel (per expert and output channel), kept as the float32
    values int8 can hold."""
    import jax.numpy as jnp

    out = dict(lp)
    for name in MATRICES:
        if name in lp:
            w = _dense(lp, name)
            scale = jnp.maximum(
                jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0, 1e-8)
            out[name] = jnp.round(w / scale) * scale
            out.pop(name + "_scale", None)
    return out


#: what the control puts in the program's place. The first two have to
#: come out as not correct: the precision below the stated one, and one
#: of the k experts left out. The third is shown and not required to
#: fail: the program's activations are bf16 (the stated precision) and
#: its gate multiplies them in float32, so a gate whose operands are
#: rounded to bf16 is no lower precision than the program's own (on the
#: chip it reads a sixth of the program's distance from float32).
CONTROLS = {
    "int8_weights": {"lower": to_int8},
    "dropped_expert": {"experts_per_tok": -1},
    "bf16_router": {"router_dtype": "bfloat16"},
}
MUST_FAIL = ("int8_weights", "dropped_expert")


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it (the
    whole padded sequence every step: under the causal mask a position
    sees nothing after it)."""
    from chipbench import traffic

    how = dict(how)
    cut = how.pop("experts_per_tok", 0)
    if cut:
        hf = {**hf, "num_experts_per_tok": hf["num_experts_per_tok"] + cut}
    rng = np.random.default_rng(seed)
    total = prompt_len + out_len
    out = []
    for _ in range(streams):
        prompt = [int(v) for v in rng.integers(
            traffic.FIRST_ID, hf["vocab_size"], prompt_len)]
        ids = prompt + [0] * out_len
        toks, lps = [], []
        for t in range(prompt_len - 1, total - 1):
            lp = log_probs(params, hf, ids, [t], **how)[0]
            ids[t + 1] = int(lp.argmax())
            toks.append(ids[t + 1])
            lps.append(float(lp.max()))
        out.append({"prompt": prompt, "out": toks, "logprobs": lps})
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.deepseek_v2_lite [--seeds a,b]
    [--config deepseek-v2-lite-1chip]: each of CONTROLS decodes the
    benchmark's greedy streams and goes through `compare` against the
    reference as it stands, under the configuration's
    `reference_tolerance`; those of MUST_FAIL have to come out as not
    correct."""
    import argparse
    import json
    import sys

    import jax
    import jax.numpy as jnp

    from chipbench import control, manifest
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="deepseek-v2-lite-1chip")
    ap.add_argument("--seeds", default="1234,1")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ns = ap.parse_args(argv)
    conf = manifest.config_of(manifest.load(), {"config": ns.config})
    on_chip = jax.devices()[0].platform == "tpu"
    serve = conf if on_chip else conf["rehearsal"]
    hf = conf if on_chip else serve["hf"]
    params = control.build_params(serve)
    me = sys.modules[__name__]
    me.PROBE = False
    fooled = []
    for name in ns.controls.split(","):
        how = dict(CONTROLS[name])
        if "router_dtype" in how:
            how["router_dtype"] = jnp.dtype(how["router_dtype"])
        for seed in (int(s) for s in ns.seeds.split(",")):
            streams = control_streams(params, hf, seed, how)
            res = check_reference(params, hf, streams,
                                  conf["reference_tolerance"], me)
            print(json.dumps({"note": "control", "control": name,
                              "seed": seed, **res}), flush=True)
            if res["passed"] and name in MUST_FAIL:
                fooled.append((name, seed))
    print(json.dumps({"control_comes_out_not_correct": not fooled,
                      "passed": fooled}), flush=True)
    return 1 if fooled else 0


if __name__ == "__main__":
    raise SystemExit(main())
