"""The plain reference of Falcon-H1 (TII Falcon-H1-34B-Instruct,
`model_type` falcon_h1), as the configuration `falcon-h1-34b-1chip`
brings it (`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no state pool, no
chunked scan, no kernels, one layer at a time so that it fits.

The model, from its published config.json and the Falcon-H1 / Mamba-2
descriptions (arXiv 2507.22448, 2405.21060). Every layer runs a Mamba-2
mixer and rotary grouped-query attention on the SAME normed input, adds
both to the residual, then a SwiGLU MLP; nine muP multipliers scale
activations at run time:

    e   = embed[ids] * embedding_multiplier
    x   = RMSNorm_in(h)
    m   = Mamba2(x * ssm_in_multiplier) * ssm_out_multiplier
    a   = Attn(x * attention_in_multiplier) * attention_out_multiplier
    h   = h + m + a
    y   = RMSNorm_ff(h)
    h   = h + down(up(y) * silu(gate(y) * mlp_multipliers[0]))
              * mlp_multipliers[1]
    logits = head(RMSNorm_final(h)) * lm_head_multiplier

- `Mamba2`: `in_proj` (no bias) to z | x | B | C | dt with d_inner =
  `mamba_d_ssm` = `mamba_n_heads` x `mamba_d_head` (NOT `mamba_expand` x
  hidden), B and C `mamba_n_groups` groups of `mamba_d_state`; the
  projection's OUTPUT times `ssm_multipliers[0..4]` over those five
  segments; a causal depthwise conv of `mamba_d_conv` taps with bias over
  x | B | C (zeros before the first token), then SiLU; `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)` a head; head h of group h // (heads /
  groups): `S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t`, `y_t = S_t C_t
  + D_h x_t`, HERE TOKEN BY TOKEN (`lax.scan`), never in chunks;
  `mamba_rms_norm` with `mamba_norm_before_gate` false: the gated norm,
  gate first, `RMSNorm(y * silu(z))` over the groups with a learned
  weight; `out_proj`.
- `Attn`: `num_attention_heads` query and `num_key_value_heads` KV heads
  of `head_dim`, no bias; `k = k_proj(x) * key_multiplier` BEFORE the
  rotary embedding; half-split rotary over the whole head at
  `rope_theta`, no scaling; causal softmax at 1 / sqrt(head_dim).
- Final RMSNorm, untied head.

Fed the served engine's own parameter tree (models/falcon_h1.py: the
`layers` stack in layer order, published shapes and scales; no
multiplier is folded into a weight).

Departures from the published description: (1) the file's cut: the
first `num_hidden_layers` layers (they are all alike). (2) Attention
runs in blocks of query rows and the head in blocks of the vocabulary,
so that a 5,000-token prompt and a 261,120-id head fit beside the
weights: the same sums, row by row and column by column. Nothing else.

`compare` reads, beside the log-probs, the precision the recurrent state
is carried in (`state_distance` of chipbench/references/nemotron_h.py,
through this module's traces): the program's own pool and decode
routine on the reference's inputs, against the reference's recurrence.
That is the one place where this module runs code of the program.

`python -m chipbench.references.falcon_h1` is this configuration's
control: see `main`.
"""

from __future__ import annotations

import math
from pathlib import Path

import numpy as np

from chipbench import manifest
from chipbench import reference as dense

#: the state's own comparison (the program's pool and decode routine on
#: the reference's inputs) is Nemotron-H's module's, which reads nothing
#: of that model: a trace of `u`, `decay`, `b`, `c`, `start`, `end` a layer
_nh = manifest._load(Path(__file__).with_name("nemotron_h.py"),
                     "chipbench_reference_module_nemotron_h")
state_distance = _nh.state_distance

QUERY_BLOCK = 512  # rows of the score matrix computed at once
VOCAB_BLOCK = 32768  # columns of the head cast to float32 at once

MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
)


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def mamba_branch(x, lp: dict, hf: dict, state_dtype=None, split=None):
    """`Mamba2(x)` over x [T, H] (f32, the normed input times
    `ssm_in_multiplier`), from an empty state. `state_dtype` is the
    control's: the state carried in a lower precision. With `split` it
    returns (out, trace): the recurrence's own inputs of the tokens from
    `split` on (`u` = dt x, `decay` = exp(dt A), `b`, `c`), the state
    those tokens start from and the state the last one leaves."""
    import jax
    import jax.numpy as jnp

    nh, hd = hf["mamba_n_heads"], hf["mamba_d_head"]
    n, g, kk = hf["mamba_d_state"], hf["mamba_n_groups"], hf["mamba_d_conv"]
    di, t = hf["mamba_d_ssm"], x.shape[0]
    mz, mx, mb, mc, mdt = hf["ssm_multipliers"]
    zxbcdt = x @ _f32(lp["in_proj"])
    z = zxbcdt[:, :di] * mz
    xbc = jnp.concatenate([
        zxbcdt[:, di : 2 * di] * mx,
        zxbcdt[:, 2 * di : 2 * di + g * n] * mb,
        zxbcdt[:, 2 * di + g * n : 2 * di + 2 * g * n] * mc,
    ], axis=1)
    dt = zxbcdt[:, 2 * di + 2 * g * n :] * mdt
    padded = jnp.concatenate([jnp.zeros((kk - 1, xbc.shape[1])), xbc])
    w = _f32(lp["conv_w"])  # [K, C]
    xbc = jax.nn.silu(_f32(lp["conv_b"]) + sum(
        padded[i : i + t] * w[i] for i in range(kk)))
    xs = xbc[:, :di].reshape(t, nh, hd)
    bmat = jnp.repeat(xbc[:, di : di + g * n].reshape(t, g, n), nh // g, 1)
    cmat = jnp.repeat(xbc[:, di + g * n :].reshape(t, g, n), nh // g, 1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))  # [T, nh]
    a = -jnp.exp(_f32(lp["A_log"]))
    carried = jnp.float32 if state_dtype is None else state_dtype

    def step(s, tok):
        xt, dtt, bt, ct = tok
        s = (_f32(s) * jnp.exp(dtt * a)[:, None, None]
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        s = s.astype(carried)
        return s, jnp.einsum("hpn,hn->hp", _f32(s), ct)

    toks = (xs, dt, bmat, cmat)
    s0 = jnp.zeros((nh, hd, n), carried)
    if split is None:
        _, y = jax.lax.scan(step, s0, toks)
    else:
        s_mid, y0 = jax.lax.scan(step, s0, tuple(v[:split] for v in toks))
        s_end, y1 = jax.lax.scan(step, s_mid, tuple(v[split:] for v in toks))
        y = jnp.concatenate([y0, y1])
        per = nh // g
        trace = {
            "u": (dt[:, :, None] * xs)[split:],
            "decay": jnp.exp(dt * a)[split:],
            "b": bmat[split:, ::per], "c": cmat[split:, ::per],
            "start": _f32(s_mid), "end": _f32(s_end),
        }
    y = y + _f32(lp["D"])[None, :, None] * xs
    y = y.reshape(t, di) * jax.nn.silu(z)
    yg = y.reshape(t, g, di // g)
    yg = yg / jnp.sqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + hf["rms_norm_eps"])
    out = (yg.reshape(t, di) * _f32(lp["gate_norm"])) @ _f32(lp["out_proj"])
    return out if split is None else (out, trace)


def attn_branch(x, lp: dict, hf: dict):
    """`Attn(x)` over x [T, H] (f32, the normed input times
    `attention_in_multiplier`)."""
    import jax
    import jax.numpy as jnp

    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    d, t = hf["head_dim"], x.shape[0]
    q = (x @ _f32(lp["wq"])).reshape(t, heads, d)
    k = ((x @ _f32(lp["wk"])) * hf["key_multiplier"]).reshape(t, kv_heads, d)
    v = (x @ _f32(lp["wv"])).reshape(t, kv_heads, d)
    inv = 1.0 / (float(hf["rope_theta"])
                 ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(z):
        z1, z2 = z[..., : d // 2], z[..., d // 2:]
        return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], -1)

    q, k = rope(q), rope(k)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    rows = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        s = jnp.einsum("thd,khd->htk", q[lo:hi], k[:hi]) / math.sqrt(d)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        rows.append(jnp.einsum("htk,khd->thd", p, v[:hi]))
    return jnp.concatenate(rows).reshape(t, heads * d) @ _f32(lp["wo"])


def block(h, lp: dict, hf: dict, state_dtype=None, split=None,
          attention=True):
    """One layer over h [T, H] (f32). `attention` False is the control's:
    the attention branch left out of the sum."""
    import jax

    eps = hf["rms_norm_eps"]
    x = dense._rms(h, lp["norm"], eps)
    m = mamba_branch(x * hf["ssm_in_multiplier"], lp, hf, state_dtype, split)
    trace = None
    if split is not None:
        m, trace = m
    h = h + m * hf["ssm_out_multiplier"]
    if attention:
        h = h + attn_branch(x * hf["attention_in_multiplier"], lp, hf) \
            * hf["attention_out_multiplier"]
    y = dense._rms(h, lp["mlp_norm"], eps)
    gate = jax.nn.silu((y @ _f32(lp["w_gate"])) * hf["mlp_multipliers"][0])
    h = h + ((y @ _f32(lp["w_up"])) * gate) @ _f32(lp["w_down"]) \
        * hf["mlp_multipliers"][1]
    return h if split is None else (h, trace)


MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_gate", "w_up",
            "w_down")


def to_int8(lp: dict) -> dict:
    """The layer's matrices one precision below bf16: int8, symmetric per
    output channel, kept as the float32 values int8 can hold."""
    import jax.numpy as jnp

    out = dict(lp)
    for name in MATRICES:
        w = _f32(lp[name])
        scale = jnp.maximum(
            jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0, 1e-8)
        out[name] = jnp.round(w / scale) * scale
    return out


#: every key of the configuration this module reads
HF_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_n_groups", "mamba_d_conv",
) + MULTIPLIERS
_LAYERS: dict = {}


def _layer_fn(hf: dict, lower, state_dtype, split, attention):
    """One jitted layer a distinct reading of the configuration (the
    control decodes token by token: hundreds of calls of each)."""
    import jax

    key = (tuple(tuple(v) if isinstance(v, list) else v
                 for v in (hf[k] for k in HF_KEYS)),
           lower, str(state_dtype), split, attention)
    if key not in _LAYERS:
        low = lower or (lambda lp: lp)
        _LAYERS[key] = jax.jit(lambda x, lp: block(
            x, low(lp), hf, state_dtype, split, attention))
    return _LAYERS[key]


def log_probs(params: dict, hf: dict, ids, at, lower=None, state_dtype=None,
              attention=True, split=None, traces=None) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32. `lower`, `state_dtype`
    and `attention` are the control's. With `split`, every layer's trace
    of the tokens from `split` on (`mamba_branch`) is appended to
    `traces`, in layer order."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        layer = _layer_fn(hf, lower, state_dtype, split, attention)
        x = _f32(params["embed"][ids]) * hf["embedding_multiplier"]
        for i in range(hf["num_hidden_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
            if split is not None:
                x, trace = x
                traces.append(trace)

        head = _LAYERS.setdefault(
            "head", jax.jit(lambda h, w: h @ _f32(w)))
        h = dense._rms(x[jnp.asarray(at)], params["final_norm"],
                       hf["rms_norm_eps"])
        w = params["lm_head"]
        logits = jnp.concatenate([
            head(h, w[:, lo : lo + VOCAB_BLOCK])
            for lo in range(0, w.shape[1], VOCAB_BLOCK)
        ], axis=1)
        out = jax.nn.log_softmax(
            logits * hf["lm_head_multiplier"], axis=-1)
    return np.asarray(out)


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    """`chipbench.reference.compare` through this module's `log_probs`,
    and, where `hf` names the served preset, the state's distance
    (`state_distance`) under `reference_tolerance.max_ssm_state_distance`
    of the same file. The harness's verdict reads four keys
    (chipbench/run.py `check_reference`, not a configuration's to edit):
    a state past its limit is reported as a mean log-prob drift past
    every limit, the measured one kept beside it."""
    traces: list = []

    def forward(p, c, ids, at):
        traces.append([])
        return log_probs(p, c, ids, at, split=int(at[0]) + 1,
                         traces=traces[-1], **how)

    res = dense.compare(params, hf, streams, forward=forward)
    if hf.get("preset"):
        res["ssm_state_distance"] = state_distance(hf, streams, traces)
        limit = hf.get("reference_tolerance", {}).get(
            "max_ssm_state_distance")
        if limit is not None and not res["ssm_state_distance"] <= limit:
            res["mean_logprob_drift_of_tokens"] = res["mean_logprob_drift"]
            res["mean_logprob_drift"] = float("inf")
    return res


def served_widths(cfg) -> dict:
    """A FalconH1Config's sizes and multipliers under the published
    file's keys: every one of them is compared with the configuration
    file."""
    m = cfg.mamba
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "mamba_d_ssm": m.d_inner,
        "mamba_n_heads": m.num_heads,
        "mamba_d_head": m.head_dim,
        "mamba_d_state": m.state_size,
        "mamba_n_groups": m.n_groups,
        "mamba_d_conv": m.conv_kernel,
        "mamba_chunk_size": m.chunk_size,
        **{k: _as_published(getattr(cfg, k)) for k in MULTIPLIERS},
    }


def _as_published(v):
    """A multiplier as the file holds it: a list where the config has a
    tuple."""
    return list(v) if isinstance(v, tuple) else v


# -- the control --------------------------------------------------------------


def _swapped_bc(hf: dict) -> dict:
    mz, mx, mb, mc, mdt = hf["ssm_multipliers"]
    return {"ssm_multipliers": [mz, mx, mc, mb, mdt]}


#: what the control puts in the program's place; each has to come out as
#: not correct: (a) the recurrent state carried in bfloat16 instead of the
#: float32 the configuration states (it fails on the state itself,
#: `state_distance`: at the harness's lengths the log-probs cannot see
#: it), (b) the weights one precision below bf16, (c) `key_multiplier`
#: taken as 1, (d) the B and C entries of `ssm_multipliers` exchanged, (e)
#: the attention branch left out of the sum. `hf` entries are functions of
#: the configuration that give the keys the lowered reference reads
#: differently
CONTROLS = {
    "bf16_state": {"state_dtype": "bfloat16"},
    "int8_weights": {"lower": to_int8},
    "key_multiplier_1": {"hf": lambda hf: {"key_multiplier": 1.0}},
    "ssm_multipliers_bc_swapped": {"hf": _swapped_bc},
    "no_attention": {"attention": False},
}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it (the
    whole padded sequence every step: a position sees nothing after it,
    in attention by the mask and in the Mamba-2 mixer by the recurrence's
    direction). Only a control that lowers the STATE brings the state its
    layers are left in (`ssm_state`: what `state_distance` reads in the
    program's place); the others are judged by their log-probs alone
    (their own state would read far from the reference's whatever the
    log-prob limits are, since their weights or their block differ)."""
    from chipbench import traffic

    how = dict(how)
    if "hf" in how:
        hf = {**hf, **how.pop("hf")(hf)}
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
        if "state_dtype" in how:
            traces: list = []
            log_probs(params, hf, ids, [total - 1], split=prompt_len,
                      traces=traces, **how)
            out[-1]["ssm_state"] = [np.asarray(tr["end"]) for tr in traces]
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.falcon_h1 [--seeds a,b] [--config
    falcon-h1-34b-1chip] [--controls a,b]: each of CONTROLS decodes the
    benchmark's greedy streams and goes through `compare` against the
    reference as it stands, under the configuration's
    `reference_tolerance`; each has to come out as not correct."""
    import argparse
    import json
    import sys

    import jax
    import jax.numpy as jnp

    from chipbench import control
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="falcon-h1-34b-1chip")
    ap.add_argument("--seeds", default="1234,1")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ns = ap.parse_args(argv)
    conf = manifest.config_of(manifest.load(), {"config": ns.config})
    on_chip = jax.devices()[0].platform == "tpu"
    serve = conf if on_chip else conf["rehearsal"]
    hf = conf if on_chip else {
        **serve["hf"], "reference_tolerance": conf["reference_tolerance"]}
    params = control.build_params(serve)
    me = sys.modules[__name__]
    fooled = []
    for name in ns.controls.split(","):
        how = dict(CONTROLS[name])
        if "state_dtype" in how:
            how["state_dtype"] = jnp.dtype(how["state_dtype"])
        for seed in (int(s) for s in ns.seeds.split(",")):
            streams = control_streams(params, hf, seed, how)
            res = check_reference(params, hf, streams,
                                  conf["reference_tolerance"], me)
            print(json.dumps({"note": "control", "control": name,
                              "seed": seed, **res}), flush=True)
            if res["passed"]:
                fooled.append((name, seed))
    print(json.dumps({"control_comes_out_not_correct": not fooled,
                      "passed": fooled}), flush=True)
    return 1 if fooled else 0


if __name__ == "__main__":
    raise SystemExit(main())
