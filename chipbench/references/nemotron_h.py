"""The plain reference of Nemotron-H (NVIDIA-Nemotron-3-Nano-30B-A3B,
`model_type` nemotron_h), as the configuration
`nemotron3-nano-30b-a3b-1chip` brings it (`reference_module` in its
file): float32, `jax.default_matmul_precision("highest")`, no cache, no
state pool, no chunking, no kernels, one layer at a time so that it fits.

The model, from its published config.json and the Nemotron-H / Mamba-2
descriptions (arXiv 2504.03624, 2405.21060): every layer is one mixer
behind a pre-RMSNorm and a residual add, `x + mixer(RMSNorm(x))`, its
kind the layer's character in `hybrid_override_pattern`.

- `M`, Mamba-2: `in_proj` (no bias) to z | xBC | dt with d_inner =
  `mamba_num_heads` x `mamba_head_dim` and xBC = x | B | C (`n_groups`
  groups of `ssm_state_size` each); a causal depthwise conv of
  `conv_kernel` taps with bias over xBC (zeros before the first token),
  then SiLU; `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)` a head;
  head h of group h // (heads / n_groups): `S_t = exp(dt_t A) S_(t-1) +
  dt_t x_t (x) B_t`, `y_t = S_t C_t + D_h x_t`, HERE TOKEN BY TOKEN
  (`lax.scan`), never in chunks; the gated norm, gate first,
  `RMSNorm(y * silu(z))` over `n_groups` groups with a learned weight;
  `out_proj`.
- `*`: grouped-query attention, `num_attention_heads` query and
  `num_key_value_heads` KV heads of `head_dim`, no bias, causal softmax at
  1 / sqrt(head_dim), NO rotary embedding.
- `E`: router in float32, sigmoid scores, the top `num_experts_per_tok`
  of scores + correction bias (`n_group` 1: no group limit), weights the
  uncorrected scores renormalised (`norm_topk_prob`) times
  `routed_scaling_factor`; a routed expert is `down(relu(up x)^2)`; one
  shared expert of the same form at `moe_shared_expert_intermediate_size`
  is added for every token. Every expert HELD is computed for every token
  and weighted by a [T, E] matrix that is zero off the top k and off the
  experts held.
- Final RMSNorm, untied head.

Fed the served engine's own parameter tree (`mamba`, `attn`, `moe`
stacks of models/nemotron_h.py, by kind, in layer order).

Departures from the published description: (1) no rotary embedding in
the attention layers although the config carries `rope_theta`:
Nemotron-H's block applies none (the family's convention, `assumed` in
the configuration file); program and reference alike. (2) The file's
cut: the layers are the first `num_hidden_layers` characters of the
pattern, and of the router's experts this chip holds
`n_routed_experts` starting at `experts_held_first`; what the absent
experts would add is left out, here as in the program (model-configs
guide, section 4). (3) The routed experts are computed densely: the same
sum in another order. (4) An expert's matrices arrive zero-padded from
1856 to 1920 columns / rows (`assumed`): relu(0)^2 = 0, so the padding
adds exactly nothing.

`compare` reads, beside the log-probs, the precision the recurrent state
is carried in (`state_distance`): the program's own pool and decode
routine on the reference's inputs, against the reference's recurrence.
That is the one place where this module runs code of the program.

`python -m chipbench.references.nemotron_h` is this configuration's
control: see `main`.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench import reference as dense


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def pattern_of(hf: dict) -> str:
    return hf["hybrid_override_pattern"][: hf["num_hidden_layers"]]


def _eps(hf: dict) -> float:
    return hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5))


def _relu2(x):
    import jax.numpy as jnp

    x = jnp.maximum(x, 0.0)
    return x * x


def mamba_block(x, lp: dict, hf: dict, state_dtype=None, skip=True,
                split=None):
    """One Mamba-2 layer over x [T, H] (f32), from an empty state.
    `state_dtype` and `skip` are the control's: the state carried in a
    lower precision, the `D x` term left out. With `split` it returns
    (out, trace): the recurrence's own inputs of the tokens from `split`
    on (`u` = dt x, `decay` = exp(dt A), `b`, `c`), the state those
    tokens start from and the state the last one leaves
    (`state_distance`)."""
    import jax
    import jax.numpy as jnp

    nh, hd = hf["mamba_num_heads"], hf["mamba_head_dim"]
    n, g, kk = hf["ssm_state_size"], hf["n_groups"], hf["conv_kernel"]
    di, t = nh * hd, x.shape[0]
    h = dense._rms(x, lp["norm"], _eps(hf))
    zxbcdt = h @ _f32(lp["in_proj"])
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di : 2 * di + 2 * g * n],
                  zxbcdt[:, 2 * di + 2 * g * n :])
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
    if skip:
        y = y + _f32(lp["D"])[None, :, None] * xs
    y = y.reshape(t, di) * jax.nn.silu(z)
    yg = y.reshape(t, g, di // g)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + _eps(hf))
    y = yg.reshape(t, di) * _f32(lp["gate_norm"])
    out = x + y @ _f32(lp["out_proj"])
    return out if split is None else (out, trace)


def attn_block(x, lp: dict, hf: dict):
    import jax
    import jax.numpy as jnp

    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    d, t = hf["head_dim"], x.shape[0]
    h = dense._rms(x, lp["norm"], _eps(hf))
    q = (h @ _f32(lp["wq"])).reshape(t, heads, d)
    k = (h @ _f32(lp["wk"])).reshape(t, kv_heads, d)
    v = (h @ _f32(lp["wv"])).reshape(t, kv_heads, d)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("thd,khd->htk", q, k) / math.sqrt(d)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("htk,khd->thd", p, v).reshape(t, heads * d)
    return x + a @ _f32(lp["wo"])


def route(h, lp: dict, hf: dict):
    """(weights [T, E] over ALL the router's experts, zero off the top
    k; the top-k ids [T, k])."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(h @ _f32(lp["w_router"]))
    _, topi = jax.lax.top_k(scores + _f32(lp["router_bias"]),
                            hf["num_experts_per_tok"])
    topw = jnp.take_along_axis(scores, topi, axis=-1)
    if hf.get("norm_topk_prob", True):
        topw = topw / (topw.sum(-1, keepdims=True) + 1e-20)
    topw = topw * hf.get("routed_scaling_factor", 1.0)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], topi].set(topw)
    return weight, topi


def moe_block(x, lp: dict, hf: dict):
    """Returns (x, top-k ids [T, k])."""
    import jax.numpy as jnp

    h = dense._rms(x, lp["norm"], _eps(hf))
    weight, topi = route(h, lp, hf)
    first, held = hf.get("experts_held_first", 0), lp["we_up"].shape[0]
    hidden = _relu2(jnp.einsum("th,ehi->eti", h, _f32(lp["we_up"])))
    routed = jnp.einsum("eti,eih,te->th", hidden, _f32(lp["we_down"]),
                        weight[:, first : first + held])
    shared = _relu2(h @ _f32(lp["ws_up"])) @ _f32(lp["ws_down"])
    return x + routed + shared, topi


MATRICES = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "we_up",
            "we_down", "ws_up", "ws_down")


def to_int8(lp: dict) -> dict:
    """The layer's matrices one precision below bf16: int8, symmetric per
    output channel (per expert and output channel), kept as the float32
    values int8 can hold."""
    import jax.numpy as jnp

    out = dict(lp)
    for name in MATRICES:
        if name in lp:
            w = _f32(lp[name])
            scale = jnp.maximum(
                jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0, 1e-8)
            out[name] = jnp.round(w / scale) * scale
    return out


def layers_of(params: dict, hf: dict):
    """(kind, the layer's own tree) in layer order."""
    import jax

    seen = {"M": 0, "*": 0, "E": 0}
    stacks = {"M": params["mamba"], "*": params["attn"], "E": params["moe"]}
    for sym in pattern_of(hf):
        i = seen[sym]
        seen[sym] += 1
        yield sym, jax.tree.map(lambda a: a[i], stacks[sym])


def _jitted(hf: dict, lower, state_dtype, skip, split=None):
    import jax

    low = lower or (lambda lp: lp)
    return {
        "M": jax.jit(lambda x, lp: mamba_block(
            x, low(lp), hf, state_dtype, skip, split)),
        "*": jax.jit(lambda x, lp: attn_block(x, low(lp), hf)),
        "E": jax.jit(lambda x, lp: moe_block(x, low(lp), hf)[0]),
    }


def log_probs(params: dict, hf: dict, ids, at, lower=None, state_dtype=None,
              skip=True, split=None, traces=None) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32. `lower`, `state_dtype`
    and `skip` are the control's. With `split`, every Mamba-2 layer's
    trace of the tokens from `split` on (`mamba_block`) is appended to
    `traces`, in layer order."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        fns = _jitted(hf, lower, state_dtype, skip, split)
        x = _f32(params["embed"][ids])
        for sym, lp in layers_of(params, hf):
            x = fns[sym](x, lp)
            if sym == "M" and split is not None:
                x, trace = x
                traces.append(trace)

        @jax.jit
        def head(x, norm, w):
            h = dense._rms(x, norm, _eps(hf))
            return jax.nn.log_softmax(h @ _f32(w), axis=-1)

        out = head(x[jnp.asarray(at)], params["final_norm"],
                   params["lm_head"])
    return np.asarray(out)


#: where `compare` leaves what its routing probe read, for the reader of
#: `moe_experts_hbm_share.nano3` (chipbench/layer_metrics/)
PROBE_FILE = "nemotron_h_routing_probe.json"
#: the control switches the probe off: it compares many streams
PROBE = True


def routing_probe(params: dict, hf: dict, rows: int = 64, tokens: int = 32,
                  seed: int = 1234) -> dict:
    """How the served weights route one decode step's worth of rows: the
    last tokens of `rows` random sequences through these layers at the
    default matmul precision (only the top-k choice is read), and per
    expert layer how many of the experts HELD they touch, how many of all
    the router's experts, and the share of the assignments that fall on
    an expert held. Read outside every step and every timing."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic

    ids = jnp.asarray(np.random.default_rng(seed).integers(
        traffic.FIRST_ID, hf["vocab_size"], (rows, tokens)), jnp.int32)
    fns = {
        "M": jax.jit(jax.vmap(lambda x, lp: (mamba_block(x, lp, hf), 0),
                              in_axes=(0, None))),
        "*": jax.jit(jax.vmap(lambda x, lp: (attn_block(x, lp, hf), 0),
                              in_axes=(0, None))),
        "E": jax.jit(jax.vmap(lambda x, lp: moe_block(x, lp, hf),
                              in_axes=(0, None))),
    }
    x = _f32(params["embed"][ids])
    first = hf.get("experts_held_first", 0)
    held_touched, all_touched, share = [], [], []
    for sym, lp in layers_of(params, hf):
        x, topi = fns[sym](x, lp)
        if sym != "E":
            continue
        picks = np.asarray(topi[:, -1]).ravel()
        held = lp["we_up"].shape[0]
        here = picks[(picks >= first) & (picks < first + held)]
        held_touched.append(int(len(set(here.tolist()))))
        all_touched.append(int(len(set(picks.tolist()))))
        share.append(len(here) / max(len(picks), 1))
    return {"rows": rows, "experts_touched": float(np.mean(held_touched)),
            "experts_touched_of_all": float(np.mean(all_touched)),
            "assignments_held_share": float(np.mean(share)),
            "per_layer_touched": held_touched}


def decode_through_the_pool(pool, layer, tr: dict):
    """`tr`'s tokens through the program's decode routine in `pool`
    [layers, 4 entries, heads, head_dim, state] (slot 1 of two
    generations: entries 1 and 3), layer `layer`, from `tr["start"]`:
    (the pool, the state after the last token in float32)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops import ssm_state

    entry = lambda k: jnp.reshape(1 + 2 * (k % 2), (1,))  # noqa: E731
    pool = ssm_state.write_rows(pool, layer, entry(0), tr["start"][None])

    def step(pool, tok):
        k, u, decay, b, c = tok
        _, pool = ssm_state.ssm_decode_step(
            pool, layer, entry(k), entry(k + 1), u[None], decay[None],
            b[None], c[None])
        return pool, None

    n = tr["u"].shape[0]
    pool, _ = jax.lax.scan(
        step, pool, (jnp.arange(n), tr["u"], tr["decay"], tr["b"], tr["c"]))
    return pool, _f32(pool[layer, 1 + 2 * (n % 2)])


def served_states(hf: dict, traces: list) -> list:
    """What the PROGRAM's state routines make of the decoded tokens of one
    stream: per Mamba-2 layer the state after the last of them, float32
    [heads, head_dim, state]. The pool is the one the engine allocates
    (the adapter's own `init_kv`, one slot of two generations), each
    layer's slot starts from the reference's state after the prompt
    (`write_rows`, which rounds to whatever the pool holds), and every
    token goes through `ops/ssm_state.ssm_decode_step` (the kernel on a
    TPU) with the reference's own `u`, `decay`, `b` and `c`, read from
    one generation and written to the other as a dispatch does. Start and
    inputs are the reference's, so what differs from the reference's
    state is what the pool and the routine round: nothing else."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.registry import get_model

    pool = get_model(hf["preset"]).init_kv(2, 1, state_slots=1).ssm
    run = jax.jit(decode_through_the_pool)
    out = []
    for layer, tr in enumerate(traces):
        tr = {k: tr[k] for k in ("start", "u", "decay", "b", "c")}
        pool, end = run(pool, jnp.int32(layer), tr)
        out.append(end)
    return out


def state_distance(hf: dict, streams: list[dict], traces: list) -> float:
    """The largest distance, over streams and Mamba-2 layers, between the
    state a stream's decoded tokens leave and the reference's, as a share
    of the reference's norm. The state is the program's (`served_states`)
    unless the stream brings its own `ssm_state` (the control: a lowered
    reference in the program's place). It judges the PRECISION THE STATE
    IS CARRIED IN, which the log-probs cannot see at these lengths (the
    configuration file gives the readings): float32 carried through 64
    tokens reads a rounding of float32, bfloat16 a rounding of bfloat16
    times the root of the steps a head remembers."""
    worst = 0.0
    for s, trs in zip(streams, traces):
        theirs = s.get("ssm_state") or served_states(hf, trs)
        for got, tr in zip(theirs, trs):
            want = np.asarray(tr["end"], np.float64)
            worst = max(worst, float(
                np.linalg.norm(np.asarray(got, np.float64) - want)
                / max(np.linalg.norm(want), 1e-30)))
    return worst


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
    if PROBE:
        import json
        import os

        from chipbench import manifest

        probe = routing_probe(params, hf)
        res["experts_held_touched_at_64_rows"] = probe["experts_touched"]
        res["assignments_held_share"] = probe["assignments_held_share"]
        manifest.RUN_DIR.mkdir(parents=True, exist_ok=True)
        with open(manifest.RUN_DIR / PROBE_FILE, "w") as f:
            json.dump({"pid": os.getpid(), **probe}, f)
    return res


def served_widths(cfg) -> dict:
    """A NemotronHConfig's sizes under the published file's keys: every
    one of them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size,
        "mamba_num_heads": cfg.mamba_num_heads,
        "mamba_head_dim": cfg.mamba_head_dim,
        "ssm_state_size": cfg.ssm_state_size,
        "n_groups": cfg.n_groups,
        "conv_kernel": cfg.conv_kernel,
        "chunk_size": cfg.chunk_size,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "intermediate_size": cfg.moe_intermediate_size,
        "moe_shared_expert_intermediate_size":
            cfg.moe_shared_expert_intermediate_size,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_group": cfg.n_group,
        "topk_group": cfg.topk_group,
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct: the recurrent state carried in bfloat16 instead of the
#: float32 the configuration states, the weights one precision below
#: bf16, one of the k experts left out, the `D x` skip term left out. The
#: last three fail on the log-probs. The first does not, at the harness's
#: lengths (112 tokens: a mean drift of 0.008-0.021 on the chip, a sixth
#: to a fifteenth of the program's own distance from float32), and fails
#: on the state itself (`state_distance`)
CONTROLS = {
    "bf16_state": {"state_dtype": "bfloat16"},
    "int8_weights": {"lower": to_int8},
    "dropped_expert": {"experts_per_tok": -1},
    "no_skip_term": {"skip": False},
}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it (the
    whole padded sequence every step: a position sees nothing after it,
    in attention by the mask and in the state-space layers by the
    recurrence's direction), each with the state its Mamba-2 layers are
    left in (`ssm_state`: what `state_distance` reads in the program's
    place)."""
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
        traces: list = []
        log_probs(params, hf, ids, [total - 1], split=prompt_len,
                  traces=traces, **how)
        out.append({"prompt": prompt, "out": toks, "logprobs": lps,
                    "ssm_state": [np.asarray(tr["end"]) for tr in traces]})
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.nemotron_h [--seeds a,b] [--config
    nemotron3-nano-30b-a3b-1chip] [--controls a,b]: each of CONTROLS
    decodes the benchmark's greedy streams and goes through `compare`
    against the reference as it stands, under the configuration's
    `reference_tolerance`; each has to come out as not correct."""
    import argparse
    import json
    import sys

    import jax
    import jax.numpy as jnp

    from chipbench import control, manifest
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="nemotron3-nano-30b-a3b-1chip")
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
    me.PROBE = False
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
