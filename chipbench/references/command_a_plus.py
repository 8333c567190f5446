"""The plain reference of Command A+'s language model (CohereLabs
command-a-plus-05-2026, `model_type` cohere2_moe), as the configuration
`command-a-plus-1chip` brings it (`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no capacity, one layer at a time so that it fits.

The model, from its published config.json and, for what the file does not
define, the conventions the configuration lists under `assumed`:

    x  = LayerNorm(h)          one norm a layer: the mean taken off, a
                               learned weight, no bias, eps `layer_norm_eps`
    h' = h + Attn(x) + FFN(x)  `use_parallel_block`: both read the same x
    logits = logit_scale E LayerNorm_f(h)    E the embedding (tied)

- Attn: `q = W_q x` (`num_attention_heads` of `head_dim`), `k = W_k x`, `v
  = W_v x` (`num_key_value_heads`), no bias, no q/k norm. A layer is
  SLIDING or FULL by `layer_types`: sliding, `rope_gptj` (adjacent pairs
  `(x[2j], x[2j+1])`, the whole head, theta `rope_theta`) on q and k and the
  keys `s` in `[max(0, t - (sliding_window - 1)), t]`; full, NO rope and
  every `s <= t`. `o[t, h] = sum_s softmax_s(q[t, h] . k[s, h / G] /
  sqrt(head_dim)) v[s, h / G]`, G query heads a KV head; `y = W_o
  concat_h(o)`.
- FFN: `s = sigmoid(W_r x)` over all the published experts, the
  `num_experts_per_tok` of highest `s` (a STABLE descending sort: ties to the
  lower index), `w_e = s_e / sum_chosen s`; `routed = sum_e w_e E_e(x)` over
  the experts HELD (`experts_held` = [first, count]), expert by expert,
  every assignment, `E(x) = W_down (silu(W_gate x) * W_up x)`; `shared = 1 /
  n sum_{j < n} E_j(x)` over the `num_shared_experts` shared experts, EACH
  computed by itself from its own columns of the program's fused matrices
  and the mean taken (the program computes one fused MLP and scales it);
  `FFN(x) = routed + shared`.

Fed the served engine's own parameter tree (models/cohere2_moe.py: one
stack `layers`, a layer's leaves at its published index, an expert's
matrices at its place in the held range, the shared experts side by side).

Departures from the published description: (1) the file's cut: layers 0-3
of 32, experts 0-15 of 128, ids 0-32,767 of 262,144. (2) Attention runs in
UNIFORM blocks of `QUERY_BLOCK` query rows under `lax.map`: the same sums.
(3) What config.json names and does not define is the file's `assumed`: the
shared experts' "average", their width, the window's ends. (4) No vision
tower.

`compare` also judges, at a context past the ring's length on the
reference's own hidden states (`long_path`), the program's window
attention through a ring that has WRAPPED and its full layer's attention
over pages, a prompt piece and decode rows each. That is the one place
where this module runs code of the program.

`python -m chipbench.references.command_a_plus` is this configuration's
control: see `main`.
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import manifest
from chipbench import reference as dense
from chipbench.references import keye_vl as keye_ref

QUERY_BLOCK = 32  # query rows whose scores are computed at once
#: the context the attention paths are judged at: past `ring_tokens` (4,608)
#: so the ring has wrapped, 12 pieces of 512
LONG_CONTEXT = 6144
#: the judged queries: the last piece through the chunk paths, the last rows
#: of it through the decode paths (`judged` of a rehearsal's `hf`)
JUDGED = (512, 16)
FULL = "full_attention"


_f32 = keye_ref._f32
_int8 = keye_ref._int8
_distance = keye_ref._distance


def layer_norm(x, w, eps):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * _f32(w)


def _rope(z, positions, theta):
    """Adjacent-pair (`rope_gptj`) rotary of z [T, heads, d] at positions."""
    import jax.numpy as jnp

    d = z.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = _f32(positions)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = z[..., 0::2], z[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(z.shape)


def attended_keys(positions_q, t: int, window):
    """bool [Tq, T]: the keys each query attends: every `s <= t`, under a
    `window` the last `window` of them, its own among them."""
    import jax.numpy as jnp

    s = jnp.arange(t)[None]
    at = positions_q[:, None]
    keep = s <= at
    return keep if window is None else keep & (s >= at - (window - 1))


def attention_under(q, k, v, keep):
    """softmax(q . k / sqrt(d)) v over the keys `keep` [Tq, T] names, G
    query heads a KV head: q [Tq, Hq, d], k and v [T, Hkv, d] -> [Tq, Hq,
    d]."""
    import jax
    import jax.numpy as jnp

    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,khd->htk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("htk,khd->thd", p, v)


def attention_branch(x, lp: dict, hf: dict, kind: str, positions,
                     window=None, rope_full=False, tail=0):
    """Attn(x) over x [T, H] (normed), causal. Returns (out [T, H], the
    heads' outputs before W_o of the last `tail` queries [tail, Hq, d]).
    `window` overrides the file's and `rope_full` rotates a full layer's q
    and k too (the controls and the tests' cases)."""
    import jax
    import jax.numpy as jnp

    hq, hkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    t = x.shape[0]
    q = (x @ _f32(lp["wq"])).reshape(t, hq, d)
    k = (x @ _f32(lp["wk"])).reshape(t, hkv, d)
    v = (x @ _f32(lp["wv"])).reshape(t, hkv, d)
    sliding = kind != FULL
    if sliding or rope_full:
        q, k = (_rope(a, positions, hf["rope_theta"]) for a in (q, k))
    win = (window or hf["sliding_window"]) if sliding else None
    pad = -t % QUERY_BLOCK
    blocks = (t + pad) // QUERY_BLOCK

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])

    o = jax.lax.map(
        lambda args: attention_under(
            args[0], k, v, attended_keys(args[1], t, win)),
        (blocked(q), blocked(positions)))
    o = o.reshape(blocks * QUERY_BLOCK, hq, d)[:t]
    out = o.reshape(t, hq * d) @ _f32(lp["wo"])
    return out, o[t - tail:] if tail else None


def expert(x, wg, wu, wd):
    import jax

    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def moe_branch(x, lp: dict, hf: dict, held=None, shared=True, mean=True):
    """FFN(x) over x [T, H] (normed): the held experts' terms, expert by
    expert, every assignment, and the mean of the shared experts. `held`
    overrides the file's share and `shared` False leaves the shared experts
    out (the test of the shares adding up); `mean` False SUMS the shared
    experts (the control without the 1/4)."""
    import jax
    import jax.numpy as jnp

    first, count = held or hf["experts_held"]
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _f32(lp["w_router"]))
    order = jnp.argsort(-s, axis=-1, stable=True)[:, :k]  # [T, k]
    top = jnp.take_along_axis(s, order, axis=-1)
    if hf.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def one(y, args):
        e, wg, wu, wd = args
        share = jnp.sum(jnp.where(order == first + e, top, 0.0), axis=-1)
        return y + share[:, None] * expert(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), lp["we_gate"][:count], lp["we_up"][:count],
        lp["we_down"][:count]))
    if shared:
        n, i = hf["num_shared_experts"], hf["intermediate_size"]
        each = [expert(x, lp["ws_gate"][:, j * i:(j + 1) * i],
                       lp["ws_up"][:, j * i:(j + 1) * i],
                       lp["ws_down"][j * i:(j + 1) * i]) for j in range(n)]
        y = y + sum(each) / (n if mean else 1)
    return y


def block(h, lp: dict, hf: dict, kind: str, positions, moe=None, **attn):
    """One layer over h [T, H]: (h', the attention trace)."""
    x = layer_norm(h, lp["norm"], hf["layer_norm_eps"])
    a, trace = attention_branch(x, lp, hf, kind, positions, **attn)
    return h + a + moe_branch(x, lp, hf, **(moe or {})), trace


# -- one precision down, for the control -------------------------------------


def to_int8(lp: dict) -> dict:
    """A layer one precision below bf16: every matrix int8, symmetric per
    output channel, kept as the float32 values int8 can hold (the router
    stays float32, as the configuration states it). `hidden_states` and
    `log_probs` lower the embedding, a scale a row as a table and a scale
    a column as the head it also is."""
    out = dict(lp)
    for name, w in lp.items():
        if w.ndim >= 2 and name != "w_router":
            out[name] = _int8(w, -2)
    return out


_LAYERS: dict = {}


def _layer_fn(hf: dict, kind: str, lower, how: dict):
    """One jitted layer a distinct reading of the configuration."""
    import jax

    widths = tuple((k, str(v)) for k, v in sorted(hf.items())
                   if isinstance(v, (int, float, list))
                   and k != "layer_types")
    low = lower or (lambda lp: lp)
    key = (kind, lower, widths,
           tuple(sorted((k, str(v)) for k, v in how.items())))
    if key not in _LAYERS:
        _LAYERS[key] = jax.jit(lambda h, lp, pos: block(
            h, low(lp), hf, kind, pos, **how))
    return _LAYERS[key]


def hidden_states(params: dict, hf: dict, ids, lower=None, each=None,
                  **how):
    """The residual stream after the last layer over the sequence `ids`
    [T] (f32). `each` is called with every layer's input, kind, index and
    trace as the layer is done."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(params["embed"][ids])
    if lower is not None:
        h = _int8(h, -1)
    for li in range(hf["num_hidden_layers"]):
        lp = jax.tree.map(lambda w, i=li: w[i], params["layers"])
        kind = hf["layer_types"][li]
        h_in = h
        with jax.default_matmul_precision("highest"):
            h, trace = _layer_fn(hf, kind, lower, how)(h, lp, pos)
        if each is not None:  # (outside the precision the reference asks)
            each({"input": h_in, "kind": kind, "layer": li, "heads": trace})
        del trace, h_in
    return h


def log_probs(params: dict, hf: dict, ids, at, **how) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of the
    sequence `ids`: [len(at), vocab] float32."""
    import jax
    import jax.numpy as jnp

    x = hidden_states(params, hf, ids, **how)
    with jax.default_matmul_precision("highest"):
        low = how.get("lower") is not None
        head = _LAYERS.setdefault(("head", low), jax.jit(
            lambda h, e: h @ (_int8(e, 1) if low else _f32(e)).T))
        h = layer_norm(x[jnp.asarray(at)], params["final_norm"],
                       hf["layer_norm_eps"])
        logits = head(h, params["embed"]) * hf.get("logit_scale", 1)
        out = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(out)


# -- the window and the full path at depth, on the program's own routines -----


def _program_cfg(hf: dict):
    import jax

    from dynamo_tpu.models.registry import get_model

    return get_model(
        hf["preset"], dtype=hf.get("dtype", "bfloat16"),
        attention_impl=hf.get("attention_impl") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"),
    ).config


def _judge(cfg, kind: str, context: int, page: int, judged, fault):
    """The program's side of one layer's attention, jitted: the layer's
    norm and projections of the reference's input, the rows of all but the
    last `judged[0]` tokens landed in the cache as the steps of a prompt
    land them (a sliding layer: ONE sequence's ring, piece by piece, so
    that it wraps; a full layer: its pages), the last `judged[0]` queries as
    ONE prompt piece and the last `judged[1]` as decode steps, one after
    the other, each landing its row. Returns (piece [Qc, Hq, d], decode
    [Qd, Hq, d]): the heads' outputs before W_o."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import cohere2_moe as c2
    from dynamo_tpu.models.llama import (
        KVPages, StepGroup, _mm, apply_rope, attention_block,
        land_staged_kv, maybe_decode_work)
    from dynamo_tpu.ops.paged_attention import decode_work_list

    qc, qd = judged
    sliding = kind != FULL
    if fault == "short_window":  # the window one token short
        cfg = dataclasses.replace(
            cfg, sliding_window=cfg.sliding_window - 1)
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    geo = cfg.swa_geo if sliding else cfg.full_geo
    dpad = geo.kv_head_dim - d
    one = dataclasses.replace(cfg, layer_types=(c2.SLIDING, c2.FULL))
    n_pages = context // page
    step = min(qc, cfg.ring_run // page * page)
    lo = context - qc

    def judge(h_in, lp):
        x = c2.layer_norm(h_in.astype(cfg.dtype)[None], lp["norm"],
                          cfg.layer_norm_eps)
        pos = jnp.arange(context, dtype=jnp.int32)[None]
        q = _mm(x[:, lo:], lp, "wq", cfg.dtype).reshape(1, qc, hq, d)
        k = _mm(x, lp, "wk", cfg.dtype).reshape(1, context, hkv, d)
        v = _mm(x, lp, "wv", cfg.dtype).reshape(1, context, hkv, d)
        if sliding or fault == "rope_full":
            q = apply_rope(q, pos[:, lo:], cfg.swa_geo)
            k = apply_rope(k, pos, cfg.swa_geo)
        if dpad:
            q, k, v = (jnp.pad(a, ((0, 0),) * 3 + ((0, dpad),))
                       for a in (q, k, v))
        cache = c2.init_cache(one, n_pages + 1, page, 1)
        tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        slot, zero = jnp.ones((1,), jnp.int32), jnp.int32(0)
        cut = lambda a, at0, n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, at0, n, 1)

        def group(at0, n):
            at = at0 + jnp.arange(n, dtype=jnp.int32)[None]
            return StepGroup(jnp.zeros((1, n), jnp.int32), at,
                             jnp.ones((1, n), bool), tables,
                             state_rows=jnp.ones((1, 2), jnp.int32))

        def land(state, at0, n):
            """Rows `at0` .. `at0 + n` into the cache, as a step does."""
            g = group(at0, n)
            rows = (cut(k, at0, n), cut(v, at0, n))
            if not sliding:
                if not cfg.kernels:
                    from dynamo_tpu.models.llama import paged_scatter_kv

                    return paged_scatter_kv(state, zero, *rows, tables,
                                            g.positions, g.valid)
                return land_staged_kv(
                    state, tuple(r[None] for r in rows), tables,
                    g.positions, g.valid)
            if not cfg.kernels:
                return c2.ring_write(state, zero, *rows, slot, g.positions,
                                     g.valid)
            return c2.land_rings(state, *(r[None] for r in rows), slot,
                                 g.positions, g.valid, page)

        def attend(state, at0, n):
            """The `n` queries from position `at0` on as ONE step of the
            program over the cache as it stands, the rows in hand (without
            the kernels the program writes them first, itself)."""
            g = group(at0, n)
            qs, ks, vs = cut(q, at0 - lo, n), cut(k, at0, n), cut(v, at0, n)
            if sliding:
                walk = None
                if cfg.kernels and n == 1:
                    walk = c2.ring_walk(g.positions, g.valid, slot, cfg, page)
                    walk = (*walk, decode_work_list(walk[0], walk[1]))
                o, _ = c2.window_attend(qs, ks, vs, state, zero, g, walk, cfg,
                                        page)
            elif cfg.kernels and n > 1:
                o = c2.full_piece(qs, ks, vs, state, zero, g, cfg)
            else:
                work = maybe_decode_work(geo, g.tokens, g.positions, None,
                                         tables)
                strip = lambda a: a[..., :d]  # noqa: E731
                o, _, _ = attention_block(
                    strip(qs), strip(ks), strip(vs), state, zero, tables,
                    g.positions, g.valid, geo, decode_work=work)
            return o.reshape(n, hq, -1)[..., :d]

        state = (cache.ring, cache.ring_v) if sliding else KVPages(
            k=cache.k, v=cache.v)
        before = jax.lax.fori_loop(
            0, lo // step, lambda i, st: land(st, i * step, step), state)
        o_c = attend(before, lo, qc)
        # the piece's rows up to the first decode row: whole pages, then
        # the rest (the page writer takes runs of one length a call)
        whole = (qc - qd) // page * page
        state = land(before, lo, whole) if whole else before
        if qc - qd > whole:
            state = land(state, lo + whole, qc - qd - whole)

        def decode(st, t):
            o = attend(st, t, 1)
            return land(st, t, 1), o[0]

        _, o_d = jax.lax.scan(
            decode, state, jnp.arange(context - qd, context, dtype=jnp.int32))
        return o_c, o_d

    return jax.jit(judge)


def long_path(params: dict, hf: dict, context: int = LONG_CONTEXT,
              seed: int = 1234, fault=None) -> dict:
    """The program's attention at `context` tokens against the reference,
    layer by layer on the REFERENCE's hidden states: `window_attn_distance`
    (a sliding layer's heads through a ring that has wrapped, a prompt piece
    and decode rows, against the reference's attention over the window's
    keys, as a share of its norm, the largest over layers, paths and heads)
    and `full_attn_distance` (the full layer's over its pages, likewise)."""
    import jax

    from chipbench import traffic

    t0 = time.perf_counter()
    cfg = _program_cfg(hf)
    page = hf.get("page_size", 64)
    qc, qd = judged = tuple(hf.get("judged", JUDGED))
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    judges = {kind: _judge(cfg, kind, context, page, judged, fault)
              for kind in set(hf["layer_types"][:hf["num_hidden_layers"]])}
    worst = {"window": 0.0, "full": 0.0}
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731

    def judge_layer(tr):
        lp = jax.tree.map(lambda w, i=tr["layer"]: w[i], params["layers"])
        o_c, o_d = judges[tr["kind"]](tr["input"], lp)
        want = f32(tr["heads"])
        name = "full" if tr["kind"] == FULL else "window"
        worst[name] = max(worst[name], _distance(f32(o_c), want),
                          _distance(f32(o_d), want[qc - qd:]))

    hidden_states(params, hf, ids, each=judge_layer, tail=qc)
    return {"window_attn_distance": worst["window"],
            "full_attn_distance": worst["full"], "long_context": context,
            "long_path_s": round(time.perf_counter() - t0, 1)}


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    """`chipbench.reference.compare` through this module's `log_probs`,
    and, where `hf` names the served preset, `long_path`'s two readings
    under `reference_tolerance.max_window_attn_distance` and
    `max_full_attn_distance` of the same file. The harness's verdict reads
    four keys (chipbench/run.py `check_reference`): a distance past its
    limit is reported as a mean log-prob drift past every limit, the
    measured one kept beside it. A stream may bring the control's readings
    in the program's place (`long_path`)."""
    def forward(p, c, ids, at):
        return log_probs(p, c, ids, at, **how)

    t0 = time.perf_counter()
    res = dense.compare(params, hf, streams, forward=forward)
    res["streams_s"] = round(time.perf_counter() - t0, 1)
    if not hf.get("preset"):
        return res
    tol = hf.get("reference_tolerance", {})
    theirs = next((s["long_path"] for s in streams if "long_path" in s), None)
    res.update(theirs if theirs is not None else long_path(
        params, hf, context=hf.get("long_context", LONG_CONTEXT)))
    failed = [name for name in ("window_attn_distance", "full_attn_distance")
              if res[name] > tol.get("max_" + name, math.inf)]
    if failed:
        res["failed_by"] = failed
        res["mean_logprob_drift_of_tokens"] = res["mean_logprob_drift"]
        res["mean_logprob_drift"] = float("inf")
    return res


def served_widths(cfg) -> dict:
    """A Cohere2MoeConfig's sizes under the published file's keys: every
    one of them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta,
        "sliding_window": cfg.sliding_window,
        "num_experts": cfg.experts_here,
        "num_experts_published": cfg.n_routed_experts,
        "experts_held": list(cfg.experts_held or (0, cfg.n_routed_experts)),
        "num_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "logit_scale": cfg.logit_scale,
        "layer_norm_eps": cfg.layer_norm_eps,
        "vocab_size": cfg.vocab_size,
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct: (a) the weights one precision below bf16, (b) the shared
#: experts summed, not averaged (the missing 1/4), both in the REFERENCE
#: that decodes the streams (they fail on the streams' log-probs); (c) the
#: program's window one token short and (d) a rope on the program's full
#: layer, faults PLANTED in the program's attention at depth (they fail on
#: a distance alone: the short streams never leave the window, and (d)'s
#: streams are the reference's own)
CONTROLS = {
    "int8_weights": {"lower": to_int8},
    "shared_summed": {"moe": {"mean": False}},
    "short_window": {"walk": {"fault": "short_window"}},
    "rope_full": {"walk": {"fault": "rope_full"}},
}
_LONG_UNTOUCHED = {"window_attn_distance": 0.0, "full_attn_distance": 0.0}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it, as
    chipbench/references/dots3.py `control_streams`."""
    from chipbench import traffic

    how = dict(how)
    walk = how.pop("walk", None)
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
    out[0]["long_path"] = dict(_LONG_UNTOUCHED) if walk is None else (
        long_path(params, hf, hf.get("long_context", LONG_CONTEXT), seed,
                  **walk))
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.command_a_plus [--seeds a,b] [--config
    command-a-plus-1chip] [--controls a,b]: each of CONTROLS decodes the
    benchmark's greedy streams and goes through `compare` against the
    reference as it stands, under the configuration's
    `reference_tolerance`; each has to come out as not correct."""
    import argparse
    import json
    import sys

    import jax

    from chipbench import control
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="command-a-plus-1chip")
    ap.add_argument("--seeds", default="1234")
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
        for seed in (int(s) for s in ns.seeds.split(",")):
            streams = control_streams(params, hf, seed, dict(CONTROLS[name]))
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
