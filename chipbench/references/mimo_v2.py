"""The plain reference of MiMo-V2.5's language model (XiaomiMiMo,
`model_type` mimo_v2), as the configuration `mimo-v2.5-1chip` brings it
(`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no capacity, one layer at a time so that it fits.

The model, from its published config.json and, for what the file does not
define, the conventions the configuration lists under `assumed`:

    h = h + Attn_l(RMSNorm(h));  h = h + FFN_l(RMSNorm(h))   pre-norm, a
                                 weight and no bias, eps `layernorm_epsilon`
    logits = W_head RMSNorm_f(h)                             untied

- Attn, both kinds: `q = W_q x` (`num_attention_heads` of `head_dim` 192),
  `k = W_k x` (Hkv heads of 192), `v = W_v x` (Hkv heads of `v_head_dim`
  128), no bias. Rope on the FIRST `int(head_dim x partial_rotary_factor)` =
  64 dims of q and k, pairs split by halves of the 64 (`rotate_half`), the
  other 128 pass. `v <- attention_value_scale v`. `s[t, u] = q_t . k_u /
  sqrt(head_dim)`, heads / Hkv query heads a KV head.
  - FULL (`hybrid_layer_pattern` 0): Hkv = `num_key_value_heads`, theta
    `rope_theta`, every `u <= t`, `p = softmax_u(s)`;
  - WINDOW (`hybrid_layer_pattern` 1): Hkv = `swa_num_key_value_heads`,
    theta `swa_rope_theta`, `u` in `[t - (sliding_window - 1), t]`, `p[t, u]
    = exp(s[t, u]) / (exp(b_h) + sum_u' exp(s[t, u']))`, `b_h` one learned
    scalar a QUERY head (`add_swa_attention_sink_bias`): a column beside
    the scaled scores, dropped after the softmax.
  `o_t = sum_u p[t, u] v_u`; `y = W_o concat_h(o)`.
- FFN, `moe_layer_freq` 0: `W_down (silu(W_gate x) * W_up x)`,
  `intermediate_size` wide. `moe_layer_freq` 1: `g = sigmoid(W_r x)` over
  all the published experts, `choice = g + e_bias`, the
  `num_experts_per_tok` of highest `choice` (a STABLE descending sort: ties
  to the lower index), `w_e = g_e / sum_chosen g` (`norm_topk_prob`), times
  `routed_scaling_factor` (null: 1); `FFN(x) = sum_e w_e E_e(x)` over the
  experts HELD (`experts_held` = [first, count]), expert by expert, every
  assignment; no shared expert.

Fed the served engine's own parameter tree (models/mimo_v2.py: stacks
`full`, `swa`, `dense`, `moe`, a held layer's leaves in the order of
`layer_ids`, an expert's matrices at its place in the held range).

Departures from the published description: (1) the file's cut: the
published layers `layer_ids` (0 and 6-11) of 48, experts 0-15 of 256, ids
0-19,071 of 152,576. (2) Attention runs in UNIFORM blocks of `QUERY_BLOCK`
query rows under `lax.map`: the same sums. (3) What config.json names and
does not define is the file's `assumed`: where the sink enters, where the
value scale, which dims rotate, the window's ends. (4) No MTP layers, no
vision or audio tower; `attention_chunk_size` is not modelled.

`compare` also judges, at a context past the ring's length on the
reference's own hidden states (`long_path`), the program's window
attention through a ring that has WRAPPED and its full layers' attention
over pages, a prompt piece and decode rows each. That is the one place
where this module runs code of the program.

`python -m chipbench.references.mimo_v2` is this configuration's control:
see `main`.
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import manifest
from chipbench import reference as dense
from chipbench.references import keye_vl as keye_ref

QUERY_BLOCK = 32  # query rows whose scores are computed at once
#: the context the attention paths are judged at: the mean prompt of the
#: cell's traffic (`longctx`: 8,193-16,384), 24 pieces of 512: a window
#: layer's ring of 640 rows has wrapped 19 times, a full layer's decode row
#: walks 192 pages in 32 blocks of 6 and its piece attends 12k gathered keys
LONG_CONTEXT = 12288
#: the judged queries: the last piece through the chunk paths, the last rows
#: of it through the decode paths (`judged` of a rehearsal's `hf`)
JUDGED = (512, 16)

_f32 = keye_ref._f32
_int8 = keye_ref._int8
_distance = keye_ref._distance


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        _f32(w))


def _rope(z, positions, theta, rotary: int):
    """Rotary of the first `rotary` dims of z [T, heads, d] at `positions`,
    pairs split by halves of the `rotary` (`rotate_half`); the rest pass."""
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                           / rotary))
    ang = _f32(positions)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    lo, hi = z[..., :rotary // 2], z[..., rotary // 2:rotary]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, z[..., rotary:]], axis=-1)


def attended_keys(positions_q, t: int, window):
    """bool [Tq, T]: the keys each query attends: every `s <= t`, under a
    `window` the last `window` of them, its own among them."""
    import jax.numpy as jnp

    s = jnp.arange(t)[None]
    at = positions_q[:, None]
    keep = s <= at
    return keep if window is None else keep & (s >= at - (window - 1))


def attention_under(q, k, v, keep, sink=None):
    """softmax(q . k / sqrt(d)) v over the keys `keep` [Tq, T] names, G
    query heads a KV head: q [Tq, Hq, d], k [T, Hkv, d], v [T, Hkv, dv] ->
    [Tq, Hq, dv]. `sink` [Hq]: one more column of that logit beside the
    scaled scores, dropped after the softmax."""
    import jax
    import jax.numpy as jnp

    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,khd->htk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(keep[None], s, -jnp.inf)
    if sink is not None:
        col = jnp.broadcast_to(_f32(sink)[:, None, None], (*s.shape[:2], 1))
        s = jnp.concatenate([s, col], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :keep.shape[-1]]
    return jnp.einsum("htk,khd->thd", p, v)


def rotary_dims(hf: dict) -> int:
    return int(hf["head_dim"] * hf["partial_rotary_factor"])


def attention_branch(x, lp: dict, hf: dict, window_layer: bool, positions,
                     window=None, sink=True, value_scale=True,
                     thetas_swapped=False, rope_whole_head=False, tail=0):
    """Attn(x) over x [T, H] (normed), causal. Returns (out [T, H], the
    heads' outputs before W_o of the last `tail` queries [tail, Hq, dv]).
    The keywords are the controls' and the tests' cases: another `window`,
    no sink, no value scale, each kind under the other's theta, the whole
    head rotated."""
    import jax
    import jax.numpy as jnp

    hq, d, dv = hf["num_attention_heads"], hf["head_dim"], hf["v_head_dim"]
    hkv = hf["swa_num_key_value_heads" if window_layer
             else "num_key_value_heads"]
    t = x.shape[0]
    q = (x @ _f32(lp["wq"])).reshape(t, hq, d)
    k = (x @ _f32(lp["wk"])).reshape(t, hkv, d)
    v = (x @ _f32(lp["wv"])).reshape(t, hkv, dv)
    if value_scale:
        v = hf["attention_value_scale"] * v
    theta = hf["swa_rope_theta" if window_layer != thetas_swapped
               else "rope_theta"]
    rotary = d if rope_whole_head else rotary_dims(hf)
    q, k = (_rope(a, positions, theta, rotary) for a in (q, k))
    win = (window or hf["sliding_window"]) if window_layer else None
    b_h = lp["sink"] if window_layer and sink else None
    pad = -t % QUERY_BLOCK
    blocks = (t + pad) // QUERY_BLOCK

    def blocked(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])

    o = jax.lax.map(
        lambda args: attention_under(
            args[0], k, v, attended_keys(args[1], t, win), b_h),
        (blocked(q), blocked(positions)))
    o = o.reshape(blocks * QUERY_BLOCK, hq, dv)[:t]
    out = o.reshape(t, hq * dv) @ _f32(lp["wo"])
    return out, o[t - tail:] if tail else None


def expert(x, wg, wu, wd):
    import jax

    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def moe_branch(x, lp: dict, hf: dict, held=None, bias=True):
    """FFN(x) over x [T, H] (normed) of an expert layer: the held experts'
    terms, expert by expert, every assignment. `held` overrides the file's
    share (the test of the shares adding up); `bias` False leaves the
    correction bias out of the choice (the tests' case)."""
    import jax
    import jax.numpy as jnp

    first, count = held or hf["experts_held"]
    k = hf["num_experts_per_tok"]
    g = jax.nn.sigmoid(x @ _f32(lp["w_router"]))
    choice = g + _f32(lp["router_bias"]) if bias else g
    order = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]  # [T, k]
    top = jnp.take_along_axis(g, order, axis=-1)
    if hf.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * (hf.get("routed_scaling_factor") or 1.0)

    def one(y, args):
        e, wg, wu, wd = args
        share = jnp.sum(jnp.where(order == first + e, top, 0.0), axis=-1)
        return y + share[:, None] * expert(x, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(count), lp["we_gate"][:count], lp["we_up"][:count],
        lp["we_down"][:count]))
    return y


def block(h, ap: dict, fp: dict, hf: dict, window_layer: bool, moe: bool,
          positions, moe_how=None, **attn):
    """One layer over h [T, H]: (h', the attention trace). `ap` the
    attention's leaves, `fp` the FFN's."""
    eps = hf["layernorm_epsilon"]
    a, trace = attention_branch(
        rms_norm(h, ap["attn_norm"], eps), ap, hf, window_layer, positions,
        **attn)
    h = h + a
    x = rms_norm(h, fp["mlp_norm"], eps)
    if moe:
        return h + moe_branch(x, fp, hf, **(moe_how or {})), trace
    return h + expert(x, fp["w_gate"], fp["w_up"], fp["w_down"]), trace


def held_layers(hf: dict) -> list:
    """[(window layer?, expert layer?, attention stack, index in it, FFN
    stack, index in it)] a held layer, in the order of `layer_ids` (default:
    the first `num_hidden_layers`), read from the PUBLISHED lists."""
    ids = hf.get("layer_ids") or range(hf["num_hidden_layers"])
    out, n = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for li in ids:
        window_layer = bool(hf["hybrid_layer_pattern"][li])
        moe = bool(hf["moe_layer_freq"][li])
        a, f = "swa" if window_layer else "full", "moe" if moe else "dense"
        out.append((window_layer, moe, a, n[a], f, n[f]))
        n[a] += 1
        n[f] += 1
    return out


# -- one precision down, for the control -------------------------------------


def to_int8(lp: dict) -> dict:
    """A layer's leaves one precision below bf16: every matrix int8,
    symmetric per output channel, kept as the float32 values int8 can hold
    (the router, its bias and the sinks stay float32, as the configuration
    states them). `hidden_states` and `log_probs` lower the embedding, a
    scale a row, and the head, a scale a column."""
    out = dict(lp)
    for name, w in lp.items():
        if w.ndim >= 2 and name != "w_router":
            out[name] = _int8(w, -2)
    return out


_LAYERS: dict = {}


def _layer_fn(hf: dict, window_layer: bool, moe: bool, lower, how: dict):
    """One jitted layer a distinct reading of the configuration."""
    import jax

    widths = tuple((k, str(v)) for k, v in sorted(hf.items())
                   if isinstance(v, (int, float)) or k == "experts_held")
    low = lower or (lambda lp: lp)
    key = (window_layer, moe, lower, widths,
           tuple(sorted((k, str(v)) for k, v in how.items())))
    if key not in _LAYERS:
        _LAYERS[key] = jax.jit(lambda h, ap, fp, pos: block(
            h, low(ap), low(fp), hf, window_layer, moe, pos, **how))
    return _LAYERS[key]


def hidden_states(params: dict, hf: dict, ids, lower=None, each=None,
                  **how):
    """The residual stream after the last layer over the sequence `ids`
    [T] (f32). `each` is called with every layer's input, kind, place and
    trace as the layer is done."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(params["embed"][ids])
    if lower is not None:
        h = _int8(h, -1)
    for li, (window_layer, moe, a, ai, f, fi) in enumerate(held_layers(hf)):
        ap = jax.tree.map(lambda w, i=ai: w[i], params[a])
        fp = jax.tree.map(lambda w, i=fi: w[i], params[f])
        h_in = h
        with jax.default_matmul_precision("highest"):
            h, trace = _layer_fn(hf, window_layer, moe, lower, how)(
                h, ap, fp, pos)
        if each is not None:  # (outside the precision the reference asks)
            each({"input": h_in, "window": window_layer, "layer": li,
                  "stack": a, "index": ai, "heads": trace})
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
            lambda h, w: h @ (_int8(w, 0) if low else _f32(w))))
        h = rms_norm(x[jnp.asarray(at)], params["final_norm"],
                     hf["layernorm_epsilon"])
        out = jax.nn.log_softmax(head(h, params["lm_head"]), axis=-1)
    return np.asarray(out)


# -- the window and the full path at depth, on the program's own routines -----

#: faults PLANTED in the program's attention at depth, as changes to its
#: configuration (`sink_left_out` silences the layer's sinks instead)
FAULTS = {
    "window_129": lambda c: {"sliding_window": c.sliding_window + 1},
    "thetas_swapped": lambda c: {"rope_theta": c.swa_rope_theta,
                                 "swa_rope_theta": c.rope_theta},
    "rope_whole_head": lambda c: {"rotary_dim": c.head_dim},
    "value_scale_left_out": lambda c: {"attention_value_scale": 1.0},
    "sink_left_out": lambda c: {},
}


def _program_cfg(hf: dict):
    import jax

    from dynamo_tpu.models.registry import get_model

    return get_model(
        hf["preset"], dtype=hf.get("dtype", "bfloat16"),
        attention_impl=hf.get("attention_impl") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"),
    ).config


def _judge(cfg, window_layer: bool, context: int, page: int, judged, fault):
    """The program's side of one layer's attention, jitted: the layer's
    norm and projections of the reference's input (the value scaled, the
    rope on its dims, as the program's layer does), the rows of all but the
    last `judged[0]` tokens landed in the cache as the steps of a prompt
    land them (a window layer: ONE sequence's ring, piece by piece, so that
    it wraps; a full layer: its pages), the last `judged[0]` queries as ONE
    prompt piece and the last `judged[1]` as decode steps, one after the
    other, each landing its row. Returns (piece [Qc, Hq, dv], decode [Qd,
    Hq, dv]): the heads' outputs before W_o."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import mimo_v2 as mm
    from dynamo_tpu.models.llama import (
        KVPages, StepGroup, _mm, maybe_decode_work, rms_norm as norm)
    from dynamo_tpu.ops.paged_attention import decode_work_list

    qc, qd = judged
    if fault:
        cfg = dataclasses.replace(cfg, **FAULTS[fault](cfg))
    kind = mm.SLIDING if window_layer else mm.FULL
    hq, hkv, dk, dv = (cfg.num_heads, cfg.kv_heads(kind), cfg.head_dim,
                       cfg.v_head_dim)
    one = dataclasses.replace(
        cfg, layer_types=(mm.FULL, mm.SLIDING), moe_layers=(True, True),
        layer_ids=None)
    n_pages = context // page
    step = min(qc, cfg.ring_run // page * page)
    lo = context - qc

    def judge(h_in, lp):
        x = norm(h_in.astype(cfg.dtype)[None], lp["attn_norm"],
                 cfg.rms_norm_eps)
        pos = jnp.arange(context, dtype=jnp.int32)[None]
        q = _mm(x[:, lo:], lp, "wq", cfg.dtype).reshape(1, qc, hq, dk)
        k = _mm(x, lp, "wk", cfg.dtype).reshape(1, context, hkv, dk)
        v = (_mm(x, lp, "wv", cfg.dtype).astype(jnp.float32)
             * cfg.attention_value_scale).astype(cfg.dtype).reshape(
            1, context, hkv, dv)
        q = mm.partial_rope(q, pos[:, lo:], cfg, kind)
        k = mm.partial_rope(k, pos, cfg, kind)
        sink = lp.get("sink")
        if fault == "sink_left_out" and sink is not None:
            sink = jnp.full_like(sink, -1e30)
        cache = mm.init_cache(one, n_pages + 1, page, 1)
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
            """Rows `at0` .. `at0 + n` into the cache, as a step does under
            the kernels (one write of the rows in parts)."""
            from dynamo_tpu.ops.kv_update import paged_write

            g = group(at0, n)
            rows = tuple(mm.pack(cut(a, at0, n)) for a in (k, v))
            if window_layer:
                return mm.land_rings(state, *rows, slot, g.positions,
                                     g.valid, page)
            return KVPages(*paged_write(
                state.k, state.v, *rows, tables, g.positions, g.valid))

        def attend(state, at0, n):
            """The `n` queries from position `at0` on as ONE step of the
            program over the cache as it stands, the rows in hand (without
            the kernels the program writes them first, itself, into a copy
            this throws away)."""
            g = group(at0, n)
            qs, ks, vs = cut(q, at0 - lo, n), cut(k, at0, n), cut(v, at0, n)
            if window_layer:
                walk = None
                if cfg.kernels and n == 1:
                    walk = mm.ring_walk(g.positions, g.valid, slot, cfg, page)
                    walk = (*walk, decode_work_list(walk[0], walk[1]))
                o, _ = mm.window_attend(qs, ks, vs, sink, state, zero, g,
                                        walk, cfg, page)
            else:
                work = maybe_decode_work(cfg, g.tokens, g.positions, None,
                                         tables)
                o, _ = mm.full_attend(qs, ks, vs, state, zero, g, work, cfg)
            return o.reshape(n, hq, dv)

        state = (cache.ring, cache.ring_v) if window_layer else KVPages(
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
    (a window layer's heads through a ring that has wrapped, a prompt piece
    and decode rows, against the reference's attention over the window's
    keys under the sink, as a share of its norm, the largest over layers,
    paths and heads) and `full_attn_distance` (a full layer's over its
    pages, likewise)."""
    import jax

    from chipbench import traffic

    t0 = time.perf_counter()
    cfg = _program_cfg(hf)
    page = hf.get("page_size", 64)
    qc, qd = judged = tuple(hf.get("judged", JUDGED))
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    judges = {w: _judge(cfg, w, context, page, judged, fault)
              for w in {layer[0] for layer in held_layers(hf)}}
    worst = {"window": 0.0, "full": 0.0}
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731

    def judge_layer(tr):
        lp = jax.tree.map(lambda w, i=tr["index"]: w[i], params[tr["stack"]])
        o_c, o_d = judges[tr["window"]](tr["input"], lp)
        want = f32(tr["heads"])
        name = "window" if tr["window"] else "full"
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
    """A MimoV2Config's sizes under the published file's keys: every one
    of them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "layer_ids": list(cfg.published_ids),
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "swa_num_key_value_heads": cfg.swa_num_kv_heads,
        "head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta,
        "swa_rope_theta": cfg.swa_rope_theta,
        "sliding_window": cfg.sliding_window,
        "attention_value_scale": cfg.attention_value_scale,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.n_routed_experts,
        "experts_held": list(cfg.experts_held or (0, cfg.n_routed_experts)),
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "layernorm_epsilon": cfg.rms_norm_eps,
        "vocab_size": cfg.vocab_size,
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct. `int8_weights`: the weights one precision below bf16, in
#: the REFERENCE that decodes the streams. The five others are one reading
#: of the row each gone wrong, TWICE: in the reference that decodes the
#: streams (they fail on the streams' log-probs, where 112 tokens can show
#: it) and PLANTED in the program's attention at depth (`walk`: they fail on
#: a distance, which is what `window_129` has alone: no stream of the
#: benchmark leaves a 128-token window)
CONTROLS = {
    "int8_weights": {"lower": to_int8},
    "sink_left_out": {"sink": False, "walk": {"fault": "sink_left_out"}},
    "value_scale_left_out": {"value_scale": False,
                             "walk": {"fault": "value_scale_left_out"}},
    "window_129": {"walk": {"fault": "window_129"}},
    "thetas_swapped": {"thetas_swapped": True,
                       "walk": {"fault": "thetas_swapped"}},
    "rope_whole_head": {"rope_whole_head": True,
                        "walk": {"fault": "rope_whole_head"}},
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
    """python -m chipbench.references.mimo_v2 [--seeds a,b] [--config
    mimo-v2.5-1chip] [--controls a,b]: each of CONTROLS decodes the
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
    ap.add_argument("--config", default="mimo-v2.5-1chip")
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
