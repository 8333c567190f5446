"""The plain reference of dots3-note-prev's language model (dots-studio,
`model_type` dots3_note), as the configuration `dots3-note-prev-1chip`
brings it (`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no capacity, no absorbed form, one layer at a time so that it
fits.

The model, from its published config.json and, for what the file does not
define, the conventions the configuration lists under `assumed`; `x` is a
layer's RMS-normed input (eps `rms_norm_eps`), every norm has a learned
weight, `H` = `hidden_size`:

    h = embed[ids];  h = h + Attn(RMSNorm(h));  h = h + FFN(RMSNorm(h))
    logits = W_head RMSNorm(h)                       (untied)

A layer's attention is FULL or SLIDING by `layer_types`, each a latent
attention of its own geometry (heads n, ranks r_q and r_kv, head parts d_n
| d_r, value d_v, theta: the plain keys in a full layer, the `swa_` keys in
a sliding one):

- `c_q = a_q RMSNorm(W_qa x)`, `q = W_qb c_q` as n heads of (d_n | d_r);
  `(c | k_r) = W_kva x`, `c_kv = a_kv RMSNorm(c)`; `k_h = (W_kb^K,h c_kv |
  rope(k_r))`, `v_h = W_kb^V,h c_kv`; rope on q's last d_r and on k_r,
  adjacent pairs `(x[2j], x[2j+1])` at the kind's theta;
  `a = sqrt(H / rank)` (`apply_mla_qkv_lora_rescale`, assumed);
- the indexer (full layers): `qI = W_qI c_q` (`index_n_heads` x
  `index_head_dim`), `kI = LayerNorm(W_kI x)` (one key a token, learned
  weight and bias), `w = W_w x / sqrt(heads x dim)`, rope on the first
  `qk_rope_head_dim` dimensions of qI and kI; `I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`; `S_t` = the `min(index_topk, t + 1)` positions
  `s <= t` of highest `I[t, .]`, ties to the earlier (a STABLE descending
  sort here; the program has no sort);
- attention: full `o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k_h[s] /
  sqrt(d_n + d_r)) v_h[s]`; sliding the same sum over `s` in `[max(0, t -
  (sliding_window_size - 1)), t]`; both `y = W_o concat_h(g_h o[t, h])`
  with `g = sigmoid(W_g x)` (`attention_gate_type` headwise, assumed);
- FFN: SwiGLU of `intermediate_size` in the first `first_k_dense_replace`
  layers; after them `s = sigmoid(W_r x)` over all the published experts,
  the `num_experts_per_tok` of highest `s + b` (stable), `p_e = s_e /
  sum_chosen s`, times `routed_scaling_factor`; `y = sum_e p_e E_e(x)` over
  the experts HELD (`experts_held` = [first, count]) `+ E_shared(x)`,
  expert by expert, every assignment.

Fed the served engine's own parameter tree (models/dots3.py: the stacks
`full`, `swa`, `dense`, `moe`, a layer's leaves at its index among the
layers that read the stack, an expert's matrices at its place in the held
range).

Departures from the published description: (1) the file's cut: layers 0-8
of 46, experts 0-7 of 256, ids 0-19,007 of 152,064. (2) DeepSeek-V3.2-Exp's
inference code rotates qI and kI by a Hadamard matrix and quantises them to
fp8; neither is here. (3) Attention runs in UNIFORM blocks of `QUERY_BLOCK`
query rows under `lax.map` and the head in blocks of the vocabulary: the
same sums. (4) What config.json names and does not define is the file's
`assumed`: the gate, the rescale, the window's ends, the indexer's norm,
rope and weight scale. (5) No vision tower, audio encoder or MTP module.

`compare` also judges, at the timed sizes on the reference's own hidden
states (`sparse_path`), the program's selection, its full-layer attention
under its own selection (chunk kernel and decode walk) and its window
attention through a ring that has wrapped. That is the one place where
this module runs code of the program.

`python -m chipbench.references.dots3` is this configuration's control:
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
VOCAB_BLOCK = 32768  # columns of the head cast to float32 at once
SPARSE_CONTEXT = 12288
#: the judged queries: the last chunk through the chunk paths, the last
#: rows of it through the decode paths (`judged` of a rehearsal's `hf`)
JUDGED = (512, 16)
FULL = "full_attention"

_f32 = keye_ref._f32
_layer_norm = keye_ref._layer_norm


def selected_tokens(scores, positions_q, topk: int):
    """bool [Tq, T]: the `min(topk, t + 1)` highest of each query's scores
    over `s <= t`, ties to the earlier: the rank in a stable descending
    sort (the rank as the sort's inverse permutation, by a scatter: a
    second sort of 12,288 keys is 10 s more of the TPU's compiler)."""
    import jax.numpy as jnp

    tq, t = scores.shape
    causal = jnp.arange(t)[None] <= positions_q[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.zeros((tq, t), jnp.int32).at[
        jnp.arange(tq)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (tq, t)))
    return causal & (rank < topk)

agreement = keye_ref.agreement
_distance = keye_ref._distance
_int8 = keye_ref._int8


def geometry(hf: dict, kind: str) -> dict:
    p = "" if kind == FULL else "swa_"
    return {
        "n": hf[p + "num_attention_heads"], "rq": hf[p + "q_lora_rank"],
        "c": hf[p + "kv_lora_rank"], "dn": hf[p + "qk_nope_head_dim"],
        "dr": hf[p + "qk_rope_head_dim"], "dv": hf[p + "v_head_dim"],
        "theta": hf[p + "rope_theta"],
    }


def stacks_of(hf: dict) -> list:
    """Per layer ((attention stack, index), (FFN stack, index))."""
    out, n = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for li, kind in enumerate(hf["layer_types"][:hf["num_hidden_layers"]]):
        a = "full" if kind == FULL else "swa"
        f = "dense" if li < hf["first_k_dense_replace"] else "moe"
        out.append(((a, n[a]), (f, n[f])))
        n[a] += 1
        n[f] += 1
    return out


def _rope(z, positions, theta):
    """Adjacent-pair rotary of z [T, .., d] at positions [T]."""
    import jax.numpy as jnp

    d = z.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = _f32(positions)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    for _ in range(z.ndim - 2):
        cos, sin = cos[:, None], sin[:, None]
    even, odd = z[..., 0::2], z[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(z.shape)


def attention_under(q, k, v, selected):
    """softmax(q . k / sqrt(d)) v over the keys `selected` [Tq, T] names:
    q [Tq, n, d], k [T, n, d], v [T, n, dv] -> [Tq, n, dv]."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("thd,khd->htk", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(selected[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("htk,khd->thd", p, v)


def attention_under_blocked(q, k, v, selected):
    """`attention_under` in blocks of `QUERY_BLOCK` query rows under
    `lax.map` (128 heads x 512 queries x 12,288 keys of float32 scores
    would be 3.2 GB)."""
    import jax

    n = q.shape[0]
    if n <= QUERY_BLOCK or n % QUERY_BLOCK:
        return attention_under(q, k, v, selected)
    blocks = n // QUERY_BLOCK
    out = jax.lax.map(
        lambda args: attention_under(args[0], k, v, args[1]),
        (q.reshape(blocks, QUERY_BLOCK, *q.shape[1:]),
         selected.reshape(blocks, QUERY_BLOCK, -1)))
    return out.reshape(n, *out.shape[2:])


def window_keys(positions_q, t: int, window: int):
    """bool [Tq, T]: the `window` keys up to each query's own."""
    import jax.numpy as jnp

    s = jnp.arange(t)[None]
    at = positions_q[:, None]
    return (s <= at) & (s >= at - (window - 1))


def attention_branch(x, lp: dict, hf: dict, kind: str, positions,
                     select=True, gate=True, rescale=True, index_rope=True,
                     window=None, tail=0):
    """Attn(x) over x [T, H] (normed), causal. Returns (out [T, H], trace:
    k and v, and of the last `tail` queries q and the keys they attend).
    `select` False is dense attention in a full layer; `gate`, `rescale`,
    `index_rope` False leave that part out; `window` overrides the file's
    (the controls and the tests' cases)."""
    import jax
    import jax.numpy as jnp

    g = geometry(hf, kind)
    n, c, dn, dr, dv = g["n"], g["c"], g["dn"], g["dr"], g["dv"]
    eps, hid = hf["rms_norm_eps"], hf["hidden_size"]
    t = x.shape[0]
    a_q = math.sqrt(hid / g["rq"]) if rescale else 1.0
    a_kv = math.sqrt(hid / c) if rescale else 1.0
    c_q = a_q * dense._rms(x @ _f32(lp["wq_a"]), lp["q_a_norm"], eps)

    def queries(cq, positions):
        """q of some rows from their query latent (a block at a time: 128
        heads of 12,288 tokens would be 1.2 GB, and as much again rotated)."""
        q = (cq @ _f32(lp["wq_b"])).reshape(-1, n, dn + dr)
        return jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], positions, g["theta"])], -1)

    kv_a = x @ _f32(lp["wkv_a"])
    c_kv = a_kv * dense._rms(kv_a[:, :c], lp["kv_a_norm"], eps)
    k_r = _rope(kv_a[:, c:], positions, g["theta"])
    wkv_b = _f32(lp["wkv_b"]).reshape(c, n, dn + dv)
    k = jnp.concatenate([
        jnp.einsum("tc,chd->thd", c_kv, wkv_b[..., :dn]),
        jnp.broadcast_to(k_r[:, None], (t, n, dr))], axis=-1)
    v = jnp.einsum("tc,chd->thd", c_kv, wkv_b[..., dn:])
    full = kind == FULL
    if full:
        nj, di = hf["index_n_heads"], hf["index_head_dim"]
        rd = hf["qk_rope_head_dim"]

        def rot(z):
            if not index_rope:
                return z
            return jnp.concatenate(
                [_rope(z[..., :rd], positions, hf["rope_theta"]),
                 z[..., rd:]], axis=-1)

        qi = rot((c_q @ _f32(lp["wi_q"])).reshape(t, nj, di))
        ki = rot(_layer_norm(x @ _f32(lp["wi_k"]), lp["ik_norm"],
                             lp["ik_bias"], eps))
        w = (x @ _f32(lp["wi_w"])) / math.sqrt(nj * di)
    else:
        qi = jnp.zeros((t, 1, 1))
        ki = jnp.zeros((t, 1))
        w = jnp.zeros((t, 1))
    win = window or hf["sliding_window_size"]
    topk = hf["index_topk"]

    pad = -t % QUERY_BLOCK
    blocks = (t + pad) // QUERY_BLOCK

    def block(args):
        cqb, qib, wb, pb = args  # a block of query rows
        qb = queries(cqb, pb)
        if not full:
            sel = window_keys(pb, t, win)
        elif select:
            scores = jnp.einsum(
                "tj,tjs->ts", wb, jax.nn.relu(
                    jnp.einsum("tjd,sd->tjs", qib, ki)))
            sel = selected_tokens(scores, pb, topk)
        else:
            sel = jnp.arange(t)[None] <= pb[:, None]
        return attention_under(qb, k, v, sel), sel

    def blocked(a, fill=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])

    out, sel = jax.lax.map(block, (blocked(c_q), blocked(qi), blocked(w),
                                   blocked(positions)))
    out = out.reshape(blocks * QUERY_BLOCK, n, dv)[:t]
    sel = sel.reshape(blocks * QUERY_BLOCK, t)[:t]
    if gate:
        out = out * jax.nn.sigmoid(x @ _f32(lp["w_headgate"]))[..., None]
    at = t - tail if tail else t
    trace = {"q": queries(c_q[at:], positions[at:]), "k": k, "v": v,
             "selected": sel[at:]}
    return out.reshape(t, n * dv) @ _f32(lp["wo"]), trace


def dense_branch(x, lp: dict):
    import jax

    return (jax.nn.silu(x @ _f32(lp["w_gate"])) * (x @ _f32(lp["w_up"]))
            ) @ _f32(lp["w_down"])


def moe_branch(x, lp: dict, hf: dict, bias=True, held=None, shared=True):
    """The expert layer over x [T, H] (normed): the held experts' terms,
    expert by expert, every assignment, and the shared expert. `bias`
    False routes without the correction bias; `held` overrides the file's
    share and `shared` False leaves the shared expert out (the test of the
    shares adding up)."""
    import jax
    import jax.numpy as jnp

    first, count = held or hf["experts_held"]
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ _f32(lp["w_router"]))
    choice = s + (_f32(lp["router_bias"])[None] if bias else 0.0)
    order = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]  # [T, k]
    top = jnp.take_along_axis(s, order, axis=-1)
    if hf.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * hf.get("routed_scaling_factor", 1.0)

    def ffn(wg, wu, wd):
        return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)

    def expert(y, args):
        e, wg, wu, wd = args
        share = jnp.sum(jnp.where(order == first + e, top, 0.0), axis=-1)
        return y + share[:, None] * ffn(wg, wu, wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(count), lp["we_gate"][:count], lp["we_up"][:count],
        lp["we_down"][:count]))
    if shared:
        y = y + ffn(lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y


def attention_block(h, alp: dict, hf: dict, kind: str, positions, **how):
    a, trace = attention_branch(
        dense._rms(h, alp["attn_norm"], hf["rms_norm_eps"]), alp, hf, kind,
        positions, **how)
    return h + a, trace


def ffn_block(h, flp: dict, hf: dict, dense_ffn: bool, **moe):
    x = dense._rms(h, flp["mlp_norm"], hf["rms_norm_eps"])
    return h + (dense_branch(x, flp) if dense_ffn else moe_branch(
        x, flp, hf, **moe))


# -- one precision down, for the control -------------------------------------


def to_int8(lp: dict) -> dict:
    """A stack's layer one precision below bf16: every matrix int8,
    symmetric per output channel, kept as the float32 values int8 can hold
    (the router and its biases stay float32, as the configuration states
    them). `hidden_states` and `log_probs` lower the embedding (a scale a
    row) and the head (a scale a column) beside it."""
    out = dict(lp)
    for name, w in lp.items():
        if w.ndim >= 2 and name != "w_router":
            out[name] = _int8(w, -2)
    return out


_LAYERS: dict = {}


def _layer_fn(hf: dict, kind: str, dense_ffn: bool, lower, how: dict):
    """One layer a distinct reading of the configuration, as TWO jitted
    functions (an attention block a kind of layer, an FFN a kind: a full
    layer's attention at 12,288 tokens takes the TPU's compiler ~20 s and
    is the same under a dense MLP and under experts)."""
    import jax

    how = dict(how)
    moe = how.pop("moe", None) or {}
    widths = tuple((k, str(v)) for k, v in sorted(hf.items())
                   if isinstance(v, (int, float, list))
                   and k != "layer_types")
    low = lower or (lambda lp: lp)
    ka = ("attn", kind, lower, widths,
          tuple(sorted((k, str(v)) for k, v in how.items())))
    if ka not in _LAYERS:
        _LAYERS[ka] = jax.jit(lambda h, alp, pos: attention_block(
            h, low(alp), hf, kind, pos, **how))
    kf = ("ffn", dense_ffn, lower, widths, tuple(sorted(moe.items())))
    if kf not in _LAYERS:
        _LAYERS[kf] = jax.jit(lambda h, flp: ffn_block(
            h, low(flp), hf, dense_ffn, **moe))
    attend, ffn = _LAYERS[ka], _LAYERS[kf]

    def layer(h, alp, flp, pos):
        h, trace = attend(h, alp, pos)
        return ffn(h, flp), trace

    return layer


def hidden_states(params: dict, hf: dict, ids, lower=None, each=None,
                  **how):
    """The residual stream after the last layer over the sequence `ids`
    [T] (f32). `each` is called with every layer's input, kind, stack and
    trace as the layer is done (a layer's keys and values are 2 GB at
    12,288 tokens: they are judged and dropped, never kept)."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
    h = _f32(params["embed"][ids])
    if lower is not None:
        h = _int8(h, -1)
    for li, ((a, ai), (f, fi)) in enumerate(stacks_of(hf)):
        alp = jax.tree.map(lambda w, i=ai: w[i], params[a])
        flp = jax.tree.map(lambda w, i=fi: w[i], params[f])
        kind = hf["layer_types"][li]
        h_in = h
        with jax.default_matmul_precision("highest"):
            h, trace = _layer_fn(hf, kind, f == "dense", lower, how)(
                h, alp, flp, pos)
        if each is not None:  # (outside the precision the reference asks)
            each({"input": h_in, "kind": kind, "stack": (a, ai),
                  "ffn": (f, fi), **trace})
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
        h = dense._rms(x[jnp.asarray(at)], params["final_norm"],
                       hf["rms_norm_eps"])
        w = params["lm_head"]
        logits = jnp.concatenate([
            head(h, w[:, lo : lo + VOCAB_BLOCK])
            for lo in range(0, w.shape[1], VOCAB_BLOCK)
        ], axis=1)
        out = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(out)


# -- the sparse and the window path, judged on the program's own routines -----


def _program_cfg(hf: dict):
    import jax

    from dynamo_tpu.models.registry import get_model

    return get_model(
        hf["preset"], dtype=hf.get("dtype", "bfloat16"),
        attention_impl=hf.get("attention_impl") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"),
    ).config


def _judge_full(cfg, context: int, page: int, judged, fault):
    """The program's side of one FULL layer, jitted: the layer's
    projections of the reference's input, its latent, rope key and index
    keys landed in a cache as a step lands them, then the LAST `judged[0]`
    queries through the chunk path and the last `judged[1]` through the
    decode path. Returns (chunk attention [Qc, n, dv], its selection [Qc,
    T], decode attention [Qd, n, dv], its selection [Qd, T])."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import dots3, mla
    from dynamo_tpu.models.llama import StepGroup, maybe_decode_work, rms_norm
    from dynamo_tpu.ops.kv_update import paged_write

    n_pages = context // page
    qc, qd = judged
    geo = cfg.full_geo
    nope = geo.qk_nope_head_dim
    one = dataclasses.replace(cfg, layer_types=(dots3.FULL,))

    def judge(h_in, lp):
        x = rms_norm(h_in.astype(cfg.dtype)[None], lp["attn_norm"],
                     cfg.rms_norm_eps)
        pos = jnp.arange(context, dtype=jnp.int32)[None]
        tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        ones = jnp.ones((1, context), bool)
        lo = context - qc
        tail = pos[:, lo:]
        # the keys of every token, the queries of the judged ones alone
        # (128 absorbed heads of 12,288 tokens would be 3.2 GB)
        _, c_kv, kv_a, _ = mla.latent_projections(
            x, lp, geo, cfg.rescale(geo))
        q, _, _, c_q = mla.latent_projections(
            x[:, lo:], lp, geo, cfg.rescale(geo))
        q_lat, w_uv = mla.absorbed_query(q, lp, geo)
        _, ki, _ = dots3.index_projections(x, None, lp, cfg)
        qi, _, w = dots3.index_projections(x[:, lo:], c_q, lp, cfg)
        qp = mla._interleaved_rope(q[..., nope:], tail, geo)
        kp = mla._interleaved_rope(
            kv_a[..., geo.kv_lora_rank:], pos, geo).astype(cfg.dtype)
        qi = dots3.index_rope(qi, tail, cfg)
        ki = dots3.index_rope(ki, pos, cfg).astype(cfg.dtype)
        cache = dots3.init_cache(one, n_pages + 1, page, 0)
        k_pool, v_pool = paged_write(
            cache.k, cache.v, c_kv[None, :, :, None],
            mla._pad_last(kp, geo.kv_rope_dim)[None, :, :, None], tables,
            pos, ones)
        ki_pool = dots3.land_index_keys(cache.ki, ki[None], tables, pos, ones)
        if fault == "swapped_rows":  # two cached tokens change places
            swap = lambda a: a.at[0, 1, 0].set(a[0, 1, 1]).at[  # noqa: E731
                0, 1, 1].set(a[0, 1, 0])
            k_pool, v_pool = swap(k_pool), swap(v_pool)
        kv = (k_pool, v_pool)
        zero = jnp.int32(0)

        def value(o_lat):  # [.., n, c] -> [.., n, dv], float32
            return jnp.einsum("...hc,chv->...hv", o_lat.astype(jnp.float32),
                              w_uv.astype(jnp.float32))

        piece = lambda a: a[:, lo:]  # noqa: E731
        g = StepGroup(jnp.zeros((1, qc), jnp.int32), tail, ones[:, lo:],
                      tables)
        o_c, _, _, _, _, sel_c = dots3.full_attend(
            q_lat, qp, piece(c_kv), piece(kp), qi, piece(ki), w, kv,
            ki_pool, zero, g, None, cfg)
        # the decode path: a row a position, every row the same pages
        rows = lambda a: a[0, -qd:, None]  # noqa: E731
        gd = StepGroup(jnp.zeros((qd, 1), jnp.int32), rows(pos),
                       jnp.ones((qd, 1), bool),
                       jnp.broadcast_to(tables, (qd, n_pages)))
        work = maybe_decode_work(cfg, gd.tokens, gd.positions, None,
                                 gd.page_tables)
        o_d, _, _, _, _, sel_d = dots3.full_attend(
            rows(q_lat), rows(qp), rows(c_kv), rows(kp), rows(qi), rows(ki),
            rows(w), kv, ki_pool, zero, gd, work, cfg)
        return (value(o_c[0]), sel_c[0, :, :context],
                value(o_d[:, 0]), sel_d[:, 0, :context])

    return jax.jit(judge)


def _judge_window(cfg, context: int, judged, fault):
    """The program's side of one SLIDING layer, jitted: the layer's rows
    written into ONE sequence's ring chunk by chunk as the steps of a
    prompt write them (the ring wraps `context / ring_tokens` times), the
    last `judged[0]` queries as a prompt chunk and the last `judged[1]` as
    decode steps, one after the other. Returns (chunk attention [Qc, n,
    dv], decode attention [Qd, n, dv])."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import dots3, mla
    from dynamo_tpu.models.llama import StepGroup, rms_norm

    qc, qd = judged
    geo = cfg.swa_geo
    nope = geo.qk_nope_head_dim
    if fault == "short_window":  # the window one token short
        cfg = dataclasses.replace(
            cfg, sliding_window=cfg.sliding_window - 1)
    one = dataclasses.replace(cfg, layer_types=(dots3.FULL, dots3.SLIDING))
    step = min(qc, cfg.ring_run)

    def judge(h_in, lp):
        x = rms_norm(h_in.astype(cfg.dtype)[None], lp["attn_norm"],
                     cfg.rms_norm_eps)
        pos = jnp.arange(context, dtype=jnp.int32)[None]
        lo = context - qc
        # the rows of every token, the queries of the judged ones alone
        _, c_kv, kv_a, _ = mla.latent_projections(
            x, lp, geo, cfg.rescale(geo))
        q, _, _, _ = mla.latent_projections(
            x[:, lo:], lp, geo, cfg.rescale(geo))
        q_lat, w_uv = mla.absorbed_query(q, lp, geo)
        qp = mla._interleaved_rope(q[..., nope:], pos[:, lo:], geo)
        kp = mla._interleaved_rope(
            kv_a[..., geo.kv_lora_rank:], pos, geo).astype(cfg.dtype)
        cache = dots3.init_cache(one, 2, 4, 1)
        rings = (cache.ring, cache.ring_pe)
        slot, zero = jnp.ones((1,), jnp.int32), jnp.int32(0)
        cut = lambda a, at0, n: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, at0, n, 1)

        def write(rings, at0, n):
            at = at0 + jnp.arange(n, dtype=jnp.int32)[None]
            return dots3.ring_write(
                rings, zero, cut(c_kv, at0, n),
                mla._pad_last(cut(kp, at0, n), geo.kv_rope_dim), slot, at,
                jnp.ones((1, n), bool))

        def attend(rings, at0, n):
            """The `n` queries from position `at0` on (of the tail) as
            ONE step of the program: attention and the rows' way into the
            ring."""
            at = at0 + jnp.arange(n, dtype=jnp.int32)[None]
            g = StepGroup(jnp.zeros((1, n), jnp.int32), at,
                          jnp.ones((1, n), bool),
                          jnp.zeros((1, 1), jnp.int32),
                          state_rows=jnp.ones((1, 2), jnp.int32))
            return dots3.window_attend(
                cut(q_lat, at0 - lo, n), cut(qp, at0 - lo, n),
                cut(c_kv, at0, n), cut(kp, at0, n), rings, zero, g, cfg)

        def value(o_lat):
            return jnp.einsum("...hc,chv->...hv", o_lat.astype(jnp.float32),
                              w_uv.astype(jnp.float32))

        before = jax.lax.fori_loop(
            0, lo // step, lambda i, r: write(r, i * step, step), rings)
        o_c = attend(before, lo, qc)[0][0]
        ring_d = write(before, lo, qc - qd) if qc > qd else before

        def decode(r, t):
            o, r = attend(r, t, 1)
            return r, o[0, 0]

        _, o_d = jax.lax.scan(
            decode, ring_d,
            jnp.arange(context - qd, context, dtype=jnp.int32))
        return value(o_c), value(o_d)

    return jax.jit(judge)


def sparse_path(params: dict, hf: dict, context: int = SPARSE_CONTEXT,
                seed: int = 1234, fault=None) -> dict:
    """The program's selection and attention at `context` tokens against
    the reference, layer by layer on the REFERENCE's hidden states:
    `selected_tokens_agreement` (the mean over the judged queries of the
    full layers; `_min` the smallest), `sparse_attn_distance` (a full
    layer's chunk kernel and decode walk against the reference's attention
    under the selection the program itself made, as a share of its norm,
    the largest over layers, paths and heads) and `window_attn_distance`
    (a sliding layer's attention through a ring that has wrapped, chunk
    and decode, against the reference's window attention, likewise)."""
    import jax

    from chipbench import traffic

    t0 = time.perf_counter()
    cfg = _program_cfg(hf)
    page = hf.get("page_size", 64)
    qc, qd = judged = tuple(hf.get("judged", JUDGED))
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    full = _judge_full(cfg, context, page, judged,
                       fault if fault == "swapped_rows" else None)
    window = _judge_window(cfg, context, judged,
                           fault if fault == "short_window" else None)
    agree, worst = [], {"full": 0.0, "swa": 0.0}
    under = jax.jit(attention_under_blocked)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731

    def judge_layer(tr):
        a, ai = tr["stack"]
        lp = jax.tree.map(lambda w, i=ai: w[i], params[a])
        theirs = np.asarray(tr["selected"])
        if tr["kind"] == FULL:
            attn_c, sel_c, attn_d, sel_d = full(tr["input"], lp)
            agree.append(agreement(np.asarray(sel_c), theirs))
            agree.append(agreement(np.asarray(sel_d), theirs[qc - qd:]))
            with jax.default_matmul_precision("highest"):
                want_c = under(tr["q"], tr["k"], tr["v"], sel_c)
                want_d = under(tr["q"][qc - qd:], tr["k"], tr["v"], sel_d)
            worst["full"] = max(
                worst["full"], _distance(f32(attn_c), f32(want_c)),
                _distance(f32(attn_d), f32(want_d)))
        else:
            attn_c, attn_d = window(tr["input"], lp)
            with jax.default_matmul_precision("highest"):
                want = under(tr["q"], tr["k"], tr["v"], theirs)
            worst["swa"] = max(
                worst["swa"], _distance(f32(attn_c), f32(want)),
                _distance(f32(attn_d), f32(want)[qc - qd:]))

    hidden_states(params, hf, ids, each=judge_layer, tail=qc)
    agree = np.concatenate(agree)
    return {"selected_tokens_agreement": float(agree.mean()),
            "selected_tokens_agreement_min": float(agree.min()),
            "sparse_attn_distance": worst["full"],
            "window_attn_distance": worst["swa"],
            "sparse_context": context, "sparse_queries": int(agree.size),
            "sparse_path_s": round(time.perf_counter() - t0, 1)}


def lowered_sparse_path(params: dict, hf: dict, context: int,
                        seed: int = 1234, **how) -> dict:
    """A control that lowers the REFERENCE's selection (`select`): its
    selection of the judged queries in the full layers against the
    reference's as it stands; the attention distances are 0 by
    construction (the selection is what is lowered)."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic

    qc = tuple(hf.get("judged", JUDGED))[0]
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    pos = jnp.arange(context, dtype=jnp.int32)
    agree = []

    def lowered_layer(tr):
        if tr["kind"] != FULL:
            return
        (a, ai), (f, fi) = tr["stack"], tr["ffn"]
        alp = jax.tree.map(lambda w, i=ai: w[i], params[a])
        flp = jax.tree.map(lambda w, i=fi: w[i], params[f])
        with jax.default_matmul_precision("highest"):
            _, trace = _layer_fn(hf, FULL, f == "dense", None,
                                 {**how, "tail": qc})(
                tr["input"], alp, flp, pos)
        agree.append(agreement(np.asarray(trace["selected"]),
                               np.asarray(tr["selected"])))

    hidden_states(params, hf, ids, each=lowered_layer, tail=qc)
    agree = np.concatenate(agree)
    return {"selected_tokens_agreement": float(agree.mean()),
            "selected_tokens_agreement_min": float(agree.min()),
            "sparse_attn_distance": 0.0, "window_attn_distance": 0.0,
            "sparse_context": context}


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    """`chipbench.reference.compare` through this module's `log_probs`,
    and, where `hf` names the served preset, `sparse_path`'s three readings
    under `reference_tolerance.min_selected_tokens_agreement`,
    `max_sparse_attn_distance` and `max_window_attn_distance` of the same
    file. The harness's verdict reads four keys (chipbench/run.py
    `check_reference`): a reading past its limit is reported as a mean
    log-prob drift past every limit, the measured one kept beside it. A
    stream may bring the control's readings in the program's place
    (`sparse_path`)."""
    def forward(p, c, ids, at):
        return log_probs(p, c, ids, at, **how)

    t0 = time.perf_counter()
    res = dense.compare(params, hf, streams, forward=forward)
    res["streams_s"] = round(time.perf_counter() - t0, 1)
    if not hf.get("preset"):
        return res
    tol = hf.get("reference_tolerance", {})
    theirs = next((s["sparse_path"] for s in streams if "sparse_path" in s),
                  None)
    res.update(theirs if theirs is not None else sparse_path(
        params, hf, context=hf.get("sparse_context", SPARSE_CONTEXT)))
    failed = [
        name for name, ok in (
            ("selected_tokens_agreement", res["selected_tokens_agreement"]
             >= tol.get("min_selected_tokens_agreement", -math.inf)),
            ("sparse_attn_distance", res["sparse_attn_distance"]
             <= tol.get("max_sparse_attn_distance", math.inf)),
            ("window_attn_distance", res["window_attn_distance"]
             <= tol.get("max_window_attn_distance", math.inf)),
        ) if not ok
    ]
    if failed:
        res["failed_by"] = failed
        res["mean_logprob_drift_of_tokens"] = res["mean_logprob_drift"]
        res["mean_logprob_drift"] = float("inf")
    return res


def served_widths(cfg) -> dict:
    """A Dots3Config's sizes under the published file's keys: every one of
    them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "swa_num_attention_heads": cfg.swa_num_heads,
        "swa_num_key_value_heads": cfg.swa_num_heads,
        "swa_q_lora_rank": cfg.swa_q_lora_rank,
        "swa_kv_lora_rank": cfg.swa_kv_lora_rank,
        "swa_qk_nope_head_dim": cfg.swa_qk_nope_head_dim,
        "swa_qk_rope_head_dim": cfg.swa_qk_rope_head_dim,
        "swa_v_head_dim": cfg.swa_v_head_dim,
        "swa_rope_theta": cfg.swa_rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
        "attention_gate_type": "headwise" if cfg.headwise_gate else None,
        "swa_attention_gate_type": "headwise" if cfg.headwise_gate else None,
        "index_n_heads": cfg.index_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "n_routed_experts": cfg.experts_here,
        "n_routed_experts_published": cfg.n_routed_experts,
        "experts_held": list(cfg.experts_held or (0, cfg.n_routed_experts)),
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "topk_method": cfg.topk_method,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.rms_norm_eps,
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct: (a) the weights one precision below bf16 (it fails on the
#: streams' log-probs), (b) the selection OFF in the reference (dense
#: attention at 12,288 tokens: it fails on the selected tokens alone; the
#: greedy streams never reach `index_topk`, so its streams ARE the
#: reference's), (c) a fault PLANTED in the program's cache: two cached
#: tokens of one page change places in the latent and the rope-key pool,
#: the index keys left alone (it fails on the sparse attention's distance
#: alone), (d) the program's window one token short (it fails on the
#: window attention's distance alone)
CONTROLS = {
    "int8_weights": {"lower": to_int8},
    "selection_off": {"sparse": {"select": False}},
    "swapped_rows": {"walk": {"fault": "swapped_rows"}},
    "short_window": {"walk": {"fault": "short_window"}},
}
_SPARSE_UNTOUCHED = {"selected_tokens_agreement": 1.0,
                     "sparse_attn_distance": 0.0,
                     "window_attn_distance": 0.0}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it, as
    chipbench/references/keye_vl.py `control_streams`."""
    from chipbench import traffic

    how = dict(how)
    sparse, walk = how.pop("sparse", None), how.pop("walk", None)
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
    context = hf.get("sparse_context", SPARSE_CONTEXT)
    if walk is not None:
        out[0]["sparse_path"] = sparse_path(params, hf, context, seed, **walk)
    elif sparse is not None:
        out[0]["sparse_path"] = lowered_sparse_path(
            params, hf, context, seed, **sparse)
    else:
        out[0]["sparse_path"] = dict(_SPARSE_UNTOUCHED)
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.dots3 [--seeds a,b] [--config
    dots3-note-prev-1chip] [--controls a,b]: each of CONTROLS decodes the
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
    ap.add_argument("--config", default="dots3-note-prev-1chip")
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
