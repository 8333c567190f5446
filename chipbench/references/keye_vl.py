"""The plain reference of Keye-VL-2.0-30B-A3B's language model (Kwai-Keye,
`model_type` KeyeVL2), as the configuration `keye-vl2-30b-a3b-1chip`
brings it (`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no kernels, no
batching, no capacity, one layer at a time so that it fits.

The model, from its published config.json (the widths, `sa_config`,
`rope_scaling.mrope_section`) and DeepSeek-V3.2-Exp's published indexer;
`x` is a layer's RMS-normed input (eps `rms_norm_eps`), every norm has a
learned weight:

    h = embed[ids];  h = h + Attn(RMSNorm(h));  h = h + MoE(RMSNorm(h))
    logits = W_head RMSNorm(h)                       (untied)

- projections: `q = W_q x` (`num_attention_heads` x `head_dim`), `k = W_k
  x`, `v = W_v x` (`num_key_value_heads`), no bias; RMSNorm over the head
  dimension on q and on k; rotary in the half-split convention at
  `rope_theta`, the `head_dim / 2` frequency pairs split `mrope_section`
  over the (temporal, height, width) position components;
- indexer: `qI = W_qI x` (`indexer_num_heads` x `indexer_head_dim`), `kI
  = LayerNorm(W_kI x)` (`indexer_num_kv_heads` = 1: ONE key a token;
  learned weight and bias), `w = W_w x / sqrt(heads x dim)`; rotary on
  all of qI and kI at the temporal position; `I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`; `S_t` = the `min(topk, t + 1)` positions `s <=
  t` of highest `I[t, .]`, ties to the earlier (a STABLE descending sort
  here; the program has no sort);
- attention: `o[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] /
  sqrt(head_dim)) v[s, g(h)]`, `W_o`;
- experts: `p = softmax(W_r x)` over all `num_local_experts`, the
  `num_experts_per_tok` highest renormalised to sum 1, `y = sum_e p_e
  W_d^e (silu(W_g^e x) * W_u^e x)` over the experts HELD (`experts_held` =
  [first, count]: a chip of an expert-parallel deployment routes over all
  and adds its own experts' terms; what the absent ones would add is left
  out, here as in the program), expert by expert, every assignment.

Fed the served engine's own parameter tree (models/keye_vl.py: `layers`
stacked in layer order, an expert's matrices at its place in the held
range).

Departures from the published description: (1) the file's cut: 8 of 48
layers, experts 0-15 of 128. (2) DeepSeek-V3.2-Exp's inference code
rotates qI and kI by a Hadamard matrix and quantises them to fp8 before
the scores (devices of an fp8 kernel); neither is here: the
configuration states bf16 keys in the cache and float32 scores. (3) Its
index key rotates 64 of its 128 dimensions; this row's whole index head
is 64, all of it rotated (`assumed`). (4) Attention runs in UNIFORM
blocks of `QUERY_BLOCK` query rows under `lax.map` (one compiled body)
and the head in blocks of the vocabulary, so that a 12,288-token
sequence fits beside the weights: the same sums. (5) What `config.json`
does not say is the file's `assumed`: q/k-norm, the LayerNorm on kI, the
`1 / sqrt(heads x dim)` on w. (6) No vision tower: text tokens, whose
three position components are equal.

`compare` also judges the SPARSE PATH at the timed sizes (`sparse_path`):
the program's index keys through its cache layout, its `attn/index`,
`attn/select`, chunk kernel and decode walk on the reference's own hidden
states. That is the one place where this module runs code of the program.

`python -m chipbench.references.keye_vl` is this configuration's control:
see `main`.
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import manifest
from chipbench import reference as dense

QUERY_BLOCK = 128  # query rows whose scores are computed at once
VOCAB_BLOCK = 32768  # columns of the head cast to float32 at once
#: the context `sparse_path` judges the selection and the attention at
SPARSE_CONTEXT = 12288
#: its judged queries: the last chunk through the chunk path, the last
#: rows of it through the decode path (`judged` of a rehearsal's `hf`)
JUDGED = (512, 16)


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(w) + _f32(b)


def _rope(z, positions, theta, sections=None):
    """Half-split rotary of z [T, heads, d]; positions [T] or [3, T] with
    `sections` frequency pairs a component."""
    import jax.numpy as jnp

    d = z.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 2:
        ang = _f32(positions)[..., None] * inv  # [3, T, d / 2]
        parts, off = [], 0
        for j, n in enumerate(sections):
            parts.append(ang[j, :, off:off + n])
            off += n
        ang = jnp.concatenate(parts, axis=-1)
    else:
        ang = _f32(positions)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    z1, z2 = z[..., : d // 2], z[..., d // 2:]
    return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], -1)


def selected_tokens(scores, positions_q, topk: int):
    """bool [Tq, T]: the `min(topk, t + 1)` highest of each query's scores
    over `s <= t`, ties to the earlier: the rank in a stable descending
    sort."""
    import jax.numpy as jnp

    t = scores.shape[1]
    causal = jnp.arange(t)[None] <= positions_q[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return causal & (rank < topk)


def attention_under(q, k, v, selected):
    """softmax(q . k / sqrt(d)) v over the keys `selected` [Tq, T] names:
    q [Tq, Hq, d], k, v [T, Hkv, d] -> [Tq, Hq, d]."""
    import jax
    import jax.numpy as jnp

    g = q.shape[1] // k.shape[1]
    kk, vv = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,khd->htk", q, kk) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(selected[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("htk,khd->thd", p, vv)


def attention_branch(x, lp: dict, hf: dict, positions, select=True,
                     topk=None, unit_weights=False, tail=0):
    """Attn(x) over x [T, H] (normed), causal. Returns (out [T, H], trace:
    k and v after norm and rotary, and of the last `tail` queries q and
    the selection). `select` False is dense attention; `topk` overrides the
    file's; `unit_weights` replaces the head weights w by ones (the
    controls)."""
    import jax
    import jax.numpy as jnp

    sa = hf["sa_config"]
    hq, hkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    nj, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    topk = topk or sa["topk"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    sections = hf["rope_scaling"]["mrope_section"]
    t = x.shape[0]
    pos3 = positions if positions.ndim == 2 else jnp.stack([positions] * 3)
    temporal = pos3[0]
    q = dense._rms((x @ _f32(lp["wq"])).reshape(t, hq, d), lp["q_norm"], eps)
    k = dense._rms((x @ _f32(lp["wk"])).reshape(t, hkv, d), lp["k_norm"], eps)
    v = (x @ _f32(lp["wv"])).reshape(t, hkv, d)
    q, k = _rope(q, pos3, theta, sections), _rope(k, pos3, theta, sections)
    qi = _rope((x @ _f32(lp["wi_q"])).reshape(t, nj, di), temporal, theta)
    ki = _rope(_layer_norm(x @ _f32(lp["wi_k"]), lp["ik_norm"],
                           lp["ik_bias"], eps)[:, None], temporal, theta)[:, 0]
    w = (x @ _f32(lp["wi_w"])) / math.sqrt(nj * di)
    if unit_weights:
        w = jnp.ones_like(w)

    pad = -t % QUERY_BLOCK
    blocks = (t + pad) // QUERY_BLOCK

    def block(args):
        qb, qib, wb, pb = args  # a block of query rows
        if select:
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

    out, sel = jax.lax.map(block, (blocked(q), blocked(qi), blocked(w),
                                   blocked(temporal)))
    out = out.reshape(blocks * QUERY_BLOCK, hq * d)[:t]
    sel = sel.reshape(blocks * QUERY_BLOCK, t)[:t]
    at = t - tail if tail else t
    trace = {"q": q[at:], "k": k, "v": v, "selected": sel[at:]}
    return out @ _f32(lp["wo"]), trace


def moe_branch(x, lp: dict, hf: dict):
    """MoE(x) over x [T, H] (normed): the held experts' terms, expert by
    expert, every assignment."""
    import jax
    import jax.numpy as jnp

    first, count = hf["experts_held"]
    k = hf["num_experts_per_tok"]
    p = jax.nn.softmax(x @ _f32(lp["w_router"]), axis=-1)
    order = jnp.argsort(-p, axis=-1, stable=True)[:, :k]  # [T, k]
    top = jnp.take_along_axis(p, order, axis=-1)
    if hf.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)

    def expert(y, args):
        e, wg, wu, wd = args
        share = jnp.sum(jnp.where(order == first + e, top, 0.0), axis=-1)
        out = (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), (
        jnp.arange(count), lp["we_gate"], lp["we_up"], lp["we_down"]))
    return y


def block(h, lp: dict, hf: dict, positions, **how):
    eps = hf["rms_norm_eps"]
    a, trace = attention_branch(dense._rms(h, lp["attn_norm"], eps), lp, hf,
                                positions, **how)
    h = h + a
    return h + moe_branch(dense._rms(h, lp["mlp_norm"], eps), lp, hf), trace


# -- one precision down, for the control -------------------------------------

MATRICES = ("wq", "wk", "wv", "wo", "wi_q", "wi_k", "wi_w", "we_gate",
            "we_up", "we_down")


def _int8(w, axis: int):
    """`w` as int8 holds it, symmetric, one scale an output channel (the
    maximum over `axis`, the input's), as float32."""
    import jax.numpy as jnp

    w = _f32(w)
    scale = jnp.maximum(
        jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0, 1e-8)
    return jnp.round(w / scale) * scale


def to_int8(lp: dict) -> dict:
    """The layer's matrices one precision below bf16: int8, symmetric per
    output channel, kept as the float32 values int8 can hold (the router
    stays float32, as the configuration states it). `hidden_states` and
    `log_probs` lower the embedding (a scale a row) and the head (a scale
    a column) beside it: every bf16 weight of the model."""
    out = dict(lp)
    for name in MATRICES:
        out[name] = _int8(lp[name], -2)
    return out


_LAYERS: dict = {}


def _layer_fn(hf: dict, lower, how: dict):
    """One jitted layer a distinct reading of the configuration."""
    import jax

    key = (lower, tuple(sorted((k, str(v)) for k, v in how.items())),
           tuple((k, str(hf[k])) for k in (
               "hidden_size", "num_attention_heads", "num_key_value_heads",
               "head_dim", "num_experts_per_tok", "experts_held",
               "sa_config", "rope_scaling", "rope_theta")))
    if key not in _LAYERS:
        low = lower or (lambda lp: lp)
        _LAYERS[key] = jax.jit(
            lambda h, lp, pos: block(h, low(lp), hf, pos, **how))
    return _LAYERS[key]


def hidden_states(params: dict, hf: dict, ids, lower=None, traces=None,
                  positions=None, **how):
    """The residual stream after the last layer over the sequence `ids`
    [T] (f32). Each layer's input and trace are appended to `traces`."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    pos = (jnp.arange(ids.shape[0], dtype=jnp.int32) if positions is None
           else jnp.asarray(positions, jnp.int32))
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"][ids])
        if lower is not None:
            h = _int8(h, -1)
        for i in range(params["layers"]["wq"].shape[0]):
            lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            h_in = h
            h, trace = _layer_fn(hf, lower, how)(h, lp, pos)
            if traces is not None:
                traces.append({"input": h_in, **trace})
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


# -- the sparse path, judged on the program's own routines --------------------


def agreement(mine, theirs) -> np.ndarray:
    """|mine and theirs| / max(|mine|, |theirs|) a query: bool [Q, T]."""
    both = np.sum(mine & theirs, axis=-1)
    return both / np.maximum(
        np.maximum(mine.sum(-1), theirs.sum(-1)), 1)


def _distance(got, want) -> float:
    """||got - want|| / ||want||, the largest over heads: [Q, Hq, d]."""
    num = np.sqrt(np.sum((got - want) ** 2, axis=(0, 2)))
    den = np.sqrt(np.sum(want ** 2, axis=(0, 2)))
    return float(np.max(num / np.maximum(den, 1e-30)))


def _judge_fn(cfg, context: int, page: int, judged, fault):
    """The program's side of one layer, jitted: the layer's projections of
    the reference's input, its K, V and index keys landed in a cache as a
    step lands them, then the LAST `JUDGED_CHUNK` queries through the
    chunk path and the last `JUDGED_DECODE` through the decode path.
    Returns (chunk attention [Qc, Hq, d], its selection [Qc, T], decode
    attention [Qd, Hq, d], its selection [Qd, T])."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import keye_vl as kv_mod
    from dynamo_tpu.models.llama import StepGroup, land_staged_kv, rms_norm

    n_pages = context // page
    qc, qd = judged
    hq, d = cfg.num_heads, cfg.head_dim

    def judge(h_in, lp):
        x = rms_norm(h_in.astype(cfg.dtype)[None], lp["attn_norm"],
                     cfg.rms_norm_eps)
        pos = jnp.arange(context, dtype=jnp.int32)[None]
        tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        ones = jnp.ones((1, context), bool)
        whole = StepGroup(jnp.zeros((1, context), jnp.int32), pos, ones,
                          tables)
        q, k, v, qi, ki, w = kv_mod.project(x, lp, cfg)
        q, k, qi, ki = kv_mod.rotate(q, k, qi, ki, whole, cfg)
        cache = kv_mod.init_cache(
            dataclasses.replace(cfg, num_layers=1), n_pages + 1, page)
        dpad = cache.k.shape[-1] - d
        pad = ((0, 0),) * 3 + ((0, dpad),)
        kv = land_staged_kv(
            cache.pages, (jnp.pad(k, pad)[None], jnp.pad(v, pad)[None]),
            tables, pos, ones)
        ki_pool = kv_mod.land_index_keys(cache.ki, ki[None], tables, pos,
                                         ones)
        if fault == "wrong_token":  # two cached tokens change places
            swap = lambda a: a.at[0, 1, 0].set(a[0, 1, 1]).at[  # noqa: E731
                0, 1, 1].set(a[0, 1, 0])
            kv = kv._replace(k=swap(kv.k), v=swap(kv.v))
        lo = context - qc
        piece = lambda a: a[:, lo:]  # noqa: E731
        g = StepGroup(whole.tokens[:, lo:], pos[:, lo:], ones[:, lo:], tables)
        attn_c, _, _, _, sel_c = kv_mod.token_attention(
            piece(q), piece(k), piece(v), piece(qi), piece(ki), piece(w),
            kv, ki_pool, jnp.int32(0), g, cfg)
        # the decode path: a row a position, every row the same pages
        rows = lambda a: a[0, context - qd:, None]  # noqa: E731
        gd = StepGroup(jnp.zeros((qd, 1), jnp.int32), rows(pos),
                       jnp.ones((qd, 1), bool),
                       jnp.broadcast_to(tables, (qd, n_pages)))
        work = None
        if cfg.kernels:
            from dynamo_tpu.ops.paged_attention import decode_work_list

            work = decode_work_list(gd.page_tables, gd.positions[:, 0])
        attn_d, _, _, _, sel_d = kv_mod.token_attention(
            rows(q), rows(k), rows(v), rows(qi), rows(ki), rows(w), kv,
            ki_pool, jnp.int32(0), gd, cfg, work)
        return (attn_c[0].reshape(qc, hq, d), sel_c[0, :, :context],
                attn_d[:, 0].reshape(qd, hq, d), sel_d[:, 0, :context])

    return jax.jit(judge)


def sparse_path(params: dict, hf: dict, context: int = SPARSE_CONTEXT,
                seed: int = 1234, fault=None) -> dict:
    """The selection and the attention of the program at `context` tokens
    against the reference, layer by layer on the REFERENCE's hidden
    states: `selected_tokens_agreement` (the mean over the judged queries
    of `agreement`; `selected_tokens_agreement_min` the smallest) and
    `sparse_attn_distance` (each path's output against the reference's
    attention under the selection the program itself made, as a share of
    its norm, the largest over layers, paths and heads)."""
    import jax
    import jax.numpy as jnp

    from chipbench import traffic
    from dynamo_tpu.models.registry import get_model

    t0 = time.perf_counter()
    cfg = get_model(
        hf["preset"], dtype=hf.get("dtype", "bfloat16"),
        attention_impl=hf.get("attention_impl") or (
            "pallas" if jax.default_backend() == "tpu" else "xla"),
    ).config
    page = hf.get("page_size", 64)
    qc, qd = judged = tuple(hf.get("judged", JUDGED))
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    traces: list = []
    hidden_states(params, hf, ids, traces=traces, tail=qc)
    judge = _judge_fn(cfg, context, page, judged, fault)
    agree, worst = [], 0.0
    under = jax.jit(attention_under)
    for i, tr in enumerate(traces):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        attn_c, sel_c, attn_d, sel_d = judge(tr["input"], lp)
        theirs = np.asarray(tr["selected"])
        agree.append(agreement(np.asarray(sel_c), theirs))
        agree.append(agreement(np.asarray(sel_d), theirs[qc - qd:]))
        with jax.default_matmul_precision("highest"):
            want_c = under(tr["q"], tr["k"], tr["v"], sel_c)
            want_d = under(tr["q"][qc - qd:], tr["k"], tr["v"], sel_d)
        worst = max(worst,
                    _distance(np.asarray(attn_c, np.float32),
                              np.asarray(want_c)),
                    _distance(np.asarray(attn_d, np.float32),
                              np.asarray(want_d)))
    agree = np.concatenate(agree)
    return {"selected_tokens_agreement": float(agree.mean()),
            "selected_tokens_agreement_min": float(agree.min()),
            "sparse_attn_distance": worst, "sparse_context": context,
            "sparse_queries": int(agree.size),
            "sparse_path_s": round(time.perf_counter() - t0, 1)}


def lowered_sparse_path(params: dict, hf: dict, context: int,
                        seed: int = 1234, **how) -> dict:
    """A control that lowers the REFERENCE's selection (`select`, `topk`,
    `unit_weights`): its selection of the judged queries against the
    reference's as it stands, and its attention against the reference's
    attention under the lowered selection (0 by construction: the
    selection is what is lowered, and the agreement judges it)."""
    from chipbench import traffic

    import jax
    import jax.numpy as jnp

    qc = tuple(hf.get("judged", JUDGED))[0]
    rng = np.random.default_rng(seed)
    ids = rng.integers(traffic.FIRST_ID, hf["vocab_size"], context)
    real: list = []
    hidden_states(params, hf, ids, traces=real, tail=qc)
    # on the reference's own hidden states, layer by layer
    pos = jnp.arange(context, dtype=jnp.int32)
    agree = []
    with jax.default_matmul_precision("highest"):
        for i, tr in enumerate(real):
            lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
            _, trace = _layer_fn(hf, None, {**how, "tail": qc})(
                tr["input"], lp, pos)
            agree.append(agreement(np.asarray(trace["selected"]),
                                   np.asarray(tr["selected"])))
    agree = np.concatenate(agree)
    return {"selected_tokens_agreement": float(agree.mean()),
            "selected_tokens_agreement_min": float(agree.min()),
            "sparse_attn_distance": 0.0, "sparse_context": context}


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    """`chipbench.reference.compare` through this module's `log_probs`,
    and, where `hf` names the served preset, the sparse path's two
    readings (`sparse_path`) under `reference_tolerance.
    min_selected_tokens_agreement` and `max_sparse_attn_distance` of the
    same file. The harness's verdict reads four keys (chipbench/run.py
    `check_reference`, not a configuration's to edit): a reading past its
    limit is reported as a mean log-prob drift past every limit, the
    measured one kept beside it. A stream may bring the control's
    readings in the program's place (`sparse_path`)."""
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
        ) if not ok
    ]
    if failed:
        res["failed_by"] = failed
        res["mean_logprob_drift_of_tokens"] = res["mean_logprob_drift"]
        res["mean_logprob_drift"] = float("inf")
    return res


def served_widths(cfg) -> dict:
    """A KeyeVLConfig's sizes under the published file's keys: every one
    of them is compared with the configuration file."""
    return {
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_local_experts": cfg.n_routed_experts,
        "num_experts": cfg.experts_here,
        "experts_held": list(cfg.experts_held or (0, cfg.n_routed_experts)),
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section),
                         "rope_type": "default", "type": "default"},
        "sa_config": {
            "indexer_head_dim": cfg.index_head_dim,
            "indexer_num_heads": cfg.index_heads,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": cfg.index_topk},
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct: (a) the weights one precision below bf16 (it fails on the
#: streams' log-probs), (b) the selection OFF, dense attention at 12,288
#: tokens, (c) `topk` 1024, (d) the head weights `w` replaced by ones
#: (b-d fail on the selected tokens: the greedy streams never reach
#: `topk`, so their streams ARE the reference's), (e) a fault PLANTED in
#: the program's cache (`sparse_path`'s `fault`): two cached tokens of one
#: page change places in K and V, the index keys left alone, so the
#: selection is the reference's and the attention reads another token's
#: row (it fails on the attention's distance, and on it alone)
CONTROLS = {
    "int8_weights": {"lower": to_int8},
    "selection_off": {"sparse": {"select": False}},
    "topk_1024": {"sparse": {"topk": 1024}},
    "unit_head_weights": {"sparse": {"unit_weights": True}},
    "wrong_token": {"walk": {"fault": "wrong_token"}},
}
_SPARSE_UNTOUCHED = {"selected_tokens_agreement": 1.0,
                     "sparse_attn_distance": 0.0}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it (the
    whole padded sequence every step: a position sees nothing after it).
    A control that lowers the SPARSE path decodes as the reference does
    (112 tokens never reach `topk`) and brings `lowered_sparse_path`'s
    readings; one that plants a fault in the program's cache brings
    `sparse_path`'s."""
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
    """python -m chipbench.references.keye_vl [--seeds a,b] [--config
    keye-vl2-30b-a3b-1chip] [--controls a,b]: each of CONTROLS decodes
    the benchmark's greedy streams and goes through `compare` against the
    reference as it stands, under the configuration's
    `reference_tolerance`; each has to come out as not correct."""
    import argparse
    import json
    import sys

    import jax

    from chipbench import control
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="keye-vl2-30b-a3b-1chip")
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
