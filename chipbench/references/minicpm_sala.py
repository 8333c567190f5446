"""The plain reference of MiniCPM-SALA (openbmb MiniCPM-SALA 9B,
`model_type` minicpm_sala), as the configuration `minicpm-sala-9b-1chip`
brings it (`reference_module` in its file): float32,
`jax.default_matmul_precision("highest")`, no cache, no state pool, no
chunked scan, no kernels, one layer at a time so that it fits.

The model, from its published config.json (`mixer_types`, the widths, the
muP scalars), MiniCPM4's `sparse_config` (InfLLM-v2, arXiv 2506.07900)
and the Lightning Attention papers (arXiv 2401.04658, 2501.08313); `x` is
a layer's RMS-normed input, every norm has a learned weight:

    h   = embed[ids] * scale_emb
    h   = h + r Mixer(RMSNorm(h)),  h = h + r MLP(RMSNorm(h))
    r   = scale_depth / sqrt(mup_denominator)  (the PUBLISHED depth, 32)
    MLP(x) = W_d (silu(W_g x) * W_u x)
    logits = W_head (RMSNorm(h) / (hidden / dim_model_base))

- `lightning-attn`: `q, k, v = W_q x, W_k x, W_v x` in `lightning_nh`
  heads of `lightning_head_dim`; RMSNorm over the head dimension on q and
  k (`qk_norm`); half-split rotary at `rope_theta` on q and k
  (`lightning_use_rope`); per head `S_t = lambda S_(t-1) + k_t^T v_t`,
  `o_t = (q_t / sqrt(d)) S_t`, HERE TOKEN BY TOKEN (`lax.scan`), never in
  chunks; `lambda_h = exp(-2^(-8 (h + 1) / heads) (1 - l / (L - 1) +
  1e-5))` for head h = 0.. of the layer with PUBLISHED index l of L = 32
  (`layer_indices` in the file); RMSNorm over the head dimension on o
  (`use_output_norm`); `o * sigmoid(W_z x)` (`use_output_gate`); `W_o`.
- `minicpm4`: `num_attention_heads` query and `num_key_value_heads` KV
  heads of `head_dim`; RMSNorm over the head dimension on q and k; NO
  rotary (`attn_use_rope` false); scores at 1 / sqrt(head_dim); for the
  query at position t with n = t + 1 tokens of context, per KV head:
  n < `dense_len`: causal softmax over all n keys; else compressed keys
  `Kc_j = mean(k[stride j : stride j + kernel])` of every window ending at
  or before t, `p_h = softmax_j(q_h . Kc_j / sqrt(d))` a query head, `P =
  sum of p_h` over the KV head's query heads, `B_b = max P_j, j in [cpb b
  - 1, cpb b + cpb - 1]` (cpb = block / stride; a max-pool of cpb + 1,
  stride cpb, padding 1), block 0 and the blocks holding the last `window`
  tokens +infinity, the `topk` highest blocks (ties to the earlier),
  causal softmax over the keys of those blocks; `o * sigmoid(W_z x)`
  (`attn_use_output_gate`); `W_o`.

Fed the served engine's own parameter tree (models/minicpm_sala.py:
`sparse` and `lightning`, a stack a kind in layer order, published shapes;
no scalar is folded into a weight).

Departures from the published description: (1) the file's cut: the
layers `layer_indices` names. (2) THE RULE IS BY QUERY POSITION: the
published full-sequence forward applies the sparse rule to every query of
a sequence of `dense_len` tokens or more, a decode step to the query it
has; here a query under `dense_len` is dense whatever follows it, which
is what a decode step computes and what makes a chunked prefill, a decode
through the cache and one full forward agree. (3) Attention runs in blocks
of query rows and the head in blocks of the vocabulary, so that a
12,288-token sequence and the head fit beside the weights: the same sums.
(4) Sizes `config.json` does not give are the file's `assumed`: the
`sparse_config`, the decay slopes, the output norm over the head
dimension, no feature map beyond the norm.

`compare` also judges the sparse path at the timed sizes
(`sparse_path`): the program's compressed keys, its selection and its
walk on the reference's own queries and keys. That is the one place where
this module runs code of the program.

`python -m chipbench.references.minicpm_sala` is this configuration's
control: see `main`.
"""

from __future__ import annotations

import math
import time

import numpy as np

from chipbench import manifest
from chipbench import reference as dense

QUERY_BLOCK = 256  # query rows whose scores are computed at once
VOCAB_BLOCK = 32768  # columns of the head cast to float32 at once
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
#: the context `sparse_path` judges the selection and the walk at
SPARSE_CONTEXT = 12288

SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "init_blocks",
               "window_size", "topk", "dense_len")


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _head_norm(x, w, eps):
    """RMSNorm over the last (head) dimension."""
    return dense._rms(x, w, eps)


def log_decay(hf: dict, published_index: int):
    import jax.numpy as jnp

    heads = hf["lightning_nh"]
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / heads) * (
        1.0 - published_index / (hf["mup_denominator"] - 1) + 1e-5)


def lightning_branch(x, lp: dict, hf: dict, published_index: int,
                     state_dtype=None, split=None):
    """`Mixer(x)` of a lightning layer over x [T, H] (f32), from an empty
    state. `state_dtype` is the control's. With `split`, also the trace
    `state_distance` reads: the recurrence's inputs from token `split`
    on, the state they start from and the state the last one leaves."""
    import jax
    import jax.numpy as jnp

    nh, d, t = hf["lightning_nh"], hf["lightning_head_dim"], x.shape[0]
    eps = hf["rms_norm_eps"]
    q = _head_norm((x @ _f32(lp["wq"])).reshape(t, nh, d), lp["q_norm"], eps)
    k = _head_norm((x @ _f32(lp["wk"])).reshape(t, nh, d), lp["k_norm"], eps)
    v = (x @ _f32(lp["wv"])).reshape(t, nh, d)
    inv = 1.0 / (float(hf["rope_theta"])
                 ** (jnp.arange(0, d, 2, jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(z):
        z1, z2 = z[..., : d // 2], z[..., d // 2:]
        return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin], -1)

    q, k = rope(q) / math.sqrt(d), rope(k)
    lam = jnp.exp(log_decay(hf, published_index))  # [nh]
    carried = jnp.float32 if state_dtype is None else state_dtype

    def step(s, tok):  # s [nh, d (of v), d (of k)]
        qt, kt, vt = tok
        s = (_f32(s) * lam[:, None, None]
             + vt[:, :, None] * kt[:, None, :]).astype(carried)
        return s, jnp.einsum("hpn,hn->hp", _f32(s), qt)

    toks = (q, k, v)
    s0 = jnp.zeros((nh, d, d), carried)
    trace = None
    if split is None:
        _, o = jax.lax.scan(step, s0, toks)
    else:
        s_mid, o0 = jax.lax.scan(step, s0, tuple(a[:split] for a in toks))
        s_end, o1 = jax.lax.scan(step, s_mid, tuple(a[split:] for a in toks))
        o = jnp.concatenate([o0, o1])
        trace = {"u": v[split:], "decay": jnp.broadcast_to(
            lam, (t - split, nh)), "b": k[split:], "c": q[split:],
            "start": _f32(s_mid), "end": _f32(s_end)}
    o = _head_norm(o, lp["o_norm"], eps).reshape(t, nh * d)
    out = (o * jax.nn.sigmoid(x @ _f32(lp["wz"]))) @ _f32(lp["wo"])
    return out if split is None else (out, trace)


def compressed_keys(k, sp: dict):
    """`Kc_j = mean(k[stride j : stride j + kernel])`, every whole window
    of k [T, ..., D]: [J, ..., D]."""
    import jax.numpy as jnp

    kk, st = sp["kernel_size"], sp["kernel_stride"]
    n = (k.shape[0] - kk) // st + 1
    if n <= 0:
        return jnp.zeros((0, *k.shape[1:]), k.dtype)
    # window j is rows st j .. st j + kk - 1: its i-th row, every j at once
    return jnp.stack([k[i : i + st * (n - 1) + 1 : st]
                      for i in range(kk)]).mean(axis=0)


def selected_blocks(q, kc, positions, sp: dict, n_blocks: int):
    """The blocks each query of one KV head attends over: q [T, G, D] its
    query heads at `positions` [T], kc [J, D] the sequence's compressed
    keys. Returns [T, n_blocks] bool."""
    import jax
    import jax.numpy as jnp

    kk, st, s = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    cpb = s // st
    d = q.shape[-1]
    n = positions + 1
    j = jnp.arange(kc.shape[0])
    seen = (st * j + kk)[None, :] <= n[:, None]  # the window ends by t
    sc = jnp.einsum("tgd,jd->tgj", q, kc) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[:, None], sc, -jnp.inf), axis=-1)
    p = jnp.where(seen[:, None], p, 0.0).sum(axis=1)  # [T, J]
    p = jnp.where(jnp.isnan(p), 0.0, p)
    # B_b = max P_j over j in [cpb b - 1, cpb b + cpb - 1]: P with one
    # column before it and columns up to cpb n_blocks after, all under
    # every probability
    room = max(cpb * n_blocks - p.shape[1], 0)
    pp = jnp.pad(p[:, : cpb * n_blocks], ((0, 0), (1, room)),
                 constant_values=-1.0)
    score = jnp.maximum(
        pp[:, 1:].reshape(-1, n_blocks, cpb).max(axis=2),
        pp[:, : cpb * n_blocks : cpb])
    blk = jnp.arange(n_blocks)[None]
    own = (positions // s)[:, None]
    near = (jnp.maximum(positions - sp["window_size"] + 1, 0) // s)[:, None]
    score = jnp.where((blk < sp["init_blocks"]) | (blk >= near), jnp.inf,
                      score)
    score = jnp.where(blk <= own, score, -jnp.inf)
    order = jnp.argsort(-score, axis=1, stable=True)
    rank = jnp.argsort(order, axis=1)
    return (blk <= own) & ((n < sp["dense_len"])[:, None]
                           | (rank < sp["topk"]))


def sparse_attention(q, k, v, sp: dict, select: bool = True, trace=None,
                     compress_stride=None):
    """A `minicpm4` layer's attention: q [T, Hq, D], k, v [T, Hkv, D]
    (normed), by query position. `select` False and `compress_stride` are
    the control's: dense attention everywhere, and the compressed keys
    taken at another stride. Returns [T, Hq, D]."""
    import jax
    import jax.numpy as jnp

    t, hq, d = q.shape
    hkv = k.shape[1]
    g, s = hq // hkv, sp["block_size"]
    n_blocks = -(-t // s)
    spc = sp if compress_stride is None else {
        **sp, "kernel_stride": compress_stride}
    kc = compressed_keys(k, spc)  # [J, Hkv, D]
    key_block = jnp.arange(t) // s
    rows = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        pos = jnp.arange(lo, hi)
        heads = []
        for h in range(hkv):
            qh = q[lo:hi, h * g : (h + 1) * g]  # [tq, G, D]
            keep = pos[:, None] >= jnp.arange(hi)[None, :]
            if select and hi >= sp["dense_len"]:
                sel = selected_blocks(qh, kc[:, h], pos, spc, n_blocks)
                if trace is not None:
                    trace.setdefault("selected", {}).setdefault(
                        h, []).append(sel)
                keep &= sel[:, key_block[:hi]]
            sc = jnp.einsum("tgd,kd->tgk", qh, k[:hi, h]) / math.sqrt(d)
            p = jax.nn.softmax(
                jnp.where(keep[:, None], sc, -jnp.inf), axis=-1)
            heads.append(jnp.einsum("tgk,kd->tgd", p, v[:hi, h]))
        rows.append(jnp.concatenate(heads, axis=1))
    return jnp.concatenate(rows)


def attention_under(q, k, v, selected, positions, sp: dict):
    """Causal softmax attention of the queries q [t, Hkv, G, D] at
    `positions` [t] over the keys of the blocks `selected` [t, Hkv, blocks]
    names and no others, k, v [T, Hkv, D] the whole sequence's: what ANY
    walk over those blocks has to give, whoever chose them. [t, Hkv, G,
    D]."""
    import jax
    import jax.numpy as jnp

    s, d = sp["block_size"], q.shape[-1]
    key = jnp.arange(k.shape[0])
    rows = []
    for lo in range(0, q.shape[0], QUERY_BLOCK):
        sel = selected[lo : lo + QUERY_BLOCK]
        pos = positions[lo : lo + QUERY_BLOCK]
        pad = max(0, -(-k.shape[0] // s) - sel.shape[-1])
        sel = jnp.pad(sel, ((0, 0), (0, 0), (0, pad)))
        keep = sel[:, :, key // s] & (key[None, None] <= pos[:, None, None])
        sc = jnp.einsum("thgd,khd->thgk", q[lo : lo + QUERY_BLOCK],
                        k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(keep[:, :, None], sc, -jnp.inf), axis=-1)
        rows.append(jnp.einsum("thgk,khd->thgd", p, v))
    return jnp.concatenate(rows)


def sparse_branch(x, lp: dict, hf: dict, select=True, trace=None,
                  compress_stride=None):
    """`Mixer(x)` of a `minicpm4` layer over x [T, H] (f32)."""
    import jax

    hq, hkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    t, eps = x.shape[0], hf["rms_norm_eps"]
    q = _head_norm((x @ _f32(lp["wq"])).reshape(t, hq, d), lp["q_norm"], eps)
    k = _head_norm((x @ _f32(lp["wk"])).reshape(t, hkv, d), lp["k_norm"], eps)
    v = (x @ _f32(lp["wv"])).reshape(t, hkv, d)
    if trace is not None:
        trace.update(q=q, k=k, v=v)
    o = sparse_attention(q, k, v, hf["sparse_config"], select, trace,
                         compress_stride)
    if trace is not None:
        trace["o"] = o
    return (o.reshape(t, hq * d)
            * jax.nn.sigmoid(x @ _f32(lp["wz"]))) @ _f32(lp["wo"])


def block(h, lp: dict, hf: dict, kind: str, published_index: int, **how):
    """One layer over h [T, H] (f32). Returns (h, the layer's trace)."""
    import jax

    eps = hf["rms_norm_eps"]
    r = hf["scale_depth"] / math.sqrt(hf["mup_denominator"])
    x = dense._rms(h, lp["norm"], eps)
    trace = None
    if kind == LIGHTNING:
        m = lightning_branch(x, lp, hf, published_index,
                             how.get("state_dtype"), how.get("split"))
        if how.get("split") is not None:
            m, trace = m
    else:
        trace = {} if how.get("sparse_trace") else None
        m = sparse_branch(x, lp, hf, how.get("select", True), trace,
                          how.get("compress_stride"))
    h = h + r * m
    y = dense._rms(h, lp["mlp_norm"], eps)
    h = h + r * ((jax.nn.silu(y @ _f32(lp["w_gate"]))
                  * (y @ _f32(lp["w_up"]))) @ _f32(lp["w_down"]))
    return h, trace


MATRICES = ("wq", "wk", "wv", "wz", "wo", "w_gate", "w_up", "w_down")


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


def layers_of(params: dict, hf: dict):
    """(kind, published index, the layer's leaves) in layer order, from
    the two stacks of the served tree (one a kind, each in layer order),
    ONE LAYER AT A TIME (a layer's slices are 0.57 GB beside 10 GB of
    weights)."""
    import jax

    at = {"minicpm4": 0, "lightning-attn": 0}
    stack = {"minicpm4": "sparse", "lightning-attn": "lightning"}
    for kind, idx in zip(hf["mixer_types"], hf["layer_indices"]):
        yield kind, idx, jax.tree.map(
            lambda a, i=at[kind]: a[i], params[stack[kind]])
        at[kind] += 1


_LAYERS: dict = {}


def _layer_fn(hf: dict, kind: str, lower, how: dict):
    """One jitted layer a kind and a distinct reading of the
    configuration; the published index is an argument (the decay reads
    it), so the lightning layers share one program."""
    import jax

    key = (kind, lower, tuple(sorted(
        (k, str(v)) for k, v in how.items())), tuple(
        (k, hf[k]) for k in ("hidden_size", "num_attention_heads",
                             "scale_depth", "dim_model_base",
                             "mup_denominator", "scale_emb")),
        tuple(sorted(hf["sparse_config"].items())))
    if key not in _LAYERS:
        low = lower or (lambda lp: lp)
        _LAYERS[key] = jax.jit(lambda x, lp, idx: block(
            x, low(lp), hf, kind, idx, **how))
    return _LAYERS[key]


def hidden_states(params: dict, hf: dict, ids, lower=None, traces=None,
                  **how):
    """The residual stream after the last layer over the sequence `ids`
    [T] (f32). Every layer's trace is appended to `traces`."""
    import jax
    import jax.numpy as jnp

    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids]) * hf["scale_emb"]
        for kind, idx, lp in layers_of(params, hf):
            x, trace = _layer_fn(hf, kind, lower, how)(
                x, lp, jnp.float32(idx))
            if traces is not None:
                traces.append((kind, trace))
    return x


def log_probs(params: dict, hf: dict, ids, at, **how) -> np.ndarray:
    """log-softmax of the next-token distribution at positions `at` of
    the sequence `ids`: [len(at), vocab] float32. `how` is the control's
    (`lower`, `state_dtype`, `select`, `compress_stride`) or a trace's
    (`split`, `sparse_trace`, `traces`)."""
    import jax
    import jax.numpy as jnp

    x = hidden_states(params, hf, ids, **how)
    with jax.default_matmul_precision("highest"):
        head = _LAYERS.setdefault("head", jax.jit(lambda h, w: h @ _f32(w)))
        h = dense._rms(x[jnp.asarray(at)], params["final_norm"],
                       hf["rms_norm_eps"])
        h = h / (hf["hidden_size"] / hf["dim_model_base"])
        w = params["lm_head"]
        logits = jnp.concatenate([
            head(h, w[:, lo : lo + VOCAB_BLOCK])
            for lo in range(0, w.shape[1], VOCAB_BLOCK)
        ], axis=1)
        out = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(out)


# -- the state and the sparse path, judged on the program's own routines ----


def state_distance(hf: dict, streams, traces) -> float:
    """The precision the lightning state is carried in: per lightning
    layer, the program's pool (the adapter's `init_kv`) and its decode
    routine (`ops/ssm_state.ssm_decode_step`) started from the
    reference's state after the prompt and fed the reference's own inputs
    of the decoded tokens, against the state the reference's recurrence
    leaves, as a share of its norm; the largest over layers and streams.
    A stream that brings its own state (`ssm_state`, the control's) is
    read in the program's place."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.registry import get_model
    from dynamo_tpu.ops import ssm_state

    adapter = get_model(hf["preset"], dtype=hf.get("dtype", "bfloat16"))
    worst = 0.0
    one = jnp.ones((1,), jnp.int32)

    @jax.jit
    def decode(pool, li, start, u, dec, b, c):
        """The layer's decoded tokens through the program's routine, one
        a step, from the reference's state after the prompt."""
        def step(pool, tok):
            return ssm_state.ssm_decode_step(
                pool, li, one, one, *(x[None] for x in tok))[1], None

        pool = pool.at[li, 1].set(start)
        return jax.lax.scan(step, pool, (u, dec, b, c))[0][li, 1]

    for s, tr in zip(streams, traces):
        lightning = [t for kind, t in tr if kind == LIGHTNING]
        theirs = s.get("ssm_state")
        pool = None if theirs else adapter.init_kv(
            2, hf["sparse_config"]["block_size"], state_slots=1).ssm
        for li, t in enumerate(lightning):
            got = np.asarray(theirs[li], np.float32) if theirs else (
                np.asarray(decode(pool, jnp.int32(li), t["start"], t["u"],
                                  t["decay"], t["b"], t["c"])))
            want = np.asarray(t["end"])
            worst = max(worst, float(
                np.linalg.norm(got - want) / np.linalg.norm(want)))
    return worst


class _Distance:
    """The two readings of the sparse path, over (query, KV head) pairs.

    `selected_pages_agreement`: |mine and theirs| / max(|mine|, |theirs|)
    of the selected blocks, the mean over pairs (`_min` the smallest): a
    pair whose 64th block is a near-tie in bfloat16 reads 63 / 64, dense
    attention over 192 blocks 0.33. The SELECTION's judge.

    `sparse_attn_distance`: over ALL pairs, per (sparse layer, KV head)
    the distance of the attention outputs from the reference's attention
    UNDER THE SAME SELECTION (`attention_under` the blocks the judged
    path itself selected) as a share of its norm (sqrt(sum |got - want|^2
    / sum |want|^2)), the largest over layers and KV heads: a walk over
    those pages has to give those sums, whichever pages they are. The
    WALK's judge: a wrong page in a list, a wrong tail mask or a wrong
    merge of the chunk's own keys moves it; a near-tie at rank 64, which
    swaps a block and moves that query's output by a tenth or more, does
    not (the agreement judges that). `sparse_selection_matched_share` is
    the share of pairs whose selection IS the reference's."""

    def __init__(self):
        self.sums: dict = {}
        self.shares: list = []
        self.matched = 0

    def add(self, layer: int, got, want, mine, theirs) -> None:
        """got, want [T, Hkv, G, D] (want under `mine`); mine, theirs [T,
        Hkv, blocks]."""
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        n = min(mine.shape[-1], theirs.shape[-1])
        assert not mine[..., n:].any() and not theirs[..., n:].any()
        mine, theirs = mine[..., :n], theirs[..., :n]
        both = (mine & theirs).sum(-1)
        most = np.maximum(mine.sum(-1), theirs.sum(-1))
        self.shares += list((both / np.maximum(most, 1)).ravel())
        self.matched += int((mine == theirs).all(-1).sum())
        err = (np.linalg.norm(got - want, axis=-1) ** 2).sum(-1)  # [T, Hkv]
        size = (np.linalg.norm(want, axis=-1) ** 2).sum(-1)
        for h in range(got.shape[1]):
            num, den = self.sums.get((layer, h), (0.0, 0.0))
            self.sums[(layer, h)] = (num + float(err[:, h].sum()),
                                     den + float(size[:, h].sum()))

    def readings(self) -> dict:
        return {
            "selected_pages_agreement": float(np.mean(self.shares)),
            "selected_pages_agreement_min": float(np.min(self.shares)),
            "sparse_attn_distance": max(
                math.sqrt(n / d) for n, d in self.sums.values()),
            "sparse_selection_matched_share": self.matched / len(self.shares),
        }


def reference_sparse_layers(params: dict, hf: dict, context: int,
                            seed: int = 1234) -> list[dict]:
    """One sequence of `context` seeded ids through the reference: every
    sparse layer's q [T, Hq, D], k, v [T, Hkv, D], output o [T, Hq, D]
    (on the device) and the selected blocks of the queries from `first`
    on (`selected` [T - first, Hkv, blocks])."""
    import jax.numpy as jnp

    from chipbench import traffic

    ids = np.random.default_rng(seed).integers(
        traffic.FIRST_ID, hf["vocab_size"], context)
    traces: list = []
    hidden_states(params, hf, ids, traces=traces, sparse_trace=True)
    out = []
    for kind, tr in traces:
        if kind != SPARSE:
            continue
        sel = [jnp.concatenate(blocks) for _, blocks in sorted(
            tr.get("selected", {}).items())]
        sel = np.asarray(jnp.stack(sel, axis=1)) if sel else np.zeros(
            (0, hf["num_key_value_heads"], 1), bool)
        out.append({**{n: tr[n] for n in "qkvo"}, "selected": sel,
                    "first": context - len(sel)})
    return out


def sparse_path(params: dict, hf: dict, context: int = SPARSE_CONTEXT,
                seed: int = 1234, queries: int = 16,
                fault: str | None = None) -> dict:
    """The sparse path at the timed sizes, which the harness's greedy
    streams (112 tokens) never reach. The PROGRAM's routines see the
    reference's q, k, v of every sparse layer (`reference_sparse_layers`),
    a KV head a row, through the cache the adapter builds: the chunk path
    of `models/minicpm_sala.sparse_attention` 512 tokens at a time (its
    compressed keys, its selection, each query's walk over its own list),
    each step's writes landed as a step program lands them
    (`land_sparse`), and for the last `queries` positions the decode path
    (the selection as a list, the page walk over it, the current token
    merged). Every query past `dense_len` is judged, chunk and decode
    alike: its selection against the reference's, its output against the
    reference's attention under the selection the program made
    (`_Distance`'s readings).

    `fault` is the control's: "wrong_page" PLANTS a fault in the walk.
    Before the first step that starts past `dense_len` the K and V pages of blocks 0
    and 1 change places in every sparse layer's pool, the compressed keys
    left alone: every list still names the pages the selection chose, and
    the page it names for block 0 (always selected) holds block 1's keys,
    as a page table off by one entry would have it. The agreement cannot
    see it; the distance has to."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import minicpm_sala as sala
    from dynamo_tpu.models.llama import KVPages
    from dynamo_tpu.models.registry import get_model
    from dynamo_tpu.ops import sparse_select as ss

    t_start = time.perf_counter()
    sp = hf["sparse_config"]
    s = sp["block_size"]
    on_tpu = jax.default_backend() == "tpu"
    adapter = get_model(
        hf["preset"], dtype=hf.get("dtype", "bfloat16"),
        attention_impl="pallas" if on_tpu else hf.get("attention_impl"))
    cfg = adapter.config
    hkv, per = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    layers = reference_sparse_layers(params, hf, context, seed)
    jax.block_until_ready(layers[-1]["o"])
    t_reference = time.perf_counter()
    chunk = min(512, context // 2)
    while context % chunk:
        chunk //= 2
    pages = context // s + 2
    cache = adapter.init_kv(pages, s, state_slots=1)
    tables = jnp.arange(1, pages, dtype=jnp.int32)[None]
    vt = (tables[:, None, :] * hkv
          + jnp.arange(hkv, dtype=jnp.int32)[None, :, None]).reshape(hkv, -1)
    kv, kc_pool = KVPages(k=cache.k, v=cache.v), cache.kc
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attend(kv, kc_pool, li, q, k, v, pos):
        """q [t, Hkv, G, D], k, v [t, Hkv, D] of the reference, a KV head
        a row; the program's output [t, Hkv, G, D] and selection."""
        rows = lambda a: jnp.swapaxes(a, 0, 1).astype(cfg.dtype)  # noqa: E731
        q, k, v = rows(q), rows(k)[:, :, None], rows(v)[:, :, None]
        ok = jnp.ones(pos.shape, bool)
        attn, kv, staged, fresh, _ = sala.sparse_attention(
            q, k, v, kv, kc_pool, li, vt, pos, ok, cfg)
        kc, _ = sala.compressed_keys_of(k, kv, kc_pool, li, vt, pos, ok, cfg)
        sel = ss.select_blocks(q, kc, pos, cfg.sparse, scale)
        out = attn.astype(jnp.float32).reshape(hkv, pos.shape[1], per, -1)
        return (jnp.swapaxes(out, 0, 1), kv, staged, fresh,
                jnp.swapaxes(sel, 0, 1))

    attend = jax.jit(attend)
    land = jax.jit(lambda kv, kc_pool, staged, fresh, pos: sala.land_sparse(
        kv, kc_pool, staged, fresh, vt, pos, jnp.ones(pos.shape, bool), cfg))
    with jax.default_matmul_precision("highest"):
        under = jax.jit(lambda q, k, v, sel, pos: attention_under(
            q, k, v, sel, pos, sp))

    def swapped(pool):
        """Blocks 0 and 1 of every KV head change places, every layer."""
        a, b = vt[:, 0], vt[:, 1]
        return pool.at[:, a].set(pool[:, b]).at[:, b].set(pool[:, a])
    stack = lambda xs: jax.tree.map(  # noqa: E731
        lambda *a: jnp.stack(a), *xs)
    dist = _Distance()
    ahead = context - queries
    ahead -= ahead % chunk
    steps = [(lo, lo + chunk) for lo in range(0, ahead, chunk)]
    steps += [(t, t + 1) for t in range(ahead, context)]
    for lo, hi in steps:
        if fault == "wrong_page" and lo >= sp["dense_len"]:
            kv, fault = KVPages(k=swapped(kv.k), v=swapped(kv.v)), None
        pos = jnp.broadcast_to(
            jnp.arange(lo, hi, dtype=jnp.int32), (hkv, hi - lo))
        staged, fresh = [], []
        for li, tr in enumerate(layers):
            got, kv, st, fr, sel = attend(
                kv, kc_pool, jnp.int32(li),
                tr["q"][lo:hi].reshape(hi - lo, hkv, per, -1),
                tr["k"][lo:hi], tr["v"][lo:hi], pos)
            staged.append(st)
            fresh.append(fr)
            if lo >= tr["first"]:
                with jax.default_matmul_precision("highest"):
                    want = under(
                        tr["q"][lo:hi].reshape(hi - lo, hkv, per, -1),
                        tr["k"], tr["v"], sel, pos[0])
                dist.add(li, got, want, sel,
                         tr["selected"][lo - tr["first"] : hi - tr["first"]])
        kv, kc_pool = land(
            kv, kc_pool, None if staged[0] is None else stack(staged),
            stack(fresh), pos)
    return {
        **dist.readings(), "sparse_context": context,
        "sparse_reference_s": round(t_reference - t_start, 1),
        "sparse_path_s": round(time.perf_counter() - t_start, 1),
    }


def lowered_sparse_path(params: dict, hf: dict, context: int, seed: int = 1234,
                        **how) -> dict:
    """`sparse_path` with the reference as `how` lowers it (`select`
    False: dense attention past `dense_len`; `compress_stride`: the
    compressed keys taken at another stride) in the program's place."""
    import jax
    import jax.numpy as jnp

    sp = hf["sparse_config"]
    hkv = hf["num_key_value_heads"]
    dist = _Distance()
    with jax.default_matmul_precision("highest"):
        for li, tr in enumerate(
                reference_sparse_layers(params, hf, context, seed)):
            mine: dict = {}
            o = sparse_attention(tr["q"], tr["k"], tr["v"], sp, trace=mine,
                                 **how)
            lo = tr["first"]
            if "selected" in mine:
                sel = np.asarray(jnp.stack([
                    jnp.concatenate(b) for _, b in sorted(
                        mine["selected"].items())], axis=1))
            else:  # dense: every block up to the query's own
                blk = np.arange(tr["selected"].shape[-1])[None, None]
                own = (np.arange(lo, context) // sp["block_size"])
                sel = np.broadcast_to(
                    blk <= own[:, None, None],
                    (context - lo, hkv, blk.shape[-1]))
            # a lowered reference under its OWN selection computes the
            # same sums: it can fail by the selection alone
            got = np.asarray(o)[lo:].reshape(context - lo, hkv, -1,
                                             o.shape[-1])
            dist.add(li, got, got, sel, tr["selected"])
    return {**dist.readings(), "sparse_context": context}


def compare(params: dict, hf: dict, streams: list[dict], **how) -> dict:
    """`chipbench.reference.compare` through this module's `log_probs`,
    and, where `hf` names the served preset, the state's distance
    (`state_distance`) and the sparse path's two readings (`sparse_path`)
    under `reference_tolerance.max_ssm_state_distance`,
    `min_selected_pages_agreement` and `max_sparse_attn_distance` of the
    same file. The harness's verdict reads four keys (chipbench/run.py
    `check_reference`, not a configuration's to edit): a reading past its
    limit is reported as a mean log-prob drift past every limit, the
    measured one kept beside it. A stream may bring the control's
    readings in the program's place (`ssm_state`, `sparse_path`)."""
    traces: list = []

    def forward(p, c, ids, at):
        traces.append([])
        return log_probs(p, c, ids, at, split=int(at[0]) + 1,
                         traces=traces[-1], **how)

    res = dense.compare(params, hf, streams, forward=forward)
    if not hf.get("preset"):
        return res
    tol = hf.get("reference_tolerance", {})
    t0 = time.perf_counter()
    res["ssm_state_distance"] = state_distance(hf, streams, traces)
    res["state_distance_s"] = round(time.perf_counter() - t0, 1)
    theirs = next((s["sparse_path"] for s in streams if "sparse_path" in s),
                  None)
    res.update(theirs if theirs is not None else sparse_path(
        params, hf, context=hf.get("sparse_context", SPARSE_CONTEXT)))
    failed = [
        name for name, ok in (
            ("ssm_state_distance", res["ssm_state_distance"]
             <= tol.get("max_ssm_state_distance", math.inf)),
            ("selected_pages_agreement", res["selected_pages_agreement"]
             >= tol.get("min_selected_pages_agreement", -math.inf)),
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
    """A MiniCPMSALAConfig's sizes and scalars under the published
    file's keys: every one of them is compared with the configuration
    file."""
    return {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "mixer_types": list(cfg.mixer_types),
        "layer_indices": list(cfg.layer_indices),
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "lightning_nh": cfg.lightning_heads,
        "lightning_nkv": cfg.lightning_heads,
        "lightning_head_dim": cfg.lightning_head_dim,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "scale_emb": cfg.scale_emb,
        "scale_depth": cfg.scale_depth,
        "dim_model_base": cfg.dim_model_base,
        "mup_denominator": cfg.mup_denominator,
        "sparse_config": dict(zip(SPARSE_KEYS, cfg.sparse)),
    }


# -- the control --------------------------------------------------------------

#: what the control puts in the program's place; each has to come out as
#: not correct: (a) the lightning state carried in bfloat16 instead of the
#: float32 the configuration states (it fails on the state itself), (b)
#: the weights one precision below bf16, (c) the selection OFF: dense
#: attention at every position (it fails on the sparse path's attention
#: output at 12,288 tokens: the greedy streams never reach `dense_len`),
#: (d) the compressed keys taken at stride 32 instead of 16, windows that
#: no longer overlap (it fails on the selected pages)
#: (e) a fault planted in the PROGRAM's walk (`sparse_path`'s `fault`):
#: the selection untouched, one page of every list holding another
#: block's keys (it fails on the attention's distance, and on it alone)
CONTROLS = {
    "bf16_state": {"state_dtype": "bfloat16"},
    "int8_weights": {"lower": to_int8},
    "selection_off": {"sparse": {"select": False}},
    "compressed_stride_32": {"sparse": {"compress_stride": 32}},
    "wrong_page": {"walk": {"fault": "wrong_page"}},
}
#: what a control that does not touch the sparse path reads there (the
#: path compared would be the program's own: not its to fail by)
_SPARSE_UNTOUCHED = {"selected_pages_agreement": 1.0,
                     "sparse_attn_distance": 0.0}


def control_streams(params, hf, seed, how, prompt_len=48, out_len=64,
                    streams=2):
    """Greedy streams decoded by the reference as `how` changes it (the
    whole padded sequence every step: a position sees nothing after it).
    A control that lowers the STATE brings the state its lightning layers
    are left in (`ssm_state`); one that lowers the SPARSE path decodes as
    the reference does (112 tokens never reach `dense_len`) and brings
    `lowered_sparse_path`'s readings (`sparse_path`)."""
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
        if "state_dtype" in how:
            traces: list = []
            hidden_states(params, hf, ids, split=prompt_len, traces=traces,
                          **how)
            out[-1]["ssm_state"] = [np.asarray(tr["end"])
                                    for kind, tr in traces
                                    if kind == LIGHTNING]
    context = hf.get("sparse_context", SPARSE_CONTEXT)
    if walk is not None:
        out[0]["sparse_path"] = sparse_path(
            params, hf, context, seed, **walk)
    elif sparse is not None:
        out[0]["sparse_path"] = lowered_sparse_path(
            params, hf, context, seed, **sparse)
    else:
        out[0]["sparse_path"] = dict(_SPARSE_UNTOUCHED)
    return out


def main(argv=None) -> int:
    """python -m chipbench.references.minicpm_sala [--seeds a,b]
    [--config minicpm-sala-9b-1chip] [--controls a,b]: each of CONTROLS
    decodes the benchmark's greedy streams and goes through `compare`
    against the reference as it stands, under the configuration's
    `reference_tolerance`; each has to come out as not correct."""
    import argparse
    import json
    import sys

    import jax
    import jax.numpy as jnp

    from chipbench import control
    from chipbench.run import check_reference

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--config", default="minicpm-sala-9b-1chip")
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
