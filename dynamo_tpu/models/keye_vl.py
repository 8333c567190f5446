"""The language model of Keye-VL-2.0-30B-A3B (Kwai-Keye, `model_type`
KeyeVL2): the Qwen3-MoE block with a learned INDEXER in front of its
attention (`sa_config`, DeepSeek-Sparse-Attention's lightning indexer).

    h = h + W_o Attn(x),  x = RMSNorm(h)
    h = h + MoE(RMSNorm(h));   logits = W_head RMSNorm(h), untied

- projections: q (`num_heads` x `head_dim`), k, v (`num_kv_heads`), no
  bias; RMSNorm with a learned weight over the head dimension on q and k;
  rotary (NeoX halves, `rope_theta`), the frequency pairs split
  `mrope_section` over the (temporal, height, width) position components
  (`StepGroup.rope_positions` [3, B, T]; a text token's three are its
  index, which is ordinary rope);
- indexer: `qI = W_qI x` (`index_heads` x `index_head_dim`), `kI =
  LayerNorm(W_kI x)` (ONE key a token, learned weight and bias), `w = W_w
  x / sqrt(index_heads x index_head_dim)`, rotary on all of qI and kI at
  the temporal position; `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`
  in float32; the query attends the `min(index_topk, t + 1)` tokens `s <=
  t` of highest `I[t, .]`, ties to the earlier token, ONE set for all its
  heads (ops/token_select.py);
- attention: softmax over that set at 1 / sqrt(head_dim);
- experts (every layer): softmax over all `n_routed_experts` in float32,
  the `num_experts_per_tok` highest renormalised, SwiGLU experts of
  `moe_intermediate_size`, EVERY assignment computed (`models/mla.py`
  `_routed_experts` over ops/grouped_matmul.py: no capacity). A chip may
  hold a share `experts_held = (first, count)`: it routes over all of them
  and adds its own experts' terms.

The cache (`KeyeCache`) is models/llama.py's K and V pools [L, P, S, Hkv,
D] and beside them the INDEX KEYS, a row a token at its page's slot, TWO
LAYERS' keys side by side in a row ([L / 2, P, S, 2 Di]: `index_pool`). A
step scores a row's cached index keys out of that pool IN PLACE, streamed
page by page through one kernel (`step_scores` over ops/index_scores.py), and
its own tokens' from what is in hand (`attn/index`; without the kernels
one gathered copy of the row's keys, `index_keys_of`, under
`ts.index_scores`), stages its index keys as it stages K and V, and lands
every layer's once (`attn/kv_update`); the selection (`attn/select`) is
exact and sort-free. A decode row walks its pages
under a bit a cached token (`attn/paged`, ops/paged_attention.py
`token_bits`) and merges its own token; a prompt chunk attends by
tile of queries under a mask bit a (query, key) (`attn/flash`,
ops/sparse_chunk.py `token_chunk_attention`); K and V are staged and
landed once a step as models/llama.py does. Without the kernels
(`attention_impl` "xla") everything is scattered first and attention is
dense scores under the mask.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import mla as mla_mod
from dynamo_tpu.models.llama import (
    KVPages,
    LlamaConfig,
    StepGroup,
    _mm,
    apply_rope,
    join_rows,
    land_staged_kv,
    paged_gather,
    paged_scatter_kv,
    rms_norm,
    split_rows,
)
from dynamo_tpu.ops import token_select as ts

#: float32 index scores one call of the selection may hold: past it a
#: prompt step scores and selects a row at a time
SELECT_BYTES = 96 << 20
#: `ModelAdapter.step_twins`: one program a shape (a step program holds the
#: chunk kernel, the grouped matmuls and the selection's loops; no step
#: here reads `StepGroup.first_chunk`)
STEP_TWINS = False
#: the expert matrices, held out of the layer scan's slices
EXPERTS = ("we_gate", "we_up", "we_down")


@dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    # -- experts (the names models/mla.py's gate and grouped FFN read) -------
    moe_intermediate_size: int = 32
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    #: (first, count): the experts this chip holds of every layer; None: all
    experts_held: Optional[tuple] = None
    norm_topk_prob: bool = True
    topk_method: str = "greedy"
    routed_scaling_factor: float = 1.0
    # -- the indexer (`sa_config`) -------------------------------------------
    index_heads: int = 4
    index_head_dim: int = 8
    index_topk: int = 8
    rope_theta: float = 1e7
    #: frequency pairs of head_dim / 2 a position component
    mrope_section: tuple = (2, 3, 3)
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else (
            self.n_routed_experts)

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    @property
    def attn_cfg(self) -> LlamaConfig:
        """The attention as models/llama.py sees it (rope, cache rows)."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, mrope_section=self.mrope_section,
            qk_norm=True, rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            attention_impl=self.attention_impl,
        )

    @property
    def index_rope_cfg(self) -> LlamaConfig:
        return LlamaConfig(head_dim=self.index_head_dim,
                           rope_theta=self.rope_theta)

    @staticmethod
    def keye_vl2_30b_a3b(num_layers: int = 48,
                         experts_held: Optional[tuple] = None
                         ) -> "KeyeVLConfig":
        """As config.json publishes the language model (hidden 2048, 32 / 4
        heads of 128, 128 experts of 768 top-8 renormalised in every layer,
        an indexer of 16 heads of 64 over one 64-wide key, topk 2048,
        151,936 ids untied, theta 1e7, mrope [16, 24, 24])."""
        return KeyeVLConfig(
            vocab_size=151936, hidden_size=2048, num_layers=num_layers,
            num_heads=32, num_kv_heads=4, head_dim=128,
            moe_intermediate_size=768, n_routed_experts=128,
            num_experts_per_tok=8, experts_held=experts_held,
            index_heads=16, index_head_dim=64, index_topk=2048,
            mrope_section=(16, 24, 24),
        )

    @staticmethod
    def keye_vl2_1chip() -> "KeyeVLConfig":
        """One chip of the deployment chipbench/configs/
        keye-vl2-30b-a3b-1chip.json states: 8 of 48 layers (a pipeline
        stage), experts 0-15 of 128 (an 8-way expert-parallel share)."""
        return KeyeVLConfig.keye_vl2_30b_a3b(8, (0, 16))

    @staticmethod
    def tiny(vocab_size: int = 256) -> "KeyeVLConfig":
        """Two layers at toy widths: 2 query heads a KV head, 8 experts top
        2, 4 index heads of 8, the 8 highest tokens a query."""
        return KeyeVLConfig(vocab_size=vocab_size, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------


class KeyeCache(NamedTuple):
    """`k`, `v` models/llama.py's pools [L, P, S, Hkv, D]; `ki` the index
    keys beside them (`index_pool`), a page's third resident (written with
    the page's K and V rows, freed and shared with the page). `walked`
    counts, on the device, what the decode steps ATTEND, a layer each,
    summed since the cache was made and wrapping at 2**32: int32 [6], the
    tokens the rows' selections name and the tokens the rows hold
    (`ModelAdapter.walk_pages`, in tokens here: `tokens_attended`), then
    the (query, key) pairs a prompt chunk's kernel multiplies and the
    pairs its queries' selections name (`chunk_pairs`), a fifth left at 0
    (models/dots3.py's held experts touched) and the passes over a
    share's assignments beyond an expert layer's first
    (`mla._routed_experts`)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # never set: no quantised pages
    v_scale: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None
    walked: Optional[jax.Array] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return False

    @property
    def pages(self) -> KVPages:
        return KVPages(k=self.k, v=self.v)


def walk_count(cache: KeyeCache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def page_bytes(cfg: KeyeVLConfig, page_size: int) -> int:
    """One page of every layer: K, V and the index keys."""
    item = jnp.dtype(cfg.dtype).itemsize
    return cfg.num_layers * page_size * item * (
        2 * cfg.num_kv_heads * cfg.attn_cfg.kv_head_dim + cfg.index_head_dim)


def index_pool(cfg: KeyeVLConfig, num_pages: int, page_size: int):
    """The index keys' pool: [ceil(L / 2), P, S, 2 Di], a token's keys of
    layers 2i and 2i + 1 side by side in one row. At the published 64-wide
    key that is a 128-lane row: a pool [L, P, S, 64] the TPU either pads to
    128 lanes (twice the bytes) or lays out with the pages minor-most, and
    a step then copies all 590 MB of it into the order its gathers want
    (the compile for the described v5e, tests/test_tpu_compile.py)."""
    return jnp.zeros((-(-cfg.num_layers // 2), num_pages, page_size,
                      2 * cfg.index_head_dim), cfg.dtype)


def index_keys_of(ki_pool, layer, tables, ki_new, positions):
    """A group's index keys as its queries score them: [B, MP * S, Di] by
    position, the cached ones of `layer` gathered through the page tables
    and the step's own `ki_new` [B, T, Di] put in at `positions` [B, T]
    (the pool does not hold them yet)."""
    b, t, di = ki_new.shape
    n_l, n_p, s, _ = ki_pool.shape
    # pages out of the pool flattened over layer pairs: a slice of one
    # pair's pages first would be copied out whole (147 MB at 9,000 pages)
    both = ki_pool.reshape(n_l * n_p, s, 2 * di)[
        (layer // 2) * n_p + tables].reshape(b, -1, 2 * di)
    ki = jnp.where(layer % 2 == 1, both[..., di:], both[..., :di])
    n = ki.shape[1]
    if t == 1:
        at = jnp.arange(n, dtype=jnp.int32)[None, :, None]
        return jnp.where(at == positions[:, :1, None], ki_new, ki)
    # a chunk's positions are contiguous from its first; the rows past its
    # valid prefix land after every valid query and are never scored
    put = jax.vmap(lambda a, x, at: lax.dynamic_update_slice_in_dim(
        a, x, at, axis=0))
    return put(jnp.pad(ki, ((0, 0), (0, t), (0, 0))), ki_new.astype(ki.dtype),
               positions[:, 0])[:, :n]


def land_index_keys(ki_pool, ki_new, tables, positions, valid):
    """Land one step of every layer's index keys, `ki_new` [L, B, T, Di]:
    one row a token and layer pair, padding redirected to the null page.
    A scatter of ROWS of the pool flattened to [L / 2 * P * S, 2 Di]: one
    over [:, page, slot] makes the compiler re-lay the whole pool out and
    back (two 590 MB copies a step at 9,000 pages)."""
    n_l, b, t, di = ki_new.shape
    pairs, n_p, s, _ = ki_pool.shape
    if n_l % 2:
        ki_new = jnp.pad(ki_new, ((0, 1), (0, 0), (0, 0), (0, 0)))
    rows = jnp.concatenate([ki_new[0::2], ki_new[1::2]], axis=-1)
    page = jnp.take_along_axis(tables, positions // s, axis=1)
    at = (jnp.where(valid, page, 0) * s
          + jnp.where(valid, positions % s, 0)).reshape(-1)  # [B * T]
    at = (jnp.arange(pairs, dtype=at.dtype)[:, None] * (n_p * s)
          + at[None]).reshape(-1)
    return ki_pool.reshape(pairs * n_p * s, 2 * di).at[at].set(
        rows.reshape(-1, 2 * di).astype(ki_pool.dtype), mode="drop"
    ).reshape(ki_pool.shape)


def init_cache(cfg: KeyeVLConfig, num_pages: int, page_size: int
               ) -> KeyeCache:
    rows = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
            cfg.attn_cfg.kv_head_dim)
    return KeyeCache(
        k=jnp.zeros(rows, cfg.dtype), v=jnp.zeros(rows, cfg.dtype),
        ki=index_pool(cfg, num_pages, page_size),
        walked=jnp.zeros((6,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: KeyeVLConfig) -> dict:
    h, d = cfg.hidden_size, cfg.head_dim
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    j, di = cfg.index_heads, cfg.index_head_dim
    e, i = cfg.experts_here, cfg.moe_intermediate_size
    return {
        "attn_norm": (h,), "wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd),
        "wo": (qd, h), "q_norm": (d,), "k_norm": (d,),
        "wi_q": (h, j * di), "wi_k": (h, di), "wi_w": (h, j),
        "ik_norm": (di,), "ik_bias": (di,),
        "mlp_norm": (h,), "w_router": (h, cfg.n_routed_experts),
        "we_gate": (e, h, i), "we_up": (e, h, i), "we_down": (e, i, h),
    }


#: the router's draw, times 1 / sqrt(hidden): logits of standard deviation 2
ROUTER_SPREAD = 2.0


def init_params(key: jax.Array, cfg: KeyeVLConfig) -> dict:
    """Seeded weights at a trained block's scale: every matrix normal at 1 /
    sqrt(fan in) (its input is normed or unit-scale, so its output is),
    norm weights one, the index key's bias zero; the ROUTER at
    `ROUTER_SPREAD` / sqrt(hidden) in float32, so that its logits spread
    (standard deviation 2: the eighth and the ninth expert lie 0.1 apart on
    average, not 0.05) and a flat router does not make the comparison
    blind. The index scores need no help: qI . kI over 64 unit dimensions
    has standard deviation 8, sixteen weighted heads of it 0.6. An expert
    is drawn by its PUBLISHED number (`experts_held[0]` + its place here),
    so a share holds what the whole model holds there."""
    shapes = _layer_shapes(cfg)
    first = cfg.experts_held[0] if cfg.experts_held else 0

    def normal(k, shape, fan_in, dtype=cfg.dtype, spread=1.0):
        return (jax.random.normal(k, shape, jnp.float32)
                * (spread / math.sqrt(fan_in))).astype(dtype)

    def leaf(name, shape, k):
        if name.endswith("norm"):
            return jnp.ones(shape, cfg.dtype)
        if name == "ik_bias":
            return jnp.zeros(shape, cfg.dtype)
        if name == "w_router":
            return normal(k, shape, shape[0], jnp.float32, ROUTER_SPREAD)
        if name in EXPERTS:
            return jnp.stack([
                normal(jax.random.fold_in(k, first + e), shape[1:], shape[1])
                for e in range(shape[0])])
        return normal(k, shape, shape[0])

    def layer(li: int) -> dict:
        lk = jax.random.fold_in(key, 1 + li)
        return {name: leaf(name, shape, jax.random.fold_in(lk, n))
                for n, (name, shape) in enumerate(shapes.items())}

    layers = [layer(li) for li in range(cfg.num_layers)]
    h, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": normal(jax.random.fold_in(key, 0), (v, h), 1.0),
        "layers": {name: jnp.stack([lp[name] for lp in layers])
                   for name in shapes},
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": normal(jax.random.fold_in(key, 1 + cfg.num_layers),
                          (h, v), h),
    }


def keye_vl_logical_axes(cfg: KeyeVLConfig) -> dict:
    """Logical axis names (parallel/logical.py): everything replicates but
    the head's vocabulary axis; the adapter refuses a mesh."""
    from dynamo_tpu.parallel.logical import L

    return {
        "embed": L(), "final_norm": L(), "lm_head": L(None, "vocab"),
        "layers": {name: L() for name in _layer_shapes(cfg)},
    }


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def tokens_attended(context, valid, topk: int):
    """What one layer of a decode step attends, counted from what its
    selection was GIVEN: int32 [2], the tokens the rows' selections name
    (`min(topk, context)` each) and the tokens the rows hold, over the
    valid rows."""
    return jnp.stack([
        jnp.sum(jnp.where(valid, n, 0))
        for n in (jnp.minimum(context, topk), context)]).astype(jnp.int32)


def chunk_pairs(positions, valid, topk: int):
    """What one layer of a prompt chunk multiplies, counted from its
    shape: int32 [2], the (query, key) pairs under the causal mask (every
    cached key and the chunk's own up to the query: what the chunk kernel
    computes at the least, whole tiles apart) and the pairs the
    selections name (`min(topk, t + 1)` a query)."""
    context = jnp.where(valid, positions + 1, 0)
    return jnp.stack([
        jnp.sum(context), jnp.sum(jnp.minimum(context, topk))
    ]).astype(jnp.int32)


def put_own(scores, own, at):
    """One row's scores [T, N] by position with those of the chunk's own
    keys `own` [T, T] put in from column `at` on (contiguous positions;
    the pool does not hold them yet). Columns past `N` belong to padding
    rows and fall away."""
    n, t = scores.shape[1], own.shape[1]
    start = jnp.clip(at, 0, n - t)  # what the update's clamp would do
    shift = at - start
    held = lax.dynamic_slice_in_dim(scores, start, t, axis=1)
    col = jnp.arange(t, dtype=jnp.int32)[None]
    return lax.dynamic_update_slice_in_dim(
        scores, jnp.where(col >= shift, jnp.roll(own, shift, axis=1), held),
        start, axis=1)


def step_scores(qi, w, ki_new, tables, positions, valid, ki_pool, layer,
                paired: bool = True):
    """The index scores of a group's queries by position, float32 [B, T,
    MP * S], on the TPU: the cached keys scored out of the pool in place
    (ops/index_scores.py), the step's own from `ki_new` and put in. What
    `ts.index_scores(qi, w, index_keys_of(..))` gives up to every query's
    own position; past it the two differ and nothing reads. `paired`
    False: a pool of one layer's key a row (models/dots3.py)."""
    from dynamo_tpu.ops.index_scores import paged_index_scores

    hist = jnp.where(valid[:, 0], positions[:, 0], 0).astype(jnp.int32)
    if qi.shape[1] == 1:  # a decode row: its own token's score, a scalar
        sc = paged_index_scores(qi, w, ki_pool, layer, tables, hist,
                                paired=paired)
        own = ts.index_scores(qi, w, ki_new.astype(ki_pool.dtype))
        at = jnp.arange(sc.shape[-1], dtype=jnp.int32)[None, None]
        return jnp.where(at == positions[:, :, None], own, sc)
    sc, own = paged_index_scores(
        qi, w, ki_pool, layer, tables, hist, ki_new, paired=paired)
    return jax.vmap(put_own)(sc, own, hist)


def chosen_keys(score, rows, n: int, positions, valid, topk: int):
    """bool [B, T, N]: the keys each query attends. `score(*rows)` gives
    the float32 index scores [B, T, N] by position (the step's own keys
    among them) of the queries whose arrays `rows` holds, a sequence
    each; positions / valid [B, T]. Scopes `index` (the scores) and
    `select`; a step none of whose queries has more than `topk` tokens of
    context computes neither."""
    b, t = positions.shape
    context = jnp.where(valid, positions + 1, 0).astype(jnp.int32)

    def one(args):
        *rows_, ctx = args
        with jax.named_scope("index"):
            sc = score(*rows_)
        with jax.named_scope("select"):
            r = sc.shape[0] * t
            return ts.select_tokens(
                sc.reshape(r, n), ctx.reshape(r), topk).reshape(-1, t, n)

    def select():
        if b > 1 and b * t * n * 4 > SELECT_BYTES:  # a row at a time
            return lax.map(one, tuple(
                x[:, None] for x in (*rows, context)))[:, 0]
        return one((*rows, context))

    def everything():
        return jnp.arange(n, dtype=jnp.int32)[None, None] < context[..., None]

    return lax.cond(jnp.any(context > topk), select, everything)


def walk_under_bits(q, k_own, v_own, kv, layer, tables, at, valid, chosen,
                    work, cfg):
    """A decode row's attention as the page walk of ops/paged_attention.py
    under a bit a cached token (every page of the row read, the tokens
    not chosen masked in the kernel: at 2,048 tokens chosen out of 8k-18k
    nearly every page holds some, and a gather of the chosen K and V ROWS,
    1 KB each, was 1.6-3.3 times slower on the chip: PERF.md 6, PR 43),
    and the exact merge of the step's
    own token, which the pools do not hold yet, where the row chose it.
    q [B, 1, Hq, D] unscaled, k_own / v_own [B, 1, Hkv, Dpad], at [B] the
    row's position, chosen [B, N]. float32 [B, Hq, D]."""
    from dynamo_tpu.models.llama import _PALLAS_DECODE_VMEM_BUDGET
    from dynamo_tpu.ops.paged_attention import paged_decode_attention

    d = q.shape[-1]
    dpad = k_own.shape[-1] - d
    qd = jnp.pad(q[:, 0], ((0, 0), (0, 0), (0, dpad))) if dpad else q[:, 0]
    hist = jnp.where(valid, at, 0).astype(jnp.int32)
    acc, m, l = paged_decode_attention(
        qd, kv.k, kv.v, layer, tables, hist, scale_dim=d, work_list=work,
        vmem_budget=_PALLAS_DECODE_VMEM_BUDGET, token_bits=chosen)
    kv_of = jnp.arange(cfg.num_heads) // (cfg.num_heads // cfg.num_kv_heads)
    own = jnp.take_along_axis(chosen, at[:, None], axis=1)  # [B, 1]
    s_self = jnp.sum(qd.astype(jnp.float32)
                     * k_own[:, 0, kv_of].astype(jnp.float32), axis=-1)
    s_self = jnp.where(own, s_self / math.sqrt(d), ts._MASKED)
    m_star = jnp.maximum(m, s_self)
    alpha, beta = jnp.exp(m - m_star), jnp.exp(s_self - m_star)
    out = (alpha[..., None] * acc + beta[..., None]
           * v_own[:, 0, kv_of].astype(jnp.float32)) / (
        alpha * l + beta)[..., None]
    return out[..., :d]


def token_attention(
    q, k, v,  # [B, T, Hq | Hkv, D] normed, post-rope
    qi, ki_new, w,  # [B, T, J, Di], [B, T, Di] post-rope, [B, T, J] f32
    kv: KVPages, ki_pool, layer, g: StepGroup, cfg: KeyeVLConfig,
    work=None,
):
    """Attention of one group's rows over the tokens their indexer
    chooses, in the write discipline of models/llama.py `attention_block`
    for K and V (staged under the kernels, scattered first without) and
    the index keys ALWAYS staged (the caller lands `ki_new` after the layer
    scan). Returns (attn [B, T, Hq * D], kv, the staged (k, v) or None,
    what the step attended: int32 [4], `tokens_attended` of a decode step
    or `chunk_pairs` of a chunk beside two zeros, and the selection the
    attention ran under: bool [B, T, N] by position)."""
    b, t, hq, d = q.shape
    tables, positions, valid = g.page_tables, g.positions, g.valid
    topk, s = cfg.index_topk, kv.page_size
    scale = 1.0 / math.sqrt(d)
    dpad = cfg.attn_cfg.kv_head_dim - d
    none = jnp.zeros((2,), jnp.int32)
    n = tables.shape[1] * s
    q_s = (q.astype(jnp.float32) * scale).astype(q.dtype)
    context = jnp.where(valid, positions + 1, 0)[:, 0]
    counted = jnp.concatenate([
        tokens_attended(context, valid[:, 0], topk), none]) if t == 1 else (
        jnp.concatenate([none, chunk_pairs(positions, valid, topk)]))
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            kv = paged_scatter_kv(kv, layer, k, v, tables, positions, valid)
        with jax.named_scope("index"):
            ki = index_keys_of(ki_pool, layer, tables, ki_new, positions)
        chosen = chosen_keys(
            ts.index_scores, (qi, w, ki), n, positions, valid, topk)
        with jax.named_scope("paged"):
            attn = ts.masked_attention(
                q_s, paged_gather(kv.k, layer, tables)[..., :d],
                paged_gather(kv.v, layer, tables)[..., :d], chosen)
        return (attn.astype(q.dtype).reshape(b, t, hq * d), kv, None,
                counted, chosen)
    pad = ((0, 0), (0, 0), (0, 0), (0, dpad))
    k_pad, v_pad = (jnp.pad(k, pad), jnp.pad(v, pad)) if dpad else (k, v)
    if t == 1:
        with jax.named_scope("index"):
            sc = step_scores(qi, w, ki_new, tables, positions, valid,
                             ki_pool, layer)[:, 0]
        with jax.named_scope("select"):
            chosen = ts.select_tokens(sc, context, topk)  # [B, N]
        with jax.named_scope("paged"):
            attn = walk_under_bits(
                q, k_pad, v_pad, kv, layer, tables, positions[:, 0],
                valid[:, 0], chosen, work, cfg)
        return (attn.astype(q.dtype).reshape(b, 1, hq * d), kv,
                (k_pad, v_pad), counted, chosen[:, None])
    from dynamo_tpu.ops.sparse_chunk import token_chunk_attention

    chosen = chosen_keys(
        lambda *rows: step_scores(*rows, ki_pool, layer),
        (qi, w, ki_new, tables, positions, valid), n, positions, valid, topk)
    with jax.named_scope("flash"):
        out = token_chunk_attention(
            jnp.pad(q_s, pad) if dpad else q_s, k_pad, v_pad, kv.k, kv.v,
            layer, tables, chosen,
            jnp.where(valid[:, 0], positions[:, 0], 0), valid)
    return (out[..., :d].reshape(b, t, hq * d), kv, (k_pad, v_pad), counted,
            chosen)


def project(x, lp, cfg: KeyeVLConfig):
    """A layer's projections of the normed input x [.., H]: (q [.., Hq,
    D], k, v [.., Hkv, D] normed; qI [.., J, Di], kI [.., Di] normed, w
    [.., J] float32 scaled), all BEFORE the rotary embedding. Scopes
    `qkv` and `index`."""
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nj, di = cfg.index_heads, cfg.index_head_dim
    lead = x.shape[:-1]
    with jax.named_scope("qkv"):
        q = rms_norm(_mm(x, lp, "wq", dtype).reshape(*lead, hq, d),
                     lp["q_norm"], eps)
        k = rms_norm(_mm(x, lp, "wk", dtype).reshape(*lead, hkv, d),
                     lp["k_norm"], eps)
        v = _mm(x, lp, "wv", dtype).reshape(*lead, hkv, d)
    with jax.named_scope("index"):
        qi = _mm(x, lp, "wi_q", dtype).reshape(*lead, nj, di)
        ki = _layer_norm(_mm(x, lp, "wi_k", dtype), lp["ik_norm"],
                         lp["ik_bias"], eps)
        w = _mm(x, lp, "wi_w", dtype).astype(jnp.float32) / math.sqrt(
            nj * di)
    return q, k, v, qi, ki, w


def rotate(q, k, qi, ki, g: StepGroup, cfg: KeyeVLConfig):
    """The rotary embedding of one group's rows: q and k by the (up to
    three) position components, qI and kI by the temporal one."""
    rp = g.positions if g.rope_positions is None else g.rope_positions
    temporal = rp[0] if rp.ndim == 3 else rp
    with jax.named_scope("qkv"):
        q = apply_rope(q, rp, cfg.attn_cfg)
        k = apply_rope(k, rp, cfg.attn_cfg)
    with jax.named_scope("index"):
        qi = apply_rope(qi, temporal, cfg.index_rope_cfg)
        ki = apply_rope(ki[:, :, None], temporal, cfg.index_rope_cfg)[:, :, 0]
    return q, k, qi, ki


def attention(x, lp, cfg: KeyeVLConfig, kv, ki_pool, layer, groups, works):
    """Returns (out shaped like x, kv, per group the staged (k, v) and
    index keys, what the groups attended: int32 [4], `tokens_attended` of
    the decode rows then `chunk_pairs` of the prompt chunks).
    Scopes, under the caller's `attn`: `qkv`, `index`, `select`, `paged`,
    `flash`, `kv_update`, `out`."""
    attns, staged, counted = [], [], jnp.zeros((4,), jnp.int32)
    for g, work, qg, kg, vg, qig, kig, wg in zip(
        groups, works,
        *(split_rows(a, groups) for a in project(x, lp, cfg))
    ):
        qg, kg, qig, kig = rotate(qg, kg, qig, kig, g, cfg)
        attn, kv, st, n, _ = token_attention(
            qg, kg, vg, qig, kig, wg, kv, ki_pool, layer, g, cfg, work)
        attns.append(attn)
        staged.append((st, kig))
        counted = counted + n
    with jax.named_scope("out"):
        return (_mm(join_rows(attns), lp, "wo", cfg.dtype), kv,
                tuple(staged), counted)


# ---------------------------------------------------------------------------
# Experts
# ---------------------------------------------------------------------------


def moe_ffn(x, lp, cfg: KeyeVLConfig, stack=None):
    """(out, int32: the passes over a share's assignments beyond the
    first, `mla._routed_experts`). Names its scopes from the top
    (`mlp/moe/route`, `mlp/moe/experts`, as models/mla.py's expert layer;
    no shared expert): the caller stands under none, for the sake of the
    share's loop. The router's product at the highest precision: a flipped
    eighth expert adds or removes a whole expert where a chip holds a
    share."""
    xf = x.reshape(-1, x.shape[-1])
    with jax.named_scope(mla_mod.MOE_SCOPE + "route"):
        topw, topi = mla_mod._gate(
            xf, lp, cfg, precision=lax.Precision.HIGHEST)
    routed, extra = mla_mod._routed_experts(
        xf, topw, topi, lp, cfg, None, stack, held=cfg.experts_held,
        scope=mla_mod.MOE_SCOPE)
    with jax.named_scope("mlp"):
        return routed.astype(cfg.dtype).reshape(x.shape), extra


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_groups(params: dict, cfg: KeyeVLConfig, groups,
                   cache: KeyeCache, mesh=None):
    """models/llama.py's `forward_groups` for this family: ONE layer scan,
    the dense work and the experts of a layer on every group's rows
    together, attention per group. Returns ([hidden [B_g, T_g, H] post
    final norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError("Keye-VL on a mesh is not implemented")
    dtype, eps = cfg.dtype, cfg.rms_norm_eps
    with jax.named_scope("embed"):
        h = join_rows([params["embed"][g.tokens].astype(dtype)
                       for g in groups])
    # the expert matrices stay out of the scan's slices: the grouped
    # matmul reads a layer of the whole stack in place
    experts = {n: params["layers"][n] for n in EXPERTS} if cfg.kernels else {}
    scanned = {n: a for n, a in params["layers"].items() if n not in experts}

    ki_pool = cache.ki
    works = [None] * len(groups)
    if cfg.kernels:  # layer-invariant: the rows a decode walk visits
        from dynamo_tpu.ops.paged_attention import decode_work_list

        with jax.named_scope("attn"):
            works = [
                decode_work_list(g.page_tables, jnp.where(
                    g.valid[:, 0], g.positions[:, 0], 0))
                if g.tokens.shape[1] == 1 else None for g in groups]

    def layer(carry, xs):
        h, kv, walked = carry
        lp, li = xs
        with jax.named_scope("attn"):
            a, kv, staged, n = attention(
                rms_norm(h, lp["attn_norm"], eps), lp, cfg, kv, ki_pool, li,
                groups, works)
            h = h + a
        with jax.named_scope("mlp"):
            x = rms_norm(h, lp["mlp_norm"], eps)
        y, extra = moe_ffn(x, lp, cfg, (experts, li) if experts else None)
        with jax.named_scope("mlp"):
            h = h + y
        # (no fifth count here: the held experts touched, models/dots3.py)
        return (h, kv, walked + jnp.concatenate(
            [n, jnp.stack([jnp.int32(0), extra])])), staged

    (h, kv, walked), staged = lax.scan(
        layer, (h, cache.pages, cache.walked),
        (scanned, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("attn"), jax.named_scope("kv_update"):
        for g, (st, ki_new) in zip(groups, staged):
            kv = land_staged_kv(kv, st, g.page_tables, g.positions, g.valid)
            ki_pool = land_index_keys(
                ki_pool, ki_new, g.page_tables, g.positions, g.valid)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), KeyeCache(
        k=kv.k, v=kv.v, ki=ki_pool, walked=walked)


def forward_hidden(params, cfg: KeyeVLConfig, tokens, positions, valid,
                   cache, page_tables, first_chunk: bool = False, mesh=None,
                   rope_positions=None):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   rope_positions)],
        cache, mesh=mesh)
    return h, cache


def compute_logits(params: dict, cfg: KeyeVLConfig, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        return (hidden @ params["lm_head"]).astype(jnp.float32)
