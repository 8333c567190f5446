"""The language model of MiMo-V2.5 (XiaomiMiMo, `model_type` mimo_v2: the
MiMo-V2-Flash 309B-A15B language model): five window layers to one full
layer by `hybrid_layer_pattern`, the two kinds of layer with KV geometries
of their own, keys wider than values, a learned sink in the window layers'
softmax, rope on a third of the head.

    h = h + Attn_l(RMSNorm(h));  h = h + FFN_l(RMSNorm(h))   pre-norm, a
                                 weight and no bias, eps 1e-5, float32
    logits = W_head RMSNorm_f(h)                             untied

- Attn, both kinds: `q = W_q x` (`num_heads` of `head_dim` = 192), `k = W_k
  x` (Hkv heads of 192), `v = W_v x` (Hkv heads of `v_head_dim` = 128), no
  bias, no q/k norm. Rope on the FIRST `rotary_dim` = 64 of the 192 dims of
  q and k, pairs split by halves of the 64 (`rotate_half`), the other 128
  unrotated. `v <- attention_value_scale v` (0.707) as the row is
  projected: the cache holds the scaled value, which is multiplying `o`.
  `s[t, u] = q_t . k_u / sqrt(192)`, `num_heads / Hkv` query heads a KV head.
  - a FULL layer (F): Hkv = `num_kv_heads` (4), theta `rope_theta` (1e7),
    every `u <= t`, a plain softmax;
  - a WINDOW layer (S): Hkv = `swa_num_kv_heads` (8), theta
    `swa_rope_theta` (1e4), `u` in `[t - (sliding_window - 1), t]`, and a
    SINK: `p[t, u] = exp(s[t, u]) / (exp(b_h) + sum_u' exp(s[t, u']))`, `b_h`
    one learned float32 scalar a QUERY head (`sink`): a key of value 0.
  `o_t = sum_u p[t, u] v_u`; `y = W_o concat_h(o)`, W_o [heads x 128, H].
- FFN: a layer is DENSE (`moe_layer_freq` 0: SwiGLU `intermediate_size`
  wide) or holds experts: `g = sigmoid(W_r x)` in float32 over all the
  published experts, `choice = g + e_bias` ("noaux_tc", one group), the
  `num_experts_per_tok` highest of `choice` (ties to the lower index), `w =
  g_top / sum(g_top)`; `FFN(x) = sum_e w_e E_e(x)`, NO shared expert
  (models/mla.py `_gate`, `_routed_experts`). A chip may hold a share
  `experts_held = (first, count)`: the router keeps its width and its top k
  over all, the chip adds the terms of its own experts.

THE CACHED TOKEN is 192 + 128 columns a KV head, and a 192-wide minor
dimension is padded to 256 lanes wherever it stands alone (a pool [.., Hkv,
192] takes a third more bytes). So TWO KV heads' rows stand side by side, 2
x 192 = 384 = 3 lane tiles of key and 2 x 128 = 2 of value (`pack`), and
every lane tile (PART) is a pool entry of its own: the K pool is [3 x
layers, P, S, Hkv / 2, 128], part t of layer l at `t x layers + l`, the V
pool [2 x layers, ..]. Every pool is 128 wide, nothing is padded (a token
and KV head is 640 B in bf16 exactly), and the page writer
(ops/kv_update.py `paged_write`) and `gather_pages` take them as GQA pools
of more layers. The decode walk (ops/paged_attention.py
`paged_decode_attention`, `parts`) lands a page's parts in their lanes of
one slot and scores PAIR-HEADS: a query head's 192 dims in its own head's
half of a 384-wide query, zeros in the other (`widen`), its output the own
half of a 256-wide accumulator (`narrow`): the same products, no more MXU
work than the walk's one dot for all heads already does.

Two caches (`MimoCache`). The F layers' PAGES, the engine's one page list:
2,560 B a token and layer. The S layers keep NO pages: a sequence's last
`ring_tokens` rows a layer live in the slot pool (`StepGroup.state_rows`) at
`position mod ring_tokens`, 5,120 B a row and layer,

    ring_tokens >= (sliding_window - 1) + the longest run of positions one
                   dispatch writes (a 512-token piece; 8 fused steps)

and a whole number of pages (127 + 512 = 639 -> 640 rows = 10 pages of 64:
the RUN sets the size here, not the window; models/cohere2_moe.py's
argument), ONE generation (`STATE_IN_PLACE`); a ring row is read only where
the position it holds is not negative and lies inside the query's window.

Under the kernels (`attention_impl` "pallas") both caches are read only
inside the layer loops, the step's rows in hand, staged and landed ONCE:

- a decode row, S layer (`attn/window`): the ring pages in reach (3 of 10,
  models/cohere2_moe.py `ring_walk`) walked under a bit a ring row, 8 query
  heads a KV head; the SINK and the row's own token are merged into the
  walk's `(acc, m, l)` by the caller (`fold_sink`, `_fold_own`);
- a decode row, F layer (`attn/paged`): the same walk over the row's pages,
  16 query heads a KV head, no sink;
- a prompt piece, S layer (`attn/window`): ops/flash_prefill.py
  `ring_prefill_attention` with the sink, over the slot's ring (gathered by
  page a part, laid out a KV head at a time) and the piece's own rows; a
  512-token piece is four query tiles that each reach two own tiles, the
  first alone the ring;
- a prompt piece, F layer (`attn/flash`): the same kernel with a window no
  position reaches over the row's gathered pages (models/cohere2_moe.py
  `full_piece`'s way).
  In both the keys go in 256 wide (192 + 64 zeros, q likewise): a copy that
  is made anyway, lane-aligned slices a head, and on a 128-wide MXU the
  192-wide product takes the two passes the 256-wide one does.

Without the kernels the rows are written first and ring and pages are
attended in plain XLA with the sink (models/llama.py `paged_attention`: the
tests' yardstick).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import mla
from dynamo_tpu.models.cohere2_moe import (
    _WALK_VMEM_BUDGET,
    EXPERTS,
    FULL,
    ROUTER_SPREAD,
    SLIDING,
    _fold_own,
    land_rings,
    ring_pages,
    ring_tables,
    ring_walk,
    ring_write,
)
from dynamo_tpu.models.dots3 import BIAS_SPREAD, ring_positions
from dynamo_tpu.models.llama import (
    KVPages,
    LlamaConfig,
    StepGroup,
    _mm,
    apply_rope,
    join_rows,
    maybe_decode_work,
    paged_attention,
    rms_norm,
    split_rows,
)

#: `ModelAdapter.step_twins`: one program a shape, as models/dots3.py
STEP_TWINS = False
#: `ModelAdapter.state_in_place`: the slot pool holds KV written by
#: position (module text): one generation, nothing to flip on a commit
STATE_IN_PLACE = True
#: a lane tile: the width of every pool entry (module text)
PART = 128


@dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 256
    hidden_size: int = 64
    #: the dense layers' MLP
    intermediate_size: int = 128
    #: one routed expert
    moe_intermediate_size: int = 32
    #: every HELD layer's kind (`hybrid_layer_pattern` 0 / 1 spelled out)
    layer_types: tuple = (FULL, SLIDING, SLIDING, FULL)
    #: every held layer's FFN: True where it holds experts (`moe_layer_freq`)
    moe_layers: tuple = (False, True, True, True)
    #: the published index of every held layer (its weights are drawn by
    #: it); None: the first `len(layer_types)`
    layer_ids: Optional[tuple] = None
    num_heads: int = 16
    num_kv_heads: int = 4
    swa_num_kv_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    #: the leading dims of q and k that rotate (`partial_rotary_factor`)
    rotary_dim: int = 64
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    attention_value_scale: float = 0.707
    #: keys a window query attends, its own among them
    sliding_window: int = 5
    #: rows of a sequence's ring a window layer (module text)
    ring_tokens: int = 40
    n_routed_experts: int = 8
    num_experts_per_tok: int = 2
    #: (first, count): the experts this chip holds of every layer; None: all
    experts_held: Optional[tuple] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"

    def __post_init__(self):
        if self.layer_types[0] != FULL or len(self.moe_layers) != len(
                self.layer_types):
            raise ValueError(
                f"layer_types {self.layer_types!r}: a full layer first and "
                "an FFN kind a layer are what is built")
        if any(k != FULL for k, moe in zip(self.layer_types, self.moe_layers)
               if not moe):
            raise ValueError("a dense layer under a window is not built")
        if self.ring_tokens < self.sliding_window:
            raise ValueError("ring_tokens holds less than a window")
        if (2 * self.head_dim) % PART or (2 * self.v_head_dim) % PART or (
                self.num_kv_heads % 2 or self.swa_num_kv_heads % 2):
            raise ValueError(
                "two KV heads' keys and values side by side are whole lane "
                f"tiles of {PART} (module text): head_dim {self.head_dim}, "
                f"v_head_dim {self.v_head_dim}, KV heads "
                f"{self.num_kv_heads} / {self.swa_num_kv_heads}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot a sequence: the window ones' rings."""
        return self.num_layers - self.full_layers

    @property
    def published_ids(self) -> tuple:
        return self.layer_ids or tuple(range(self.num_layers))

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else (
            self.n_routed_experts)

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    @property
    def parts(self) -> tuple:
        """(kp, vp): lane tiles of two KV heads' keys and of their values."""
        return 2 * self.head_dim // PART, 2 * self.v_head_dim // PART

    @property
    def ring_run(self) -> int:
        """The longest run of positions one dispatch may write."""
        return self.ring_tokens - (self.sliding_window - 1)

    @property
    def periods(self) -> tuple:
        """((layer index of a full layer, the window layers after it), ..)."""
        full = [i for i, k in enumerate(self.layer_types) if k == FULL]
        return tuple((li, nxt - li - 1) for li, nxt in zip(
            full, full[1:] + [self.num_layers]))

    def kv_heads(self, kind: str) -> int:
        return self.num_kv_heads if kind == FULL else self.swa_num_kv_heads

    def geo(self, kind: str, **over) -> LlamaConfig:
        """A layer's attention as models/llama.py's yardstick sees it."""
        return LlamaConfig(**{**dict(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_heads=self.num_heads, num_kv_heads=self.kv_heads(kind),
            head_dim=self.head_dim, dtype=self.dtype,
            attention_impl=self.attention_impl, num_layers=1), **over})

    def rope_geo(self, kind: str) -> LlamaConfig:
        """The rotated dims as a head of their own: `rotary_dim` wide, split
        by halves, under the kind's theta."""
        return self.geo(
            kind, head_dim=self.rotary_dim,
            rope_theta=self.rope_theta if kind == FULL else (
                self.swa_rope_theta))

    @property
    def moe_geo(self) -> mla.MlaConfig:
        """The FFNs as models/mla.py's router and grouped FFN see them."""
        return mla.MlaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, dtype=self.dtype,
            attention_impl=self.attention_impl,
            intermediate_size=self.intermediate_size,
            rms_norm_eps=self.rms_norm_eps,
            n_routed_experts=self.n_routed_experts, n_shared_experts=0,
            moe_intermediate_size=self.moe_intermediate_size,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob, topk_method="noaux_tc",
            n_group=1, topk_group=1, first_k_dense_replace=0)

    @staticmethod
    def mimo_v2_5(layer_ids: Optional[tuple] = None,
                  experts_held: Optional[tuple] = None,
                  vocab_size: int = 152576) -> "MimoV2Config":
        """As config.json publishes the language model: hidden 4096, 48
        layers (full at 0, 5, 11, 17, .., 47: `hybrid_layer_pattern`), 64
        query heads of 192 | 128, 4 KV heads in the full and 8 in the window
        layers, 64 rotated dims under thetas 1e7 | 1e4, a window of 128 under
        a sink a head, values scaled by 0.707, layer 0 dense 16,384 wide,
        then 256 sigmoid-routed experts of 2,048, top 8 renormalised, no
        shared expert, 152,576 ids untied. `layer_ids` holds some of the
        published layers; the ring is 640 rows (10 pages of 64): 127 behind
        a query + a run of 512."""
        full = {0} | set(range(5, 48, 6))
        ids = tuple(range(48)) if layer_ids is None else tuple(layer_ids)
        return MimoV2Config(
            vocab_size=vocab_size, hidden_size=4096, intermediate_size=16384,
            moe_intermediate_size=2048,
            layer_types=tuple(FULL if i in full else SLIDING for i in ids),
            moe_layers=tuple(i > 0 for i in ids), layer_ids=ids,
            num_heads=64, num_kv_heads=4, swa_num_kv_heads=8, head_dim=192,
            v_head_dim=128, rotary_dim=64, rope_theta=1e7,
            swa_rope_theta=1e4, attention_value_scale=0.707,
            sliding_window=128, ring_tokens=640, n_routed_experts=256,
            num_experts_per_tok=8, experts_held=experts_held)

    @staticmethod
    def mimo_v2_5_1chip() -> "MimoV2Config":
        """One chip of the deployment chipbench/configs/mimo-v2.5-1chip.json
        states: the published layer 0 (full, dense) and one whole period,
        layers 6-11 (W W W W W F), experts 0-15 of 256 (a 16-way
        expert-parallel share), ids 0-19,071 (an 8-way share)."""
        return MimoV2Config.mimo_v2_5(
            (0, 6, 7, 8, 9, 10, 11), (0, 16), 19072)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MimoV2Config":
        """Seven layers (F dense, W W F, W W F) at toy widths but the
        published head: 16 query heads of 192 | 128 over 4 and 8 KV heads,
        64 rotated dims, a window of 5 in a ring of 40 (a run of 36: the
        rehearsal's T bucket of 32), 8 experts top 2 all held through a
        share's path."""
        return MimoV2Config(
            vocab_size=vocab_size, dtype=jnp.float32, experts_held=(0, 8),
            layer_types=(FULL, SLIDING, SLIDING, FULL, SLIDING, SLIDING,
                         FULL),
            moe_layers=(False,) + (True,) * 6)


# ---------------------------------------------------------------------------
# The caches
# ---------------------------------------------------------------------------


class MimoCache(NamedTuple):
    """`k`, `v` the full layers' pages in lane parts, [3 x F, P, S, Hkv / 2,
    128] and [2 x F, ..] (module text); `ring`, `ring_v` the window layers'
    slot pool likewise, [3 x W, slots + 1, R, Hs / 2, 128] and [2 x W, ..]
    (slot 0 the null slot), ONE generation; `walked` the device's running
    count, laid out as models/cohere2_moe.py's six: the keys the window
    layers' decode rows attended, the tokens those rows held, the (query,
    key) pairs inside the band of the window layers' prompt pieces, the
    pairs under the causal mask of the full layers', the held experts a
    layer's rows chose, the passes over a share's assignments beyond a
    layer's first."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # never set: no quantised pages
    v_scale: Optional[jax.Array] = None
    ring: Optional[jax.Array] = None
    ring_v: Optional[jax.Array] = None
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


def walk_count(cache: MimoCache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def _token_bytes(cfg: MimoV2Config, kind: str) -> int:
    """One cached token of one layer: K and V rows of every KV head."""
    return cfg.kv_heads(kind) * (cfg.head_dim + cfg.v_head_dim) * jnp.dtype(
        cfg.dtype).itemsize


def page_bytes(cfg: MimoV2Config, page_size: int) -> int:
    """One page of every full layer."""
    return cfg.full_layers * page_size * _token_bytes(cfg, FULL)


def state_bytes_per_slot(cfg: MimoV2Config) -> int:
    """`ModelAdapter.state_slot_bytes`: one sequence's rings."""
    return cfg.state_layers * cfg.ring_tokens * _token_bytes(cfg, SLIDING)


def init_cache(cfg: MimoV2Config, num_pages: int, page_size: int,
               state_slots: int) -> MimoCache:
    if cfg.ring_tokens % page_size:
        raise ValueError(
            f"ring_tokens {cfg.ring_tokens} is not a whole number of pages "
            f"of {page_size}: the ring is walked and written as pages")
    kp, vp = cfg.parts

    def pool(parts, layers, rows, heads):
        return jnp.zeros((parts * layers, *rows, heads // 2, PART), cfg.dtype)

    pages = (num_pages, page_size)
    slots = (state_slots + 1, cfg.ring_tokens)
    f, w = cfg.full_layers, cfg.state_layers
    return MimoCache(
        k=pool(kp, f, pages, cfg.num_kv_heads),
        v=pool(vp, f, pages, cfg.num_kv_heads),
        ring=pool(kp, w, slots, cfg.swa_num_kv_heads),
        ring_v=pool(vp, w, slots, cfg.swa_num_kv_heads),
        walked=jnp.zeros((6,), jnp.int32),
    )


def pack(x):
    """Rows [B, T, Hkv, d] as the pools hold them: [2 d / 128, B, T, Hkv / 2,
    128], two heads side by side cut into lane tiles (module text)."""
    b, t, h, d = x.shape
    return jnp.moveaxis(
        x.reshape(b, t, h // 2, 2 * d // PART, PART), 3, 0)


def unpack(parts, d: int):
    """`pack` undone: [n, .., Hkv / 2, 128] -> [.., Hkv, d]."""
    x = jnp.moveaxis(parts, 0, -2)
    return x.reshape(*x.shape[:-3], 2 * x.shape[-3], d)


def _half(cfg: MimoV2Config, kind: str):
    """bool [Hq]: a query head's KV head is the SECOND of its pair."""
    g = cfg.num_heads // cfg.kv_heads(kind)
    return (jnp.arange(cfg.num_heads) // g) % 2 == 1


def widen(q, second):
    """A walk's query [B, Hq, dk] as its pair-head sees it: [B, Hq, 2 dk],
    the head's own half and zeros."""
    zero = jnp.zeros_like(q)
    second = second[None, :, None]
    return jnp.concatenate(
        [jnp.where(second, zero, q), jnp.where(second, q, zero)], axis=-1)


def narrow(acc, second):
    """A walk's accumulator [B, Hq, 2 dv]: the head's own half."""
    dv = acc.shape[-1] // 2
    return jnp.where(second[None, :, None], acc[..., dv:], acc[..., :dv])


def _layers_of(pool, parts: int, layer):
    """Layer `layer`'s entries of a pool in parts: [parts, ..]."""
    split = pool.reshape(parts, pool.shape[0] // parts, *pool.shape[1:])
    return lax.dynamic_index_in_dim(split, layer, 1, keepdims=False)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _stack_shapes(cfg: MimoV2Config) -> dict:
    """{stack: {leaf: shape of one layer}}: `full` / `swa` the attention of
    each kind, `dense` / `moe` the FFNs."""
    h, hq, dk, dv = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                     cfg.v_head_dim)
    e, i = cfg.experts_here, cfg.moe_intermediate_size

    def attn(hkv):
        return {"attn_norm": (h,), "wq": (h, hq * dk), "wk": (h, hkv * dk),
                "wv": (h, hkv * dv), "wo": (hq * dv, h)}

    return {
        "full": attn(cfg.num_kv_heads),
        "swa": {**attn(cfg.swa_num_kv_heads), "sink": (hq,)},
        "dense": {"mlp_norm": (h,), "w_gate": (h, cfg.intermediate_size),
                  "w_up": (h, cfg.intermediate_size),
                  "w_down": (cfg.intermediate_size, h)},
        "moe": {"mlp_norm": (h,), "w_router": (h, cfg.n_routed_experts),
                "router_bias": (cfg.n_routed_experts,),
                "we_gate": (e, h, i), "we_up": (e, h, i),
                "we_down": (e, i, h)},
    }


def layer_stacks(cfg: MimoV2Config) -> list:
    """[((attention stack, index in it), (FFN stack, index in it))] a held
    layer."""
    out, n = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for kind, moe in zip(cfg.layer_types, cfg.moe_layers):
        a, f = "full" if kind == FULL else "swa", "moe" if moe else "dense"
        out.append(((a, n[a]), (f, n[f])))
        n[a] += 1
        n[f] += 1
    return out


def init_params(key: jax.Array, cfg: MimoV2Config) -> dict:
    """Seeded weights at a trained block's scale: every matrix normal at 1
    / sqrt(fan in), norm weights one, the ROUTER in float32 at
    `ROUTER_SPREAD` / sqrt(hidden), its correction biases normal at
    models/dots3.py's `BIAS_SPREAD` (they move a choice between near-equal
    scores and not the weights), the SINKS float32 standard normal (a
    sink at the scale of a score: it takes a share of every window query's
    softmax, so leaving it out shows). An expert is drawn by its PUBLISHED
    number and a layer's leaves by the layer's published index
    (`layer_ids`), so a share holds what the whole model holds there."""
    shapes = _stack_shapes(cfg)
    first = cfg.experts_held[0] if cfg.experts_held else 0
    f32 = jnp.float32

    def normal(k, shape, fan_in, dtype=cfg.dtype, spread=1.0):
        return (jax.random.normal(k, shape, f32)
                * (spread / math.sqrt(fan_in))).astype(dtype)

    def leaf(name, shape, k):
        if name.endswith("norm"):
            return jnp.ones(shape, cfg.dtype)
        if name == "w_router":
            return normal(k, shape, shape[0], f32, ROUTER_SPREAD)
        if name == "router_bias":
            return BIAS_SPREAD * jax.random.normal(k, shape, f32)
        if name == "sink":
            return jax.random.normal(k, shape, f32)
        if name in EXPERTS:  # (one traced draw for all of a layer's)
            return jax.vmap(lambda e: normal(
                jax.random.fold_in(k, first + e), shape[1:], shape[1]))(
                jnp.arange(shape[0]))
        return normal(k, shape, shape[0])

    def drawn(stack: str, name: str, published: int):
        lk = jax.random.fold_in(
            jax.random.fold_in(key, 1 + published),
            sorted(shapes).index(stack))
        return leaf(name, shapes[stack][name], jax.random.fold_in(
            lk, list(shapes[stack]).index(name)))

    # a leaf at a time, every layer's under the other: what is built beside
    # the finished leaves is one leaf twice over (2 x 1.6 GB for the held
    # experts' matrices of the one-chip preset), not a stack of layers
    layers: dict = {name: [] for name in shapes}
    for li, pair in zip(cfg.published_ids, layer_stacks(cfg)):
        for stack, _ in pair:
            layers[stack].append(li)
    h, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": normal(jax.random.fold_in(key, 0), (v, h), 1.0),
        **{stack: {name: jnp.stack([drawn(stack, name, li) for li in ids])
                   for name in shapes[stack]}
           for stack, ids in layers.items() if ids},
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": normal(jax.random.fold_in(key, 1 << 20), (h, v), h),
    }


def mimo_v2_logical_axes(cfg: MimoV2Config) -> dict:
    """Logical axis names (parallel/logical.py): everything replicates but
    the vocabulary axis of table and head; the adapter refuses a mesh."""
    from dynamo_tpu.parallel.logical import L

    used = {s for pair in layer_stacks(cfg) for s, _ in pair}
    return {"embed": L("vocab", None), "final_norm": L(),
            "lm_head": L(None, "vocab"),
            **{stack: {name: L() for name in leaves}
               for stack, leaves in _stack_shapes(cfg).items()
               if stack in used}}


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def partial_rope(x, positions, cfg: MimoV2Config, kind: str):
    """Rope on the first `rotary_dim` dims of x [B, T, H, head_dim]."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [apply_rope(x[..., :r], positions, cfg.rope_geo(kind)), x[..., r:]],
        axis=-1)


def fold_sink(acc, m, l, sink):
    """The sink [Hq] merged exactly into a walk's running state (acc [B,
    Hq, dv] unnormalised, m and l [B, Hq]): a key of logit `sink` and value
    0. Returns (acc, m, l)."""
    m_new = jnp.maximum(m, sink[None])
    alpha = jnp.exp(m - m_new)
    return (alpha[..., None] * acc, m_new,
            alpha * l + jnp.exp(sink[None] - m_new))


def piece_attention(q, k, v, pools, layer, tables, key_pos, positions, valid,
                    cfg: MimoV2Config, kind: str, window: int, sinks=None):
    """A prompt piece's attention under the kernels, either kind of layer: q
    [B, T, Hq, dk] post-rope and unscaled, k [B, T, Hkv, dk] and v [B, T,
    Hkv, dv] the piece's own rows; the cached keys are the pages `tables`
    [B, n] of layer `layer` of `pools` (K and V in parts) and `key_pos` [B,
    n x S] the position each of their rows holds (negative: none). A row's
    pages are gathered a part at a time (ops/flash_prefill.py
    `gather_pages`), put together and laid out a KV head at a time, the
    keys 256 wide (module text): a copy; then `ring_prefill_attention`, a
    row of the batch at a time where the piece has several. Returns [B, T,
    Hq x dv]."""
    from dynamo_tpu.ops.flash_prefill import (
        gather_pages,
        ring_prefill_attention,
    )

    b, t = positions.shape
    dk, dv = cfg.head_dim, cfg.v_head_dim
    pad = -dk % PART
    wide = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))  # noqa: E731
    first = jnp.where(valid[:, 0], positions[:, 0], 0)
    scaled = wide((q.astype(jnp.float32) / math.sqrt(dk)).astype(cfg.dtype))
    k = wide(k)
    q_pos = jnp.where(valid, positions, first[:, None])
    cur_pos = jnp.where(valid, positions, -1)

    def cached(pool, n_parts, width, i):
        n_l = pool.shape[0] // n_parts
        rows = unpack(jnp.stack([
            gather_pages(pool, layer + p * n_l, tables[i])
            for p in range(n_parts)]), width)  # [n x S, Hkv, width]
        return jnp.swapaxes(rows, 0, 1)[None]

    def row(i):
        one = lambda a: lax.dynamic_slice_in_dim(a, i, 1, 0)  # noqa: E731
        kp, vp = cfg.parts
        return ring_prefill_attention(
            one(scaled), one(k), one(v), wide(cached(pools[0], kp, dk, i)),
            cached(pools[1], vp, dv, i), one(q_pos), one(key_pos),
            one(cur_pos), window=window, sinks=sinks)[0]

    o = row(0)[None] if b == 1 else lax.map(
        row, jnp.arange(b, dtype=jnp.int32))
    return o.reshape(b, t, -1)


def window_attend(q, k, v, sink, rings, layer, g: StepGroup, walk,
                  cfg: MimoV2Config, page: int):
    """One group's attention in a window layer: q [B, T, Hq, dk], k [B, T,
    Hkv, dk] post-rope, v [B, T, Hkv, dv], `sink` [Hq] float32; `rings` the
    slot pools in parts, `layer` the layer's index among the window layers.
    Under the kernels the ring is read as it stands and the rows are the
    caller's to land; without them the rows are written first and the whole
    ring attended in XLA. Returns (o [B, T, Hq x dv], rings). Scopes:
    `window`, `kv_update`."""
    b, t = g.positions.shape
    r, w = cfg.ring_tokens, cfg.sliding_window
    if t > cfg.ring_run:
        raise ValueError(
            f"a piece of {t} tokens would overwrite ring rows its own "
            f"windows need: ring_tokens {r} holds sliding_window - 1 = "
            f"{w - 1} and a run of {cfg.ring_run}")
    kp, vp = cfg.parts
    n_w = rings[0].shape[0] // kp  # the window layers the pool holds
    slots = g.state_rows[:, 1]
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            written = []
            for ring, rows in zip(rings, (k, v)):
                for p, part in enumerate(pack(rows)):
                    (ring,) = ring_write((ring,), layer + p * n_w, part,
                                         None, slots, g.positions, g.valid)
                written.append(ring)
            rings = tuple(written)
        with jax.named_scope("window"):
            last = jnp.max(jnp.where(g.valid, g.positions, -1), axis=1)
            held = ring_positions(last, r)
            o = paged_attention(
                q, unpack(_layers_of(rings[0], kp, layer)[:, slots],
                          cfg.head_dim),
                unpack(_layers_of(rings[1], vp, layer)[:, slots],
                       cfg.v_head_dim),
                g.positions, cfg.geo(SLIDING),
                key_positions=jnp.where(held >= 0, held, 1 << 30),
                window=jnp.int32(w), sinks=sink)
        return o, rings
    with jax.named_scope("window"):
        if t == 1:
            from dynamo_tpu.ops.paged_attention import paged_decode_attention

            tables, hist, bits, work = walk
            second = _half(cfg, SLIDING)
            acc, m, l = paged_decode_attention(
                widen(q[:, 0], second), *ring_pages(rings, page), layer,
                tables, hist, scale=1.0 / math.sqrt(cfg.head_dim),
                work_list=work, token_bits=bits, parts=cfg.parts,
                vmem_budget=_WALK_VMEM_BUDGET)
            o = _fold_own(*fold_sink(narrow(acc, second), m, l, sink),
                          q[:, 0], k[:, 0], v[:, 0], cfg.geo(SLIDING))
            return o.astype(cfg.dtype).reshape(b, 1, -1), rings
        first = jnp.where(g.valid[:, 0], g.positions[:, 0], 0)
        o = piece_attention(
            q, k, v, ring_pages(rings, page), layer,
            ring_tables(slots, r, page), ring_positions(first - 1, r),
            g.positions, g.valid, cfg, SLIDING, w, sinks=sink)
    return o, rings


def full_attend(q, k, v, kv: KVPages, layer, g: StepGroup, work,
                cfg: MimoV2Config):
    """One group's attention in a full layer, `layer` its index among the
    full layers: as `window_attend` over the row's pages, causal, no sink.
    Returns (o [B, T, Hq x dv], kv). Scopes: `paged` (a decode row's walk),
    `flash` (a piece), `kv_update`."""
    b, t = g.positions.shape
    kp, vp = cfg.parts
    n_f, page = kv.k.shape[0] // kp, kv.k.shape[2]
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            at = jnp.where(g.valid, g.positions, 0)
            pages = jnp.where(g.valid, jnp.take_along_axis(
                g.page_tables, at // page, axis=1), 0).reshape(-1)
            slot = (at % page).reshape(-1)
            pools = []
            for pool, rows, n in ((kv.k, k, kp), (kv.v, v, vp)):
                for p, part in enumerate(pack(rows)):
                    pool = pool.at[layer + p * n_f, pages, slot].set(
                        part.reshape(b * t, *part.shape[2:]).astype(
                            pool.dtype), mode="drop")
                pools.append(pool)
            kv = KVPages(k=pools[0], v=pools[1])
        with jax.named_scope("paged"):
            rows = lambda pool, n, d: unpack(  # noqa: E731
                _layers_of(pool, n, layer)[:, g.page_tables], d).reshape(
                b, -1, cfg.num_kv_heads, d)
            o = paged_attention(
                q, rows(kv.k, kp, cfg.head_dim),
                rows(kv.v, vp, cfg.v_head_dim), g.positions, cfg.geo(FULL))
        return o, kv
    if t == 1:
        from dynamo_tpu.ops.paged_attention import paged_decode_attention

        with jax.named_scope("paged"):
            second = _half(cfg, FULL)
            acc, m, l = paged_decode_attention(
                widen(q[:, 0], second), kv.k, kv.v, layer, g.page_tables,
                jnp.where(g.valid[:, 0], g.positions[:, 0], 0),
                scale=1.0 / math.sqrt(cfg.head_dim), work_list=work,
                parts=cfg.parts, vmem_budget=_WALK_VMEM_BUDGET)
            o = _fold_own(narrow(acc, second), m, l, q[:, 0], k[:, 0],
                          v[:, 0], cfg.geo(FULL))
        return o.astype(cfg.dtype).reshape(b, 1, -1), kv
    with jax.named_scope("flash"):
        n = g.page_tables.shape[1] * page
        first = jnp.where(g.valid[:, 0], g.positions[:, 0], 0)
        at = jnp.arange(n, dtype=jnp.int32)[None]
        o = piece_attention(
            q, k, v, (kv.k, kv.v), layer, g.page_tables,
            jnp.where(at < first[:, None], at, -1), g.positions, g.valid,
            cfg, FULL, 1 << 30)
    return o, kv


def moe_ffn(x, lp, cfg: MimoV2Config, stack=None):
    """The expert layer, composed of models/mla.py's parts as
    models/dots3.py composes its own, with NO shared expert: the router's
    product at the highest precision, the share's experts. Returns (out,
    int32 [2]: how many of the experts HELD some row chose and how many
    passes over the share's assignments the layer took beyond its first).
    Names its scopes from the top (`mlp/moe/route`, `mlp/moe/experts`): the
    caller stands under none, for the sake of the share's loop."""
    geo = cfg.moe_geo
    xf = x.reshape(-1, x.shape[-1])
    first, count = cfg.experts_held or (0, cfg.n_routed_experts)
    with jax.named_scope(mla.MOE_SCOPE + "route"):
        topw, topi = mla._gate(xf, lp, geo, precision=lax.Precision.HIGHEST)
        touched = jnp.sum(jnp.any(
            topi[..., None] == first + jnp.arange(count), axis=(0, 1)
        ).astype(jnp.int32))
    routed, extra = mla._routed_experts(
        xf, topw, topi, lp, geo, None, stack, held=cfg.experts_held,
        scope=mla.MOE_SCOPE)
    with jax.named_scope("mlp"):
        return (routed.astype(cfg.dtype).reshape(x.shape),
                jnp.stack([touched, extra]))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_groups(params: dict, cfg: MimoV2Config, groups,
                   cache: MimoCache, mesh=None):
    """models/llama.py's `forward_groups` for this family, as
    models/dots3.py's: ONE scan over the full layers in the published order
    (`MimoV2Config.periods`), each followed by its FFN (dense or experts, a
    `lax.cond` where the model has both) and by the window layers before
    the next full one (a loop of as many turns as there are); ONE body a
    kind of layer whatever the depth, the four stacks closed over and read
    in place. A layer's norm, projections, `wo` and FFN run on every
    group's rows together, attention a group. Returns ([hidden [B_g, T_g, H]
    post final norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError("mimo_v2 on a mesh is not implemented")
    if cfg.state_layers and any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with window layers needs each row's ring slot "
            "(StepGroup.state_rows)")
    eps, geo, page = cfg.rms_norm_eps, cfg.moe_geo, cache.page_size
    hq, dk, dv = cfg.num_heads, cfg.head_dim, cfg.v_head_dim
    kp, vp = cfg.parts
    n_f, n_w = cfg.full_layers, cfg.state_layers
    with jax.named_scope("embed"):
        h = join_rows([params["embed"][g.tokens].astype(cfg.dtype)
                       for g in groups])
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(cfg, g.tokens, jnp.where(
                g.valid, g.positions, 0), cache.pages, g.page_tables)
            for g in groups]
        walks = []
        for g in groups:  # a decode row's walk of its ring, layer-invariant
            walk = None
            if cfg.kernels and n_w and g.tokens.shape[1] == 1:
                from dynamo_tpu.ops.paged_attention import decode_work_list

                tables, hist, bits = ring_walk(
                    g.positions, g.valid, g.state_rows[:, 1], cfg, page)
                walk = (tables, hist, bits, decode_work_list(tables, hist))
            walks.append(walk)
    # ONE layer's count (`MimoCache.walked`): the keys its decode rows attend
    # under the window and hold, the (query, key) pairs of its prompt pieces
    # inside the band and under the causal mask
    counted = jnp.zeros((4,), jnp.int32)
    for g in groups:
        context = jnp.where(g.valid, g.positions + 1, 0)
        n = jnp.stack([jnp.sum(jnp.minimum(context, cfg.sliding_window)),
                       jnp.sum(context)])
        zero = jnp.zeros((2,), jnp.int32)
        counted = counted + jnp.concatenate(
            [n, zero] if g.tokens.shape[1] == 1 else [zero, n])
    experts = {n: params["moe"][n] for n in EXPERTS} if (
        cfg.kernels and "moe" in params) else {}

    def leaves(stack: str, li):
        return {n: lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
                for n, w in params[stack].items() if n not in experts}

    def attention(h, lp, kind: str, attend):
        """One layer's attention block: `attend(i, g, q, k, v)` is its
        kind's attention of one group, [B, T, Hq x dv]."""
        hkv = cfg.kv_heads(kind)
        with jax.named_scope("qkv"):
            x = rms_norm(h, lp["attn_norm"], eps)
            lead = x.shape[:-1]
            q = _mm(x, lp, "wq", cfg.dtype).reshape(*lead, hq, dk)
            k = _mm(x, lp, "wk", cfg.dtype).reshape(*lead, hkv, dk)
            v = (_mm(x, lp, "wv", cfg.dtype).astype(jnp.float32)
                 * cfg.attention_value_scale).astype(cfg.dtype).reshape(
                *lead, hkv, dv)
        outs = []
        for i, (g, qg, kg, vg) in enumerate(zip(groups, *(
                split_rows(a, groups) for a in (q, k, v)))):
            with jax.named_scope("qkv"):
                qg = partial_rope(qg, g.positions, cfg, kind)
                kg = partial_rope(kg, g.positions, cfg, kind)
            outs.append(attend(i, g, qg, kg, vg))
        with jax.named_scope("out"):
            return h + _mm(join_rows(outs), lp, "wo", cfg.dtype)

    # an FFN returns (h, `moe_ffn`'s two counts)
    def dense_mlp(h, li):
        with jax.named_scope("mlp"), jax.named_scope("dense"):
            lp = leaves("dense", li)
            return h + mla._dense_ffn(
                rms_norm(h, lp["mlp_norm"], eps), lp, geo
            ), jnp.zeros((2,), jnp.int32)

    def expert_mlp(h, li):
        with jax.named_scope("mlp"):
            lp = leaves("moe", li)
            x = rms_norm(h, lp["mlp_norm"], eps)
        y, counts = moe_ffn(x, lp, cfg, (experts, li) if experts else None)
        with jax.named_scope("mlp"):
            return h + y, counts

    def stage(st, rows, li):
        """A layer's rows in parts into its place of a group's stage."""
        return lax.dynamic_update_slice_in_dim(
            st, pack(rows).astype(st.dtype)[:, None], li, axis=1)

    def sliding_layer(j, carry, at):
        h, rings, staged, touched = carry
        si = at["swa"] + j
        staged = list(staged)

        def attend(i, g, q, k, v):
            nonlocal rings
            o, rings = window_attend(q, k, v, lp["sink"], rings, si, g,
                                     walks[i], cfg, page)
            if cfg.kernels:  # the rows wait for the one landing
                staged[i] = (stage(staged[i][0], k, si),
                             stage(staged[i][1], v, si))
            return o

        with jax.named_scope("attn"):
            lp = leaves("swa", si)
            h = attention(h, lp, SLIDING, attend)
        h, n = expert_mlp(h, at["moe_s"] + j)
        return h, rings, tuple(staged), touched + n

    def period(carry, at):
        h, kv, rings, staged_s, walked = carry
        staged_f = [None] * len(groups)

        def attend(i, g, q, k, v):
            nonlocal kv
            o, kv = full_attend(q, k, v, kv, at["full"], g, works[i], cfg)
            if cfg.kernels:
                staged_f[i] = (pack(k), pack(v))
            return o

        with jax.named_scope("attn"):
            h = attention(h, leaves("full", at["full"]), FULL, attend)
        if "dense" not in params:
            h, touched = expert_mlp(h, at["ffn"])
        elif "moe" not in params:
            h, touched = dense_mlp(h, at["ffn"])
        else:
            h, touched = lax.cond(
                at["dense"], dense_mlp, expert_mlp, h, at["ffn"])
        if n_w:  # the window layers up to the next full one
            h, rings, staged_s, touched = lax.fori_loop(
                0, at["n_s"], lambda j, c: sliding_layer(j, c, at),
                (h, rings, staged_s, touched))
        scale = jnp.stack([at["n_s"], at["n_s"], at["n_s"], jnp.int32(1)])
        walked = walked + jnp.concatenate([counted * scale, touched])
        return (h, kv, rings, staged_s, walked), tuple(staged_f)

    # where each period's layers lie in their stacks
    index = {"full": [], "dense": [], "ffn": [], "n_s": [], "swa": [],
             "moe_s": []}
    stacks = layer_stacks(cfg)
    for fi, (li, n_s) in enumerate(cfg.periods):
        (_, _), (ffn, ffn_i) = stacks[li]
        index["full"].append(fi)
        index["dense"].append(ffn == "dense")
        index["ffn"].append(ffn_i)
        index["n_s"].append(n_s)
        index["swa"].append(stacks[li + 1][0][1] if n_s else 0)
        index["moe_s"].append(stacks[li + 1][1][1] if n_s else 0)
    hs = cfg.swa_num_kv_heads // 2
    room = lambda g, n: jnp.zeros(  # noqa: E731
        (n, n_w, *g.tokens.shape, hs, PART), cfg.dtype)
    (h, kv, rings, staged_s, walked), staged_f = lax.scan(
        period,
        (h, cache.pages, (cache.ring, cache.ring_v),
         tuple((room(g, kp), room(g, vp)) if cfg.kernels and n_w else ()
               for g in groups),
         cache.walked),
        {name: jnp.asarray(v, jnp.bool_ if name == "dense" else jnp.int32)
         for name, v in index.items()})
    if cfg.kernels:
        # every layer's rows of the step, one write a group and cache: the
        # full layers' ([F, parts, ..] as the scan stacked them, to parts x
        # F) into their pages, the window layers' into the ring as the
        # pages it is, at `position mod ring_tokens`
        from dynamo_tpu.ops.kv_update import paged_write

        flat = lambda st: st.reshape(-1, *st.shape[2:])  # noqa: E731
        with jax.named_scope("attn"), jax.named_scope("kv_update"):
            for g, st_f, st_s in zip(groups, staged_f, staged_s):
                k_pool, v_pool = paged_write(
                    kv.k, kv.v, *(flat(jnp.swapaxes(st, 0, 1))
                                  for st in st_f),
                    g.page_tables, g.positions, g.valid)
                kv = KVPages(k=k_pool, v=v_pool)
                if n_w:
                    rings = land_rings(
                        rings, *(flat(st) for st in st_s),
                        g.state_rows[:, 1], g.positions, g.valid, page)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), MimoCache(
        k=kv.k, v=kv.v, ring=rings[0], ring_v=rings[1], walked=walked)


def forward_hidden(params, cfg: MimoV2Config, tokens, positions, valid,
                   cache, page_tables, state_rows, first_chunk: bool = False,
                   mesh=None):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh)
    return h, cache


def compute_logits(params: dict, cfg: MimoV2Config, hidden: jax.Array):
    """The untied head over the ids held."""
    with jax.named_scope("lm_head"):
        return (hidden @ params["lm_head"]).astype(jnp.float32)
