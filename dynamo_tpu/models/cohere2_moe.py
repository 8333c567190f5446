"""The language model of Command A+ (CohereLabs command-a-plus-05-2026,
`model_type` cohere2_moe): a parallel attention + expert block, three
window layers to one full layer, by `layer_types`.

    x  = LayerNorm(h)          ONE norm a layer: Cohere's, mean-subtracting,
                               a weight and no bias, float32
    h' = h + Attn(x) + FFN(x)  `use_parallel_block`: both read the SAME x
    logits = logit_scale E LayerNorm_f(h)    E the embedding (tied)

- Attn: `q = W_q x` (heads of `head_dim`), `k = W_k x`, `v = W_v x` (KV
  heads), no bias, no q/k norm; `o = softmax(q k^T / sqrt(head_dim)) v` a
  head, `q_per_kv` query heads a KV head; `y = W_o concat(o)`.
  - a SLIDING layer (S): `rope_gptj` (adjacent pairs, the whole head, theta
    `rope_theta`) on q and k; query t attends the keys `s` in `[t -
    (sliding_window - 1), t]`;
  - a FULL layer (F): NO rope; query t attends every `s <= t`.
- FFN: `s = sigmoid(W_r x)` in float32 over all the published experts, the
  `num_experts_per_tok` highest (ties to the lower index), `w = s_top /
  sum(s_top)` (models/mla.py `_gate` under "sigmoid"), `routed = sum_e w_e
  E_e(x)` with `E(x) = W_down (silu(W_gate x) * W_up x)`, EVERY assignment
  computed; `shared = 1 / n sum_{j < n} E_j(x)` over the `n_shared_experts`
  shared experts; `FFN(x) = routed + shared`. The shared experts are held
  FUSED: one gated MLP `n x intermediate_size` wide (`ws_gate`, `ws_up` the
  experts' matrices side by side, `ws_down` one under the other) whose
  output is scaled by `1 / n`: the sum over the fused width IS the sum of
  the experts' outputs, so this is the same mathematics. A chip may hold a
  share `experts_held = (first, count)` of the routed experts (models/mla.py
  `_routed_experts` with `held`): the router keeps its width and its top k
  over all, the chip adds the terms of its own experts.

Two caches (`Cohere2Cache`). The F layers' PAGES: K and V pools [F layers,
P, S, KV heads, head_dim], the engine's one page list. The S layers keep NO
pages: a sequence's last `ring_tokens` rows of K and V a layer live in the
engine's slot pool (`StepGroup.state_rows`), a row at `position mod
ring_tokens`:

    ring_tokens >= (sliding_window - 1) + the longest run of positions one
                   dispatch writes (a 512-token piece; 8 fused steps)

and a whole number of the engine's pages, so that nothing a later query's
window needs is overwritten by the step itself or by a dispatch launched
ahead and rolled back (models/dots3.py's argument; the published 4,095 +
513 = 4,608 rows = 72 pages of 64). The ring is KV written by position,
benign in place: ONE generation (`STATE_IN_PLACE`). A slot's content is what
its last owner left; a ring row is read only where the position it holds,
reckoned from the row's last written one, is not negative and lies inside
the query's window.

Under the kernels (`attention_impl` "pallas") both caches are read only
inside the layer loops, the step's rows in hand, staged and landed ONCE
after them (ops/kv_update.py `paged_write`: the ring IS pages of a GQA
cache, 72 a slot, written at `position mod ring_tokens`):

- a decode row, S layer (`attn/window`): the ring pages IN REACH (65 of
  72, `ring_walk`) walked by ops/paged_attention.py `paged_decode_attention`
  over GQA rows under a bit a ring row (its position inside the window);
- a decode row, F layer (`attn/paged`): the same walk over the row's pages
  (models/llama.py `attention_block`, q unrotated);
- a prompt piece, S layer (`attn/window`): ops/flash_prefill.py
  `ring_prefill_attention`: the slot's ring rows as they lie (copied out a
  KV head at a time) and the piece's own rows, a band by position, a chain
  of key tiles;
- a prompt piece, F layer (`attn/flash`): the same kernel with a window no
  position reaches over the row's pages, gathered and laid out a KV head at
  a time, no rope (`full_piece`; models/llama.py's
  `paged_prefill_attention` at 128 query heads takes the compiler minutes
  a program).

Without the kernels the rows are written first, and ring and pages are
attended in plain XLA (models/llama.py `paged_attention`: the tests'
yardstick; its float32 scores [B, heads, T, ring] are for small sizes).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import mla
from dynamo_tpu.models.dots3 import ring_positions
from dynamo_tpu.models.llama import (
    KVPages,
    LlamaConfig,
    StepGroup,
    _mm,
    apply_rope,
    attention_block,
    join_rows,
    land_staged_kv,
    maybe_decode_work,
    paged_attention,
    split_rows,
)

FULL, SLIDING = "full_attention", "sliding_attention"
#: `ModelAdapter.step_twins`: one program a shape, as models/dots3.py
STEP_TWINS = False
#: `ModelAdapter.state_in_place`: the slot pool holds KV written by
#: position (module text): one generation, nothing to flip on a commit
STATE_IN_PLACE = True
#: the expert matrices, held out of a layer's slices under the kernels
EXPERTS = ("we_gate", "we_up", "we_down")
#: the router's draw, times 1 / sqrt(hidden): logits of standard deviation 2
ROUTER_SPREAD = 2.0
#: VMEM both decode walks plan for (ops/paged_attention.py `_block_pages`),
#: the ring's and the full layer's: at 32 rows x 128 heads the kernel's
#: estimate counts 10 MiB of whole-batch q, accumulator and m|l blocks, and
#: models/llama.py's 12 left TWO pages a block where the rule wants 4 (1 MiB
#: of K and V a turn: 13 MiB by the estimate; the compile for the described
#: v5e fits it under a 7 MiB limit). One page a block ran at 52 % of the
#: HBM floor, two at 67, four at 77-79 (PR 55, scripts/paged_decode_bench.py)
#: since a block is folded in one-page sub-tiles: the [128, 2048] float32
#: temporaries that took the TPU's compiler 165 s a step program (PR 52) are
#: gone, a step program compiles in what one page a block took
_WALK_VMEM_BUDGET = 16 << 20


@dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    #: the width of ONE expert, routed or shared (`intermediate_size`)
    intermediate_size: int = 32
    #: every layer's kind, as config.json lists them (read, not derived)
    layer_types: tuple = (SLIDING, SLIDING, FULL)
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 5e4
    #: keys a sliding query attends, its own among them
    sliding_window: int = 9
    #: rows of a sequence's ring a sliding layer (module text)
    ring_tokens: int = 48
    n_routed_experts: int = 8
    n_shared_experts: int = 4
    num_experts_per_tok: int = 2
    #: (first, count): the experts this chip holds of every layer; None: all
    experts_held: Optional[tuple] = None
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    layer_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"

    def __post_init__(self):
        n_s = self.sliding_per_period
        period = (SLIDING,) * n_s + (FULL,)
        if (FULL not in self.layer_types
                or self.layer_types != period * self.full_layers):
            raise ValueError(
                f"layer_types {self.layer_types!r}: whole periods of "
                "sliding layers closed by a full one are what is built")
        if self.ring_tokens < self.sliding_window:
            raise ValueError("ring_tokens holds less than a window")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    @property
    def sliding_per_period(self) -> int:
        """The sliding layers before each full one (the published 3)."""
        return (self.layer_types + (FULL,)).index(FULL)

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot a sequence: the sliding ones' rings."""
        return self.num_layers - self.full_layers

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else (
            self.n_routed_experts)

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def ring_run(self) -> int:
        """The longest run of positions one dispatch may write."""
        return self.ring_tokens - (self.sliding_window - 1)

    def _attention(self, **kind) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, dtype=self.dtype,
            attention_impl=self.attention_impl, **kind)

    @property
    def full_geo(self) -> LlamaConfig:
        """A full layer as models/llama.py's attention sees it: no rope."""
        return self._attention(num_layers=self.full_layers, use_rope=False)

    @property
    def swa_geo(self) -> LlamaConfig:
        """A sliding layer: rope_gptj, adjacent pairs over the whole head."""
        return self._attention(
            num_layers=self.state_layers, rope_theta=self.rope_theta,
            rope_interleaved=True)

    @property
    def moe_geo(self) -> mla.MlaConfig:
        """The expert layer as models/mla.py's router and grouped FFN see
        it: the "sigmoid" rule, the shared experts fused."""
        return mla.MlaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, dtype=self.dtype,
            attention_impl=self.attention_impl,
            n_routed_experts=self.n_routed_experts,
            n_shared_experts=self.n_shared_experts,
            moe_intermediate_size=self.intermediate_size,
            num_experts_per_tok=self.num_experts_per_tok,
            norm_topk_prob=self.norm_topk_prob, topk_method="sigmoid",
            first_k_dense_replace=0)

    @staticmethod
    def command_a_plus(num_layers: int = 32,
                       experts_held: Optional[tuple] = None,
                       vocab_size: int = 262144) -> "Cohere2MoeConfig":
        """As config.json publishes the language model: hidden 4096, 32
        layers (S S S F eight times), 128 query heads over 8 KV heads of
        128, theta 50,000 on the sliding layers and none on the full ones,
        a window of 4,096, 128 sigmoid-routed experts of 4,096 top 8
        renormalised and 4 shared experts of 4,096 averaged in every layer,
        262,144 ids tied. `num_layers` cuts depth from the end; the ring is
        4,608 rows (72 pages of 64): 4,095 behind a query + a run of 513."""
        kinds = tuple(FULL if i % 4 == 3 else SLIDING for i in range(32))
        return Cohere2MoeConfig(
            vocab_size=vocab_size, hidden_size=4096, intermediate_size=4096,
            layer_types=kinds[:num_layers], num_heads=128, num_kv_heads=8,
            head_dim=128, rope_theta=5e4, sliding_window=4096,
            ring_tokens=4608, n_routed_experts=128, n_shared_experts=4,
            num_experts_per_tok=8, experts_held=experts_held)

    @staticmethod
    def command_a_plus_1chip() -> "Cohere2MoeConfig":
        """One chip of the deployment chipbench/configs/
        command-a-plus-1chip.json states: layers 0-3 (a pipeline stage, one
        whole period), experts 0-15 of 128 (an 8-way expert-parallel
        share), ids 0-32,767 (an 8-way share of the vocabulary)."""
        return Cohere2MoeConfig.command_a_plus(4, (0, 16), 32768)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Cohere2MoeConfig":
        """Six layers (S S F twice: a scanned period in two layer bodies)
        at toy widths: 4 query heads over 2 KV heads, a window of 9 in a
        ring of 48 (a run of 40: the rehearsal's T bucket of 32), 8 experts
        top 2 all held through a share's path, 4 shared experts."""
        return Cohere2MoeConfig(
            vocab_size=vocab_size, dtype=jnp.float32, experts_held=(0, 8),
            layer_types=(SLIDING, SLIDING, FULL) * 2)


# ---------------------------------------------------------------------------
# The caches
# ---------------------------------------------------------------------------


class Cohere2Cache(NamedTuple):
    """`k`, `v` the full layers' pages [F, P, S, Hkv, D]; `ring`, `ring_v`
    the sliding layers' slot pool, K and V [S layers, slots + 1, R, Hkv,
    D] (slot 0 the null slot), ONE generation; `walked` the device's
    running count, laid out as models/dots3.py's six: first the keys the
    sliding layers' decode rows attended (ring rows in the window and the
    row's own), second the tokens those rows held (what pages for these
    layers would have walked), a sliding layer each; third the (query, key)
    pairs inside the band of the sliding layers' prompt pieces, fourth the
    pairs under the causal mask of the full layers'; fifth the held experts
    a layer's rows chose and sixth the passes over a share's assignments
    beyond a layer's first (`mla._routed_experts`)."""

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


def walk_count(cache: Cohere2Cache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def page_bytes(cfg: Cohere2MoeConfig, page_size: int) -> int:
    """One page of every full layer: K and V rows."""
    return (cfg.full_layers * page_size * 2 * cfg.num_kv_heads
            * cfg.full_geo.kv_head_dim * jnp.dtype(cfg.dtype).itemsize)


def state_bytes_per_slot(cfg: Cohere2MoeConfig) -> int:
    """`ModelAdapter.state_slot_bytes`: one sequence's rings."""
    return (cfg.state_layers * cfg.ring_tokens * 2 * cfg.num_kv_heads
            * cfg.swa_geo.kv_head_dim * jnp.dtype(cfg.dtype).itemsize)


def init_cache(cfg: Cohere2MoeConfig, num_pages: int, page_size: int,
               state_slots: int) -> Cohere2Cache:
    if cfg.ring_tokens % page_size:
        raise ValueError(
            f"ring_tokens {cfg.ring_tokens} is not a whole number of pages "
            f"of {page_size}: the ring is walked and written as pages")
    geo = cfg.full_geo
    pool = (cfg.full_layers, num_pages, page_size, cfg.num_kv_heads,
            geo.kv_head_dim)
    ring = (cfg.state_layers, state_slots + 1, cfg.ring_tokens,
            cfg.num_kv_heads, geo.kv_head_dim)
    return Cohere2Cache(
        k=jnp.zeros(pool, cfg.dtype), v=jnp.zeros(pool, cfg.dtype),
        ring=jnp.zeros(ring, cfg.dtype), ring_v=jnp.zeros(ring, cfg.dtype),
        walked=jnp.zeros((6,), jnp.int32),
    )


def ring_pages(rings, page: int):
    """The slot pool as the pages of a GQA cache it is: [S layers, (slots
    + 1) x R / page, page, Hkv, D], the same bytes; slot s holds pages `s R
    / page` on, page 0 belongs to the null slot."""
    n_s, r = rings[0].shape[1:3]
    return tuple(ring.reshape(ring.shape[0], n_s * (r // page), page,
                              *ring.shape[3:])  # (K and V may differ in
                 for ring in rings)  # layers: models/mimo_v2.py's lane parts)


def ring_tables(slots, r: int, page: int):
    """int32 [B, R / page]: a slot's ring pages in the order of the ring."""
    n = r // page
    return slots[:, None] * n + jnp.arange(n, dtype=jnp.int32)[None]


def ring_walk(positions, valid, slots, cfg: Cohere2MoeConfig, page: int):
    """What a decode row's walk of its ring reads, the same in every sliding
    layer: (page tables [B, n], history lengths [B], bits [B, n x page]):
    the `n = ceil((window - 1) / page) + 1` consecutive ring pages that hold
    the `window - 1` positions before the row's own (65 of 72 at the
    published sizes), in position order; column i holds position `base + i`,
    a key where that is not negative and inside the window."""
    w, pages = cfg.sliding_window, cfg.ring_tokens // page
    n = -(-(w - 1) // page) + 1
    if n > pages:
        raise ValueError(
            f"a ring of {pages} pages of {page} is walked as {n}: a window "
            f"of {w} needs ring_tokens >= {n * page}")
    at = jnp.where(valid[:, 0], positions[:, 0], 0)
    first = (at - (w - 1)) // page  # the page of the window's first position
    tables = slots[:, None] * pages + (
        first[:, None] + jnp.arange(n, dtype=jnp.int32)[None]) % pages
    held = first[:, None] * page + jnp.arange(n * page, dtype=jnp.int32)[None]
    bits = (held >= 0) & (held >= at[:, None] - (w - 1)) & (
        held < at[:, None])
    return tables, jnp.where(valid[:, 0], at - first * page, 0), bits


def land_rings(rings, k_stage, v_stage, slots, positions, valid, page: int):
    """Under the kernels: a step's staged rows [S layers, B, T, Hkv, D] of
    every sliding layer into the rings in ONE write (ops/kv_update.py
    `paged_write`: the ring as the pages it is, a row at `position mod R`
    of its slot; padding goes to the null slot's page)."""
    from dynamo_tpu.ops.kv_update import paged_write

    r = rings[0].shape[2]
    landed = paged_write(
        *ring_pages(rings, page), k_stage, v_stage,
        ring_tables(slots, r, page), positions % r, valid)
    return tuple(new.reshape(ring.shape) for new, ring in zip(landed, rings))


def ring_write(rings, layer, k_rows, v_rows, slots, positions, valid):
    """Without the kernels: write a group's K and V rows [B, T, Hkv, D] of
    sliding layer `layer` at `position mod R` of each row's slot [B];
    padding goes to the null slot. `rings` = (ring, ring_v)."""
    n_l, n_s, r = rings[0].shape[:3]
    slot = jnp.where(valid, slots[:, None], 0)
    at = ((layer * n_s + slot) * r + positions % r).reshape(-1)
    return tuple(
        ring.reshape(n_l * n_s * r, -1).at[at].set(
            rows.reshape(at.shape[0], -1).astype(ring.dtype), mode="drop"
        ).reshape(ring.shape)
        for ring, rows in zip(rings, (k_rows, v_rows)))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: Cohere2MoeConfig) -> dict:
    """{leaf: shape} of one layer: every layer holds the same leaves."""
    h, d = cfg.hidden_size, cfg.head_dim
    e, i = cfg.experts_here, cfg.intermediate_size
    si = i * cfg.n_shared_experts
    return {
        "norm": (h,),
        "wq": (h, cfg.num_heads * d), "wk": (h, cfg.num_kv_heads * d),
        "wv": (h, cfg.num_kv_heads * d), "wo": (cfg.num_heads * d, h),
        "w_router": (h, cfg.n_routed_experts),
        "we_gate": (e, h, i), "we_up": (e, h, i), "we_down": (e, i, h),
        "ws_gate": (h, si), "ws_up": (h, si), "ws_down": (si, h),
    }


def init_params(key: jax.Array, cfg: Cohere2MoeConfig) -> dict:
    """Seeded weights at a trained block's scale: every matrix normal at 1
    / sqrt(fan in) (its input is normed or unit-scale, so its output is),
    `ws_down` by one expert's width, norm weights one, the ROUTER in
    float32 at `ROUTER_SPREAD` / sqrt(hidden) (logits of standard deviation
    2 under the sigmoid, as models/dots3.py's), the EMBEDDING at 1 /
    sqrt(hidden): it is the head too (tied), and a head's logits have unit
    spread at that scale; the first norm takes the small rows it gives as
    any others. An expert is drawn by its PUBLISHED number and a layer's
    leaves by the layer's published index, so a share holds what the whole
    model holds there."""
    shapes = _layer_shapes(cfg)
    first = cfg.experts_held[0] if cfg.experts_held else 0
    f32 = jnp.float32

    def normal(k, shape, fan_in, dtype=cfg.dtype, spread=1.0):
        return (jax.random.normal(k, shape, f32)
                * (spread / math.sqrt(fan_in))).astype(dtype)

    def leaf(name, shape, k):
        if name == "norm":
            return jnp.ones(shape, cfg.dtype)
        if name == "w_router":
            return normal(k, shape, shape[0], f32, ROUTER_SPREAD)
        if name in EXPERTS:
            return jnp.stack([
                normal(jax.random.fold_in(k, first + e), shape[1:], shape[1])
                for e in range(shape[0])])
        if name == "ws_down":
            return normal(k, shape, cfg.intermediate_size)
        return normal(k, shape, shape[0])

    # a leaf at a time, every layer's under the other: what is built beside
    # the finished leaves is one leaf twice over (2 x 2.1 GB for the held
    # experts' matrices of the one-chip preset), not the model
    h, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": normal(jax.random.fold_in(key, 0), (v, h), h),
        "layers": {
            name: jnp.stack([
                leaf(name, shape, jax.random.fold_in(
                    jax.random.fold_in(key, 1 + li), n))
                for li in range(cfg.num_layers)])
            for n, (name, shape) in enumerate(shapes.items())},
        "final_norm": jnp.ones((h,), cfg.dtype),
    }


def cohere2_moe_logical_axes(cfg: Cohere2MoeConfig) -> dict:
    """Logical axis names (parallel/logical.py): everything replicates but
    the vocabulary axis of the tied table, which is the head's; the adapter
    refuses a mesh."""
    from dynamo_tpu.parallel.logical import L

    return {"embed": L("vocab", None), "final_norm": L(),
            "layers": {name: L() for name in _layer_shapes(cfg)}}


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------


def layer_norm(x, w, eps: float):
    """Cohere's LayerNorm: the mean taken off, no bias, float32 inside."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def _fold_own(acc, m, l, q, k, v, cfg: Cohere2MoeConfig):
    """A decode row's own token folded exactly into a walk's running state
    (ops/paged_attention.py's module text): acc [B, Hq, D] unnormalised, m
    and l [B, Hq]; q [B, Hq, D] unscaled, k and v [B, Hkv, D] the row's own.
    Returns [B, Hq, D] float32."""
    kv_of = jnp.arange(cfg.num_heads) // cfg.q_per_kv
    s_self = jnp.sum(
        q.astype(jnp.float32) * k[:, kv_of].astype(jnp.float32), axis=-1
    ) / math.sqrt(cfg.head_dim)
    m_star = jnp.maximum(m, s_self)
    alpha, beta = jnp.exp(m - m_star), jnp.exp(s_self - m_star)
    return (alpha[..., None] * acc
            + beta[..., None] * v[:, kv_of].astype(jnp.float32)
            ) / (alpha * l + beta)[..., None]


def window_attend(q, k, v, rings, layer, g: StepGroup, walk,
                  cfg: Cohere2MoeConfig, page: int):
    """One group's attention in a sliding layer: q [B, T, Hq, D], k and v
    [B, T, Hkv, D] post-rope. Under the kernels the ring is read as it
    stands, as pages of `page` rows, the group's own rows in hand (a decode
    row walks the ring pages in reach `walk` names, a piece runs
    `ring_prefill_attention` over the slot's ring) and the rows are the
    caller's to land; without them the rows are written first and the whole
    ring attended in XLA. Returns (o [B, T, Hq x D], rings). Scopes:
    `window`, `kv_update`."""
    b, t = g.positions.shape
    r, w = cfg.ring_tokens, cfg.sliding_window
    if t > cfg.ring_run:
        raise ValueError(
            f"a piece of {t} tokens would overwrite ring rows its own "
            f"windows need: ring_tokens {r} holds sliding_window - 1 = "
            f"{w - 1} and a run of {cfg.ring_run}")
    slots = g.state_rows[:, 1]
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            rings = ring_write(rings, layer, k, v, slots, g.positions,
                               g.valid)
        with jax.named_scope("window"):
            last = jnp.max(jnp.where(g.valid, g.positions, -1), axis=1)
            held = ring_positions(last, r)
            o = paged_attention(
                q, rings[0][layer, slots], rings[1][layer, slots],
                g.positions, cfg.swa_geo,
                key_positions=jnp.where(held >= 0, held, 1 << 30),
                window=jnp.int32(w))
        return o, rings
    with jax.named_scope("window"):
        if t == 1:
            from dynamo_tpu.ops.paged_attention import paged_decode_attention

            tables, hist, bits, work = walk
            acc, m, l = paged_decode_attention(
                q[:, 0], *ring_pages(rings, page), layer, tables, hist,
                scale_dim=cfg.head_dim, work_list=work, token_bits=bits,
                vmem_budget=_WALK_VMEM_BUDGET)
            o = _fold_own(acc, m, l, q[:, 0], k[:, 0], v[:, 0], cfg)
            return o.astype(cfg.dtype).reshape(b, 1, -1), rings
        first = jnp.where(g.valid[:, 0], g.positions[:, 0], 0)
        o = piece_attention(
            q, k, v, ring_pages(rings, page), layer,
            ring_tables(slots, r, page), ring_positions(first - 1, r),
            g.positions, g.valid, cfg, w)
    return o, rings


def full_piece(q, k, v, kv: KVPages, layer, g: StepGroup,
               cfg: Cohere2MoeConfig):
    """Under the kernels, a prompt piece's attention in a FULL layer over
    its paged history and itself: the row's pages gathered and laid out a
    KV head at a time (a copy of the context's K and V a piece, 75 MB at
    18k tokens beside 0.4-0.6 TFLOP of attention), then the banded kernel
    with a window no position reaches: causal attention, the same chain of
    key tiles. models/llama.py's `paged_prefill_attention` computes the same
    but unrolls its 8 KV heads x 16 query heads of [2048, 128] float32 in
    one cell, which took the TPU's compiler 165 s a step program (the
    compile for the described v5e, PR 52: past the 120 s a client waits for
    its first token), so a piece of THIS family does not take it. q [B, T,
    Hq, D] unrotated, k and v [B, T, Hkv, D]. Returns [B, T, Hq x D]."""
    n = g.page_tables.shape[1] * kv.k.shape[2]
    first = jnp.where(g.valid[:, 0], g.positions[:, 0], 0)
    at = jnp.arange(n, dtype=jnp.int32)[None]
    return piece_attention(
        q, k, v, (kv.k, kv.v), layer, g.page_tables,
        jnp.where(at < first[:, None], at, -1), g.positions, g.valid, cfg,
        1 << 30)


def piece_attention(q, k, v, pools, layer, tables, key_pos, positions, valid,
                    cfg: Cohere2MoeConfig, window: int):
    """A prompt piece's attention under the kernels, either kind of layer:
    q [B, T, Hq, D] post-rope and unscaled, k and v [B, T, Hkv, D] the
    piece's own rows; the cached keys are the pages `tables` [B, n] of layer
    `layer` of `pools` (K, V: [L, P, S, Hkv, D]) and `key_pos` [B, n x S]
    the position each of their rows holds (negative: none). A row's pages
    are gathered and laid out a KV head at a time, [Hkv, n x S, D]: a copy
    (a ring 9.4 MB each of K and V at the published sizes), where reading a
    pool in place a head at a time would want it laid out [.., Hkv x D]:
    another tiling, which the compiler reaches by copying the whole pool (1
    GB each, seen in the compile for the described v5e). Then
    ops/flash_prefill.py `ring_prefill_attention`, a row of the batch at a
    time where the piece has several (the copies stay one row's). Returns
    [B, T, Hq x D]."""
    from dynamo_tpu.ops.flash_prefill import (
        gather_pages,
        ring_prefill_attention,
    )

    b, t = positions.shape
    first = jnp.where(valid[:, 0], positions[:, 0], 0)
    scaled = (q.astype(jnp.float32) / math.sqrt(cfg.head_dim)).astype(
        cfg.dtype)
    q_pos = jnp.where(valid, positions, first[:, None])
    cur_pos = jnp.where(valid, positions, -1)

    def row(i):
        one = lambda a: lax.dynamic_slice_in_dim(a, i, 1, 0)  # noqa: E731
        cached = (jnp.swapaxes(gather_pages(pool, layer, tables[i]), 0, 1)[
            None] for pool in pools)
        return ring_prefill_attention(
            one(scaled), one(k), one(v), *cached, one(q_pos), one(key_pos),
            one(cur_pos), window=window)[0]

    o = row(0)[None] if b == 1 else lax.map(
        row, jnp.arange(b, dtype=jnp.int32))
    return o.reshape(b, t, -1)


def moe_ffn(x, lp, cfg: Cohere2MoeConfig, stack=None):
    """FFN(x) of the module text, composed of models/mla.py's parts as
    models/dots3.py composes its own: the router's product at the highest
    precision (a flipped eighth expert adds or removes a whole expert where
    a chip holds a share), the share's experts, the fused shared experts
    times `1 / n_shared_experts`. Returns (out, int32 [2]: how many of the
    experts HELD some row chose and how many passes over the share's
    assignments the layer took beyond its first). Names its scopes from the
    top (`mlp/moe/route`, `mlp/moe/experts`, `mlp/moe/shared`): the caller
    stands under none, for the sake of the share's loop."""
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
    with jax.named_scope(mla.MOE_SCOPE + "shared"):
        shared = mla._shared_expert(xf, lp, geo) * jnp.asarray(
            1.0 / cfg.n_shared_experts, cfg.dtype)
    with jax.named_scope("mlp"):
        return ((routed.astype(cfg.dtype) + shared).reshape(x.shape),
                jnp.stack([touched, extra]))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_groups(params: dict, cfg: Cohere2MoeConfig, groups,
                   cache: Cohere2Cache, mesh=None):
    """models/llama.py's `forward_groups` for this family: ONE scan over
    the periods of the published order, each a loop over its sliding layers
    and then its full layer; ONE body a kind of layer whatever the depth,
    the layers' stack closed over and read in place. A layer's norm,
    projections, `wo` and experts run on every group's rows together,
    attention a group. Returns ([hidden [B_g, T_g, H] post final norm per
    group], the new cache)."""
    if mesh is not None:
        raise ValueError("cohere2_moe on a mesh is not implemented")
    if any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with window layers needs each row's ring slot "
            "(StepGroup.state_rows)")
    eps, n_s = cfg.layer_norm_eps, cfg.sliding_per_period
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    full_geo, swa_geo, page = cfg.full_geo, cfg.swa_geo, cache.page_size
    dpad = swa_geo.kv_head_dim - d
    with jax.named_scope("embed"):
        h = join_rows([params["embed"][g.tokens].astype(cfg.dtype)
                       for g in groups])
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(full_geo, g.tokens, jnp.where(
                g.valid, g.positions, 0), cache.pages, g.page_tables)
            for g in groups]
        walks = []
        for g in groups:  # a decode row's walk of its ring, layer-invariant
            walk = None
            if cfg.kernels and g.tokens.shape[1] == 1:
                from dynamo_tpu.ops.paged_attention import decode_work_list

                tables, hist, bits = ring_walk(
                    g.positions, g.valid, g.state_rows[:, 1], cfg, page)
                walk = (tables, hist, bits, decode_work_list(tables, hist))
            walks.append(walk)
    # a layer's count (`Cohere2Cache.walked`): the keys its decode rows
    # attend under the window and hold, the (query, key) pairs of its
    # prompt pieces inside the band and under the causal mask
    counted = jnp.zeros((4,), jnp.int32)
    for g in groups:
        context = jnp.where(g.valid, g.positions + 1, 0)
        n = jnp.stack([jnp.sum(jnp.minimum(context, cfg.sliding_window)),
                       jnp.sum(context)])
        zero = jnp.zeros((2,), jnp.int32)
        counted = counted + jnp.concatenate(
            [n, zero] if g.tokens.shape[1] == 1 else [zero, n])
    # a period's: the window layers take the first of each pair, n_s times;
    # the full layer the second
    counted = counted * jnp.asarray([n_s, n_s, n_s, 1], jnp.int32)
    experts = {n: params["layers"][n] for n in EXPERTS} if cfg.kernels else {}

    def leaves(li):
        return {n: lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
                for n, w in params["layers"].items() if n not in experts}

    def block(h, li, attend):
        """One layer: `attend(g, i, work, q, k, v)` is its kind's attention
        of one group, [B, T, Hq x D]."""
        lp = leaves(li)
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                x = layer_norm(h, lp["norm"], eps)
                lead = x.shape[:-1]
                q = _mm(x, lp, "wq", cfg.dtype).reshape(*lead, hq, d)
                k = _mm(x, lp, "wk", cfg.dtype).reshape(*lead, hkv, d)
                v = _mm(x, lp, "wv", cfg.dtype).reshape(*lead, hkv, d)
            outs = [attend(i, g, qg, kg, vg) for i, (g, qg, kg, vg) in
                    enumerate(zip(groups, *(split_rows(a, groups)
                                            for a in (q, k, v))))]
            with jax.named_scope("out"):
                a = _mm(join_rows(outs), lp, "wo", cfg.dtype)
        y, counts = moe_ffn(x, lp, cfg, (experts, li) if experts else None)
        with jax.named_scope("mlp"):
            return h + a + y, counts

    def sliding_layer(j, carry, p):
        h, rings, staged, touched = carry
        si = p * n_s + j
        staged = list(staged)

        def attend(i, g, q, k, v):
            nonlocal rings
            with jax.named_scope("qkv"):
                q = apply_rope(q, g.positions, swa_geo)
                k = apply_rope(k, g.positions, swa_geo)
                if dpad:  # the cache's lane padding (`kv_head_dim`)
                    q, k, v = (jnp.pad(a, ((0, 0),) * 3 + ((0, dpad),))
                               for a in (q, k, v))
            o, rings = window_attend(q, k, v, rings, si, g, walks[i], cfg,
                                     page)
            if dpad:
                o = o.reshape(*o.shape[:2], hq, -1)[..., :d].reshape(
                    *o.shape[:2], hq * d)
            if cfg.kernels:  # the rows wait for the one landing
                staged[i] = tuple(
                    lax.dynamic_update_index_in_dim(st, rows.astype(st.dtype),
                                                    si, 0)
                    for st, rows in zip(staged[i], (k, v)))
            return o

        h, n = block(h, p * (n_s + 1) + j, attend)
        return h, rings, tuple(staged), touched + n

    def period(carry, p):
        h, kv, rings, staged_s, walked = carry
        h, rings, staged_s, touched = lax.fori_loop(
            0, n_s, lambda j, c: sliding_layer(j, c, p),
            (h, rings, staged_s, jnp.zeros((2,), jnp.int32)))
        staged_f = [None] * len(groups)

        def attend(i, g, q, k, v):
            nonlocal kv
            if cfg.kernels and g.tokens.shape[1] > 1:
                if dpad:
                    q, k, v = (jnp.pad(a, ((0, 0),) * 3 + ((0, dpad),))
                               for a in (q, k, v))
                with jax.named_scope("flash"):
                    o = full_piece(q, k, v, kv, p, g, cfg)
                staged_f[i] = (k, v)
                return o.reshape(*o.shape[:2], hq, -1)[..., :d].reshape(
                    *o.shape[:2], hq * d)
            o, kv, staged_f[i] = attention_block(
                q, k, v, kv, p, g.page_tables, g.positions, g.valid,
                full_geo, decode_work=works[i],
                decode_vmem_budget=_WALK_VMEM_BUDGET)
            return o

        h, n = block(h, p * (n_s + 1) + n_s, attend)
        walked = walked + jnp.concatenate([counted, touched + n])
        return (h, kv, rings, staged_s, walked), tuple(staged_f)

    stage = lambda g: jnp.zeros(  # noqa: E731
        (cfg.state_layers, *g.tokens.shape, hkv, swa_geo.kv_head_dim),
        cfg.dtype)
    (h, kv, rings, staged_s, walked), staged_f = lax.scan(
        period,
        (h, cache.pages, (cache.ring, cache.ring_v),
         tuple((stage(g), stage(g)) if cfg.kernels else () for g in groups),
         cache.walked),
        jnp.arange(cfg.full_layers, dtype=jnp.int32))
    if cfg.kernels:
        # every layer's rows of the step, one write a group and pool: the
        # full layers' into their pages, the sliding layers' into the ring
        # as the pages it is, at `position mod ring_tokens`
        with jax.named_scope("attn"), jax.named_scope("kv_update"):
            for g, st_f, st_s in zip(groups, staged_f, staged_s):
                kv = land_staged_kv(kv, st_f, g.page_tables, g.positions,
                                    g.valid)
                rings = land_rings(rings, *st_s, g.state_rows[:, 1],
                                   g.positions, g.valid, page)
    with jax.named_scope("final_norm"):
        h = layer_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), Cohere2Cache(
        k=kv.k, v=kv.v, ring=rings[0], ring_v=rings[1], walked=walked)


def forward_hidden(params, cfg: Cohere2MoeConfig, tokens, positions, valid,
                   cache, page_tables, state_rows, first_chunk: bool = False,
                   mesh=None):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh)
    return h, cache


def compute_logits(params: dict, cfg: Cohere2MoeConfig, hidden: jax.Array):
    """`logit_scale` x E h: the head IS the embedding, over the ids held."""
    with jax.named_scope("lm_head"):
        return (hidden @ params["embed"].T).astype(
            jnp.float32) * cfg.logit_scale
