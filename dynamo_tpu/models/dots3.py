"""The language model of dots3-note-prev (dots-studio, `model_type`
dots3_note): two kinds of latent attention in one stack, by `layer_types`.

    h = h + Attn(x),  x = RMSNorm(h);   h = h + FFN(RMSNorm(h))
    logits = W_head RMSNorm(h), untied

- latent projections (models/mla.py `latent_projections`, one geometry a
  kind of layer): `c_q = a_q RMSNorm(W_qa x)`, `q = W_qb c_q`; `(c | k_r) =
  W_kva x`, `c_kv = a_kv RMSNorm(c)`, ONE rope key `k_r` a token; DeepSeek's
  interleaved rope on q's rope part and on `k_r`; the rescale
  (`apply_mla_qkv_lora_rescale`) `a = sqrt(hidden / rank)` on the normed
  latents, not on the rope key. The cache holds `c_kv` and `k_r`; attention
  runs in models/mla.py's absorbed form or, for a prompt piece under the
  kernels, in the plain one (below).
- a FULL layer (F; 128 heads, c 512): DeepSeek-V3.2-Exp's lightning
  indexer over the latent cache: `qI = W_qI c_q` (`index_heads` x
  `index_head_dim`), `kI = LayerNorm(W_kI x)` (ONE key a token), `w = W_w x
  / sqrt(heads x dim)`, rope on the first `qk_rope_head_dim` dimensions of
  qI and kI; `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])` in float32;
  the query attends the `min(index_topk, t + 1)` tokens `s <= t` of highest
  `I[t, .]`, ties to the earlier token, one set for all its heads
  (ops/token_select.py: models/keye_vl.py's rule, unchanged).
- a SLIDING layer (S; 64 heads, c 1024, its own theta): the same algebra
  over the keys `s` in `[t - (sliding_window - 1), t]`.
- both: a head-wise output gate `g = sigmoid(W_g x)`, `y = W_o concat_h(g_h
  o_h)`.
- FFN: SwiGLU in the first `first_k_dense_replace` layers; after them
  DeepSeek-V3's experts (models/mla.py `_gate` under `noaux_tc`: sigmoid
  scores, the highest `s + b` chosen, weights from the uncorrected scores
  renormalised; one shared expert), EVERY assignment computed. A chip may
  hold a share `experts_held = (first, count)`.

Three caches (`Dots3Cache`). The F layers' pages: models/mla.py's latent
pool and rope-key pool ([F layers, P, S, 1, .]) and the INDEX KEYS as the
page's third resident ([F layers, P, S, Di]: a 128-wide key fills a lane
row by itself). The S layers keep NO pages: a sequence's last tokens live in
a RING of `ring_tokens` rows of `c_kv` and `k_r` a layer in the engine's
slot pool (`StepGroup.state_rows`; two arrays, as models/mla.py's two
pools), addressed by `position mod ring_tokens`:

    ring_tokens >= (sliding_window - 1) + the longest run of positions one
                   dispatch writes (a 512-token chunk, or 8 fused steps)

so that nothing a later query's window needs is overwritten, by the step
itself or by a dispatch launched ahead and rolled back: what that one wrote
lies at positions the real dispatch writes again before anything reads
them, and what it overwrote lies more than a window behind every query to
come. The ring is KV written by position, benign in place: ONE generation
(`STATE_IN_PLACE`), where a recurrent state needs two. A slot's content
is whatever its last owner left; a key is read only where its ring row's
position, reckoned from the row's last written one, is not negative.

Under the kernels (`attention_impl` "pallas") the F layers' pools are read
only inside the layer scan: index scores out of the pool in place
(ops/index_scores.py, `paired=False`), the exact selection, and attention
in one of two FORMS by the shape of the group (`plain_full`):

- a DECODE row (T = 1) ABSORBED (`full_attend`): the walk of its pages
  under a bit a cached token (`attn/paged`, ops/paged_attention.py `latent`
  + `token_bits`), its rows alone through `absorbed_query` and the value
  up-projection;
- a prompt PIECE (T > 1: the 512 bucket, and the 32 bucket's tails and
  short prompts alike) PLAIN (`full_piece`), as latent models prefill: the
  queries as projected, and the history's latent rows sent through `wkv_b`
  INSIDE the kernel a block of 512 at a time (`attn/flash`,
  ops/flash_prefill.py `latent_plain_attention`: K and V of 128 heads over
  8k-18k keys are ~1 GB a layer and piece and are never written out),
  shared by the piece's T queries, under a mask bit a (query, key): 1,280
  FLOP a (query, key, head) as the MXU takes them where the absorbed form
  costs 2,304 and a query and an output of the latent's width around it.

The step's rows are staged and land once. A ring IS a few pages of a
latent cache (1,088 rows = 17 pages of 64 a slot), and under `attn/window`
an S layer takes one of two forms too (`plain_piece`), the step's own rows
in hand and written into the ring after the layer's attention:

- ABSORBED (`window_attend`): the decode walk over the slot's ring pages in
  reach under a bit a ring row (its position inside the query's window)
  and the absorbed chunk kernel (ops/flash_prefill.py
  `latent_prefill_attention` with `chosen`) under a mask a (query, ring
  row). A DECODE row (T = 1) and a SHORT piece (a 32-token tail, the
  ramp's short prompts): few queries, so the latent-wide query and output
  cost little and no key is up-projected;
- PLAIN (`window_piece`), as latent models prefill: a prompt PIECE whose T
  rows are not few beside the 576 ring rows in reach (4 T >= 576: a
  512-token piece). A window reaches 512 keys behind a query, so the keys
  are few (at most 1,088: 9 ring pages + the piece's 512 rows). They go
  through `wkv_b` ONCE into heads, `K_h = (W_UK,h c | k_r)`, `V_h = W_UV,h
  c`; the queries go in as projected; one banded flash pass by position
  (ops/flash_prefill.py `window_prefill_attention`) at 768 FLOP a (query,
  key, head) where the absorbed form costs 4,224 and needs a query and an
  output of the latent's width, [512, 64, 1024] float32 each, around it.

Without the kernels the rows are written first and the whole ring is
attended absorbed in plain XLA (`ring_attention`: the tests' yardstick).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import keye_vl as keye
from dynamo_tpu.models import mla
from dynamo_tpu.models.llama import (
    KVPages,
    StepGroup,
    _mm,
    join_rows,
    maybe_decode_work,
    paged_gather,
    rms_norm,
    split_rows,
)
from dynamo_tpu.ops import token_select as ts

FULL, SLIDING = "full_attention", "sliding_attention"
#: `ModelAdapter.step_twins`: one program a shape, as models/keye_vl.py
STEP_TWINS = False
#: `ModelAdapter.state_in_place`: the slot pool holds KV written by
#: position (module text): one generation, nothing to flip on a commit
STATE_IN_PLACE = True
#: the expert matrices, held out of the layer stacks' slices
EXPERTS = ("we_gate", "we_up", "we_down")
#: chunk queries one tile of the window attention takes without the
#: kernels: its float32 scores [B, heads, tile, ring] stay ~36 MB a row
WINDOW_BLOCK_Q = 128
#: the router's draw, times 1 / sqrt(hidden): logits of standard deviation 2
ROUTER_SPREAD = 2.0
#: the standard deviation of the drawn score-correction biases: small
#: beside the gaps between the highest sigmoid scores, as a trained
#: router's are (they balance the load, they do not pick the experts: at
#: 0.1 the same few experts won every token and 32 rows touched 2-4 of
#: the 8 held where even routing touches 5.1)
BIAS_SPREAD = 0.01


@dataclass(frozen=True)
class Dots3Config:
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128  # the dense layers' MLP
    #: every layer's kind, as config.json lists them (read, not derived)
    layer_types: tuple = (FULL, FULL, SLIDING, SLIDING)
    # -- the full layers' latent attention -----------------------------------
    num_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 8e7
    # -- the sliding layers' --------------------------------------------------
    swa_num_heads: int = 2
    swa_q_lora_rank: int = 32
    swa_kv_lora_rank: int = 48
    swa_qk_nope_head_dim: int = 24
    swa_qk_rope_head_dim: int = 8
    swa_v_head_dim: int = 16
    swa_rope_theta: float = 5e4
    #: keys a sliding query attends, its own among them
    sliding_window: int = 9
    #: rows of a sequence's ring a sliding layer (module text)
    ring_tokens: int = 32
    lora_rescale: bool = True  # apply_mla_qkv_lora_rescale
    headwise_gate: bool = True  # attention_gate_type "headwise"
    # -- the indexer (full layers) ---------------------------------------------
    index_heads: int = 4
    index_head_dim: int = 16
    index_topk: int = 8
    index_rope: bool = True
    # -- FFN (the names models/mla.py's gate and grouped FFN read) ------------
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 32
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    #: (first, count): the experts this chip holds of every layer; None: all
    experts_held: Optional[tuple] = None
    norm_topk_prob: bool = True
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"

    def __post_init__(self):
        if self.layer_types[0] != FULL or any(
                k not in (FULL, SLIDING) for k in self.layer_types):
            raise ValueError(f"layer_types {self.layer_types!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def full_layers(self) -> int:
        return sum(k == FULL for k in self.layer_types)

    @property
    def state_layers(self) -> int:
        """Layers that keep a slot a sequence: the sliding ones' rings."""
        return self.num_layers - self.full_layers

    @property
    def num_dense_layers(self) -> int:
        return min(self.first_k_dense_replace, self.num_layers)

    @property
    def experts_here(self) -> int:
        return self.experts_held[1] if self.experts_held else (
            self.n_routed_experts)

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    # what the engine reads of a model's attention (tp divisibility)
    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def mqa_latent_cache(self) -> bool:
        return True

    def _geometry(self, **attn) -> mla.MlaConfig:
        return mla.MlaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            attention_impl=self.attention_impl,
            n_routed_experts=self.n_routed_experts,
            n_shared_experts=self.n_shared_experts,
            moe_intermediate_size=self.moe_intermediate_size,
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_topk_prob=self.norm_topk_prob,
            topk_method=self.topk_method, n_group=self.n_group,
            topk_group=self.topk_group, **attn)

    @property
    def full_geo(self) -> mla.MlaConfig:
        """A full layer as models/mla.py sees it (and the expert layer)."""
        return self._geometry(
            num_layers=self.full_layers, num_heads=self.num_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta)

    @property
    def swa_geo(self) -> mla.MlaConfig:
        return self._geometry(
            num_layers=self.state_layers, num_heads=self.swa_num_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta)

    def rescale(self, geo: mla.MlaConfig):
        """(a_q, a_kv) of a geometry, or None."""
        if not self.lora_rescale:
            return None
        return (math.sqrt(self.hidden_size / geo.q_lora_rank),
                math.sqrt(self.hidden_size / geo.kv_lora_rank))

    @property
    def ring_run(self) -> int:
        """The longest run of positions one dispatch may write (a chunk's
        T): what `ring_tokens` leaves beside the window (module text)."""
        return self.ring_tokens - (self.sliding_window - 1)

    @property
    def ring_reach(self) -> int:
        """The ring rows a step's windows can reach: the whole pages that
        hold the `sliding_window - 1` positions before the step's first
        (`window_pages`: 9 pages = 576 of the 1,088 rows)."""
        s = self.ring_page
        return s * min(self.ring_tokens // s,
                       -(-(self.sliding_window - 1) // s) + 1)

    @property
    def ring_width(self) -> int:
        """Numbers a ring row holds: the latent and the rope key as cached
        (a whole lane tile under the kernels: models/mla.py `kv_rope_dim`)."""
        return self.swa_kv_lora_rank + self.swa_geo.kv_rope_dim

    @property
    def ring_page(self) -> int:
        """Rows of one of the pages a slot's ring is walked as."""
        return next(s for s in (64, 32, 16, 8, 4, 2, 1)
                    if self.ring_tokens % s == 0)

    @property
    def periods(self) -> tuple:
        """Per FULL layer, in order, (its published index, the sliding
        layers that follow it before the next full one): the forward pass
        is ONE scan over these periods. The published 46 layers are (0, 0),
        (1, 3), (5, 3), .., (41, 3), (45, 0); the one-chip cut (0, 0), (1,
        3), (5, 3). Every period with sliding layers has the same number
        of them."""
        at = [i for i, k in enumerate(self.layer_types) if k == FULL]
        out = tuple((i, j - i - 1)
                    for i, j in zip(at, at[1:] + [self.num_layers]))
        if len({n for _, n in out if n}) > 1:
            raise ValueError(
                f"full layers followed by different counts of sliding "
                f"layers are not built: {out}")
        if any(k != FULL for k in self.layer_types[:self.num_dense_layers]):
            raise ValueError("a dense layer under a window is not built")
        return out

    @staticmethod
    def dots3_note_prev(num_layers: int = 46,
                        experts_held: Optional[tuple] = None,
                        vocab_size: int = 152064) -> "Dots3Config":
        """As config.json publishes the language model: hidden 5120, 46
        layers (layer 0 and every fourth from layer 1 on full, the others
        sliding), full MLA 128 heads (q 1024, kv 512, 128 | 64, v 128,
        theta 8e7) under an indexer of 64 heads of 128 choosing 2,048;
        sliding MLA 64 heads (q 1024, kv 1024, 192 | 64, v 128, theta 5e4)
        under a window of 513; layer 0 dense 13,824, then 256 sigmoid
        experts of 1,536 top-8 renormalised and one shared; 152,064 ids
        untied. `num_layers` cuts depth from the end; the ring is 1,088
        rows (17 pages of 64): 512 behind a query + a 512-token chunk."""
        kinds = tuple(
            FULL if i == 0 or i % 4 == 1 else SLIDING for i in range(46))
        return Dots3Config(
            vocab_size=vocab_size, hidden_size=5120,
            intermediate_size=13824, layer_types=kinds[:num_layers],
            num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=8e7,
            swa_num_heads=64, swa_q_lora_rank=1024, swa_kv_lora_rank=1024,
            swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64,
            swa_v_head_dim=128, swa_rope_theta=5e4,
            sliding_window=513, ring_tokens=1088,
            index_heads=64, index_head_dim=128, index_topk=2048,
            moe_intermediate_size=1536, n_routed_experts=256,
            n_shared_experts=1, num_experts_per_tok=8,
            experts_held=experts_held,
        )

    @staticmethod
    def dots3_1chip() -> "Dots3Config":
        """One chip of the deployment chipbench/configs/
        dots3-note-prev-1chip.json states: layers 0-8 (a pipeline stage),
        experts 0-7 of 256 (a 32-way expert-parallel share), ids 0-19,007
        (an 8-way share of the vocabulary)."""
        return Dots3Config.dots3_note_prev(9, (0, 8), 19008)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Dots3Config":
        """Five layers (D, then the unit F S twice) at toy widths: every
        kind and a scanned unit in two layer bodies; the 8 highest tokens a
        query, a window of 9, a ring of 48 (a run of 40: the rehearsal's T
        bucket of 32)."""
        return Dots3Config(
            vocab_size=vocab_size, dtype=jnp.float32, ring_tokens=48,
            experts_held=(0, 8),  # all of them, through a share's path
            layer_types=(FULL, FULL, SLIDING, FULL, SLIDING))


# ---------------------------------------------------------------------------
# The caches
# ---------------------------------------------------------------------------


class Dots3Cache(NamedTuple):
    """`k` the full layers' latent pool [F, P, S, 1, c], `v` their
    rope-key pool [F, P, S, 1, r] (models/mla.py's layout), `ki` their
    index keys [F, P, S, Di], the page's third resident; `ring` and
    `ring_pe` the sliding layers' slot pool, latent [S layers, slots + 1,
    R, c'] and rope key [.., r'] (slot 0 the null slot), ONE generation;
    `walked` models/keye_vl.py's count of what the full layers attended,
    fifth the held experts the expert layers' rows touched and sixth the
    passes over a share's assignments beyond a layer's first
    (`mla._routed_experts`)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # never set: no quantised pages
    v_scale: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None
    ring: Optional[jax.Array] = None
    ring_pe: Optional[jax.Array] = None
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


def walk_count(cache: Dots3Cache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def page_bytes(cfg: Dots3Config, page_size: int) -> int:
    """One page of every full layer: latent, rope key as cached, index key."""
    g = cfg.full_geo
    return cfg.full_layers * page_size * jnp.dtype(cfg.dtype).itemsize * (
        g.kv_lora_rank + g.kv_rope_dim + cfg.index_head_dim)


def state_bytes_per_slot(cfg: Dots3Config) -> int:
    """`ModelAdapter.state_slot_bytes`: one sequence's rings."""
    return (cfg.state_layers * cfg.ring_tokens * cfg.ring_width
            * jnp.dtype(cfg.dtype).itemsize)


def init_cache(cfg: Dots3Config, num_pages: int, page_size: int,
               state_slots: int) -> Dots3Cache:
    pages = mla.init_kv_pages(cfg.full_geo, num_pages, page_size)
    return Dots3Cache(
        k=pages.k, v=pages.v,
        ki=jnp.zeros((cfg.full_layers, num_pages, page_size,
                      cfg.index_head_dim), cfg.dtype),
        ring=jnp.zeros((cfg.state_layers, state_slots + 1, cfg.ring_tokens,
                        cfg.swa_kv_lora_rank), cfg.dtype),
        ring_pe=jnp.zeros((cfg.state_layers, state_slots + 1,
                           cfg.ring_tokens, cfg.swa_geo.kv_rope_dim),
                          cfg.dtype),
        walked=jnp.zeros((6,), jnp.int32),
    )


def land_index_keys(ki_pool, ki_new, tables, positions, valid, layer=None):
    """Land index keys by position through the page tables, padding
    redirected to the null page: `ki_new` [F, B, T, Di] of every full layer
    (a step's staged keys), or [B, T, Di] of `layer` alone. A scatter of
    ROWS of the pool flattened to [F * P * S, Di] (models/keye_vl.py
    `land_index_keys` says why)."""
    n_l, n_p, s, di = ki_pool.shape
    page = jnp.take_along_axis(tables, positions // s, axis=1)
    at = (jnp.where(valid, page, 0) * s
          + jnp.where(valid, positions % s, 0)).reshape(-1)  # [B * T]
    if layer is None:
        at = (jnp.arange(n_l, dtype=at.dtype)[:, None] * (n_p * s)
              + at[None]).reshape(-1)
    else:
        at = layer * (n_p * s) + at
    return ki_pool.reshape(n_l * n_p * s, di).at[at].set(
        ki_new.reshape(-1, di).astype(ki_pool.dtype), mode="drop"
    ).reshape(ki_pool.shape)


def ring_write(rings, layer, c_rows, pe_rows, slots, positions, valid):
    """Write a group's latent rows [B, T, c'] and rope keys as cached [B,
    T, r'] of sliding layer `layer` at `position mod R` of each row's slot
    [B]; padding goes to the null slot. `rings` = (ring, ring_pe)."""
    n_l, n_s, r, _ = rings[0].shape
    slot = jnp.where(valid, slots[:, None], 0)
    at = ((layer * n_s + slot) * r + positions % r).reshape(-1)
    return tuple(
        ring.reshape(n_l * n_s * r, -1).at[at].set(
            rows.reshape(-1, rows.shape[-1]).astype(ring.dtype), mode="drop"
        ).reshape(ring.shape)
        for ring, rows in zip(rings, (c_rows, pe_rows)))


def ring_positions(last, r: int):
    """int32 [B, R]: the position each ring row holds once a sequence has
    written up to `last` [B] (negative: never written by this sequence)."""
    i = jnp.arange(r, dtype=jnp.int32)[None]
    return last[:, None] - (last[:, None] - i) % r


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _stack_shapes(cfg: Dots3Config) -> dict:
    """{stack: {leaf: shape}}: `full` / `swa` the attention blocks of a
    kind, `dense` / `moe` the FFNs."""
    h = cfg.hidden_size
    j, di = cfg.index_heads, cfg.index_head_dim
    e, i = cfg.experts_here, cfg.moe_intermediate_size
    si = i * cfg.n_shared_experts

    def attn(geo):
        shapes = dict(mla._attn_layer_shapes(geo))
        del shapes["mlp_norm"]
        shapes["w_headgate"] = (h, geo.num_heads)
        return shapes

    return {
        "full": {
            **attn(cfg.full_geo),
            "wi_q": (cfg.q_lora_rank, j * di), "wi_k": (h, di),
            "wi_w": (h, j), "ik_norm": (di,), "ik_bias": (di,),
        },
        "swa": attn(cfg.swa_geo),
        "dense": {
            "mlp_norm": (h,), "w_gate": (h, cfg.intermediate_size),
            "w_up": (h, cfg.intermediate_size),
            "w_down": (cfg.intermediate_size, h),
        },
        "moe": {
            "mlp_norm": (h,), "w_router": (h, cfg.n_routed_experts),
            "router_bias": (cfg.n_routed_experts,),
            "we_gate": (e, h, i), "we_up": (e, h, i), "we_down": (e, i, h),
            "ws_gate": (h, si), "ws_up": (h, si), "ws_down": (si, h),
        },
    }


def layer_stacks(cfg: Dots3Config) -> list:
    """Per layer, (its attention stack and index in it, its FFN stack and
    index in it), in the published order."""
    out, n = [], {"full": 0, "swa": 0, "dense": 0, "moe": 0}
    for li, kind in enumerate(cfg.layer_types):
        a = "full" if kind == FULL else "swa"
        f = "dense" if li < cfg.num_dense_layers else "moe"
        out.append(((a, n[a]), (f, n[f])))
        n[a] += 1
        n[f] += 1
    return out


def init_params(key: jax.Array, cfg: Dots3Config) -> dict:
    """Seeded weights at a trained block's scale: every matrix normal at 1
    / sqrt(fan in) where its input is normed or unit-scale, so that its
    output is; the matrices that read a RESCALED latent (`wq_b`, `wi_q`
    from c_q, `wkv_b` from c_kv) at 1 / (a sqrt(fan in)): their input has
    root mean square `a`; norm weights one, the index key's bias zero; the
    ROUTER in float32 at `ROUTER_SPREAD` / sqrt(hidden) (logits of
    standard deviation 2 under the sigmoid, as models/keye_vl.py's), its
    score-correction biases normal at `BIAS_SPREAD` (a trained router's
    are small and not zero: they move a choice between near-equal scores
    and not the weights). An
    expert is drawn by its PUBLISHED number, a layer's leaves by the
    layer's published index, so a share holds what the whole model holds
    there."""
    shapes = _stack_shapes(cfg)
    first = cfg.experts_held[0] if cfg.experts_held else 0
    f32 = jnp.float32

    def normal(k, shape, fan_in, dtype=cfg.dtype, spread=1.0):
        return (jax.random.normal(k, shape, f32)
                * (spread / math.sqrt(fan_in))).astype(dtype)

    def leaf(name, shape, k, rescale):
        if name.endswith("norm"):
            return jnp.ones(shape, cfg.dtype)
        if name == "ik_bias":
            return jnp.zeros(shape, cfg.dtype)
        if name == "w_router":
            return normal(k, shape, shape[0], f32, ROUTER_SPREAD)
        if name == "router_bias":
            return BIAS_SPREAD * jax.random.normal(k, shape, f32)
        if name in EXPERTS:
            return jnp.stack([
                normal(jax.random.fold_in(k, first + e), shape[1:], shape[1])
                for e in range(shape[0])])
        a_q, a_kv = rescale or (1.0, 1.0)
        a = {"wq_b": a_q, "wi_q": a_q, "wkv_b": a_kv}.get(name, 1.0)
        return normal(k, shape, shape[0], spread=1.0 / a)

    def block(stack: str, li: int) -> dict:
        lk = jax.random.fold_in(
            jax.random.fold_in(key, 1 + li), sorted(shapes).index(stack))
        geo = {"full": cfg.full_geo, "swa": cfg.swa_geo}.get(stack)
        return {name: leaf(name, shape, jax.random.fold_in(lk, n),
                           cfg.rescale(geo) if geo else None)
                for n, (name, shape) in enumerate(shapes[stack].items())}

    blocks: dict = {name: [] for name in shapes}
    for li, ((a, _), (f, _)) in enumerate(layer_stacks(cfg)):
        blocks[a].append(block(a, li))
        blocks[f].append(block(f, li))
    h, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": normal(jax.random.fold_in(key, 0), (v, h), 1.0),
        **{stack: {name: jnp.stack([b[name] for b in bs])
                   for name in shapes[stack]}
           for stack, bs in blocks.items() if bs},
        "final_norm": jnp.ones((h,), cfg.dtype),
        "lm_head": normal(jax.random.fold_in(key, 1 + cfg.num_layers),
                          (h, v), h),
    }


def dots3_logical_axes(cfg: Dots3Config) -> dict:
    """Logical axis names (parallel/logical.py): everything replicates but
    the head's vocabulary axis; the adapter refuses a mesh."""
    from dynamo_tpu.parallel.logical import L

    used = {s for pair in layer_stacks(cfg) for s, _ in pair}
    return {
        "embed": L(), "final_norm": L(), "lm_head": L(None, "vocab"),
        **{stack: {name: L() for name in leaves}
           for stack, leaves in _stack_shapes(cfg).items() if stack in used},
    }


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def head_gate(x, lp, cfg: Dots3Config):
    """sigmoid(W_g x), float32 [.., heads], or None; scope `gate`."""
    if not cfg.headwise_gate:
        return None
    with jax.named_scope("gate"):
        return jax.nn.sigmoid(
            _mm(x, lp, "w_headgate", cfg.dtype).astype(jnp.float32))


def index_projections(x, c_q, lp, cfg: Dots3Config):
    """(qI [.., J, Di] from the query latent, kI [.., Di] normed, w [.., J]
    float32 scaled), before the rotary embedding; scope `index`."""
    nj, di = cfg.index_heads, cfg.index_head_dim
    with jax.named_scope("index"):
        qi = None if c_q is None else _mm(
            c_q, lp, "wi_q", cfg.dtype).reshape(*x.shape[:-1], nj, di)
        ki = keye._layer_norm(_mm(x, lp, "wi_k", cfg.dtype), lp["ik_norm"],
                              lp["ik_bias"], cfg.rms_norm_eps)
        w = _mm(x, lp, "wi_w", cfg.dtype).astype(jnp.float32) / math.sqrt(
            nj * di)
    return qi, ki, w


def index_rope(x, positions, cfg: Dots3Config):
    """The rotary embedding on the first `qk_rope_head_dim` dimensions of
    an index query [B, T, J, Di] or key [B, T, Di]: the rope key's pairs
    at the full layers' theta."""
    if not cfg.index_rope:
        return x
    r = cfg.qk_rope_head_dim
    return jnp.concatenate([
        mla._interleaved_rope(x[..., :r], positions, cfg.full_geo),
        x[..., r:]], axis=-1)


def piece_chosen(qi, ki_new, w, ki_pool, layer, g: StepGroup,
                 cfg: Dots3Config):
    """bool [B, T, N]: under the kernels, the keys each query of a prompt
    piece attends in a full layer, by position: the index scores of the
    cached keys out of the pool in place and of the piece's own, then the
    exact selection."""
    return keye.chosen_keys(
        lambda *rows: keye.step_scores(*rows, ki_pool, layer, paired=False),
        (qi, w, ki_new, g.page_tables, g.positions, g.valid),
        g.page_tables.shape[1] * ki_pool.shape[2], g.positions, g.valid,
        cfg.index_topk)


def full_attend(ql, qp, ck, kp, qi, ki_new, w, kv, ki_pool, layer,
                g: StepGroup, work, cfg: Dots3Config):
    """One group's attention in a full layer over the tokens its indexer
    chooses, in the ABSORBED form. ql [B, T, H, c] float32 absorbed
    queries, qp [B, T, H, r], ck [B, T, c], kp [B, T, r] post-rope; qi,
    ki_new, w the indexer's. Returns (o_lat [B, T, H, c], (k, v) pools,
    ki_pool, what the kernels' discipline staged: (latent, rope key as
    cached, index keys) or None, int32 [4] what the step attended as
    models/keye_vl.py counts it, the selection bool [B, T, N])."""
    geo = cfg.full_geo
    t = ql.shape[1]
    tables, positions, valid = g.page_tables, g.positions, g.valid
    topk, s = cfg.index_topk, kv[0].shape[2]
    n = tables.shape[1] * s
    none = jnp.zeros((2,), jnp.int32)
    context = jnp.where(valid, positions + 1, 0)[:, 0]
    counted = jnp.concatenate([
        keye.tokens_attended(context, valid[:, 0], topk), none
    ]) if t == 1 else jnp.concatenate(
        [none, keye.chunk_pairs(positions, valid, topk)])
    g = g._replace(first_chunk=False)  # a history of none is a length
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            ki_pool = land_index_keys(
                ki_pool, ki_new, tables, positions, valid, layer)
        with jax.named_scope("index"):
            ki = paged_gather(ki_pool, layer, tables)  # [B, N, Di]
        chosen = keye.chosen_keys(
            ts.index_scores, (qi, w, ki), n, positions, valid, topk)
        o_lat, kv, _ = mla._attend_xla(
            ql, qp, ck, kp, geo, kv, layer, g, work, None, keep=chosen)
        return o_lat, kv, ki_pool, None, counted, chosen
    if t == 1:
        with jax.named_scope("index"):
            sc = keye.step_scores(qi, w, ki_new, tables, positions, valid,
                                  ki_pool, layer, paired=False)[:, 0]
        with jax.named_scope("select"):
            chosen = ts.select_tokens(sc, context, topk)[:, None]
    else:
        chosen = piece_chosen(qi, ki_new, w, ki_pool, layer, g, cfg)
    o_lat, kv, (c_st, pe_st) = mla._attend_kernels(
        ql, qp, ck, kp, geo, kv, layer, g, work, None, chosen=chosen)
    return o_lat, kv, ki_pool, (c_st, pe_st, ki_new), counted, chosen


def full_piece(qn, qp, ck, kp, qi, ki_new, w, kv, ki_pool, layer,
               g: StepGroup, cfg: Dots3Config, wkv_b):
    """Under the kernels, a prompt piece's attention in a full layer in the
    PLAIN form over the tokens its indexer chooses. A piece's history is
    8k-18k latent rows: K and V of 128 heads over them would be ~1 GB a
    layer and piece, so they are never written out; one kernel
    (ops/flash_prefill.py `latent_plain_attention`) takes a block of cached
    latent rows a turn and sends it through a group of heads' columns of
    `wkv_b` [c, H, nope + v] in VMEM, `K_h = (W_UK,h c | k_r)`, `V_h =
    W_UV,h c`, shared by the piece's T queries. The queries `qn` [B, T, H,
    nope] and `qp` [B, T, H, r] post-rope go in as projected, scaled, the
    rope part in the cached rope key's columns; ck [B, T, c] and kp [B, T,
    r] post-rope are the piece's own rows, in hand and not cached yet.
    Returns (o [B, T, H, v] float32, what `full_attend` stages for the
    landing, int32 [4] what it counts): no query and no output of the
    latent's width exists."""
    from dynamo_tpu.ops.flash_prefill import latent_plain_attention

    geo = cfg.full_geo
    chosen = piece_chosen(qi, ki_new, w, ki_pool, layer, g, cfg)
    pe_rows = mla._pad_last(kp, geo.kv_rope_dim)  # the rope key as cached
    with jax.named_scope("flash"):
        q = (jnp.concatenate(
            [qn, mla._pad_last(qp, geo.kv_rope_dim)], -1
        ).astype(jnp.float32) * geo.softmax_scale).astype(cfg.dtype)
        o = latent_plain_attention(
            q, wkv_b, ck, pe_rows, *kv, layer, g.page_tables,
            jnp.where(g.valid[:, 0], g.positions[:, 0], 0),
            jnp.sum(g.valid, axis=1), chosen)
    counted = jnp.concatenate([
        jnp.zeros((2,), jnp.int32),
        keye.chunk_pairs(g.positions, g.valid, cfg.index_topk)])
    return o, (ck[:, :, None, :], pe_rows[:, :, None, :], ki_new), counted


def plain_full(t: int, cfg: Dots3Config) -> bool:
    """Whether a group of T rows attends a full layer in the plain form
    (`full_piece`) under the kernels: every prompt piece (T > 1), whose T
    queries share a key's up-projection; a decode row (T = 1) has one query
    a sequence and keeps the absorbed page walk under bits."""
    return cfg.kernels and t > 1


def full_attention(x, lp, cfg: Dots3Config, kv, ki_pool, layer, groups,
                   works):
    """A full layer's attention block on the groups' rows: the projections
    and `wo` on all rows at once, the attention a group in the form its
    shape asks for (`plain_full`). Under the kernels a prompt piece (T > 1)
    attends PLAIN (`full_piece`); a decode row, and every group without the
    kernels, attends ABSORBED (`full_attend`), those groups' rows alone
    through `absorbed_query` and the value up-projection (32 of a mixed
    step's 544); the head gate a group, `wo` on the heads side by side.
    Returns (out shaped like x, (k, v), ki_pool, per group what was staged,
    int32 [4] counted). Scopes, under the caller's `attn`: `qkv`, `index`,
    `select`, `absorb`, `paged`, `flash`, `kv_update`, `gate`, `out`."""
    geo = cfg.full_geo
    n = geo.qk_nope_head_dim
    q, c_kv, kv_a, c_q = mla.latent_projections(
        x, lp, geo, cfg.rescale(geo))
    qs = split_rows(q, groups)
    # the groups that attend absorbed, their rows alone through W_UK (every
    # group: the rows as they are, not taken apart and joined again)
    rest = [i for i, g in enumerate(groups)
            if not plain_full(g.positions.shape[1], cfg)]
    q_lats, w_uv = {}, None
    if rest:
        q_lat, w_uv = mla.absorbed_query(
            q if len(rest) == len(groups) else join_rows(
                [qs[i] for i in rest]), lp, geo)
        q_lats = dict(zip(rest, split_rows(
            q_lat, [groups[i] for i in rest])))
    qi, ki, w = index_projections(x, c_q, lp, cfg)
    gate = head_gate(x, lp, cfg)
    gates = [None] * len(groups) if gate is None else split_rows(gate, groups)
    outs, staged, counted = [], [], jnp.zeros((4,), jnp.int32)
    parts = (q[..., n:], c_kv, kv_a[..., geo.kv_lora_rank:], qi, ki, w)
    for i, (g, work, gt, qg, qp, ck, kp, qig, kig, wg) in enumerate(zip(
        groups, works, gates, qs, *(split_rows(a, groups) for a in parts)
    )):
        with jax.named_scope("qkv"):
            qp = mla._interleaved_rope(qp, g.positions, geo)
            kp = mla._interleaved_rope(kp, g.positions, geo).astype(cfg.dtype)
        with jax.named_scope("index"):
            qig = index_rope(qig, g.positions, cfg)
            kig = index_rope(kig, g.positions, cfg).astype(cfg.dtype)
        if i in q_lats:
            o_lat, kv, ki_pool, st, cnt, _ = full_attend(
                q_lats[i], qp, ck, kp, qig, kig, wg, kv, ki_pool, layer, g,
                work, cfg)
        else:
            wkv_b = mla._w(lp, "wkv_b", cfg.dtype).reshape(
                geo.kv_lora_rank, geo.num_heads, n + geo.v_head_dim)
            o, st, cnt = full_piece(
                qg[..., :n], qp, ck, kp, qig, kig, wg, kv, ki_pool, layer, g,
                cfg, wkv_b)
        staged.append(st)
        counted = counted + cnt
        # a group's heads gated and laid side by side, [B, T, H x v] in the
        # model dtype: `mla.heads_output`'s first half a group (after the
        # count, where the parent's program has it: a decode program lowers
        # to the same text). A plain piece's heads leave the kernel side by
        # side and are gated so: joined as [544, 128, 128] they were laid
        # out by head and back, 2 ms of a one-piece step (PERF.md 6, PR 53)
        with jax.named_scope("out"):
            if i in q_lats:  # the value up-projection
                o = jnp.einsum(
                    "...hc,chv->...hv", o_lat.astype(w_uv.dtype), w_uv,
                    preferred_element_type=jnp.float32)
                if gt is not None:
                    o = o * gt[..., None].astype(jnp.float32)
                o = o.reshape(*o.shape[:-2], -1)
            else:
                o = o.reshape(*o.shape[:-2], -1)
                if gt is not None:
                    o = o * jnp.repeat(gt, geo.v_head_dim, axis=-1)
        outs.append(o.astype(cfg.dtype))
    with jax.named_scope("out"):
        out = _mm(join_rows(outs), lp, "wo", cfg.dtype)
    return out, kv, ki_pool, tuple(staged), counted


def ring_attention(ql, qp, rings, layer, slots, positions, valid,
                   cfg: Dots3Config):
    """Without the kernels: o_lat [B, T, H, c] of a group's queries over
    their rows' ring, the step's own rows already in it: softmax over the
    ring rows whose position lies in `[t - (sliding_window - 1), t]`,
    absorbed form, float32. ql [B, T, H, c], qp [B, T, H, r]."""
    geo = cfg.swa_geo
    n_l, n_s, r, c = rings[0].shape
    f32 = jnp.float32
    at = layer * n_s + slots
    lat = rings[0].reshape(n_l * n_s, r, c)[at].astype(f32)
    rope = rings[1].reshape(n_l * n_s, r, -1)[at].astype(f32)
    last = jnp.max(jnp.where(valid, positions, -1), axis=1)
    held = ring_positions(last, r)[:, None]  # [B, 1, R]

    def tile(args):
        qlt, qpt, pos = args  # [B, tq, H, c], [B, tq, H, r], [B, tq]
        sc = (jnp.einsum("bthc,bkc->bhtk", qlt.astype(f32), lat)
              + jnp.einsum("bthr,bkr->bhtk", qpt.astype(f32),
                           rope[..., :qpt.shape[-1]])) * geo.softmax_scale
        at = pos[..., None]
        keep = (held >= 0) & (held <= at) & (
            held >= at - (cfg.sliding_window - 1))
        p = jax.nn.softmax(jnp.where(keep[:, None], sc, mla._MASKED), -1)
        return jnp.einsum("bhtk,bkc->bthc", p, lat)

    b, t = positions.shape
    tq = WINDOW_BLOCK_Q
    if t <= tq or t % tq:
        return tile((ql, qp, positions))
    tiles = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, t // tq, tq, *a.shape[2:]), 1, 0)
    out = lax.map(tile, (tiles(ql), tiles(qp), tiles(positions)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, *out.shape[3:])


def window_pages(positions, valid, cfg: Dots3Config):
    """The ring pages a step's windows can reach, in position order: (the
    position `base` [B] the first of them starts at, their page numbers in
    the slot's ring [B, n]). The `sliding_window - 1` positions before the
    step's first lie in `n = ceil((window - 1) / page) + 1` consecutive
    pages of the ring (9 of 17 at the published sizes): the walk reads
    those and not the whole ring."""
    s, pages = cfg.ring_page, cfg.ring_tokens // cfg.ring_page
    n = cfg.ring_reach // s
    first = jnp.where(valid[:, 0], positions[:, 0], 0)
    base = (first - (cfg.sliding_window - 1)) // s * s  # may be negative
    page = (base[:, None] // s + jnp.arange(n, dtype=jnp.int32)[None]) % pages
    return base, page


def window_keep(positions, valid, base, held: int, window: int,
                columns: int):
    """bool [B, T, columns]: the keys each query of a step attends as the
    kernels see them: column i < `held` is the ring row that holds position
    `base + i` (kept where that is not negative, before the step's first
    position and not more than `window - 1` behind the query); column
    `held + j` is the step's own row j (kept up to the query's own, where
    valid and inside the window)."""
    t = positions.shape[1]
    first = jnp.where(valid[:, 0], positions[:, 0], 0)
    at = positions[..., None]
    cached = (base[:, None] + jnp.arange(held, dtype=jnp.int32)[None])[:, None]
    ring = (cached >= 0) & (cached < first[:, None, None]) & (
        cached >= at - (window - 1))
    own = positions[:, None, :]
    mine = valid[:, None, :] & (own <= at) & (own >= at - (window - 1))
    return jnp.concatenate([
        ring, mine, jnp.zeros((*positions.shape, columns - held - t), bool)
    ], axis=-1) & valid[..., None]


def window_piece(qn, qp, ck, kp, rings, layer, g: StepGroup,
                 cfg: Dots3Config, wkv_b):
    """Under the kernels, a prompt piece's attention in a sliding layer in
    the PLAIN form, and its rows' way into the ring after it. A window
    reaches `sliding_window - 1` keys behind a query, so a piece's keys are
    few: the ring pages in reach (`window_pages`, read by page: column i
    holds position `base + i`, a key where that is not negative and before
    the piece's first) and the piece's own rows (row j its position where
    valid), at most 1,088 at the published sizes, padded to whole 128-key
    blocks while a row is one latent wide. They go through `wkv_b` [c, H,
    nope + v] ONCE into heads, `K_h = (W_UK,h c | k_r)` with the one rope
    key a token under every head and `V_h = W_UV,h c` (float32 sums,
    rounded once to the model dtype); the queries `qn` [B, T, H, nope] and
    `qp` [B, T, H, r] go in as projected, scaled; one banded pass by
    position (ops/flash_prefill.py `window_prefill_attention`; query j's
    furthest key stands at most `ring_reach` columns before its own, none
    before column j). Returns (o [B, T, H, v], rings): no query and no
    output of the latent's width exists."""
    from dynamo_tpu.ops.flash_prefill import window_prefill_attention

    geo = cfg.swa_geo
    b, t = g.positions.shape
    if t > cfg.ring_run:
        raise ValueError(
            f"a chunk of {t} tokens would overwrite ring rows its own "
            f"windows need: ring_tokens {cfg.ring_tokens} leaves a run of "
            f"{cfg.ring_run}")
    s, pages = cfg.ring_page, cfg.ring_tokens // cfg.ring_page
    n, rr = geo.qk_nope_head_dim, geo.qk_rope_head_dim
    dt, f32 = cfg.dtype, jnp.float32
    slots = g.state_rows[:, 1]
    base, page = window_pages(g.positions, g.valid, cfg)
    held = page.shape[1] * s
    first = jnp.where(g.valid[:, 0], g.positions[:, 0], 0)
    at = ((layer * rings[0].shape[1] + slots) * pages)[:, None] + page
    lat, rope = (ring.reshape(-1, s, ring.shape[-1])[at].reshape(b, held, -1)
                 for ring in rings)
    cached = base[:, None] + jnp.arange(held, dtype=jnp.int32)[None]
    more = -(held + t) % 128
    k_pos = jnp.concatenate([
        jnp.where((cached >= 0) & (cached < first[:, None]), cached, -1),
        jnp.where(g.valid, g.positions, -1),
        jnp.full((b, more), -1, jnp.int32)], axis=1)
    # a row that holds no key goes in as zeros: a ring row is whatever its
    # last owner left, and as a value a zero weight does not silence a NaN
    rows = jnp.where((k_pos >= 0)[..., None], jnp.pad(jnp.concatenate([
        jnp.concatenate([lat, rope[..., :rr]], -1),
        jnp.concatenate([ck.astype(dt), kp], -1)], axis=1),
        ((0, 0), (0, more), (0, 0))), 0)  # [B, K, c + r]
    # the WEIGHTS lay a head's key out, (W_UK,h | 0) over (0 | I): the
    # product comes out as the kernel reads it, the rope key under every
    # head; concatenating products cost two more passes over K and a
    # transposing copy of K and of V (PERF.md 6, PR 50)
    lay = jnp.pad(jnp.eye(rr, dtype=dt), ((0, 0), (n, 0)))  # [r, n + r]
    w_k = jnp.concatenate([
        jnp.pad(wkv_b[..., :n], ((0, 0), (0, 0), (0, rr))),
        jnp.broadcast_to(lay[:, None], (rr, wkv_b.shape[1], n + rr))])
    k = jnp.einsum("bkc,chd->bkhd", rows, w_k,
                   preferred_element_type=f32).astype(dt)
    v = jnp.einsum("bkc,chd->bkhd", rows[..., :-rr], wkv_b[..., n:],
                   preferred_element_type=f32).astype(dt)
    q = (jnp.concatenate([qn, qp], -1).astype(f32)
         * geo.softmax_scale).astype(dt)
    o = window_prefill_attention(
        q, k, v, jnp.where(g.valid, g.positions, first[:, None]), k_pos,
        window=cfg.sliding_window)
    with jax.named_scope("kv_update"):
        rings = ring_write(rings, layer, ck,
                           mla._pad_last(kp, geo.kv_rope_dim), slots,
                           g.positions, g.valid)
    return o, rings


def window_attend(ql, qp, ck, kp, rings, layer, g: StepGroup,
                  cfg: Dots3Config):
    """One group's attention in a sliding layer and its rows' way into the
    ring. ql [B, T, H, c] absorbed queries, qp [B, T, H, r], ck [B, T, c],
    kp [B, T, r] post-rope. Under the kernels a slot's ring is walked as
    the pages of a latent cache it is (models/mla.py `_attend_kernels`: the
    decode walk under a bit a ring row, the chunk kernel under a mask a
    (query, ring row), over the pages the windows can reach,
    `window_pages`, the step's own rows in hand), and the rows are written
    after; without them the rows are written first and the whole ring
    attended in XLA. Returns (o_lat [B, T, H, c], rings)."""
    geo = cfg.swa_geo
    b, t = g.positions.shape
    r, s = cfg.ring_tokens, cfg.ring_page
    if t > cfg.ring_run:
        raise ValueError(
            f"a chunk of {t} tokens would overwrite ring rows its own "
            f"windows need: ring_tokens {r} holds sliding_window - 1 = "
            f"{cfg.sliding_window - 1} and a run of {cfg.ring_run}")
    slots = g.state_rows[:, 1]
    pe_rows = mla._pad_last(kp, geo.kv_rope_dim)
    if not cfg.kernels:
        with jax.named_scope("kv_update"):
            rings = ring_write(rings, layer, ck, pe_rows, slots, g.positions,
                               g.valid)
        return ring_attention(ql, qp, rings, layer, slots, g.positions,
                              g.valid, cfg), rings
    n_l, n_s = rings[0].shape[:2]
    base, page = window_pages(g.positions, g.valid, cfg)
    held = page.shape[1] * s
    own_pages = -(-t // s)  # columns for the step's own rows; null pages
    tables = jnp.concatenate([
        slots[:, None] * (r // s) + page,
        jnp.zeros((b, own_pages), jnp.int32)], axis=1)
    keep = window_keep(g.positions, g.valid, base, held, cfg.sliding_window,
                       held + own_pages * s)
    # the ring as the latent cache it is, the reachable pages "history"
    as_pages = [ring.reshape(n_l, n_s * (r // s), s, 1, ring.shape[-1])
                for ring in rings]
    ringed = StepGroup(
        g.tokens, jnp.broadcast_to(
            held + jnp.arange(t, dtype=jnp.int32)[None], (b, t)),
        g.valid, tables)
    o_lat, _, (c_st, pe_st) = mla._attend_kernels(
        ql, qp, ck, kp, geo, as_pages, layer, ringed, None, None,
        chosen=keep)
    with jax.named_scope("kv_update"):
        rings = ring_write(rings, layer, c_st[:, :, 0], pe_st[:, :, 0],
                           slots, g.positions, g.valid)
    return o_lat, rings


def plain_piece(t: int, cfg: Dots3Config) -> bool:
    """Whether a group of T rows attends a sliding layer in the plain form
    (`window_piece`) under the kernels. That form up-projects `ring_reach +
    T` rows for T queries, where the absorbed one carries a latent-wide
    query and output a query: it pays where T is not small beside the ring
    rows in reach. On the chip a layer's block reads, plain against
    absorbed, ms a piece of T tokens (PERF.md 6, PR 50): 1.68 / 2.53 at
    512, 1.16 / 1.52 at 256, 0.99 / 1.10 at 128, 1.05 / 0.92 at 64, 0.92 /
    0.82 at 32, and 13.3 / 4.5 for 32 prompts of 32 tokens from position
    0: the two cross between 64 and 128 tokens."""
    return cfg.kernels and 4 * t >= cfg.ring_reach


def window_attention(x, lp, cfg: Dots3Config, rings, layer, groups):
    """A sliding layer's attention block on the groups' rows: the
    projections and `wo` on all rows at once, the attention a group in the
    form its shape asks for (`plain_piece`). Under the kernels a group
    whose T rows are not few beside the ring rows in reach (a 512-token
    piece beside 576) attends PLAIN (`window_piece`); every other group, a
    decode row (T = 1) and a short piece (32 tokens: a tail, the ramp's
    short prompts), attends ABSORBED (`window_attend`), its rows alone
    through `absorbed_query` and the value up-projection (32 of a mixed
    step's 544). Returns (out, rings). Scopes: `qkv`, `absorb`, `window`
    (and `kv_update` inside it), `gate`, `out`."""
    geo = cfg.swa_geo
    n = geo.qk_nope_head_dim
    q, c_kv, kv_a, _ = mla.latent_projections(x, lp, geo, cfg.rescale(geo))
    gate = head_gate(x, lp, cfg)
    outs = []
    parts = (q, c_kv, kv_a[..., geo.kv_lora_rank:])
    for g, qg, ck, kp in zip(
        groups, *(split_rows(a, groups) for a in parts)
    ):
        with jax.named_scope("qkv"):
            qp = mla._interleaved_rope(qg[..., n:], g.positions, geo)
            kp = mla._interleaved_rope(kp, g.positions, geo).astype(cfg.dtype)
        if plain_piece(g.positions.shape[1], cfg):
            wkv_b = mla._w(lp, "wkv_b", cfg.dtype).reshape(
                geo.kv_lora_rank, geo.num_heads, n + geo.v_head_dim)
            with jax.named_scope("window"):
                o, rings = window_piece(
                    qg[..., :n], qp, ck, kp, rings, layer, g, cfg, wkv_b)
        else:
            q_lat, w_uv = mla.absorbed_query(qg, lp, geo)
            with jax.named_scope("window"):
                o_lat, rings = window_attend(
                    q_lat, qp, ck, kp, rings, layer, g, cfg)
            with jax.named_scope("out"):
                o = jnp.einsum(
                    "...hc,chv->...hv", o_lat.astype(w_uv.dtype), w_uv,
                    preferred_element_type=jnp.float32)
        outs.append(o.astype(jnp.float32))
    with jax.named_scope("out"):
        return mla.heads_output(join_rows(outs), lp, geo, gate), rings


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def moe_ffn(x, lp, cfg: Dots3Config, stack=None):
    """The expert layer, composed of models/mla.py's parts as
    models/nemotron_h.py and models/keye_vl.py compose theirs: the router's
    product at the highest precision (a flipped eighth expert adds or
    removes a whole expert where a chip holds a share), the share's
    experts, the shared expert. Returns (out, int32 [2]: how many of the
    experts HELD some row chose, whose matrices the grouped matmuls read,
    and how many passes over the share's assignments the layer took beyond
    its first, `mla._routed_experts`). Names its scopes from the top
    (`mlp/moe/route`, `mlp/moe/experts`, `mlp/moe/shared`): the caller
    stands under none, for the sake of the share's loop."""
    geo = cfg.full_geo
    xf = x.reshape(-1, x.shape[-1])
    first, count = cfg.experts_held or (0, cfg.n_routed_experts)
    with jax.named_scope(mla.MOE_SCOPE + "route"):
        topw, topi = mla._gate(
            xf, lp, geo, precision=lax.Precision.HIGHEST)
        touched = jnp.sum(jnp.any(
            topi[..., None] == first + jnp.arange(count), axis=(0, 1)
        ).astype(jnp.int32))
    routed, extra = mla._routed_experts(
        xf, topw, topi, lp, geo, None, stack, held=cfg.experts_held,
        scope=mla.MOE_SCOPE)
    with jax.named_scope(mla.MOE_SCOPE + "shared"):
        shared = mla._shared_expert(xf, lp, geo)
    with jax.named_scope("mlp"):
        return ((routed.astype(cfg.dtype) + shared).reshape(x.shape),
                jnp.stack([touched, extra]))


def forward_groups(params: dict, cfg: Dots3Config, groups,
                   cache: Dots3Cache, mesh=None):
    """models/llama.py's `forward_groups` for this family: ONE scan over
    the full layers in the published order (`Dots3Config.periods`), each
    followed by its FFN (dense or experts, a `lax.cond` where the model
    has both) and by the sliding layers before the next full one (a loop
    of as many turns as there are: none after layer 0); ONE body a kind of
    layer whatever the depth, the four stacks closed over and read in
    place (as models/nemotron_h.py: a scan over the stacks would copy each
    layer's slice out first). The dense work and the experts of a layer run
    on every group's rows together, attention per group. Returns ([hidden
    [B_g, T_g, H] post final norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError("dots3 on a mesh is not implemented")
    if cfg.state_layers and any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with window layers needs each row's ring slot "
            "(StepGroup.state_rows)")
    eps, geo = cfg.rms_norm_eps, cfg.full_geo
    with jax.named_scope("embed"):
        h = join_rows([params["embed"][g.tokens].astype(cfg.dtype)
                       for g in groups])
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(cfg, g.tokens, jnp.where(
                g.valid, g.positions, 0), cache.pages, g.page_tables)
            for g in groups]
    experts = {n: params["moe"][n] for n in EXPERTS} if (
        cfg.kernels and "moe" in params) else {}

    def leaves(stack: str, li):
        return {n: lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
                for n, w in params[stack].items() if n not in experts}

    # an FFN returns (h, `moe_ffn`'s two counts)
    def dense_mlp(h, li):
        with jax.named_scope("mlp"):
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

    def sliding_layer(j, carry, at):
        h, rings, touched = carry
        with jax.named_scope("attn"):
            lp = leaves("swa", at["swa"] + j)
            a, rings = window_attention(
                rms_norm(h, lp["attn_norm"], eps), lp, cfg, rings,
                at["swa"] + j, groups)
        h, n = expert_mlp(h + a, at["moe_s"] + j)
        return h, rings, touched + n

    def period(carry, at):
        h, kv, ki_pool, rings, walked = carry
        with jax.named_scope("attn"):
            lp = leaves("full", at["full"])
            a, kv, ki_pool, staged, n = full_attention(
                rms_norm(h, lp["attn_norm"], eps), lp, cfg, kv, ki_pool,
                at["full"], groups, works)
            h = h + a
        if "dense" not in params:
            h, touched = expert_mlp(h, at["ffn"])
        elif "moe" not in params:
            h, touched = dense_mlp(h, at["ffn"])
        else:
            h, touched = lax.cond(
                at["dense"], dense_mlp, expert_mlp, h, at["ffn"])
        if cfg.state_layers:  # the sliding layers up to the next full one
            h, rings, touched = lax.fori_loop(
                0, at["n_s"], lambda j, c: sliding_layer(j, c, at),
                (h, rings, touched))
        return (h, kv, ki_pool, rings,
                walked + jnp.concatenate([n, touched])), staged

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
    (h, (k_pool, v_pool), ki_pool, rings, walked), staged = lax.scan(
        period,
        (h, (cache.k, cache.v), cache.ki, (cache.ring, cache.ring_pe),
         cache.walked),
        {name: jnp.asarray(v, jnp.bool_ if name == "dense" else jnp.int32)
         for name, v in index.items()})
    if cfg.kernels:
        # every full layer's rows of the step ([F, B, T, ..], in layer
        # order), in one write a group and pool
        from dynamo_tpu.ops.kv_update import paged_write

        with jax.named_scope("attn"), jax.named_scope("kv_update"):
            for g, (c_st, pe_st, ki_st) in zip(groups, staged):
                k_pool, v_pool = paged_write(
                    k_pool, v_pool, c_st, pe_st, g.page_tables, g.positions,
                    g.valid)
                ki_pool = land_index_keys(
                    ki_pool, ki_st, g.page_tables, g.positions, g.valid)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), Dots3Cache(
        k=k_pool, v=v_pool, ki=ki_pool, ring=rings[0], ring_pe=rings[1],
        walked=walked)


def forward_hidden(params, cfg: Dots3Config, tokens, positions, valid,
                   cache, page_tables, state_rows, first_chunk: bool = False,
                   mesh=None):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh)
    return h, cache


def compute_logits(params: dict, cfg: Dots3Config, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        return (hidden @ params["lm_head"]).astype(jnp.float32)
