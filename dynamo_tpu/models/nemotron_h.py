"""Nemotron-H (NVIDIA Nemotron-3-Nano-30B-A3B, `model_type` nemotron_h):
a decoder whose every layer is ONE mixer behind a pre-RMSNorm and a
residual add, `x + mixer(RMSNorm(x))`, the kind of each layer given by a
pattern string: `M` a Mamba-2 layer, `*` grouped-query attention, `E`
sparse experts.

- `M`: `in_proj` (no bias) to z | xBC | dt; a causal depthwise conv of
  `conv_kernel` taps with bias over xBC, then SiLU; `dt = softplus(dt +
  dt_bias)`, `A = -exp(A_log)` a head; head h of group h // (heads /
  n_groups) keeps `S` in R^(head_dim x state):
  `S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t`, `y_t = S_t C_t + D x_t`;
  the gated norm, gate first, `RMSNorm_grouped(y * silu(z))`; `out_proj`.
  A prompt chunk runs the same recurrence in its chunked (SSD) form
  (`ssd_chunk_scan`), a decode step one token a row in a kernel
  (ops/ssm_state.py).
- `*`: GQA attention with no rotary embedding (position reaches the model
  through the state-space layers): models/llama.py `attention_block`
  under `LlamaConfig(use_rope=False)`, the page walk, the flash chunk and
  the staged cache write that every dense decoder here uses.
- `E`: a float32 router with sigmoid scores, selection on the scores plus
  a correction bias, the weights the uncorrected scores renormalised and
  scaled (models/mla.py `_gate`, its `noaux_tc` branch); routed experts
  that are UNGATED two-matrix MLPs `down(relu(up x)^2)` through the
  dropless sorted dispatch and grouped matmul of models/mla.py
  (`_routed_experts`), plus one shared expert of the same form.

Per-sequence state is of two kinds (ROADMAP D8): pages of a paged cache
for the attention layers, indexed through the page table, and for every
`M` layer a conv window and an SSM state in ONE slot a sequence
(`HybridCache`). A step reads a row's state at one entry of the slot
pool and writes it at another (`StepGroup.state_rows`), which is how a
dispatch launched ahead of its batch can be rolled back (docs/engine.md
"What still rolls back"). A row whose chunk starts at position 0 starts
from zeros whatever its slot holds; padding tokens do not advance the
state (`dt` 0) and the conv window keeps the last VALID tokens.

The layers are scanned by UNITS of the pattern (`segments`): the first
35 published layers are five repeats of `MEMEM*E`, so a step program
holds one unrolled unit inside a `lax.scan` over its repeats; what is
left of the pattern runs unrolled after it.

An expert layer may hold a SHARE of the routed experts (`experts_held`):
one chip of an expert-parallel deployment. It routes over all of them,
computes the assignments to the experts it holds and adds nothing for
the rest; no code stands in for the other chips or their exchange.
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
    attention_block,
    join_rows,
    land_staged_kv,
    maybe_decode_work,
    rms_norm,
    split_rows,
)
from dynamo_tpu.ops import ssm_state

PATTERN_NANO3 = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: lanes of a TPU tile. An expert's width is stored in whole lanes
#: (`expert_width`: 1856 is 14.5 of them, and a weight tile of the grouped
#: matmul is whole lanes, so the two matrices carry 64 columns / rows of
#: zeros; relu(0)^2 = 0 adds nothing), and so is a slot's conv window
LANE = 128


class Mamba2Dims(NamedTuple):
    """What the Mamba-2 mixer and its slot pools read of a configuration:
    every family with such a mixer provides one (`cfg.mamba`)."""

    num_heads: int
    head_dim: int
    state_size: int
    n_groups: int
    conv_kernel: int
    chunk_size: int
    rms_norm_eps: float
    dtype: Any
    #: the state kernels (ops/ssm_state.py) where there is a TPU
    kernels: bool

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.num_heads

    @property
    def conv_state_shape(self) -> tuple:
        """A slot's conv window, `conv_kernel - 1` rows of `conv_dim`, as
        the pool holds it: in rows of 128 lanes where that divides (a DMA
        moves whole (sublane, lane) tiles)."""
        n = (self.conv_kernel - 1) * self.conv_dim
        if n % LANE == 0:
            return (n // LANE, LANE)
        return (self.conv_kernel - 1, self.conv_dim)

    @property
    def ssm_state_shape(self) -> tuple:
        return (self.num_heads, self.head_dim, self.state_size)

    @property
    def slot_bytes(self) -> int:
        """One layer's state of one sequence, one generation (the conv
        window in the model dtype, the SSM state float32)."""
        conv = math.prod(self.conv_state_shape) * jnp.dtype(
            self.dtype).itemsize
        return conv + math.prod(self.ssm_state_shape) * 4


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    pattern: str = "ME*ME*ME"
    # -- attention ---------------------------------------------------------
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    # -- Mamba-2 -----------------------------------------------------------
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    ssm_state_size: int = 16
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 8
    # -- experts -----------------------------------------------------------
    n_routed_experts: int = 8
    #: (first, count): the routed experts this model HOLDS; None = all
    experts_held: Optional[tuple] = None
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 32
    moe_shared_expert_intermediate_size: int = 64
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    #: "xla", or "pallas" / "hybrid": the attention kernels and the state
    #: kernels (ops/ssm_state.py)
    attention_impl: str = "xla"

    # what models/mla.py's gate and experts read
    topk_method = "noaux_tc"
    expert_mlp = "relu2"
    tie_word_embeddings = False

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def kernels(self) -> bool:
        return self.attention_impl in ("pallas", "hybrid")

    @property
    def mamba(self) -> Mamba2Dims:
        return Mamba2Dims(
            self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size,
            self.n_groups, self.conv_kernel, self.chunk_size,
            self.rms_norm_eps, self.dtype, self.kernels,
        )

    @property
    def state_layers(self) -> int:
        return self.count("M")

    @property
    def experts_here(self) -> int:
        return (
            self.experts_held[1] if self.experts_held
            else self.n_routed_experts
        )

    @property
    def expert_width(self) -> int:
        """Columns of an expert's `up` as stored: whole lanes under the
        kernels' tiling, always (one parameter tree whatever the impl)."""
        w = self.moe_intermediate_size
        return w if w <= LANE else -(-w // LANE) * LANE

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def attn_cfg(self) -> LlamaConfig:
        """The attention layers as models/llama.py sees them."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.count("*"), num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            attention_impl=self.attention_impl, use_rope=False,
            prefill_history_kernel=False,
        )

    @property
    def segments(self) -> list:
        """[(unit pattern, repeats)]: the longest run of whole repeats of
        a prefix of the pattern, then what is left, unrolled."""
        p = self.pattern
        best = (len(p), 1)
        for u in range(1, len(p) // 2 + 1):
            r = 1
            while p[: u * (r + 1)] == p[:u] * (r + 1):
                r += 1
            if r >= 2 and u * r > (best[0] * best[1] if best[1] > 1 else 0):
                best = (u, r)
        u, r = best
        out = [(p[:u], r)]
        if p[u * r:]:
            out.append((p[u * r:], 1))
        return out

    @staticmethod
    def nemotron3_nano(
        pattern: str = PATTERN_NANO3, experts_held: Optional[tuple] = None
    ) -> "NemotronHConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B as its config.json publishes
        it: 52 layers (23 M, 23 E, 6 *), hidden 2688, 64 Mamba heads of
        64 with state 128 in 8 groups, conv 4, chunk 128; 32 query and 2
        KV heads of 128; 128 routed experts of 1856 top-6 scaled 2.5 and
        one shared expert of 3712; 131,072 ids."""
        return NemotronHConfig(
            vocab_size=131072, hidden_size=2688, pattern=pattern,
            num_heads=32, num_kv_heads=2, head_dim=128,
            mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
            n_groups=8, conv_kernel=4, chunk_size=128,
            n_routed_experts=128, experts_held=experts_held,
            num_experts_per_tok=6, moe_intermediate_size=1856,
            moe_shared_expert_intermediate_size=3712,
            routed_scaling_factor=2.5, norm_topk_prob=True,
            rms_norm_eps=1e-5,
        )

    @staticmethod
    def nemotron3_nano_1chip() -> "NemotronHConfig":
        """One chip's part of an 8-chip deployment (chipbench/configs/
        nemotron3-nano-30b-a3b-1chip.json): the first four repeats of
        `MEMEM*E` (28 of 52 layers) and 16 of the 128 routed experts of
        every expert layer; every width as published."""
        return NemotronHConfig.nemotron3_nano(
            pattern=PATTERN_NANO3[:28], experts_held=(0, 16)
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "NemotronHConfig":
        """Every kind of layer, a scanned unit and an unrolled tail,
        2 KV heads, 8 experts of which half are held."""
        return NemotronHConfig(
            vocab_size=vocab_size, dtype=jnp.float32, experts_held=(2, 4)
        )


class HybridCache(NamedTuple):
    """The two kinds of per-sequence state: `k`, `v` the attention layers'
    pages (models/llama.py KVPages' layout, one layer axis entry an
    attention layer), `conv` and `ssm` the state-space layers' slot pools
    (ops/ssm_state.py: [M layers, entries, ...]; entry = generation *
    (slots + 1) + slot, slot 0 the null slot). `walked` is this family's
    running count on the device (`ModelAdapter.walk_pages`), int32 [6]
    laid out as models/dots3.py's: only the sixth is counted here, the
    passes over a share's assignments beyond an expert layer's first
    (`mla._routed_experts`); models/falcon_h1.py keeps none."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # never set: no quantised pages
    v_scale: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None
    walked: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return False

    @property
    def pages(self) -> KVPages:
        return KVPages(k=self.k, v=self.v)


def walk_count(cache: HybridCache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def state_bytes_per_slot(cfg) -> int:
    """Bytes one GENERATION of one sequence's state takes over all the
    layers that keep one. `cfg` is any family's configuration with a
    Mamba-2 mixer (`mamba`, `state_layers`, `attn_cfg`)."""
    return cfg.state_layers * cfg.mamba.slot_bytes


def init_cache(
    cfg, num_pages: int, page_size: int, state_slots: int
) -> HybridCache:
    """`state_slots` sequences' state besides the null slot, two
    generations each."""
    a, m = cfg.attn_cfg, cfg.mamba
    page = (a.num_layers, num_pages, page_size, a.num_kv_heads,
            a.kv_head_dim)
    entries = 2 * (state_slots + 1)
    nm = cfg.state_layers
    return HybridCache(
        k=jnp.zeros(page, cfg.dtype), v=jnp.zeros(page, cfg.dtype),
        conv=jnp.zeros((nm, entries, *m.conv_state_shape), cfg.dtype),
        ssm=jnp.zeros((nm, entries, *m.ssm_state_shape), jnp.float32),
        walked=(jnp.zeros((6,), jnp.int32)
                if isinstance(cfg, NemotronHConfig) else None),
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def mamba_shapes(m: Mamba2Dims, hidden: int) -> dict:
    """The mixer's own leaves, a layer."""
    return {
        "in_proj": (hidden, m.in_proj_dim),
        "conv_w": (m.conv_kernel, m.conv_dim),
        "conv_b": (m.conv_dim,), "dt_bias": (m.num_heads,),
        "A_log": (m.num_heads,), "D": (m.num_heads,),
        "gate_norm": (m.d_inner,), "out_proj": (m.d_inner, hidden),
    }


def _shapes(cfg: NemotronHConfig) -> dict:
    h, e = cfg.hidden_size, cfg.experts_here
    w, sw = cfg.expert_width, cfg.moe_shared_expert_intermediate_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    return {
        "mamba": {"norm": (h,), **mamba_shapes(cfg.mamba, h)},
        "attn": {
            "norm": (h,), "wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd),
            "wo": (qd, h),
        },
        "moe": {
            "norm": (h,), "w_router": (h, cfg.n_routed_experts),
            "router_bias": (cfg.n_routed_experts,),
            "we_up": (e, h, w), "we_down": (e, w, h),
            "ws_up": (h, sw), "ws_down": (sw, h),
        },
    }


_KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
#: float32 leaves (the rest take the model dtype)
_F32 = ("dt_bias", "A_log", "D", "router_bias", "w_router")


def init_params(key: jax.Array, cfg: NemotronHConfig) -> dict:
    """Seeded weights: matrices normal at 1/sqrt(fan in); the Mamba-2
    scalars as its reference initialises them (A in [1, 16), dt
    log-uniform in [1e-3, 1e-1) through the inverse softplus, D ones)."""
    counter = iter(range(1 << 30))

    def rnd(shape, scale=None, dtype=None, lo=None, hi=None):
        k = jax.random.fold_in(key, next(counter))
        if lo is not None:
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)
        scale = 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else shape[0]) \
            if scale is None else scale
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            dtype or cfg.dtype
        )

    def one(kind: str, name: str, shape):
        if name in ("norm", "gate_norm"):
            return jnp.ones(shape, cfg.dtype)
        if name == "D":
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(rnd(shape, lo=1.0, hi=16.0))
        if name == "dt_bias":
            dt = jnp.exp(rnd(shape, lo=math.log(1e-3), hi=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        if name == "conv_w":
            return rnd(shape, scale=1.0 / math.sqrt(cfg.conv_kernel))
        if name == "conv_b":
            return rnd(shape, scale=0.02)
        if name == "router_bias":
            return rnd(shape, scale=0.05, dtype=jnp.float32)
        if name == "w_router":
            return rnd(shape, dtype=jnp.float32)
        if name in ("we_up", "we_down"):
            e, rows, cols = shape
            live = cfg.moe_intermediate_size
            w = jnp.stack([
                rnd((rows if name == "we_up" else live,
                     live if name == "we_up" else cols))
                for _ in range(e)
            ])
            pad = cfg.expert_width - live  # zeros past the published width
            if name == "we_up":
                return jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
            return jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
        return rnd(shape)

    params = {"embed": rnd((cfg.vocab_size, cfg.hidden_size))}
    for sym, kind in _KINDS.items():
        n = cfg.count(sym)
        params[kind] = {
            name: jnp.stack([one(kind, name, shape) for _ in range(n)])
            for name, shape in _shapes(cfg)[kind].items()
        } if n else {}
    params["final_norm"] = jnp.ones((cfg.hidden_size,), cfg.dtype)
    params["lm_head"] = rnd((cfg.hidden_size, cfg.vocab_size))
    return params


def nemotron_h_logical_axes(cfg: NemotronHConfig) -> dict:
    """Logical axis names (parallel/logical.py). Everything replicates
    but the head's vocabulary axis and the experts' own axis; a mesh is a
    later issue's (the adapter refuses one)."""
    from dynamo_tpu.parallel.logical import L

    axes = {"embed": L(), "final_norm": L(), "lm_head": L(None, "vocab")}
    for sym, kind in _KINDS.items():
        axes[kind] = {
            name: (L("layers", "expert", None, None)
                   if name in ("we_up", "we_down") else L())
            for name in _shapes(cfg)[kind]
        } if cfg.count(sym) else {}
    return axes


# ---------------------------------------------------------------------------
# The Mamba-2 mixer
# ---------------------------------------------------------------------------


def ssd_chunk_scan(x, dt, a_head, bmat, cmat, s0, chunk: int):
    """The recurrence over a chunk of tokens in its chunked (SSD) form.
    x [B, T, H, P], dt [B, T, H] f32 (0 where a token is padding), a_head
    [H] f32 (negative), bmat and cmat [B, T, G, N], s0 [B, H, P, N] f32.
    Returns (y [B, T, H, P] f32 without the skip term, the state after
    the last token [B, H, P, N] f32). Matmul operands go in x's dtype,
    sums, decays and the state are float32. T is split into chunks of
    `chunk` tokens: inside a chunk the quadratic form (a masked product
    of C B^T with the decays), between chunks the state."""
    f32, dtype = jnp.float32, x.dtype
    b, t, h, p = x.shape
    g, n = bmat.shape[2], bmat.shape[3]
    q = min(chunk, t)
    t_in = t
    if t % q:  # whole SSD chunks: pad with tokens of dt 0, which do nothing
        pad = ((0, 0), (0, -t % q))
        x = jnp.pad(x, pad + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, pad + ((0, 0),))
        bmat = jnp.pad(bmat, pad + ((0, 0), (0, 0)))
        cmat = jnp.pad(cmat, pad + ((0, 0), (0, 0)))
        t = x.shape[1]
    nc, k = t // q, h // g
    a = (dt * a_head).reshape(b, nc, q, h)  # [B, c, q, H], <= 0
    acs = jnp.cumsum(a, axis=2)
    xdt = (x.astype(f32) * dt[..., None]).reshape(b, nc, q, g, k, p)
    bc = bmat.reshape(b, nc, q, g, n)
    cc = cmat.reshape(b, nc, q, g, n)
    # inside a chunk: y[l] += sum_{s<=l} (C_l . B_s) exp(acs_l - acs_s) xdt_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc, preferred_element_type=f32)
    seg = acs[:, :, :, None, :] - acs[:, :, None, :, :]  # [B, c, l, s, H]
    causal = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    w = cb[:, :, :, None] * decay.transpose(0, 1, 4, 2, 3).reshape(
        b, nc, g, k, q, q
    )  # [B, c, G, k, l, s]
    y = jnp.einsum(
        "bcgkls,bcsgkp->bclgkp", w.astype(dtype), xdt.astype(dtype),
        preferred_element_type=f32,
    )
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(acs[:, :, -1:, :] - acs).reshape(b, nc, q, g, k)
    states = jnp.einsum(
        "bcsgkp,bcsgn->bcgkpn", (xdt * to_end[..., None]).astype(dtype), bc,
        preferred_element_type=f32,
    )
    chunk_decay = jnp.exp(acs[:, :, -1, :]).reshape(b, nc, g, k)
    s = s0.reshape(b, g, k, p, n)
    before = []
    for c in range(nc):
        before.append(s)
        s = s * chunk_decay[:, c, :, :, None, None] + states[:, c]
    # the state a chunk starts from, read by every token of the chunk
    y = y + jnp.einsum(
        "bclgn,bcgkpn->bclgkp", cc.astype(f32), jnp.stack(before, axis=1),
        preferred_element_type=f32,
    ) * jnp.exp(acs).reshape(b, nc, q, g, k)[..., None]
    return y.reshape(b, t, h, p)[:, :t_in], s.reshape(b, h, p, n)


def ssm_recurrence(x, dt, a_head, bmat, cmat, s0):
    """The same recurrence token by token (`lax.scan`), float32: what
    `ssd_chunk_scan` is tested against."""
    f32 = jnp.float32
    k = x.shape[2] // bmat.shape[2]

    def step(s, xs):
        xt, dtt, bt, ct = xs  # [B,H,P], [B,H], [B,G,N], [B,G,N]
        bh, ch = jnp.repeat(bt, k, axis=1), jnp.repeat(ct, k, axis=1)
        s = (s * jnp.exp(dtt * a_head)[..., None, None]
             + (xt * dtt[..., None])[..., None] * bh[:, :, None, :])
        return s, jnp.sum(s * ch[:, :, None, :], axis=-1)

    s, ys = lax.scan(
        step, s0.astype(f32),
        tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, bmat, cmat)),
    )
    return jnp.moveaxis(ys, 0, 1), s


def _conv_and_state(xbc, prev, n_valid, lp, cfg: Mamba2Dims):
    """The causal depthwise conv over a chunk that continues `prev` (the
    window the sequence left: [B, K-1, C]), then SiLU; and the window it
    leaves, the last K-1 rows before the first padding token."""
    kk = cfg.conv_kernel
    full = jnp.concatenate([prev.astype(xbc.dtype), xbc], axis=1)
    t = xbc.shape[1]
    w = lp["conv_w"].astype(jnp.float32)
    out = lp["conv_b"].astype(jnp.float32) + sum(
        full[:, i : i + t].astype(jnp.float32) * w[i] for i in range(kk)
    )
    window = jax.vmap(
        lambda f, n: lax.dynamic_slice_in_dim(f, n, kk - 1, axis=0)
    )(full, n_valid)
    return jax.nn.silu(out).astype(xbc.dtype), window


def mamba_mixer(
    x: jax.Array,  # the groups' rows (join_rows), post-norm
    lp: dict,
    cfg: Mamba2Dims,
    conv_pool: jax.Array,
    ssm_pool: jax.Array,
    layer,  # this layer's index among the layers that keep a state
    groups,
    in_proj_scale: Optional[jax.Array] = None,  # [in_proj_dim]
):
    """Returns (out shaped like x, conv_pool, ssm_pool). The projections,
    the gate and the norm run on every group's rows at once; the conv and
    the recurrence per group, each row from its own slot. `in_proj_scale`
    multiplies `in_proj`'s OUTPUT column by column (models/falcon_h1.py's
    `ssm_multipliers` over the z, x, B, C and dt segments). Scopes, under
    the caller's `attn` (the layer's sequence mixer): `ssm/in_proj`,
    `ssm/conv`, `ssm/scan`, `ssm/gate_norm`, `ssm/out`."""
    f32 = jnp.float32
    h_, p_, n_, g_ = (cfg.num_heads, cfg.head_dim, cfg.state_size,
                      cfg.n_groups)
    di = cfg.d_inner
    use_kernel = None if cfg.kernels else False  # None: on a TPU
    with jax.named_scope("ssm"):
        with jax.named_scope("in_proj"):
            zxbcdt = _mm(x, lp, "in_proj", cfg.dtype)
            if in_proj_scale is not None:
                zxbcdt = zxbcdt * in_proj_scale.astype(zxbcdt.dtype)
            z = zxbcdt[..., :di]
            xbc = zxbcdt[..., di : di + cfg.conv_dim]
            dt_raw = zxbcdt[..., di + cfg.conv_dim :]
        a_head = -jnp.exp(lp["A_log"].astype(f32))
        ys = []
        for g, xbc_g, dt_g in zip(
            groups, split_rows(xbc, groups), split_rows(dt_raw, groups)
        ):
            b, t = g.tokens.shape
            ridx, widx = g.state_rows[:, 0], g.state_rows[:, 1]
            fresh = g.positions[:, 0] == 0  # starts from zeros
            n_valid = jnp.sum(g.valid, axis=1).astype(jnp.int32)
            with jax.named_scope("conv"):
                prev = ssm_state.read_rows(conv_pool, layer, ridx).reshape(
                    b, cfg.conv_kernel - 1, cfg.conv_dim
                )
                prev = jnp.where(fresh[:, None, None], 0, prev)
                xbc_g, window = _conv_and_state(xbc_g, prev, n_valid, lp, cfg)
                conv_pool = ssm_state.write_rows(
                    conv_pool, layer, widx,
                    window.reshape(b, *cfg.conv_state_shape),
                    use_kernel=use_kernel,
                )
            with jax.named_scope("scan"):
                xs = xbc_g[..., :di].reshape(b, t, h_, p_)
                bmat = xbc_g[..., di : di + g_ * n_].reshape(b, t, g_, n_)
                cmat = xbc_g[..., di + g_ * n_ :].reshape(b, t, g_, n_)
                dt = jax.nn.softplus(
                    dt_g.astype(f32) + lp["dt_bias"].astype(f32)
                ) * g.valid[..., None]
                if t == 1:
                    keep = jnp.where(fresh, 0.0, 1.0)[:, None]
                    y, ssm_pool = ssm_state.ssm_decode_step(
                        ssm_pool, layer, ridx, widx,
                        xs[:, 0].astype(f32) * dt[:, 0, :, None],
                        jnp.exp(dt[:, 0] * a_head) * keep,
                        bmat[:, 0], cmat[:, 0], use_kernel=use_kernel,
                    )
                    y = y[:, None]
                else:
                    s0 = ssm_state.read_rows(
                        ssm_pool, layer, ridx, use_kernel=use_kernel
                    )
                    s0 = jnp.where(fresh[:, None, None, None], 0.0, s0)
                    y, s_end = ssd_chunk_scan(
                        xs, dt, a_head, bmat, cmat, s0, cfg.chunk_size
                    )
                    ssm_pool = ssm_state.write_rows(
                        ssm_pool, layer, widx, s_end,
                        use_kernel=use_kernel,
                    )
                y = y + lp["D"].astype(f32)[:, None] * xs.astype(f32)
                ys.append(y.reshape(b, t, di))
        with jax.named_scope("gate_norm"):
            y = join_rows(ys) * jax.nn.silu(z.astype(f32))
            lead = y.shape[:-1]
            y = y.reshape(*lead, g_, di // g_)
            y = y * lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps
            )
            y = (
                y.reshape(*lead, di) * lp["gate_norm"].astype(f32)
            ).astype(cfg.dtype)
        with jax.named_scope("out"):
            return _mm(y, lp, "out_proj", cfg.dtype), conv_pool, ssm_pool


# ---------------------------------------------------------------------------
# Experts
# ---------------------------------------------------------------------------


def _relu2(x):
    x = jnp.maximum(x.astype(jnp.float32), 0.0)
    return x * x


def moe_ffn(x, lp, cfg: NemotronHConfig, mesh=None, stack=None):
    """(out, int32: the passes over a share's assignments beyond the
    first, `mla._routed_experts`). Names its scopes from the top
    (`mlp/moe/route`, `mlp/moe/experts`, `mlp/moe/shared`, as
    models/mla.py's expert layer): the caller stands under none, for the
    sake of the share's loop."""
    xf = x.reshape(-1, x.shape[-1])
    with jax.named_scope(mla_mod.MOE_SCOPE + "route"):
        topw, topi = mla_mod._gate(
            xf, lp, cfg, precision=lax.Precision.HIGHEST
        )
    routed, extra = mla_mod._routed_experts(
        xf, topw, topi, lp, cfg, mesh, stack, held=cfg.experts_held,
        scope=mla_mod.MOE_SCOPE,
    )
    with jax.named_scope(mla_mod.MOE_SCOPE + "shared"):
        shared = _mm(
            _relu2(_mm(xf, lp, "ws_up", cfg.dtype)).astype(cfg.dtype),
            lp, "ws_down", cfg.dtype,
        )
    with jax.named_scope("mlp"):
        return (routed.astype(cfg.dtype) + shared).reshape(x.shape), extra


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def forward_groups(
    params: dict,
    cfg: NemotronHConfig,
    groups,  # llama.StepGroup with `state_rows`, one or two
    cache: HybridCache,
    mesh=None,
):
    """models/llama.py's `forward_groups` for this family: one pass over
    the layers, the dense work of a layer on every group's rows together,
    the sequence mixers per group. Returns ([hidden [B_g, T_g, H] post
    final norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError(
            "Nemotron-H on a mesh (experts over chips) is not implemented"
        )
    if any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with state-space layers needs each row's state slot "
            "(StepGroup.state_rows)"
        )
    acfg = cfg.attn_cfg
    eps = cfg.rms_norm_eps
    with jax.named_scope("embed"):
        h = join_rows(
            [params["embed"][g.tokens].astype(cfg.dtype) for g in groups]
        )
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(
                acfg, g.tokens, g.positions, cache.pages, g.page_tables
            )
            for g in groups
        ]
    # the expert matrices stay whole: the grouped matmul reads a layer of
    # the stack in place (ops/grouped_matmul.py)
    experts = {
        n: w for n, w in params["moe"].items() if n in ("we_up", "we_down")
    }

    def mamba_layer(h, pools, lp, li):
        with jax.named_scope("attn"):
            x = rms_norm(h, lp["norm"], eps)
            out, *pools = mamba_mixer(x, lp, cfg.mamba, *pools, li, groups)
        return h + out, tuple(pools)

    def attn_layer(h, kv, lp, li):
        with jax.named_scope("attn"):
            with jax.named_scope("qkv"):
                x = rms_norm(h, lp["norm"], eps)
                lead = x.shape[:-1]
                q = _mm(x, lp, "wq", cfg.dtype).reshape(
                    *lead, cfg.num_heads, cfg.head_dim)
                k = _mm(x, lp, "wk", cfg.dtype).reshape(
                    *lead, cfg.num_kv_heads, cfg.head_dim)
                v = _mm(x, lp, "wv", cfg.dtype).reshape(
                    *lead, cfg.num_kv_heads, cfg.head_dim)
            attns, staged = [], []
            for g, work, qg, kg, vg in zip(
                groups, works, *(split_rows(a, groups) for a in (q, k, v))
            ):
                attn, kv, st = attention_block(
                    qg, kg, vg, kv, li, g.page_tables, g.positions, g.valid,
                    acfg, first_chunk=g.first_chunk, decode_work=work,
                )
                attns.append(attn)
                staged.append(st)
            with jax.named_scope("out"):
                h = h + _mm(join_rows(attns), lp, "wo", cfg.dtype)
        return h, kv, tuple(staged)

    def moe_layer(h, lp, li):
        with jax.named_scope("mlp"):
            x = rms_norm(h, lp["norm"], eps)
        y, extra = moe_ffn(x, lp, cfg, None, (experts, li))
        with jax.named_scope("mlp"):
            return h + y, extra

    def layer_params(kind: str, li):
        """Layer `li`'s own leaves of a kind's stack. The stacks stay
        whole (closed over, not scanned over): a matmul reads its layer's
        slice in place, where a scan over [repeats, layers of the unit,
        ...] copied each unit's slice out first (0.2 GB a Mamba-2 layer's
        in_proj, three times a unit: 6.4 of a decode step's 22 ms in the
        first traced run, PERF.md 6)."""
        return {
            n: lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
            for n, w in params[kind].items() if n not in experts
        }

    def unit(pattern: str):
        """One pass over the layers of `pattern`; `base[sym]` is the
        index, among its kind, of the unit's first layer of that kind."""

        def body(carry, base):
            h, kv, pools, extra = carry
            seen = {"M": 0, "*": 0, "E": 0}
            staged = []
            for sym in pattern:
                li = base[sym] + seen[sym]
                seen[sym] += 1
                lp = layer_params(_KINDS[sym], li)
                if sym == "M":
                    h, pools = mamba_layer(h, pools, lp, li)
                elif sym == "*":
                    h, kv, st = attn_layer(h, kv, lp, li)
                    staged.append(st)
                else:
                    h, n = moe_layer(h, lp, li)
                    extra = extra + n
            return (h, kv, pools, extra), tuple(staged)

        return body

    carry = (h, cache.pages, (cache.conv, cache.ssm), jnp.int32(0))
    staged_all = []
    done = {"M": 0, "*": 0, "E": 0}
    for pattern, reps in cfg.segments:
        per = {s: pattern.count(s) for s in _KINDS}
        if reps == 1:
            carry, st = unit(pattern)(carry, dict(done))
            st = jax.tree.map(lambda a: a[None], st)
        else:
            j = jnp.arange(reps, dtype=jnp.int32)
            carry, st = lax.scan(
                unit(pattern), carry,
                {s: done[s] + j * per[s] for s in _KINDS},
            )
        if per["*"] and st and st[0][0] is not None:
            staged_all.append(st)  # [reps][a layer of the unit][group]
        for s in done:
            done[s] += reps * per[s]
    h, kv, (conv, ssm), extra = carry
    if staged_all:
        # every attention layer's rows of the step, in layer order, in
        # one write a group
        with jax.named_scope("attn"), jax.named_scope("kv_update"):
            for gi, g in enumerate(groups):
                ks, vs = (
                    jnp.concatenate([
                        jnp.stack([a[gi][j] for a in seg], axis=1).reshape(
                            -1, *seg[0][gi][j].shape[1:])
                        for seg in staged_all
                    ])
                    for j in (0, 1)
                )
                kv = land_staged_kv(
                    kv, (ks, vs), g.page_tables, g.positions, g.valid
                )
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), HybridCache(
        k=kv.k, v=kv.v, conv=conv, ssm=ssm,
        walked=cache.walked.at[5].add(extra),
    )


def forward_hidden(
    params, cfg: NemotronHConfig, tokens, positions, valid, cache,
    page_tables, state_rows, first_chunk: bool = False, mesh=None,
):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh,
    )
    return h, cache


def compute_logits(params: dict, cfg: NemotronHConfig, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        return (hidden @ params["lm_head"]).astype(jnp.float32)


def forward(params, cfg, tokens, positions, valid, cache, page_tables,
            state_rows):
    h, cache = forward_hidden(
        params, cfg, tokens, positions, valid, cache, page_tables, state_rows
    )
    return compute_logits(params, cfg, h), cache
