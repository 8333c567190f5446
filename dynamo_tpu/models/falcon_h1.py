"""Falcon-H1 (TII Falcon-H1-34B-Instruct, `model_type` falcon_h1): a dense
decoder whose every layer runs a Mamba-2 mixer AND rotary grouped-query
attention on the same normed input, sums them into the residual, and
follows with a SwiGLU MLP. Nine muP multipliers scale activations at run
time; the parameter tree keeps the published shapes and scales.

    e   = embed[ids] * embedding_multiplier
    x   = RMSNorm_in(h)
    m   = Mamba2(x * ssm_in_multiplier) * ssm_out_multiplier
    a   = Attn(x * attention_in_multiplier) * attention_out_multiplier
    h   = h + m + a
    y   = RMSNorm_ff(h)
    h   = h + down(up(y) * silu(gate(y) * mlp_multipliers[0]))
              * mlp_multipliers[1]
    logits = head(RMSNorm_final(h)) * lm_head_multiplier

- `Mamba2` is models/nemotron_h.py's mixer (`mamba_mixer`: in_proj to
  z | x | B | C | dt, causal conv with bias over x|B|C, SiLU, the
  recurrence per head, the gated norm gate first, out_proj), with
  `in_proj`'s OUTPUT multiplied column by column by `ssm_multipliers[0..4]`
  over its z, x, B, C and dt segments. d_inner is `mamba_d_ssm` = heads x
  head size, whatever `mamba_expand` says.
- `Attn`: `k = k_proj(x) * key_multiplier` BEFORE the rotary embedding
  (half-split, over the whole head, `rope_theta` 1e11, no scaling), causal
  softmax at 1 / sqrt(head_dim): models/llama.py `attention_block`, the
  page walk, the flash chunk and the staged cache write of every dense
  decoder here.

Every layer owns BOTH kinds of per-sequence state (ROADMAP D8): a layer
of a step reads the row's pages AND its state slot, stages K and V AND
writes the state's other generation. The cache is models/nemotron_h.py's
`HybridCache` with as many page layers as state layers. The layers are
alike, so the step is one `lax.scan` over them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.llama import (
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
from dynamo_tpu.models.nemotron_h import (
    HybridCache,
    Mamba2Dims,
    mamba_mixer,
    mamba_shapes,
)

#: the published multipliers of Falcon-H1-34B-Instruct (its config.json)
_MUP_34B = dict(
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
)
#: the nine multiplier keys of the published config
MULTIPLIERS = tuple(_MUP_34B)


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 256
    hidden_size: int = 64
    num_layers: int = 3
    intermediate_size: int = 96
    # -- attention ---------------------------------------------------------
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 1e11
    # -- Mamba-2 -----------------------------------------------------------
    mamba_num_heads: int = 4
    mamba_head_dim: int = 16
    ssm_state_size: int = 16
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 8
    rms_norm_eps: float = 1e-5
    # -- muP, applied at run time ------------------------------------------
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: over `in_proj`'s z, x, B, C and dt columns
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    #: (inside the gate's SiLU, after `down`)
    mlp_multipliers: tuple = (1.0, 1.0)
    dtype: Any = jnp.bfloat16
    #: "xla", or "pallas" / "hybrid": the attention kernels and the state
    #: kernels (ops/ssm_state.py)
    attention_impl: str = "xla"

    @property
    def state_layers(self) -> int:
        """Every layer keeps a state, and pages."""
        return self.num_layers

    @property
    def mamba(self) -> Mamba2Dims:
        return Mamba2Dims(
            self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size,
            self.n_groups, self.conv_kernel, self.chunk_size,
            self.rms_norm_eps, self.dtype,
            self.attention_impl in ("pallas", "hybrid"),
        )

    @property
    def attn_cfg(self) -> LlamaConfig:
        """The attention branch as models/llama.py sees it."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, rms_norm_eps=self.rms_norm_eps,
            dtype=self.dtype, attention_impl=self.attention_impl,
        )

    @property
    def in_proj_scale(self) -> jax.Array:
        """`ssm_multipliers` spread over `in_proj`'s columns, float32."""
        m = self.mamba
        bc = m.n_groups * m.state_size
        widths = (m.d_inner, m.d_inner, bc, bc, m.num_heads)
        return jnp.concatenate([
            jnp.full((w,), c, jnp.float32)
            for w, c in zip(widths, self.ssm_multipliers)
        ])

    @staticmethod
    def falcon_h1_34b(num_layers: int = 72) -> "FalconH1Config":
        """Falcon-H1-34B-Instruct as its config.json publishes it: 72
        layers, hidden 5120; 32 Mamba heads of 128 (`mamba_d_ssm` 4096)
        with state 256 in 2 groups, conv 4, chunk 128; 20 query and 4 KV
        heads of 128 at rope theta 1e11; MLP 21504; 261,120 ids."""
        return FalconH1Config(
            vocab_size=261120, hidden_size=5120, num_layers=num_layers,
            intermediate_size=21504, num_heads=20, num_kv_heads=4,
            head_dim=128, rope_theta=1e11, mamba_num_heads=32,
            mamba_head_dim=128, ssm_state_size=256, n_groups=2,
            conv_kernel=4, chunk_size=128, rms_norm_eps=1e-5, **_MUP_34B,
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "FalconH1Config":
        """Three layers at toy widths, 2 query heads a KV head, the
        published multipliers but `attention_in_multiplier`, published as
        1 and here 0.5, so that a test can miss each of the nine."""
        return FalconH1Config(
            vocab_size=vocab_size, dtype=jnp.float32,
            **{**_MUP_34B, "attention_in_multiplier": 0.5},
        )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: FalconH1Config) -> dict:
    h, i = cfg.hidden_size, cfg.intermediate_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    return {
        "norm": (h,), **mamba_shapes(cfg.mamba, h),
        "wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd), "wo": (qd, h),
        "mlp_norm": (h,), "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h),
    }


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, shape, dtype):
    """One fused draw: unjitted, the float32 draw of the 261,120-id head
    alone is 5.3 GB beside the 7.8 GB already drawn."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(key: jax.Array, cfg: FalconH1Config) -> dict:
    """Seeded weights at a TRAINED block's scale: a matrix that a
    multiplier `c` follows is drawn normal at `1 / (c sqrt(fan in))`, so
    that the block WITH its multipliers has unit-scale branches (the
    embedding at `1 / embedding_multiplier`, `in_proj`'s five column
    segments each by its own, the head at `1 / (lm_head_multiplier
    sqrt(hidden))`). Drawn at `1 / sqrt(fan in)` the logits would be flat
    to a part in a hundred and a comparison on them blind. The Mamba-2
    scalars as its reference initialises them (A in [1, 16), dt
    log-uniform in [1e-3, 1e-1) through the inverse softplus, D ones)."""
    counter = iter(range(1 << 30))

    def uniform(shape, lo, hi):
        k = jax.random.fold_in(key, next(counter))
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    def normal(shape, scale):
        """`scale`: a number, or one a column."""
        k = jax.random.fold_in(key, next(counter))
        return _normal(k, jnp.asarray(scale, jnp.float32), shape, cfg.dtype)

    def matrix(shape, after=1.0):
        return normal(shape, 1.0 / (after * math.sqrt(shape[0])))

    def one(name: str, shape):
        if name in ("norm", "mlp_norm", "gate_norm"):
            return jnp.ones(shape, cfg.dtype)
        if name == "D":
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(uniform(shape, 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(uniform(shape, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
        if name == "conv_w":
            return normal(shape, 1.0 / math.sqrt(cfg.conv_kernel))
        if name == "conv_b":
            return normal(shape, 0.02)
        after = {
            "in_proj": cfg.ssm_in_multiplier * cfg.in_proj_scale,
            "out_proj": cfg.ssm_out_multiplier,
            "wq": cfg.attention_in_multiplier,
            "wk": cfg.attention_in_multiplier * cfg.key_multiplier,
            "wv": cfg.attention_in_multiplier,
            "wo": cfg.attention_out_multiplier,
            "w_gate": cfg.mlp_multipliers[0],
            "w_down": cfg.mlp_multipliers[1],
        }.get(name, 1.0)
        return matrix(shape, after)

    return {
        "embed": normal((cfg.vocab_size, cfg.hidden_size),
                        1.0 / cfg.embedding_multiplier),
        "layers": {
            name: jnp.stack([one(name, shape)
                             for _ in range(cfg.num_layers)])
            for name, shape in _layer_shapes(cfg).items()
        },
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "lm_head": matrix((cfg.hidden_size, cfg.vocab_size),
                          cfg.lm_head_multiplier),
    }


def falcon_h1_logical_axes(cfg: FalconH1Config) -> dict:
    """Logical axis names (parallel/logical.py). Everything replicates
    but the head's vocabulary axis; a mesh is a later issue's (the
    adapter refuses one)."""
    from dynamo_tpu.parallel.logical import L

    return {
        "embed": L(), "final_norm": L(), "lm_head": L(None, "vocab"),
        "layers": {name: L() for name in _layer_shapes(cfg)},
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _times(x: jax.Array, c: float) -> jax.Array:
    """x * c in x's dtype; a multiplier published as 1 multiplies
    nothing."""
    return x if c == 1.0 else x * jnp.asarray(c, x.dtype)


def forward_groups(
    params: dict,
    cfg: FalconH1Config,
    groups,  # llama.StepGroup with `state_rows`, one or two
    cache: HybridCache,
    mesh=None,
):
    """models/llama.py's `forward_groups` for this family: one scan over
    the layers, the dense work of a layer on every group's rows together,
    both sequence mixers per group. Returns ([hidden [B_g, T_g, H] post
    final norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError("Falcon-H1 on a mesh is not implemented")
    if any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with state-space layers needs each row's state slot "
            "(StepGroup.state_rows)"
        )
    acfg, eps, dtype = cfg.attn_cfg, cfg.rms_norm_eps, cfg.dtype
    in_proj_scale = cfg.in_proj_scale
    with jax.named_scope("embed"):
        h = _times(
            join_rows([params["embed"][g.tokens].astype(dtype)
                       for g in groups]),
            cfg.embedding_multiplier,
        )
    with jax.named_scope("attn"):
        works = [
            maybe_decode_work(
                acfg, g.tokens, g.positions, cache.pages, g.page_tables
            )
            for g in groups
        ]

    def layer(carry, xs):
        h, kv, pools = carry
        lp, li = xs
        with jax.named_scope("attn"):
            x = rms_norm(h, lp["norm"], eps)
            m, *pools = mamba_mixer(
                _times(x, cfg.ssm_in_multiplier), lp, cfg.mamba, *pools, li,
                groups, in_proj_scale=in_proj_scale,
            )
            with jax.named_scope("qkv"):
                xa = _times(x, cfg.attention_in_multiplier)
                lead = xa.shape[:-1]
                q = _mm(xa, lp, "wq", dtype).reshape(
                    *lead, cfg.num_heads, cfg.head_dim)
                k = _times(_mm(xa, lp, "wk", dtype), cfg.key_multiplier)
                k = k.reshape(*lead, cfg.num_kv_heads, cfg.head_dim)
                v = _mm(xa, lp, "wv", dtype).reshape(
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
                a = _times(_mm(join_rows(attns), lp, "wo", dtype),
                           cfg.attention_out_multiplier)
                h = h + _times(m, cfg.ssm_out_multiplier) + a
        with jax.named_scope("mlp"):
            y = rms_norm(h, lp["mlp_norm"], eps)
            gate = jax.nn.silu(_times(
                _mm(y, lp, "w_gate", dtype).astype(jnp.float32),
                cfg.mlp_multipliers[0]))
            up = _mm(y, lp, "w_up", dtype).astype(jnp.float32)
            h = h + _times(
                _mm((gate * up).astype(dtype), lp, "w_down", dtype),
                cfg.mlp_multipliers[1])
        return (h, kv, tuple(pools)), tuple(staged)

    (h, kv, (conv, ssm)), staged = lax.scan(
        layer, (h, cache.pages, (cache.conv, cache.ssm)),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)),
    )
    with jax.named_scope("attn"), jax.named_scope("kv_update"):
        for g, st in zip(groups, staged):
            kv = land_staged_kv(kv, st, g.page_tables, g.positions, g.valid)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), HybridCache(
        k=kv.k, v=kv.v, conv=conv, ssm=ssm
    )


def forward_hidden(
    params, cfg: FalconH1Config, tokens, positions, valid, cache,
    page_tables, state_rows, first_chunk: bool = False, mesh=None,
):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh,
    )
    return h, cache


def compute_logits(params: dict, cfg: FalconH1Config, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        return (hidden @ params["lm_head"]).astype(jnp.float32) * jnp.float32(
            cfg.lm_head_multiplier)
