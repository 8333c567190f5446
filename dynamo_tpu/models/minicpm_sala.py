"""MiniCPM-SALA (openbmb MiniCPM-SALA 9B, `model_type` minicpm_sala): a
dense decoder whose every layer is ONE sequence mixer and a SwiGLU MLP,
the mixer of each layer named by `mixer_types`: `minicpm4` block-sparse
softmax attention (InfLLM-v2's dense-sparse switchable attention), or
`lightning-attn` linear attention with a decayed state. MiniCPM's muP
scalars act at run time:

    h   = embed[ids] * scale_emb
    h   = h + r * Mixer(RMSNorm(h))
    h   = h + r * down(silu(gate(y)) * up(y)),  y = RMSNorm(h)
    r   = scale_depth / sqrt(mup_denominator)   (the PUBLISHED depth)
    logits = head(RMSNorm(h) / (hidden / dim_model_base))

- `lightning-attn`: q, k, v projected to `lightning_nh` heads of
  `lightning_head_dim`; RMSNorm over the head dimension on q and k;
  rotary on q and k; per head `S_t = lambda S_(t-1) + k_t^T v_t`, `o_t =
  (q_t / sqrt(d)) S_t`, `lambda_h = exp(-2^(-8 (h + 1) / heads) * (1 - l /
  (L - 1) + 1e-5))` with the PUBLISHED layer index `l` of `L` layers;
  RMSNorm over the head dimension on o; `o * sigmoid(W_z x)`; `W_o`. The
  recurrence IS Mamba-2's with one group a head, `dt` 1 and a constant
  `A`: a decode step is `ops/ssm_state.ssm_decode_step` (u = v, B = k, C
  = q / sqrt(d)), a prompt chunk `models/nemotron_h.ssd_chunk_scan`, the
  state one entry of the slot pool that Nemotron-H and Falcon-H1 keep
  theirs in, [layers, entries, heads, d (of v), d (of k)] float32.
- `minicpm4`: q (`num_attention_heads`), k, v (`num_key_value_heads`)
  projected, RMSNorm over the head dimension on q and k, NO rotary,
  scores at 1 / sqrt(head_dim) over the keys ops/sparse_select.py's rule
  selects for the query's position and KV head (all of them under
  `dense_len` tokens of context), `o * sigmoid(W_z x)`, `W_o`. Its cache
  holds a KV head as a row of its own ([L, P * Hkv, S, 1, D]) and the
  compressed keys beside the pages: `SalaCache`.

A decode step selects inside the walk (scope `attn/select`: the kernel of
ops/block_scores.py scores the sequence's compressed keys out of the pool
in place, `ss.blocks_of_scores` counts out the top blocks, `ss.decode_lists`
lays out the list; then the page walk over it, `attn/paged`). A prompt
chunk's queries are scored by the same kernel, a tile at a time; it attends
by TILE of 128 queries in a kernel of its own (ops/sparse_chunk.py
`sparse_chunk_attention`, scope `attn/flash`; the GQA chunk kernels cannot
slice one KV head of a tiled page): the cached pages ANY query of the tile
chose are read once, a block of pages a turn, a mask bit a (query, page)
says whose they are, and the chunk's own keys take the first turns of the
same online softmax. A query that has not reached `dense_len` chooses
every page up to its own: every bit.
(Without the kernels, `attention_impl` "xla": a gathered copy of the
compressed keys, `ss.select_blocks`' sorts, skipped by a `lax.cond` where
no query of the step has reached `dense_len`, and dense scores under each
query's block mask, `sparse_select.masked_attention`.)

The layers are held in TWO stacks, one a kind, each in layer order
(`params["sparse"]`, `params["lightning"]`), and a step program holds each
kind's body ONCE: a scan over the sparse layers, and after each sparse
layer a loop over the lightning layers that follow it (`cfg.blocks`: 6, 0,
4 and 2 of them in the 16 published layers 9-24), a layer's weights read in
place from its stack. A scan a RUN of one kind (six of them there) made six
bodies a program, and a program's first call cost 3.5-7 s from the compile
cache and 10-27 s without (PERF.md 6, PR 41).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models.llama import (
    KVPages,
    LlamaConfig,
    StepGroup,
    _mm,
    apply_rope,
    attention_block,
    join_rows,
    land_staged_kv,
    paged_scatter_kv,
    rms_norm,
    split_rows,
)
from dynamo_tpu.models.nemotron_h import ssd_chunk_scan
from dynamo_tpu.ops import sparse_select as ss
from dynamo_tpu.ops import ssm_state
from dynamo_tpu.ops.block_scores import paged_block_scores
from dynamo_tpu.ops.sparse_select import SparseDims

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
#: `mixer_types` of the published config.json, layers 0-31
MIXERS_9B = tuple(
    SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31) else LIGHTNING
    for i in range(32)
)
#: float32 scores over the compressed keys that one call of the selection
#: may hold (`sparse_attention`): a prompt step selects its rows in groups
SELECT_BYTES = 96 << 20
#: `ModelAdapter.step_twins`: a step program of this family holds eight
#: Pallas kernels in two layer bodies and costs 6-7 s to load from the
#: compile cache, 15-19 s to compile (PERF.md 6, PR 41), so the engine
#: keeps one program a shape: no step here reads `StepGroup.first_chunk`
#: (a sparse chunk's history is its tiles' page lists, a lightning chunk
#: starts from its slot), and the head over a chunk's last rows costs nothing
#: beside the decode rows'
STEP_TWINS = False
#: the muP scalars a test can miss
SCALARS = ("scale_emb", "scale_depth", "dim_model_base", "mup_denominator")


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 96
    #: the mixer of each layer HELD, and its index in the published model
    #: (a lightning layer's decay reads it)
    mixer_types: tuple = (SPARSE, LIGHTNING, LIGHTNING, SPARSE)
    layer_indices: tuple = (0, 1, 2, 3)
    # -- minicpm4 ------------------------------------------------------------
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    sparse: SparseDims = SparseDims()
    # -- lightning-attn ------------------------------------------------------
    lightning_heads: int = 4
    lightning_head_dim: int = 16
    rope_theta: float = 10000.0
    #: tokens a chunk of the chunked scan holds
    chunk_size: int = 128
    rms_norm_eps: float = 1e-6
    # -- muP, applied at run time --------------------------------------------
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    #: the published depth: the residual scale and the decay read it,
    #: whatever number of layers is held
    mup_denominator: int = 32
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"

    def __post_init__(self):
        if len(self.mixer_types) != len(self.layer_indices):
            raise ValueError("one published index a layer held")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def sparse_layers(self) -> int:
        return sum(m == SPARSE for m in self.mixer_types)

    @property
    def state_layers(self) -> int:
        """The lightning layers: each keeps a state a sequence."""
        return self.num_layers - self.sparse_layers

    @property
    def blocks(self) -> tuple:
        """The layer order as the step programs walk it: the lightning
        layers ahead of the first sparse one, then the lightning layers
        that follow each sparse layer."""
        out = [0]
        for kind in self.mixer_types:
            if kind == SPARSE:
                out.append(0)
            else:
                out[-1] += 1
        return tuple(out)

    def published(self, kind: str) -> tuple:
        """The published indices of the layers of one kind, in order."""
        return tuple(i for m, i in zip(self.mixer_types, self.layer_indices)
                     if m == kind)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def attn_cfg(self) -> LlamaConfig:
        """ONE KV head of a sparse layer as models/llama.py sees it: its
        query heads over a one-row cache, no rotary."""
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.sparse_layers,
            num_heads=self.num_heads // self.num_kv_heads, num_kv_heads=1,
            head_dim=self.head_dim, use_rope=False,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            attention_impl=self.attention_impl,
        )

    @property
    def rope_cfg(self) -> LlamaConfig:
        return LlamaConfig(head_dim=self.lightning_head_dim,
                           rope_theta=self.rope_theta)

    def log_decay(self, published_index: int) -> jax.Array:
        """log lambda of each head of a lightning layer, float32."""
        h = jnp.arange(1, self.lightning_heads + 1, dtype=jnp.float32)
        slope = jnp.exp2(-8.0 * h / self.lightning_heads)
        return -slope * jnp.float32(
            1.0 - published_index / (self.mup_denominator - 1) + 1e-5)

    @staticmethod
    def minicpm_sala_9b(layers=range(32)) -> "MiniCPMSALAConfig":
        """MiniCPM-SALA as its config.json publishes it (hidden 4096, 32
        query / 2 KV heads of 128, lightning 32 / 32 of 128, MLP 16,384,
        73,448 ids), MiniCPM4's `sparse_config` (chipbench/configs/
        minicpm-sala-9b-1chip.json `assumed`), the published `layers`
        held."""
        layers = tuple(layers)
        return MiniCPMSALAConfig(
            vocab_size=73448, hidden_size=4096, intermediate_size=16384,
            mixer_types=tuple(MIXERS_9B[i] for i in layers),
            layer_indices=layers, num_heads=32, num_kv_heads=2,
            head_dim=128, lightning_heads=32, lightning_head_dim=128,
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MiniCPMSALAConfig":
        """Five layers at toy widths (sparse, two lightning, two sparse:
        published indices 9, 10, 11, 16, 17), 2 query heads a KV head, a
        block of 4 tokens with 4 compressed keys a block, 6 blocks of a
        context of 32 or more."""
        return MiniCPMSALAConfig(
            vocab_size=vocab_size, dtype=jnp.float32,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE, SPARSE),
            layer_indices=(9, 10, 11, 16, 17), dim_model_base=16,
            chunk_size=8,
            sparse=SparseDims(kernel_size=2, kernel_stride=1, block_size=4,
                              init_blocks=1, window_size=8, topk=6,
                              dense_len=32),
        )


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------


class SalaCache(NamedTuple):
    """`k`, `v` the sparse layers' pages, a KV head a row of its own ([L,
    P * Hkv, S, 1, D]: page p of KV head h at p * Hkv + h); `kc` the
    compressed keys beside them ([L, P * Hkv * per_block, D]); `ssm` the
    lightning layers' slot pool (ops/ssm_state.py). No conv window.
    `walked` counts, on the device, what the steps READ of the pages, a KV
    head and a sparse layer each, summed since the cache was made and
    wrapping at 2**32: int32 [4], the pages the decode walks' lists named
    and the pages their rows held (`pages_walked`), then the pages a
    sparse prompt chunk's TILES read and the pages its queries'
    selections named (ops/sparse_chunk.py: their ratio is what reading a
    page once a tile saves)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None  # never set: no quantised pages
    v_scale: Optional[jax.Array] = None
    conv: Optional[jax.Array] = None  # never set
    ssm: Optional[jax.Array] = None
    kc: Optional[jax.Array] = None
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


def walk_count(cache: SalaCache) -> jax.Array:
    """`ModelAdapter.walk_pages`: the cache's running count."""
    return cache.walked


def state_bytes_per_slot(cfg: MiniCPMSALAConfig) -> int:
    """One generation of one sequence's state, all lightning layers."""
    d = cfg.lightning_head_dim
    return cfg.state_layers * cfg.lightning_heads * d * d * 4


def page_bytes(cfg: MiniCPMSALAConfig, page_size: int) -> int:
    """One page of every sparse layer: K, V and the compressed keys."""
    d = cfg.attn_cfg.kv_head_dim
    item = jnp.dtype(cfg.dtype).itemsize
    per = page_size // cfg.sparse.kernel_stride
    return cfg.sparse_layers * cfg.num_kv_heads * d * item * (
        2 * page_size + per)


def init_cache(cfg: MiniCPMSALAConfig, num_pages: int, page_size: int,
               state_slots: int) -> SalaCache:
    cfg.sparse.check(page_size)
    d = cfg.attn_cfg.kv_head_dim
    rows = num_pages * cfg.num_kv_heads
    page = (cfg.sparse_layers, rows, page_size, 1, d)
    ld = cfg.lightning_head_dim
    return SalaCache(
        k=jnp.zeros(page, cfg.dtype), v=jnp.zeros(page, cfg.dtype),
        kc=jnp.zeros((cfg.sparse_layers, rows * cfg.sparse.per_block, d),
                     cfg.dtype),
        ssm=jnp.zeros((cfg.state_layers, 2 * (state_slots + 1),
                       cfg.lightning_heads, ld, ld), jnp.float32),
        walked=jnp.zeros((4,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: MiniCPMSALAConfig, kind: str) -> dict:
    h, i = cfg.hidden_size, cfg.intermediate_size
    if kind == SPARSE:
        qd, kvd, d = (cfg.num_heads * cfg.head_dim,
                      cfg.num_kv_heads * cfg.head_dim, cfg.head_dim)
        mixer = {"wq": (h, qd), "wk": (h, kvd), "wv": (h, kvd),
                 "wz": (h, qd), "wo": (qd, h),
                 "q_norm": (d,), "k_norm": (d,)}
    else:
        qd, d = (cfg.lightning_heads * cfg.lightning_head_dim,
                 cfg.lightning_head_dim)
        mixer = {"wq": (h, qd), "wk": (h, qd), "wv": (h, qd),
                 "wz": (h, qd), "wo": (qd, h),
                 "q_norm": (d,), "k_norm": (d,), "o_norm": (d,)}
    return {"norm": (h,), **mixer, "mlp_norm": (h,),
            "w_gate": (h, i), "w_up": (h, i), "w_down": (i, h)}


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, scale, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(key: jax.Array, cfg: MiniCPMSALAConfig) -> dict:
    """Seeded weights at a TRAINED block's scale: a matrix whose output
    a scalar `c` multiplies is drawn normal at `1 / (c sqrt(fan in))`
    (the embedding at `1 / scale_emb`, `wo` and `w_down` at `1 / (r
    sqrt(fan in))`, the head at `(hidden / dim_model_base) /
    sqrt(hidden)`), so that the block WITH its scalars has unit-scale
    branches; every norm weight is one. Drawn at `1 / sqrt(fan in)` the
    log-probs would be flat and a comparison on them blind. A matrix's
    draw is numbered by its place in layer order (run of one kind by run,
    name by name inside a run), whatever stack holds it."""
    r = cfg.residual_scale

    def matrix(n: int, shape, after=1.0):
        return _normal(jax.random.fold_in(key, n),
                       jnp.float32(1.0 / (after * math.sqrt(shape[0]))),
                       shape, cfg.dtype)

    # the number of each layer's draw of each name: 0 is the embedding
    numbers, n, at = {SPARSE: [], LIGHTNING: []}, 1, 0
    kinds = cfg.mixer_types
    while at < len(kinds):
        kind, run = kinds[at], 1
        while at + run < len(kinds) and kinds[at + run] == kind:
            run += 1
        drawn = [name for name in _layer_shapes(cfg, kind)
                 if not name.endswith("norm")]
        numbers[kind] += [
            {name: n + i * run + j for i, name in enumerate(drawn)}
            for j in range(run)]
        n, at = n + len(drawn) * run, at + run

    def stack(kind: str) -> dict:
        return {
            name: (jnp.ones((len(numbers[kind]), *shape), cfg.dtype)
                   if name.endswith("norm") else jnp.stack([
                       matrix(layer[name], shape,
                              r if name in ("wo", "w_down") else 1.0)
                       for layer in numbers[kind]]))
            for name, shape in _layer_shapes(cfg, kind).items()}

    return {
        "embed": _normal(jax.random.fold_in(key, 0),
                         jnp.float32(1.0 / cfg.scale_emb),
                         (cfg.vocab_size, cfg.hidden_size), cfg.dtype),
        "sparse": stack(SPARSE),
        "lightning": stack(LIGHTNING),
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.dtype),
        "lm_head": matrix(n, (cfg.hidden_size, cfg.vocab_size),
                          cfg.dim_model_base / cfg.hidden_size),
    }


def minicpm_sala_logical_axes(cfg: MiniCPMSALAConfig) -> dict:
    """Logical axis names (parallel/logical.py): everything replicates
    but the head's vocabulary axis; the adapter refuses a mesh."""
    from dynamo_tpu.parallel.logical import L

    return {
        "embed": L(), "final_norm": L(), "lm_head": L(None, "vocab"),
        "sparse": {name: L() for name in _layer_shapes(cfg, SPARSE)},
        "lightning": {name: L() for name in _layer_shapes(cfg, LIGHTNING)},
    }


# ---------------------------------------------------------------------------
# The mixers
# ---------------------------------------------------------------------------


def _heads(x, lp, name: str, heads: int, d: int, dtype):
    """A projection as [.., heads, d]. The barrier keeps the norm over
    the head dimension that follows from choosing the matrix's layout:
    without it XLA's TPU compiler transposes q, k and v of every layer
    into a copy of its own ahead of the layer loop, 1.4 GB of temporaries
    written anew by every dispatch (tests/test_tpu_compile.py)."""
    y = lax.optimization_barrier(_mm(x, lp, name, dtype))
    return y.reshape(*x.shape[:-1], heads, d)


def lightning_mixer(x, lp, cfg: MiniCPMSALAConfig, pool, layer, log_decay,
                    groups):
    """Returns (out shaped like x, the slot pool). The projections, the
    norms and the gate run on every group's rows at once, the rotary
    embedding and the recurrence per group, each row from its own slot
    (`StepGroup.state_rows`: read at one entry, written at another). A
    row whose chunk starts at position 0 starts from zeros; padding
    tokens leave the state as it is. Scopes, under the caller's `attn`:
    `ssm/in_proj`, `ssm/scan`, `ssm/out`."""
    f32, dtype, eps = jnp.float32, cfg.dtype, cfg.rms_norm_eps
    nh, d = cfg.lightning_heads, cfg.lightning_head_dim
    use_kernel = (None if cfg.attention_impl in ("pallas", "hybrid")
                  else False)  # None: on a TPU
    with jax.named_scope("ssm"):
        with jax.named_scope("in_proj"):
            q = rms_norm(_heads(x, lp, "wq", nh, d, dtype), lp["q_norm"], eps)
            k = rms_norm(_heads(x, lp, "wk", nh, d, dtype), lp["k_norm"], eps)
            v = _heads(x, lp, "wv", nh, d, dtype)
            z = _mm(x, lp, "wz", dtype)
        ys = []
        with jax.named_scope("scan"):
            for g, qg, kg, vg in zip(
                groups, *(split_rows(a, groups) for a in (q, k, v))
            ):
                b, t = g.tokens.shape
                ridx, widx = g.state_rows[:, 0], g.state_rows[:, 1]
                fresh = g.positions[:, 0] == 0  # starts from zeros
                qg = apply_rope(qg, g.positions, cfg.rope_cfg)
                kg = apply_rope(kg, g.positions, cfg.rope_cfg)
                qg = (qg.astype(f32) / math.sqrt(d)).astype(dtype)
                live = g.valid.astype(f32)  # [B, T]: the recurrence's dt
                if t == 1:
                    decay = jnp.where(
                        g.valid, jnp.exp(log_decay)[None], 1.0
                    ) * jnp.where(fresh, 0.0, 1.0)[:, None]
                    y, pool = ssm_state.ssm_decode_step(
                        pool, layer, ridx, widx,
                        vg[:, 0].astype(f32) * live[:, :, None],
                        decay, kg[:, 0], qg[:, 0], use_kernel=use_kernel,
                    )
                    y = y[:, None]
                else:
                    s0 = ssm_state.read_rows(
                        pool, layer, ridx, use_kernel=use_kernel)
                    s0 = jnp.where(fresh[:, None, None, None], 0.0, s0)
                    y, s_end = ssd_chunk_scan(
                        vg, jnp.broadcast_to(live[..., None], (b, t, nh)),
                        log_decay, kg, qg, s0, cfg.chunk_size,
                    )
                    pool = ssm_state.write_rows(
                        pool, layer, widx, s_end, use_kernel=use_kernel)
                ys.append(y)
        with jax.named_scope("out"):
            o = rms_norm(join_rows(ys), lp["o_norm"], eps).astype(dtype)
            o = o.reshape(*o.shape[:-2], nh * d)
            o = (o.astype(f32) * jax.nn.sigmoid(z.astype(f32))).astype(dtype)
            return _mm(o, lp, "wo", dtype), pool


def virtual_rows(cfg: MiniCPMSALAConfig, g: StepGroup):
    """A group's rows as the sparse layers' cache has them, a KV head a
    row (b * Hkv + h): (page tables, positions, valid)."""
    hkv = cfg.num_kv_heads
    tables = (g.page_tables[:, None, :] * hkv
              + jnp.arange(hkv, dtype=jnp.int32)[None, :, None])
    return (tables.reshape(-1, tables.shape[-1]),
            jnp.repeat(g.positions, hkv, axis=0),
            jnp.repeat(g.valid, hkv, axis=0))


def fresh_keys_of(k, kv, layer, tables, positions, valid,
                  cfg: MiniCPMSALAConfig):
    """The compressed keys of the windows that end inside a step, for
    `land_compressed` and for the step's own selection: (kc [B', T, Dc],
    ends, j) of `ss.fresh_windows`. k [B', T, 1, D]."""
    dims = cfg.sparse
    dpad = cfg.attn_cfg.kv_head_dim - k.shape[-1]
    k_row = k[:, :, 0]
    if dpad:
        k_row = jnp.pad(k_row, ((0, 0), (0, 0), (0, dpad)))
    tail = ss.history_tail(kv.k, layer, tables, positions[:, 0],
                           dims.kernel_size - 1)
    return ss.fresh_windows(k_row, tail, positions, valid, dims)


def compressed_keys_of(k, kv, kc_pool, layer, tables, positions, valid,
                       cfg: MiniCPMSALAConfig):
    """The virtual rows' compressed keys as a step's queries see them, a
    gathered COPY (the path without kernels; the kernel of
    ops/block_scores.py reads the pool in place): (kc [B', NB *
    per_block, D] in window order, the pool's with the windows that end
    inside this chunk put in; the step's fresh ones, `fresh_keys_of`)."""
    fresh = fresh_keys_of(k, kv, layer, tables, positions, valid, cfg)
    kc = ss.with_fresh(
        ss.gather_compressed(kc_pool, layer, tables, cfg.sparse,
                             heads=cfg.num_kv_heads),
        fresh[0], fresh[1], positions[:, 0], cfg.sparse,
    )
    return kc[..., :k.shape[-1]], fresh


def pages_walked(lens, hist, valid, page_size: int):
    """What one decode step's walks read, counted from what they were
    GIVEN: int32 [2], the pages the lists name (`lens` [B'], the tokens a
    list holds, every page full but the last) and the pages the rows hold
    (`hist` [B'], the tokens cached), over the valid virtual rows. Equal
    where the lists name every page: the switch to the sparse rule did
    not fire."""
    pages = lambda n: (n + page_size - 1) // page_size  # noqa: E731
    return jnp.stack([jnp.sum(jnp.where(valid, pages(x), 0))
                      for x in (lens, hist)]).astype(jnp.int32)


def decode_selection(q, kc, tables, positions, cfg: MiniCPMSALAConfig):
    """A decode step's selection: (selected [B', NB], the page list [B',
    K] the walk takes, the tokens it holds [B'])."""
    sel = ss.select_blocks(q, kc, positions, cfg.sparse,
                           1.0 / math.sqrt(q.shape[-1]))[:, 0]
    return (sel, *ss.decode_lists(sel, tables, positions[:, 0], cfg.sparse))


def sparse_attention(
    q,  # [B', T, G, D]: a KV head's query heads, normed
    k, v,  # [B', T, 1, D]
    kv: KVPages, kc_pool, layer,
    tables, positions, valid,  # of the virtual rows
    cfg: MiniCPMSALAConfig,
):
    """Attention of virtual rows under the selection rule, in the write
    discipline of models/llama.py `attention_block`. Returns (attn [B',
    T, G * D], kv, the staged (k, v) or None, the step's fresh compressed
    keys (kc [B', T, Dc], ends, j) for `land_compressed`, and what the
    step read of the cache, int32 [4]: `pages_walked` of a decode step's
    walks, then the pages the tiles of a chunk's rows past `dense_len`
    read and the pages their queries' selections named; zeros where a
    step has none of them)."""
    dims, acfg = cfg.sparse, cfg.attn_cfg
    b, t, g, d = q.shape
    scale = 1.0 / math.sqrt(d)
    dpad = acfg.kv_head_dim - d
    start = positions[:, 0]
    none = jnp.zeros((2,), jnp.int32)
    past = valid & (positions + 1 >= dims.dense_len)  # under the sparse rule
    if acfg.attention_impl not in ("pallas", "hybrid"):
        with jax.named_scope("select"):
            kc, fresh = compressed_keys_of(
                k, kv, kc_pool, layer, tables, positions, valid, cfg)

        def chosen():
            """`select_blocks`, as many rows at a time as keep its float32
            scores ([rows, T, G, NC]) under `SELECT_BYTES`: 32 prompts of
            32 tokens side by side hold as many as four pieces of 512."""
            n = b
            while n % 2 == 0 and n * t * g * kc.shape[1] * 4 > SELECT_BYTES:
                n //= 2
            if n == b:
                return ss.select_blocks(q, kc, positions, dims, scale)
            return lax.map(
                lambda a: ss.select_blocks(*a, dims, scale),
                tuple(x.reshape(b // n, n, *x.shape[1:])
                      for x in (q, kc, positions)),
            ).reshape(b, t, -1)

        with jax.named_scope("kv_update"):
            kv = paged_scatter_kv(kv, layer, k, v, tables, positions, valid)
        with jax.named_scope("select"):
            # the sorts are skipped where every query of the step stands
            # under `dense_len`
            sel = lax.cond(
                jnp.any(past), chosen,
                lambda: ss.dense_blocks(positions, tables.shape[1], dims),
            )
        with jax.named_scope("paged"):
            attn = ss.masked_attention(
                q, kv.k, kv.v, layer, tables, positions, sel, dims, scale)
        walk = none
        if t == 1:  # the lists a walk would take, for their count alone
            walk = pages_walked(
                ss.decode_lists(sel[:, 0], tables, start, dims)[1], start,
                valid[:, 0], dims.block_size)
        return (attn.reshape(b, t, g * d), kv, None, fresh,
                jnp.concatenate([walk, none]))
    # with kernels the selection reads the compressed keys out of the pool
    # in place and neither sorts nor branches: a step none of whose
    # queries has reached `dense_len` scores its (few) pages too
    with jax.named_scope("select"):
        fresh = fresh_keys_of(k, kv, layer, tables, positions, valid, cfg)
        sel = ss.blocks_of_scores(
            paged_block_scores(q, kc_pool, layer, tables, positions, valid,
                               fresh, dims, scale, cfg.num_kv_heads),
            positions, dims)
    if t == 1:
        with jax.named_scope("select"):
            pages, lens = ss.decode_lists(sel[:, 0], tables, start, dims,
                                          counted=True)
        attn, kv, staged = attention_block(
            q, k, v, kv, layer, pages, lens[:, None], valid, acfg)
        return attn, kv, staged, fresh, jnp.concatenate([pages_walked(
            lens, start, valid[:, 0], dims.block_size), none])
    # a prompt chunk: the cache is read-only (its history), the chunk's
    # own keys are in hand and staged for the step's one write. It attends
    # by TILE of queries over the cached pages ANY query of the tile chose,
    # each read once a tile, a mask bit a (query, page), and over its own
    # keys (ops/sparse_chunk.py); a query under `dense_len` names every
    # page up to its own, so a dense chunk is the same kernel with every
    # bit set (PERF.md 6, PR 42: past ~2,000 tokens of history it beats
    # `latent_prefill_attention` fed a zero latent half)
    from dynamo_tpu.ops.sparse_chunk import sparse_chunk_attention

    pad = ((0, 0), (0, 0), (0, 0), (0, dpad))
    k_pad, v_pad = (jnp.pad(k, pad), jnp.pad(v, pad)) if dpad else (k, v)
    with jax.named_scope("flash"):
        q_s = (q.astype(jnp.float32) * scale).astype(q.dtype)
        out, (tiles, named) = sparse_chunk_attention(
            jnp.pad(q_s, pad) if dpad else q_s, k_pad[:, :, 0],
            v_pad[:, :, 0], kv.k, kv.v, layer, tables, sel,
            jnp.where(valid[:, 0], start, 0), valid)
    # counted for the rows the sparse rule reached: under `dense_len` a
    # tile of 128 names every page 128 times, whatever the queries are
    read = jnp.stack([
        jnp.sum(jnp.where(jnp.any(past, axis=1), n, 0), dtype=jnp.int32)
        for n in (tiles.sum(axis=1), named)])
    return (out[..., :d].reshape(b, t, g * d), kv, (k_pad, v_pad), fresh,
            jnp.concatenate([none, read]))


def land_sparse(kv: KVPages, kc_pool, staged, fresh, tables, positions,
                valid, cfg: MiniCPMSALAConfig):
    """Land one step of every sparse layer: the staged K and V ([L, B',
    T, 1, D] each; None under the scatter discipline, which wrote them
    already) and the fresh compressed keys ([L, ...] a leaf). Returns
    (kv, kc_pool)."""
    kc, ends, j = fresh
    return (
        land_staged_kv(kv, staged, tables, positions, valid),
        ss.land_compressed(kc_pool, (kc, ends[0], j[0]), tables, cfg.sparse),
    )


def sparse_mixer(x, lp, cfg: MiniCPMSALAConfig, kv, kc_pool, layer, groups,
                 rows):
    """Returns (out shaped like x, kv, per group the staged (k, v) and
    the fresh compressed keys, what the groups read of the cache: the
    int32 [4] of `sparse_attention`, summed). `rows` is `virtual_rows` of
    each group.
    Scopes, under the caller's `attn`: `qkv`, `select`, `paged`, `flash`,
    `kv_update`, `out`."""
    f32, dtype, eps = jnp.float32, cfg.dtype, cfg.rms_norm_eps
    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    per = hq // hkv
    with jax.named_scope("qkv"):
        q = rms_norm(_heads(x, lp, "wq", hq, d, dtype), lp["q_norm"], eps)
        k = rms_norm(_heads(x, lp, "wk", hkv, d, dtype), lp["k_norm"], eps)
        v = _heads(x, lp, "wv", hkv, d, dtype)
        z = _mm(x, lp, "wz", dtype)
    attns, staged, walked = [], [], jnp.zeros((4,), jnp.int32)
    for g, (tables, positions, valid), qg, kg, vg in zip(
        groups, rows, *(split_rows(a, groups) for a in (q, k, v))
    ):
        b, t = g.tokens.shape
        # [B, T, Hkv, ...] -> a KV head a row, b * Hkv + h
        by_head = lambda a: jnp.swapaxes(a, 1, 2).reshape(  # noqa: E731
            b * hkv, t, *a.shape[3:])
        attn, kv, st, fresh, walk = sparse_attention(
            by_head(qg.reshape(b, t, hkv, per, d)),
            by_head(kg[:, :, :, None]), by_head(vg[:, :, :, None]),
            kv, kc_pool, layer, tables, positions, valid, cfg,
        )
        walked = walked + walk
        attns.append(jnp.swapaxes(
            attn.reshape(b, hkv, t, per * d), 1, 2).reshape(b, t, hq * d))
        staged.append((st, fresh))
    with jax.named_scope("out"):
        a = join_rows(attns)
        a = (a.astype(f32) * jax.nn.sigmoid(z.astype(f32))).astype(dtype)
        return _mm(a, lp, "wo", dtype), kv, tuple(staged), walked


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _mlp(h, lp, cfg: MiniCPMSALAConfig):
    f32, dtype = jnp.float32, cfg.dtype
    with jax.named_scope("mlp"):
        y = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
        gate = jax.nn.silu(_mm(y, lp, "w_gate", dtype).astype(f32))
        up = _mm(y, lp, "w_up", dtype).astype(f32)
        return _mm((gate * up).astype(dtype), lp, "w_down", dtype)


def forward_groups(
    params: dict,
    cfg: MiniCPMSALAConfig,
    groups,  # llama.StepGroup with `state_rows`, one or two
    cache: SalaCache,
    mesh=None,
):
    """models/llama.py's `forward_groups` for this family: a scan over
    the sparse layers and, after each, a loop over the lightning layers
    that follow it (`cfg.blocks`), so a program holds each kind's body
    once; the dense work of a layer on every group's rows together, the
    sequence mixer per group. Returns ([hidden [B_g, T_g, H] post final
    norm per group], the new cache)."""
    if mesh is not None:
        raise ValueError("MiniCPM-SALA on a mesh is not implemented")
    if any(g.state_rows is None for g in groups):
        raise ValueError(
            "a model with lightning layers needs each row's state slot "
            "(StepGroup.state_rows)"
        )
    dtype, eps = cfg.dtype, cfg.rms_norm_eps

    def add(h, branch):  # h + r * branch, the product in float32
        return (h.astype(jnp.float32) + jnp.float32(cfg.residual_scale)
                * branch.astype(jnp.float32)).astype(dtype)

    with jax.named_scope("embed"):
        h = join_rows([params["embed"][g.tokens].astype(dtype)
                       for g in groups]) * jnp.asarray(cfg.scale_emb, dtype)
    rows = [virtual_rows(cfg, g) for g in groups]
    kv, kc_pool, pool = cache.pages, cache.kc, cache.ssm

    def sparse_layer(h, kv, lp, li):
        with jax.named_scope("attn"):
            a, kv, staged, walk = sparse_mixer(
                rms_norm(h, lp["norm"], eps), lp, cfg, kv, kc_pool, li,
                groups, rows)
            h = add(h, a)
        return add(h, _mlp(h, lp, cfg)), kv, staged, walk

    decays = jnp.stack([cfg.log_decay(i) for i in cfg.published(LIGHTNING)])

    def lightning_layer(li, carry):
        h, pool = carry
        lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
            a, li, keepdims=False), params["lightning"])
        with jax.named_scope("attn"):
            a, pool = lightning_mixer(
                rms_norm(h, lp["norm"], eps), lp, cfg, pool, li,
                lax.dynamic_index_in_dim(decays, li, keepdims=False), groups)
            h = add(h, a)
        return add(h, _mlp(h, lp, cfg)), pool

    def lightning_run(h, pool, first, n):
        """The `n` lightning layers from `first` on, `n` a traced count:
        one body however long each run is."""
        return lax.fori_loop(first, first + n, lightning_layer, (h, pool))

    def block(carry, xs):
        h, kv, pool, walked = carry
        lp, li, first, n = xs
        h, kv, staged, walk = sparse_layer(h, kv, lp, li)
        h, pool = lightning_run(h, pool, first, n)
        return (h, kv, pool, walked + walk), staged

    ahead, *after = cfg.blocks
    if ahead:  # a model that opens on lightning layers: a body of its own
        h, pool = lightning_run(h, pool, jnp.int32(0), jnp.int32(ahead))
    firsts = [ahead + sum(after[:i]) for i in range(len(after))]
    # every sparse layer's step, in layer order: [L, ...] a leaf
    (h, kv, pool, walked), staged = lax.scan(
        block, (h, kv, pool, cache.walked), (
            params["sparse"], jnp.arange(len(after), dtype=jnp.int32),
            jnp.asarray(firsts, jnp.int32), jnp.asarray(after, jnp.int32)))
    with jax.named_scope("attn"), jax.named_scope("kv_update"):
        for (tables, positions, valid), (st, fresh) in zip(rows, staged):
            kv, kc_pool = land_sparse(
                kv, kc_pool, st, fresh, tables, positions, valid, cfg)
    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], eps)
    return split_rows(h, groups), SalaCache(
        k=kv.k, v=kv.v, ssm=pool, kc=kc_pool, walked=walked)


def forward_hidden(
    params, cfg: MiniCPMSALAConfig, tokens, positions, valid, cache,
    page_tables, state_rows, first_chunk: bool = False, mesh=None,
):
    (h,), cache = forward_groups(
        params, cfg,
        [StepGroup(tokens, positions, valid, page_tables, first_chunk,
                   state_rows=state_rows)],
        cache, mesh=mesh,
    )
    return h, cache


def compute_logits(params: dict, cfg: MiniCPMSALAConfig, hidden: jax.Array):
    with jax.named_scope("lm_head"):
        width = jnp.asarray(cfg.dim_model_base / cfg.hidden_size,
                            hidden.dtype)
        return ((hidden * width) @ params["lm_head"]).astype(jnp.float32)
