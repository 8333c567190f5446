"""The recurrent state of state-space (Mamba-2) layers, kept per sequence
in a pool of slots beside the paged KV cache (models/nemotron_h.py).

    ssm pool   [L, S, heads, head_dim, state]  float32
    conv pool  [L, S, ...]                     model dtype

`L` counts the state-space layers, `S` the slot entries (two generations
a slot: engine/engine.py "state generations"); entry 0 is the null slot,
where padding rows read and write and nobody looks. A layer of a step
reads row b's state at entry `ridx[b]` and writes what it becomes at
`widx[b]`; the two differ when the step was launched ahead of its batch
and may yet be rolled back.

Two device routines, each with a plain `jnp` form that IS its definition
(off the TPU, and what the interpreted kernels are tested against):

`ssm_decode_step`: one token a row. `S' = decay * S + u (x) B`,
`y = S' C`, per head, with `u = dt * x` and `decay = exp(dt * A)` made
by the caller. It moves `rows x heads x head_dim x state x 4 B` in and
out of HBM and does two multiply-adds an element: bound by memory. The
kernel visits one row a grid step, the slot ids prefetched as scalars
(as ops/kv_update._rows_kernel has its pages), reads the row's block at
`ridx`, writes it at `widx` of the same buffer (aliased, so the pool is
never copied: an XLA scatter on a TPU copies the whole pool, PR 27).

`write_rows`: whole rows into their entries, one DMA a row, all in
flight at once (the conv window of every step, and the state a prompt
chunk leaves behind).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def read_rows(pool: jax.Array, layer, idx: jax.Array) -> jax.Array:
    """pool[layer, idx[b]] for every row: one gather, the rows alone."""
    return pool[layer, idx]


def _write_rows_kernel(layer_ref, idx_ref, src_ref, pool_in, pool_out, sem):
    del pool_in
    n = src_ref.shape[0]

    def copy(i):
        return pltpu.make_async_copy(
            src_ref.at[i], pool_out.at[layer_ref[0], idx_ref[i]], sem
        )

    def start(i, _):
        copy(i).start()
        return 0

    def drain(i, _):
        copy(i).wait()
        return 0

    # every row's DMA goes out before any wait: the targets are disjoint
    # (padding rows all aim at the null slot, whose content nobody reads)
    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, drain, 0)


def write_rows(
    pool: jax.Array,  # [L, S, ...]
    layer,  # scalar int32
    idx: jax.Array,  # [B] int32 entry per row
    rows: jax.Array,  # [B, ...] pool's trailing shape and dtype
    *,
    use_kernel: bool | None = None,
) -> jax.Array:
    """pool[layer, idx[b]] = rows[b], in place."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    rows = rows.astype(pool.dtype)
    if not use_kernel:
        return pool.at[layer, idx].set(rows)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _write_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_, any_],
            out_specs=any_,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={3: 0},
        interpret=jax.default_backend() != "tpu",
        name="state_write_rows",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), idx.astype(jnp.int32),
        rows, pool,
    )


def ssm_decode_reference(state, u, decay, bmat, cmat):
    """The definition, on gathered rows: state [B, H, P, N] f32, u
    [B, H, P], decay [B, H], bmat and cmat [B, G, N] (head h reads group
    h // (H // G)). Returns (y [B, H, P] f32, the new state)."""
    hpg = state.shape[1] // bmat.shape[1]
    bh = jnp.repeat(bmat, hpg, axis=1)  # [B, H, N]
    ch = jnp.repeat(cmat, hpg, axis=1)
    new = (
        state * decay[:, :, None, None]
        + u[:, :, :, None] * bh[:, :, None, :]
    )
    return jnp.sum(new * ch[:, :, None, :], axis=-1), new


def _decode_kernel(
    layer_ref, ridx_ref, widx_ref,  # scalar prefetch
    s_ref,  # [1, 1, H, P, N] the row's state at ridx
    u_ref,  # [1, H, P]
    dec_ref,  # [1, H, N] decay, the same in every lane
    b_ref,  # [1, G, N]
    c_ref,  # [1, G, N]
    o_ref,  # [1, 1, H, P, N] the row's state at widx
    y_ref,  # [1, H, P]
    *,
    heads: int,
    hpg: int,
):
    del layer_ref, ridx_ref, widx_ref
    p = s_ref.shape[3]
    # a row vector [1, P] as a column [P, 1] and back: select the
    # diagonal of its broadcast and reduce the other way (no transpose)
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1)
    )
    for h in range(heads):
        g = h // hpg
        s = s_ref[0, 0, h]  # [P, N]
        u_row = u_ref[0, h:h + 1, :]  # [1, P]
        u_col = jnp.sum(
            jnp.where(eye, jnp.broadcast_to(u_row, (p, p)), 0.0),
            axis=1, keepdims=True,
        )  # [P, 1]
        new = s * dec_ref[0, h:h + 1, :] + u_col * b_ref[0, g:g + 1, :]
        o_ref[0, 0, h] = new
        y_col = jnp.sum(new * c_ref[0, g:g + 1, :], axis=1, keepdims=True)
        y_ref[0, h:h + 1, :] = jnp.sum(
            jnp.where(eye, jnp.broadcast_to(y_col, (p, p)), 0.0),
            axis=0, keepdims=True,
        )


def ssm_decode_step(
    pool: jax.Array,  # [L, S, H, P, N] f32
    layer,  # scalar int32
    ridx: jax.Array,  # [B] int32: where each row's state is
    widx: jax.Array,  # [B] int32: where it goes
    u: jax.Array,  # [B, H, P] f32: dt * x
    decay: jax.Array,  # [B, H] f32: exp(dt * A)
    bmat: jax.Array,  # [B, G, N] f32
    cmat: jax.Array,  # [B, G, N] f32
    *,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """One token a row through the recurrence, the pool updated in
    place. Returns (y [B, H, P] f32 without the skip term, pool)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    f32 = jnp.float32
    u, decay = u.astype(f32), decay.astype(f32)
    bmat, cmat = bmat.astype(f32), cmat.astype(f32)
    if not use_kernel:
        y, new = ssm_decode_reference(
            read_rows(pool, layer, ridx), u, decay, bmat, cmat
        )
        return y, pool.at[layer, widx].set(new)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, _, heads, p, n = pool.shape
    b, groups = u.shape[0], bmat.shape[1]
    dec = jnp.broadcast_to(decay[:, :, None], (b, heads, n))
    row3 = lambda i, lay, r, w: (i, 0, 0)  # noqa: E731
    pool, y = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, hpg=heads // groups),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((b, heads, p), f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, heads, p, n),
                    lambda i, lay, r, w: (lay[0], r[i], 0, 0, 0),
                ),
                pl.BlockSpec((1, heads, p), row3),
                pl.BlockSpec((1, heads, n), row3),
                pl.BlockSpec((1, groups, n), row3),
                pl.BlockSpec((1, groups, n), row3),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, 1, heads, p, n),
                    lambda i, lay, r, w: (lay[0], w[i], 0, 0, 0),
                ),
                pl.BlockSpec((1, heads, p), row3),
            ],
        ),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="ssm_decode_step",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ridx.astype(jnp.int32), widx.astype(jnp.int32),
        pool, u, dec, bmat, cmat,
    )
    return y, pool
