"""The recurrent state of state-space (Mamba-2) layers, kept per sequence
in a pool of slots beside the paged KV cache (models/nemotron_h.py,
models/falcon_h1.py).

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
kernel visits one block of a row's heads a grid step (`head_block`: the
whole row where its state is 2 MiB or less), the slot ids prefetched as
scalars (as ops/kv_update._rows_kernel has its pages), reads the block at
`ridx`, writes it at `widx` of the same buffer (aliased, so the pool is
never copied: an XLA scatter on a TPU copies the whole pool, PR 27).

`write_rows`: whole rows into their entries, one DMA a row, all in
flight at once (the conv window of every step, and the state a prompt
chunk leaves behind: 2.1 to 4.19 MB a row, HBM to HBM, no VMEM).
`read_rows` with `use_kernel` is its mirror (the state a prompt chunk
starts from).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rows_dma(n: int, copy) -> None:
    """`copy(i)` for every row, each DMA out before any wait."""

    def start(i, _):
        copy(i).start()
        return 0

    def drain(i, _):
        copy(i).wait()
        return 0

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, drain, 0)


def _read_rows_kernel(layer_ref, idx_ref, pool_ref, out_ref, sem):
    _rows_dma(out_ref.shape[0], lambda i: pltpu.make_async_copy(
        pool_ref.at[layer_ref[0], idx_ref[i]], out_ref.at[i], sem))


def read_rows(
    pool: jax.Array, layer, idx: jax.Array, *,
    use_kernel: bool | None = False,
) -> jax.Array:
    """pool[layer, idx[b]] for every row, the rows alone. The plain form
    is one XLA gather: right for the conv window's small rows. With
    `use_kernel` (None: where there is a TPU) one DMA a row, all in
    flight at once, as `write_rows`: for the SSM state's rows of [heads,
    128, 256] float32 XLA's gather first copied the WHOLE pool into two
    temporaries, one a 128-lane half of the state axis (2 x 0.89 GB a
    layer of a mixed step at 74 entries of 4.19 MB, and over the chip's
    memory beside the weights; tests/test_tpu_compile.py)."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return pool[layer, idx]
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _read_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (idx.shape[0], *pool.shape[2:]), pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_],
            out_specs=any_,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        interpret=jax.default_backend() != "tpu",
        name="state_read_rows",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), idx.astype(jnp.int32),
        pool,
    )


def _write_rows_kernel(layer_ref, idx_ref, src_ref, pool_in, pool_out, sem):
    del pool_in
    # the targets are disjoint (padding rows all aim at the null slot,
    # whose content nobody reads)
    _rows_dma(src_ref.shape[0], lambda i: pltpu.make_async_copy(
        src_ref.at[i], pool_out.at[layer_ref[0], idx_ref[i]], sem))


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


#: bytes of one head block of a row's state in VMEM. A grid step holds
#: four of them (read at `ridx`, written at `widx`, each double-buffered)
STATE_BLOCK_BYTES = 2 * 1024 * 1024


def head_block(heads: int, hpg: int, p: int, n: int) -> int:
    """How many heads of a row's state one grid step of `ssm_decode_step`
    maps into VMEM: the most that divide `heads`, hold WHOLE groups of
    `hpg` heads (or, where one group is too much, a whole fraction of
    one, so that a block still reads one B / C row a group it touches)
    and take at most `STATE_BLOCK_BYTES` of float32 state."""
    fits = [
        d for d in range(1, heads + 1)
        if heads % d == 0 and (d % hpg == 0 or hpg % d == 0)
        and d * p * n * 4 <= STATE_BLOCK_BYTES
    ]
    return max(fits, default=1)


def _decode_kernel(
    layer_ref, ridx_ref, widx_ref,  # scalar prefetch
    s_ref,  # [1, 1, Hb, P, N] a head block of the row's state at ridx
    u_ref,  # [1, 1, Hb, P]
    dec_ref,  # [1, 1, Hb, N] decay, the same in every lane
    b_ref,  # [1, Gb, 1, N] the groups the block's heads read
    c_ref,  # [1, Gb, 1, N]
    o_ref,  # [1, 1, Hb, P, N] the same block of the row's state at widx
    y_ref,  # [1, 1, Hb, P]
    *,
    heads: int,  # of a block
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
        g = h // hpg  # among the block's groups
        s = s_ref[0, 0, h]  # [P, N]
        u_row = u_ref[0, 0, h:h + 1, :]  # [1, P]
        u_col = jnp.sum(
            jnp.where(eye, jnp.broadcast_to(u_row, (p, p)), 0.0),
            axis=1, keepdims=True,
        )  # [P, 1]
        new = s * dec_ref[0, 0, h:h + 1, :] + u_col * b_ref[0, g]
        o_ref[0, 0, h] = new
        y_col = jnp.sum(new * c_ref[0, g], axis=1, keepdims=True)
        y_ref[0, 0, h:h + 1, :] = jnp.sum(
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
    place. Returns (y [B, H, P] f32 without the skip term, pool).

    The kernel's grid runs over (rows, blocks of heads). A block is
    `head_block` heads: the most whose float32 state is at most 2 MiB,
    in whole groups, so that the four state buffers of a grid step (the
    block read at `ridx` and the block written at `widx`, each
    double-buffered by the pipeline) take 8 MiB of v5e's 16 MiB of
    scoped VMEM and the rest (`u`, `decay`, B, C and `y` of the block,
    the [P, P] diagonal select) under 0.3 MiB. At Nemotron-3-Nano's shape
    (64 heads x 64 x 128, 8 heads a group, 2.1 MB a row) a block is the
    whole row, one grid step a row as before; at Falcon-H1-34B's (32
    heads x 128 x 256, 16 heads a group, 4.19 MB a row: 16.8 MB of
    buffers if mapped whole, over the scoped limit) it is ONE GROUP of 16
    heads, 2.1 MB again, two grid steps a row, each reading one B / C
    row. Operands go in with the block as their own axis, so every block
    shape equals its array's trailing dimensions whatever the block."""
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
    hpg = heads // groups
    hb = head_block(heads, hpg, p, n)
    nb = heads // hb
    gb = max(1, hb // hpg)  # groups a block reads
    dec = jnp.broadcast_to(decay[:, :, None], (b, heads, n))
    by_block = lambda i, j, lay, r, w: (i, j, 0, 0)  # noqa: E731
    group = lambda i, j, lay, r, w: (  # noqa: E731
        i, (j * hb // hpg) // gb, 0, 0)
    pool, y = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb, hpg=min(hpg, hb)),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((b, nb, hb, p), f32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, hb, p, n),
                    lambda i, j, lay, r, w: (lay[0], r[i], j, 0, 0),
                ),
                pl.BlockSpec((1, 1, hb, p), by_block),
                pl.BlockSpec((1, 1, hb, n), by_block),
                pl.BlockSpec((1, gb, 1, n), group),
                pl.BlockSpec((1, gb, 1, n), group),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, 1, hb, p, n),
                    lambda i, j, lay, r, w: (lay[0], w[i], j, 0, 0),
                ),
                pl.BlockSpec((1, 1, hb, p), by_block),
            ],
        ),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="ssm_decode_step",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        ridx.astype(jnp.int32), widx.astype(jnp.int32),
        pool, u.reshape(b, nb, hb, p), dec.reshape(b, nb, hb, n),
        bmat[:, :, None, :], cmat[:, :, None, :],
    )
    return y.reshape(b, heads, p), pool
