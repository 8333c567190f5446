"""Grouped matmul over contiguous groups of rows: the routed experts'
product after the assignments are sorted by expert (models/mla.py).

    out[rows of group g] = x[rows of group g] @ w[g]

The contract is `jax.lax.ragged_dot`'s: x [M, K], w [G, K, N],
group_sizes [G] int32 summing to M, float32 out [M, N]; operands go to
the MXU in the dtype they come in. Off the TPU (and under a mesh, where
the expert axis is sharded and a Pallas call cannot be partitioned) it
IS `lax.ragged_dot`. On a TPU it is jax's Pallas megablox kernel
(`jax.experimental.pallas.ops.tpu.megablox.gmm`), which visits only the
(group, row tile) pairs that exist and reads an expert's matrix once per
pair: at DeepSeek-V2-Lite's decode shape (64 rows x top-6 = 384 sorted
rows over 64 experts of 2048 x 1408, bf16; PR 27, my chip run, device
time from a trace, all three projections of one layer) XLA's own
lowering of `ragged_dot` on a v5e took 3771 us, the kernel with whole-K
tiles 1535 us against the 1352 us the chip needs to read the 1.1 GB of
expert weights at 819 GB/s.

Tiles (`_tiling`), from the same runs (us for the three projections of
one layer at 64 / 512 / 2048 tokens x top-6; `ragged_dot` 3817 / 5800 /
7958): all of K and all of N in one weight tile where that is under
`_WEIGHT_TILE_BYTES` (two in flight in VMEM) and 128 rows a tile, 1535 /
1877 / 3225, bit-identical to `ragged_dot`; K in 1024s 2035 at 512
tokens and 3999 at 2048, N in 768s 1954 / 3471; 256 rows a tile 1902 /
3326, 512 rows 3480 / 4562 (a group holds 6-200 rows, so taller tiles
multiply rows that belong to other experts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: one weight tile [K, tn]; the pipeline holds two
_WEIGHT_TILE_BYTES = 6 << 20
#: rows of x a tile
_TILE_ROWS = 128


def _tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    tn = max(128, _WEIGHT_TILE_BYTES // (k * itemsize) // 128 * 128)
    return _TILE_ROWS, k, n if tn >= n else tn


def grouped_matmul(
    x: jax.Array,  # [M, K] rows sorted by group
    w: jax.Array,  # [G, K, N], or with `layer` the stack [L, G, K, N]
    group_sizes: jax.Array,  # [G] int32, sums to M
    *,
    layer: jax.Array | None = None,  # scalar int32: multiply by w[layer]
    use_kernel: bool | None = None,  # default: on a TPU
    interpret: bool = False,  # the kernel interpreted (CPU tests)
) -> jax.Array:
    """`layer` is for a layer scan: hand in the whole stack and the
    layer's index instead of the scan's slice. A Pallas call's operand is
    a buffer of its own, so a slice of the stack is first COPIED out (369
    MB a projection at DeepSeek-V2-Lite: 1.2 ms, three a layer, 21 of a
    decode step's 37 ms in the first traced run of PR 27). The kernel
    takes the stack as L*G groups (a reshape, the same bytes) with only
    this layer's sizes non-zero, and it skips empty groups."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        return jax.lax.ragged_dot(
            x, w, group_sizes, preferred_element_type=jnp.float32
        )
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    group_sizes = group_sizes.astype(jnp.int32)
    if layer is not None:
        layers, groups = w.shape[:2]
        w = w.reshape(layers * groups, *w.shape[2:])
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * groups,), jnp.int32), group_sizes,
            (layer * groups,),
        )
    m, k = x.shape
    tm, tk, tn = _tiling(k, w.shape[2], jnp.dtype(w.dtype).itemsize)
    if m % tm:  # whole row tiles; rows past the groups are never computed
        x = jnp.pad(x, ((0, -m % tm), (0, 0)))
    out = gmm(
        x, w, group_sizes, preferred_element_type=jnp.float32,
        tiling=(tm, tk, tn), interpret=interpret,
    )
    return out[:m]
