"""Pallas TPU in-place paged-KV writer.

The paged cache is [L, P, S, Hkv, D] (models/llama.py KVPages). The model's
layer scan STAGES each layer's newly-computed KV (a small [L, B, T, Hkv, D]
scan output) instead of scattering into the cache per layer — XLA lowers
those scatters at ~0.5 ms each on TPU, and 2×L of them dominated the decode
step. This kernel lands the whole step's writes afterwards in ONE launch:
for every (sequence, page-run) it issues a single strided DMA covering ALL
layers at once (the layer axis is the cache's major axis, so
cache[:, page, slot0:slot0+run] is one descriptor).

Run shape: decode writes runs of 1 slot; prefill chunks are page-aligned
(scheduler invariant) so runs are min(T, S) slots. A prompt-tail run may
carry garbage staging rows past the valid tokens — harmless, those slots
are beyond every sequence's readable history and are overwritten by decode
before they become readable. Invalid (padding) runs are redirected to the
null page 0.

Quantized pools (kv_quantize, models/llama.py): the staged model-dtype
rows are quantized HERE — per-token, per-kv-head symmetric amax scales —
and the kernel DMAs the narrow pages, so no fp copy of the cache ever
exists in HBM (the staged arrays are transient step-sized temporaries
either way). The f32 row scales land in their slot-minor planes
([L, P, Hkv, S']) through one XLA scatter per plane: a token's scales are
a strided column of single lanes there, which Mosaic's DMA (minor-dim
slices aligned to the 128-lane tile) cannot express.

A cache of ONE ROW A TOKEN (Hkv = 1: MQA, or models/mla.py's latent cache
[L, P, S, 1, 512] + [L, P, S, 1, 128]) has its slots in the tiled
(sublane, lane) dimensions, where a DMA moves whole tiles and the writer
above cannot address one slot. Its whole pages (a prefill chunk) still go
through that writer, as [L, P, S, D]; a decode step's single rows go
through `_rows_kernel`, which reads each row's aligned tile group of
slots for all layers, puts the row in and writes the group back; K and V
may differ in width.

input_output_aliasing keeps both caches in place. D must be a 128 multiple
on TPU (LlamaConfig.kv_head_dim) — Mosaic DMA minor-dim alignment.

Parity: the engine-side KV write the reference delegates to vLLM's
reshape_and_cache CUDA kernel (SURVEY.md §2.9); TPU-native equivalent as a
Pallas DMA kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _write_kernel(
    pages_ref,  # [NR] int32 target page per run (scalar prefetch)
    slots_ref,  # [NR] int32 first slot per run (scalar prefetch)
    k_src_ref,  # [L, NR, R, Hkv, D] ANY — staged K rows, run-major
    v_src_ref,
    k_in_ref,  # aliased: writes land in place
    v_in_ref,
    k_out_ref,  # [L, P, S, Hkv, D] ANY
    v_out_ref,
    sem,
    *,
    num_runs: int,
    run: int,
    align: int,
):
    del k_in_ref, v_in_ref
    pairs = ((k_src_ref, k_out_ref), (v_src_ref, v_out_ref))

    def copies(i):
        slot = slots_ref[i]
        if align:  # a one-row cache: runs start on a tile of slots
            slot = pl.multiple_of(slot, align)
        return tuple(
            pltpu.make_async_copy(
                src.at[:, i], dst.at[:, pages_ref[i], pl.ds(slot, run)], sem,
            )
            for src, dst in pairs
        )

    def start(i, _):
        for c in copies(i):
            c.start()
        return 0

    def drain(i, _):
        for c in copies(i):
            c.wait()
        return 0

    # All runs' DMAs go out before any wait: targets are disjoint (padding
    # runs all alias the null page, where content is irrelevant), so total
    # latency is one round, not NR of them.
    jax.lax.fori_loop(0, num_runs, start, 0)
    jax.lax.fori_loop(0, num_runs, drain, 0)


def _rows_kernel(
    pages_ref,  # [B] int32 target page per row (scalar prefetch)
    slots_ref,  # [B] int32 target slot per row
    k_src_ref,  # [B, L, D] f32 VMEM — the step's rows, whole
    v_src_ref,
    k_in_ref,  # aliased: writes land in place
    v_in_ref,
    k_out_ref,  # [L, P, S, D] ANY
    v_out_ref,
    k_buf,  # [2, L, TILE, D] VMEM: two rows in flight
    v_buf,
    sem,  # [2 (k, v), 2 (slot), 2 (in, out)]
    *,
    tile: int,
):
    """One row a sequence into a cache of one row a token: the slot lies
    in the tiled (sublane, lane) dimensions, where a DMA moves whole
    tiles, so each row's aligned group of `tile` slots is read (all
    layers in one strided copy), the row put in its place, and the group
    written back. Row i+1's group is on its way in while row i's is
    changed and sent out. Rows aim at different pages; frozen rows all
    aim at the null page, whose content nobody reads."""
    del k_in_ref, v_in_ref
    n_rows, layers, _ = k_src_ref.shape
    planes = ((k_src_ref, k_out_ref, k_buf), (v_src_ref, v_out_ref, v_buf))

    def copies(i, slot, out):
        first = pl.multiple_of(slots_ref[i] // tile * tile, tile)
        made = []
        for pi, (_src, cache, buf) in enumerate(planes):
            group = cache.at[:, pages_ref[i], pl.ds(first, tile)]
            pair = (buf.at[slot], group) if out else (group, buf.at[slot])
            made.append(pltpu.make_async_copy(
                *pair, sem.at[pi, slot, int(out)]))
        return made

    for c in copies(0, 0, False):
        c.start()

    def row(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_rows)
        def _():
            # the other buffer's last write-back (row i-1) must be out
            @pl.when(i >= 1)
            def _():
                for c in copies(i - 1, 1 - slot, True):
                    c.wait()

            for c in copies(i + 1, 1 - slot, False):
                c.start()

        for c in copies(i, slot, False):
            c.wait()
        at = slots_ref[i] % tile
        for src, _cache, buf in planes:
            here = jax.lax.broadcasted_iota(
                jnp.int32, buf.shape[2:], 0) == at
            for l in range(layers):
                new = jnp.broadcast_to(src[i, l:l + 1, :], buf.shape[2:])
                buf[slot, l] = jnp.where(
                    here, new.astype(buf.dtype), buf[slot, l])
        for c in copies(i, slot, True):
            c.start()
        return 0

    jax.lax.fori_loop(0, n_rows, row, 0)
    # the last two rows' write-backs are still in flight
    last = n_rows - 1
    if n_rows > 1:
        for c in copies(last - 1, (last - 1) % 2, True):
            c.wait()
    for c in copies(last, last % 2, True):
        c.wait()


def _write_single_rows(k_cache, v_cache, k_stage, v_stage, page_ids, slot_of):
    """One decode step's rows ([L, B, 1, 1, D] staged) into a one-row
    cache, in place, by `_rows_kernel`. The caches go in as [L, P, S, D],
    the view the page-walk kernel reads: one shape and so one layout for
    the pool in the whole program. (In plain XLA, one scatter over all
    layers, or a loop of `dynamic_update_slice`, asks for a layout with
    the layer axis beside the lanes and copies the whole pool into it and
    back: 3.8 GB of temporaries at deepseek-v2-lite's cache, the TPU
    compiler's memory analysis, PR 27.)"""
    shapes = k_cache.shape, v_cache.shape
    tile = 32 // jnp.dtype(k_cache.dtype).itemsize
    layers, b = k_stage.shape[:2]
    srcs = [
        stage.reshape(layers, b, stage.shape[-1]).transpose(1, 0, 2)
        .astype(jnp.float32)
        for stage in (k_stage, v_stage)
    ]
    caches = [c.reshape(*c.shape[:3], c.shape[4]) for c in (k_cache, v_cache)]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    k_cache, v_cache = pl.pallas_call(
        functools.partial(_rows_kernel, tile=tile),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in caches],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem, vmem] + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            scratch_shapes=[
                pltpu.VMEM((2, layers, tile, c.shape[-1]), c.dtype)
                for c in caches
            ] + [pltpu.SemaphoreType.DMA((2, 2, 2))],
        ),
        input_output_aliases={4: 0, 5: 1},
        interpret=jax.default_backend() != "tpu",
        name="paged_kv_write_rows",
    )(page_ids.astype(jnp.int32), slot_of.astype(jnp.int32), *srcs, *caches)
    return k_cache.reshape(shapes[0]), v_cache.reshape(shapes[1])


def paged_write(
    k_cache: jax.Array,  # [L, P, S, Hkv, D]
    v_cache: jax.Array,
    k_stage: jax.Array,  # [L, B, T, Hkv, D] — per-layer staged new KV
    v_stage: jax.Array,
    page_tables: jax.Array,  # [B, MP] int32
    positions: jax.Array,  # [B, T] int32 absolute positions
    valid: jax.Array,  # [B, T] bool
    *,
    use_kernel: bool | None = None,
    mesh=None,
    k_scale: jax.Array | None = None,  # [L, P, Hkv, S'] f32 (quantized pools)
    v_scale: jax.Array | None = None,
):
    """Write one step's staged KV for all layers into the caches in place.

    Returns (k_cache, v_cache) or, with scale planes,
    (k_cache, v_cache, k_scale, v_scale).

    Requires T == 1 (decode) or page-aligned chunk starts with T a multiple
    of min(T, S) (prefill — guaranteed by the scheduler's page-aligned
    chunking). `use_kernel` defaults to True on TPU. Under a tp mesh the
    kernel is shard_mapped: staging and cache both shard on the kv-head
    axis, every shard writes its own lanes of the same rows.

    valid=False lanes redirect to page 0 (the engine's reserved null
    page) instead of skipping the write: a padding row of a bucketed
    batch runs through the same program shape as a live one, its KV
    write lands in the null page, and no real page is touched.
    """
    quantized = k_scale is not None
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel and mesh is not None and mesh.shape.get("tp", 1) > 1:
        from functools import partial

        from jax.sharding import PartitionSpec as P

        kv_spec = P(None, None, None, "tp", None)
        scale_spec = P(None, None, "tp", None)
        in_specs = [
            kv_spec, kv_spec, kv_spec, kv_spec,
            P(None, None), P(None, None), P(None, None),
        ]
        out_specs = [kv_spec, kv_spec]
        if quantized:
            in_specs += [scale_spec, scale_spec]
            out_specs += [scale_spec, scale_spec]

        def sharded(kc, vc, ks_st, vs_st, pt, pos, vl, *scales):
            return paged_write(
                kc, vc, ks_st, vs_st, pt, pos, vl,
                use_kernel=True, mesh=None,
                k_scale=scales[0] if scales else None,
                v_scale=scales[1] if scales else None,
            )

        fn = jax.shard_map(
            sharded,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=tuple(out_specs),
            check_vma=False,
        )
        args = [k_cache, v_cache, k_stage, v_stage, page_tables, positions,
                valid]
        if quantized:
            args += [k_scale, v_scale]
        return fn(*args)
    L, b, t = k_stage.shape[0], k_stage.shape[1], k_stage.shape[2]
    s = k_cache.shape[2]

    # token-granular targets (invalid -> null page 0, slot 0): the XLA
    # scatter's, and the scale planes' on either path
    page_ids = jnp.take_along_axis(page_tables, positions // s, axis=1)
    page_ids = jnp.where(valid, page_ids, 0).reshape(-1)
    slot_of = jnp.where(valid, positions % s, 0).reshape(-1)

    if quantized:
        from dynamo_tpu.models.llama import quantize_kv_rows

        mode = "int8" if k_cache.dtype == jnp.int8 else "fp8"
        k_q, k_s = quantize_kv_rows(k_stage, mode)  # [L,B,T,Hkv,D], [L,B,T,Hkv]
        v_q, v_s = quantize_kv_rows(v_stage, mode)
        # advanced indices on the page and slot axes: updates are
        # [B*T, L, Hkv]
        k_scale = k_scale.at[:, page_ids, :, slot_of].set(
            k_s.reshape(L, b * t, -1).transpose(1, 0, 2), mode="drop"
        )
        v_scale = v_scale.at[:, page_ids, :, slot_of].set(
            v_s.reshape(L, b * t, -1).transpose(1, 0, 2), mode="drop"
        )
        scales = (k_scale, v_scale)
    else:
        k_q, v_q, scales = k_stage, v_stage, ()

    run = min(t, s)
    # A cache of one row a token (MQA; a latent cache, models/mla.py) has
    # its slots in the tiled (sublane, lane) dimensions, where Mosaic
    # cannot DMA part of a tile: runs of whole tiles of slots (a prefill
    # chunk: page-aligned, a T bucket long) go through the kernel below
    # (as [L, P, S, D], the same bytes), a decode step's single rows
    # through `_rows_kernel`. Anything else goes through the scatter,
    # which on a TPU copies the whole pool into a layout of its own: no
    # shape the engine sends with the default page size gets there.
    one_row = k_cache.shape[3] == 1
    tile = 32 // jnp.dtype(k_cache.dtype).itemsize  # slots a sublane tile
    if use_kernel and one_row and t == 1 and s % tile == 0:
        return _write_single_rows(
            k_cache, v_cache, k_q, v_q, page_ids, slot_of
        )
    if one_row and (run % tile or s % tile):
        use_kernel = False
    if not use_kernel:
        # XLA scatter fallback (CPU, meshes): token-granular, one 5D
        # advanced-index scatter per cache.
        ks = k_q.reshape(L, b * t, *k_q.shape[3:])
        vs = v_q.reshape(v_q.shape[0], b * t, *v_q.shape[3:])
        k_cache = k_cache.at[:, page_ids, slot_of].set(
            ks.astype(k_cache.dtype), mode="drop"
        )
        v_cache = v_cache.at[:, page_ids, slot_of].set(
            vs.astype(v_cache.dtype), mode="drop"
        )
        return (k_cache, v_cache, *scales)

    assert t % run == 0, f"chunk T={t} must be a multiple of run={run}"
    runs_per_seq = t // run
    nr = b * runs_per_seq
    # First token of each run determines its page/slot; invalid -> null.
    first_pos = positions[:, ::run]  # [B, T//R]
    first_valid = valid[:, ::run]
    run_pages = jnp.take_along_axis(page_tables, first_pos // s, axis=1)
    run_pages = jnp.where(first_valid, run_pages, 0).reshape(-1)
    run_slots = jnp.where(first_valid, first_pos % s, 0).reshape(-1)

    # K and V rows may differ in width (a latent cache: models/mla.py) and
    # in how many layers they stand in (lane parts: models/mimo_v2.py)
    k_src = k_q.reshape(L, nr, run, *k_stage.shape[3:]).astype(k_cache.dtype)
    v_src = v_q.reshape(
        v_q.shape[0], nr, run, *v_stage.shape[3:]).astype(v_cache.dtype)
    shapes = k_cache.shape, v_cache.shape
    if one_row:
        k_src, v_src, k_cache, v_cache = (
            x.reshape(*x.shape[:3], x.shape[4])
            for x in (k_src, v_src, k_cache, v_cache)
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    k_cache, v_cache = pl.pallas_call(
        functools.partial(
            _write_kernel, num_runs=nr, run=run,
            align=tile if one_row else 0,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        grid_spec=grid_spec,
        # operands: pages, slots, k_src, v_src, k_cache, v_cache — the
        # caches alias the outputs, keeping both pools in place
        input_output_aliases={4: 0, 5: 1},
        interpret=jax.default_backend() != "tpu",
        name="paged_kv_write",
    )(
        run_pages.astype(jnp.int32),
        run_slots.astype(jnp.int32),
        k_src,
        v_src,
        k_cache,
        v_cache,
    )
    return (k_cache.reshape(shapes[0]), v_cache.reshape(shapes[1]), *scales)
