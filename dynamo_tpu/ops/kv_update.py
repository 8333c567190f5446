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
):
    del k_in_ref, v_in_ref
    pairs = ((k_src_ref, k_out_ref), (v_src_ref, v_out_ref))

    def copies(i):
        return tuple(
            pltpu.make_async_copy(
                src.at[:, i], dst.at[:, pages_ref[i], pl.ds(slots_ref[i], run)],
                sem,
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
    page) instead of skipping the write — that redirect is what lets the
    fused K-step decode window (EngineConfig.decode_kstep) freeze
    finished rows MID-WINDOW entirely on device: a frozen row keeps
    dispatching through the same program shape, its KV writes land in
    the null page, and its real pages are untouched for the next owner.
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

    if not use_kernel:
        # XLA scatter fallback (CPU, meshes): token-granular, one 5D
        # advanced-index scatter per cache.
        ks = k_q.reshape(L, b * t, *k_q.shape[3:])
        vs = v_q.reshape(L, b * t, *v_q.shape[3:])
        k_cache = k_cache.at[:, page_ids, slot_of].set(
            ks.astype(k_cache.dtype), mode="drop"
        )
        v_cache = v_cache.at[:, page_ids, slot_of].set(
            vs.astype(v_cache.dtype), mode="drop"
        )
        return (k_cache, v_cache, *scales)

    run = min(t, s)
    assert t % run == 0, f"chunk T={t} must be a multiple of run={run}"
    runs_per_seq = t // run
    nr = b * runs_per_seq
    # First token of each run determines its page/slot; invalid -> null.
    first_pos = positions[:, ::run]  # [B, T//R]
    first_valid = valid[:, ::run]
    run_pages = jnp.take_along_axis(page_tables, first_pos // s, axis=1)
    run_pages = jnp.where(first_valid, run_pages, 0).reshape(-1)
    run_slots = jnp.where(first_valid, first_pos % s, 0).reshape(-1)

    shape_tail = k_stage.shape[3:]
    k_src = k_q.reshape(L, nr, run, *shape_tail).astype(k_cache.dtype)
    v_src = v_q.reshape(L, nr, run, *shape_tail).astype(v_cache.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    k_cache, v_cache = pl.pallas_call(
        functools.partial(_write_kernel, num_runs=nr, run=run),
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
    return (k_cache, v_cache, *scales)
