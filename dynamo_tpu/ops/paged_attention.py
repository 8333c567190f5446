"""Pallas TPU paged-attention decode kernel.

Decode (T=1) attention over the paged KV history. The XLA fallback path
(models/llama.py:paged_attention) gathers the full per-sequence KV history
into a dense [B, K, Hkv, D] array in HBM before the matmuls — 2× the HBM
traffic (read pages, write gather, read gather) plus O(B·MP·S) memory. This
kernel streams pages HBM→VMEM with multi-buffered async DMA, accumulating a
flash-style online softmax. KV bytes are read exactly once, nothing is
materialized.

The work list is FLATTENED: one kernel invocation (grid=(1,)) walks every
(sequence, page) pair of the batch back to back, so the DMA pipeline stays
full across the whole batch. The round-3 per-sequence-grid design drained
its 2-deep pipeline at every grid-cell boundary — at decode batch 128 that
is 128 pipeline restarts per layer per step, and DMA issue latency (not
bandwidth) dominated the measured 13 ms/token-row vs the ~4 ms HBM
roofline (artifacts/tpu/decode_profile.json). Per-page flash merges are
order-independent (max/rescale/add), so each page read-modify-writes its
sequence's running (m, l, acc) rows in the VMEM outputs directly — no
carried state, no sequence-boundary flushes.

Cache layout is [L, P, S, Hkv, D] (models/llama.py KVPages): one (layer,
page) slice is a contiguous [S, Hkv, D] block, so a single DMA per page
feeds the compute for EVERY kv head. D is lane-padded to a 128 multiple
(LlamaConfig.kv_head_dim): Mosaic DMA slices must be 128-aligned in the
minor dimension.

The kernel reads HISTORY ONLY (tokens already written to pages — the
current token's KV is staged and written once per step by ops/kv_update).
It returns the UNNORMALIZED accumulator plus the softmax running max and
denominator (m, l), and the caller folds the current token in exactly:

    out = (e^{m-m*}·acc + e^{s_self-m*}·v_cur) / (e^{m-m*}·l + e^{s_self-m*})

Parity: replaces the paged-attention kernels the reference gets from vLLM /
TRT-LLM (engine-delegated, SURVEY.md §2.9); on TPU the engine is first-class
so the kernel lives here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: DMA pipeline depth (slots per k/v scratch). 4 hides issue latency well
#: past the 2-deep minimum while costing only 2 extra [S, Hkv, D] buffers.
_DEPTH = 4


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32 — layer of the stacked cache to read
    nwork_ref,  # [1] int32 — valid (sequence, page) work items
    order_ref,  # [B*MP] int32 — work item -> b*MP + page ordinal
    page_of_ref,  # [B*MP] int32 — work item -> physical page id
    len_ref,  # [B] int32 HISTORY lengths (tokens already in the cache)
    # then (positional, shape depends on `quantized`):
    #   q_ref,  # [B, HQ, D] VMEM (whole batch's queries, unscaled)
    #   k_ref,  # [L, P, S, Hkv, D] in HBM/ANY (narrow dtype when quantized)
    #   v_ref,
    #   [ks_ref, vs_ref]  # [L, P, Hkv, S'] f32 scale planes (quantized)
    # outputs (whole batch resident in VMEM; read-modify-written per page):
    #   acc_ref,  # [B, HQ, D] f32 — UNNORMALIZED flash accumulator
    #   m_ref,  # [B, HQ, 128] f32 — running max (lane-broadcast)
    #   l_ref,  # [B, HQ, 128] f32 — running denominator (lane-broadcast)
    # scratch:
    #   k_scr,  # [DEPTH, S, Hkv, D] VMEM
    #   v_scr,
    #   [ks_scr, vs_scr]  # [DEPTH, Hkv, S'] f32 VMEM (quantized)
    #   sem,  # [2 or 4, DEPTH] DMA semaphores: [plane, slot]
    *refs,
    page_size: int,
    scale_dim: int,
    num_kv_heads: int,
    max_pages: int,  # MP — decodes order_ref into (sequence, ordinal)
    quantized: bool,
):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref, m_ref, l_ref,
         k_scr, v_scr, ks_scr, vs_scr, sem) = refs
    else:
        (q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
         k_scr, v_scr, sem) = refs
        ks_ref = vs_ref = ks_scr = vs_scr = None
    li = layer_ref[0]
    n = nwork_ref[0]
    hq, d = q_ref.shape[1], q_ref.shape[2]
    g = hq // num_kv_heads
    s = page_size
    inv_scale = 1.0 / math.sqrt(scale_dim)

    # Rows never visited (zero history) must read as the empty-history
    # state the caller's merge expects: acc=0, m=-inf, l=0.
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    # one DMA plane per (cache/scale, slot); scale planes ride the same
    # pipeline as their pages — a page and its scales land together
    planes = [(k_ref, k_scr), (v_ref, v_scr)]
    if quantized:
        planes += [(ks_ref, ks_scr), (vs_ref, vs_scr)]

    def copies(slot, j):
        return tuple(
            pltpu.make_async_copy(
                src.at[li, page_of_ref[j]], dst.at[slot], sem.at[pi, slot]
            )
            for pi, (src, dst) in enumerate(planes)
        )

    # prime the pipeline: DEPTH-1 transfers in flight before compute starts
    for p in range(_DEPTH - 1):
        @pl.when(p < n)
        def _(p=p):
            for c in copies(p, p):
                c.start()

    def body(j, _):
        slot = jax.lax.rem(j, _DEPTH)

        @pl.when(j + _DEPTH - 1 < n)
        def _():
            nslot = jax.lax.rem(j + _DEPTH - 1, _DEPTH)
            for c in copies(nslot, j + _DEPTH - 1):
                c.start()

        for c in copies(slot, j):
            c.wait()

        oj = order_ref[j]
        bj = oj // max_pages
        hist = len_ref[bj]
        q = q_ref[bj].astype(jnp.float32) * inv_scale  # [HQ, D]
        kp = k_scr[slot].astype(jnp.float32)  # [S, Hkv, D]
        vp = v_scr[slot].astype(jnp.float32)
        if quantized:
            # dequantize in VMEM right after the DMA lands, folded into
            # the flash merge: a key row's scale multiplies its column of
            # the scores, a value row's its column of the weights — the
            # slot-minor planes are already lane-oriented like both, and
            # no fp page ever touches HBM
            ksc = ks_scr[slot][:, :s]  # [Hkv, S]
            vsc = vs_scr[slot][:, :s]
        key_pos = (oj % max_pages) * s + jax.lax.broadcasted_iota(
            jnp.int32, (g, s), 1
        )
        key_mask = key_pos < hist  # [G, S]

        m_old = m_ref[bj]  # [HQ, 128] (column 0 is the value)
        l_old = l_ref[bj]
        acc_old = acc_ref[bj]  # [HQ, D]

        # One DMA fed all heads; the per-head dots are small but the page
        # walk is DMA-bound, so their latency hides under the next copy.
        m_out, l_out, a_out = [], [], []
        for h in range(num_kv_heads):  # static unroll
            sl = slice(h * g, (h + 1) * g)
            qh = q[sl]  # [G, D]
            ms = m_old[sl, :1]  # [G, 1]
            ls = l_old[sl, :1]
            accs = acc_old[sl]  # [G, D]
            scores = jax.lax.dot_general(
                qh, kp[:, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, S]
            if quantized:
                scores = scores * ksc[h : h + 1]
            scores = jnp.where(key_mask, scores, -1e30)
            m_new = jnp.maximum(ms, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(ms - m_new)
            l_new = ls * corr + jnp.sum(p, axis=1, keepdims=True)
            pv = p * vsc[h : h + 1] if quantized else p
            a_new = accs * corr + jax.lax.dot_general(
                pv, vp[:, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_out.append(m_new)
            l_out.append(l_new)
            a_out.append(a_new)
        acc_ref[bj] = jnp.concatenate(a_out, axis=0)
        m_ref[bj] = jnp.broadcast_to(
            jnp.concatenate(m_out, axis=0), (hq, 128)
        )
        l_ref[bj] = jnp.broadcast_to(
            jnp.concatenate(l_out, axis=0), (hq, 128)
        )
        return 0

    jax.lax.fori_loop(0, n, body, 0)


def decode_work_list(
    page_tables: jax.Array,  # [B, MP] int32
    history_lens: jax.Array,  # [B] int32
    page_size: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Compacted (sequence, page) work list for the decode kernel:
    (n_work [1], order [B*MP], page_of [B*MP]) with valid pairs first in
    (b, i) order. `order` encodes both coordinates — the kernel derives
    b = order//MP, i = order%MP with two scalar ops instead of carrying
    two more [B*MP] prefetch arrays through SMEM.

    LAYER-INVARIANT: build it once per decode step and pass it to every
    layer's paged_decode_attention — inside the per-layer scan body XLA
    is not guaranteed to hoist the sort, and re-sorting B*MP elements per
    layer re-adds fixed per-layer overhead the flattened walk exists to
    remove."""
    mp = page_tables.shape[1]
    hist = history_lens.astype(jnp.int32)
    used = -(-hist // page_size)  # cdiv
    valid = jnp.arange(mp, dtype=jnp.int32)[None, :] < used[:, None]
    flat_valid = valid.reshape(-1)
    order = jnp.argsort(~flat_valid, stable=True).astype(jnp.int32)
    page_of = page_tables.reshape(-1).astype(jnp.int32)[order]
    n_work = flat_valid.sum(dtype=jnp.int32).reshape(1)
    return n_work, order, page_of


def decode_vmem_bytes(
    b: int, hq: int, d: int, s: int, hkv: int, itemsize: int,
    quantized: bool = False,
) -> int:
    """Kernel VMEM footprint estimate: whole-batch q + f32 acc/m/l blocks
    plus the DMA scratch and the per-slot f32 k/v cast temporaries
    (`kp`/`vp` in the kernel body — one slot's pages live in f32 while
    its scores/weights compute). Quantized pools add the f32 scale-plane
    scratch (and `itemsize` is the narrow dtype's — the scratch shrinks).
    The caller routes to the XLA gather when this exceeds the budget
    instead of letting Mosaic fail allocation."""
    scale_scratch = (
        2 * _DEPTH * hkv * (-(-s // 128) * 128) * 4 if quantized else 0
    )
    return (
        b * hq * d * itemsize  # q (itemsize of q ≈ cache dtype or wider)
        + b * hq * d * 4  # acc f32
        + 2 * b * hq * 128 * 4  # m, l f32 (lane-broadcast)
        + 2 * _DEPTH * s * hkv * d * itemsize  # k/v scratch
        + 2 * s * hkv * d * 4  # kp/vp f32 cast of the active slot
        + scale_scratch
    )


def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] post-rope decode queries (D may be padded)
    k_cache: jax.Array,  # [L, P, S, Hkv, D] — full stacked cache
    v_cache: jax.Array,  # [L, P, S, Hkv, D]
    layer: jax.Array,  # scalar int32 layer index
    page_tables: jax.Array,  # [B, MP] int32
    history_lens: jax.Array,  # [B] int32 — tokens already written to pages
    *,
    scale_dim: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    work_list=None,  # precomputed decode_work_list (layer-invariant)
    k_scale: jax.Array | None = None,  # [L, P, Hkv, S'] f32 (quantized pools)
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """History-only flash attention over the paged cache.

    Returns (acc [B, Hq, D] f32 unnormalized, m [B, Hq] f32, l [B, Hq] f32)
    for the caller to merge the current token (see module docstring).
    A sequence with history_lens == 0 yields acc=0, l=0, m=-inf — the merge
    then reduces to pure self-attention.

    With `k_scale`/`v_scale` the cache holds quantized rows; each page's
    scale plane DMAs alongside it and the rows dequantize in VMEM before
    the flash merge.

    `interpret` defaults to True off-TPU so tests run the same kernel on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    hkv, s = k_cache.shape[3], k_cache.shape[2]
    if work_list is None:
        work_list = decode_work_list(page_tables, history_lens, s)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        # Heads are embarrassingly parallel: shard_map the kernel over tp
        # (q/outputs on the head axis, caches on the kv-head axis) — each
        # shard walks the same pages for its own heads, no collectives.
        # The (replicated) work list rides along so shards don't re-sort.
        from functools import partial

        from jax.sharding import PartitionSpec as P

        def sharded(q_, k_, v_, layer_, pt_, hist_, n_, od_, pg_, *scales):
            return paged_decode_attention(
                q_, k_, v_, layer_, pt_, hist_,
                scale_dim=scale_dim, interpret=interpret, mesh=None,
                work_list=(n_, od_, pg_),
                k_scale=scales[0] if scales else None,
                v_scale=scales[1] if scales else None,
            )

        in_specs = [
            P(None, "tp", None),
            P(None, None, None, "tp", None),
            P(None, None, None, "tp", None),
            P(),
            P(),
            P(),
            P(),
            P(),
            P(),
        ]
        args = [q, k_cache, v_cache, layer, page_tables, history_lens,
                *work_list]
        if quantized:
            in_specs += [P(None, None, "tp", None), P(None, None, "tp", None)]
            args += [k_scale, v_scale]
        fn = jax.shard_map(
            sharded,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(None, "tp", None), P(None, "tp"), P(None, "tp")),
            check_vma=False,
        )
        return fn(*args)
    b, hq, d = q.shape
    mp = page_tables.shape[1]
    n_work, order, page_of = work_list

    in_specs = [
        pl.BlockSpec(
            (b, hq, d), lambda i, li, n, od, pg, ln: (0, 0, 0)
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch_shapes = [
        pltpu.VMEM((_DEPTH, s, hkv, d), k_cache.dtype),
        pltpu.VMEM((_DEPTH, s, hkv, d), v_cache.dtype),
    ]
    operands = [q, k_cache, v_cache]
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scratch_shapes += [
            pltpu.VMEM((_DEPTH, *k_scale.shape[2:]), jnp.float32),
            pltpu.VMEM((_DEPTH, *v_scale.shape[2:]), jnp.float32),
        ]
        operands += [k_scale, v_scale]
    scratch_shapes.append(
        pltpu.SemaphoreType.DMA((4 if quantized else 2, _DEPTH))
    )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(1,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (b, hq, d), lambda i, li, n, od, pg, ln: (0, 0, 0)
            ),
            pl.BlockSpec(
                (b, hq, 128), lambda i, li, n, od, pg, ln: (0, 0, 0)
            ),
            pl.BlockSpec(
                (b, hq, 128), lambda i, li, n, od, pg, ln: (0, 0, 0)
            ),
        ],
        scratch_shapes=scratch_shapes,
    )
    acc, m, l = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=s,
            scale_dim=scale_dim or d,
            num_kv_heads=hkv,
            max_pages=mp,
            quantized=quantized,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, 128), jnp.float32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode_attention",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        n_work,
        order,
        page_of,
        history_lens.astype(jnp.int32),
        *operands,
    )
    return acc, m[:, :, 0], l[:, :, 0]
