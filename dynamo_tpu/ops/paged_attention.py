"""Pallas TPU paged-attention decode kernel.

Decode (T=1) attention over the paged KV history. The XLA fallback path
(models/llama.py:paged_attention) gathers the full per-sequence KV history
into a dense [B, K, Hkv, D] array in HBM before the matmuls — 2× the HBM
traffic (read pages, write gather, read gather) plus O(B·MP·S) memory. This
kernel streams pages HBM→VMEM with double-buffered async DMA, accumulating a
flash-style online softmax. KV bytes are read exactly once, nothing is
materialized.

One kernel invocation (grid=(1,)) walks every row of the batch back to
back, so the DMA pipeline stays full across rows. A turn of the walk is a
(row, BLOCK of consecutive page ordinals): up to `_block_pages` pages are
DMA'd into one slot (only the pages that exist; a short last block masks
its tail like a partly filled page) and folded in a SUB-TILE of whole pages
at a time (`_tile_pages`; for most shapes the whole block): scored in one
dot and folded in with one softmax update. A row's blocks are adjacent, so
its running (m, l, acc) stays in registers and is written once.

Why blocks, measured on a v5e at qwen2-7b's decode shape (B 64, 28/4 heads
of 128, S 64, bf16, histories 256-1400, 110 MB of K/V a layer; PR 25,
scripts/paged_decode_bench.py, device time from a trace): the walk that
took one page a turn ran 810 us a layer; with its DMAs issued and waited
and no arithmetic, 161 us; with its arithmetic on a resident slot and no
DMA, 802 us. The body set the pace, not the copies (the 13 ms against 4 ms
this docstring used to cite was a CPU run). Each turn cast a [64, 4, 128]
page to f32, gathered each kv head's rows across sublanes, issued eight
7-row f32 dots and read-modify-wrote the row's state in VMEM. Now, at 8
pages a block: 172 us as it is, 156 us DMA only, 89 us arithmetic only —
bound by its copies, at 78 % of the 134 us the chip needs to read the bytes
at 819 GB/s (phi3-mini's and llama3-8b's shapes: 86 and 89 %). 4 pages a
block gave 197 us, 2 gave 267; a third slot gave nothing.

Why sub-tiles, measured at Command A+'s shape (B 32, 128/8 heads of 128,
S 64, bf16: a page is 256 KiB of K+V and a [128, 512] f32 score tile;
histories 8,200-18,000, 1.75 GB a layer; PR 55, the same script, its
`--dma-only` / `--arith-only`): at ONE page a block, which is what the
budget left when the temporaries were reckoned a block wide, 4,095 us as
it is, 2,620 DMA only, 2,742 arithmetic only, against a floor of 2,139:
neither half is slow, they ADD. One 256 KiB copy in flight does not hide
its own latency, and the score product, the softmax chain and the value
product of the one tile hang on each other. 4 pages as ONE [128, 2048]
tile cost the TPU's compiler 165 s a step program (PR 52). 4 pages folded
a page at a time: 2,696 us as it is (79 % of the floor; 2 pages 3,189, 8
pages 2,411), 2,327 DMA only, 2,082 arithmetic only: the body under the
floor, because one sub-tile's products run beside another's chain. The
same walk of a ring under a bit a row (65 pages a row): 1,274 -> 871 us.
A sub-tile is as many whole pages as keep [Hq padded, columns] within
`_MAX_TILE_SCORES` (65,536: 32 heads x a 2,048-column block, 128 heads x
one 512-column page) and divide the block, so at 32 or fewer query heads
the block is one sub-tile and the program is what it was.

Cache layout is [L, P, S, Hkv, D] (models/llama.py KVPages): one (layer,
page) slice is a contiguous [S, Hkv, D] block, so a single DMA per page
feeds the compute for EVERY kv head. The kernel takes it as [S*Hkv, D]
rows (r = slot*Hkv + h; a bitcast, the same bytes), so a slot holds whole
(sublane, 128) tiles and no operand is relaid: ONE dot scores all query
heads against all of a block's rows, and a head keeps its own kv head's
columns by mask (Hkv times the MXU work on a unit that was idle, instead of
Hkv gathers). bf16 pools go to the MXU as they are, q/sqrt(d) and the
softmax weights in bf16 beside them (one pass each; the operand precision
the chip's default f32 dot gave the old body); products accumulate in f32,
and scores, m, l, exp and acc are f32.
D is lane-padded to a 128 multiple (LlamaConfig.kv_head_dim): Mosaic DMA
slices must be 128-aligned in the minor dimension.

A head whose key is wider than its value and no multiple of 128 lanes
(models/mimo_v2.py: 192 | 128) keeps the same layout in LANE PARTS (the
wrapper's `parts`): two KV heads side by side, each 128-lane tile of the
pair a pool entry of its own, so every pool stays [.., Hkv / 2, 128] and the
bitcast above stays one; a page's parts are as many DMAs into the lanes of
one slot, and the body is the same code over pair-heads.

The block size follows from the shapes (`_block_pages`): as many pages as
reach 1 MiB of K+V, at most 8, at most 2048 key columns, halved while
`_footprint` would pass the caller's VMEM budget. `_footprint` is what
`decode_vmem_bytes` reports: the whole-batch q, acc and m|l blocks (counted
twice, as a pipeline of more than one step would hold them; the compile
for a described v5e fits 32 rows x 128 heads at 4 pages a block under a
7 MiB limit, where this reads 13), two K and two V slots, the scale slots,
and four [Hq, columns] 32-bit temporaries of a SUB-TILE. A walk that folds
its block in several sub-tiles says so once a shape, at trace time.

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
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: DMA pipeline depth, counted in BLOCKS: one block computes while the next
#: lands. A block is 0.25-1 MiB, so two slots already hide the issue latency
#: that took four one-page slots.
_DEPTH = 2
#: pages a block holds at most, and the K+V bytes it aims for: past ~1 MiB
#: a slot only costs VMEM, and a row rarely has more pages to give
_MAX_BLOCK_PAGES = 8
_BLOCK_BYTES = 1 << 20
#: key columns one block may spread over
_MAX_BLOCK_COLUMNS = 2048
#: scores folded at once, [Hq padded, columns] in f32: 32 heads over a whole
#: block of 2048 columns, 128 heads over 512. A block with more is folded a
#: sub-tile of whole pages at a time (`_tile_pages`)
_MAX_TILE_SCORES = 1 << 16
#: masked scores; finite so that a fully masked (padded) query row stays
#: NaN-free
_MASKED = -1e30
#: scripts/paged_decode_bench.py's split of a turn, assigned before the
#: trace and by nothing that serves: "copies" leaves a block's DMAs and
#: drops its arithmetic, "body" the arithmetic on a resident slot
_PROBE = None
#: shapes whose walk in sub-tiles was already told
_told_sub_tiles: set = set()


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _footprint(
    pb: int, b: int, hq: int, d: int, s: int, hkv: int, itemsize: int,
    quantized: bool, rope_dim: int = 0, dv: int | None = None,
) -> int:
    """VMEM bytes one kernel call whose blocks hold `pb` pages is planned
    to take: the whole-batch q / acc / m+l blocks, counted twice (what a
    pipeline of several steps would hold; an upper bound at this grid of
    one, module text), `_DEPTH` K and V slots, the scale slots of a
    quantized pool, and the live temporaries of a sub-tile of the block. A
    latent cache (`rope_dim` > 0) has a `d`-wide page that is key and value
    at once and a `rope_dim`-wide rope-key page beside it; `dv` is the
    width of a GQA cache's value where it is not the key's `d`."""
    dv = d if dv is None else dv
    hqp = _round_up(hq, 8)
    n = pb * s * hkv  # key columns of a block
    tn = _tile_pages(pb, hq, s * hkv) * s * hkv  # and of a sub-tile of it
    sub = 32 // itemsize  # sublane tile of the cache dtype
    kv_width = d + rope_dim if rope_dim else d + dv  # of one cached token
    whole_batch = 2 * b * _round_up(hqp, sub) * (d + rope_dim) * itemsize  # q
    if rope_dim:
        whole_batch += 2 * b * hqp * (d + 128) * 4  # acc, and m|l
    else:
        whole_batch += 2 * b * hqp * (dv + d) * 4  # acc, and m|l (as `d`)
    slots = _DEPTH * _round_up(n, sub) * kv_width * itemsize
    # limit, scores and p in 32 bits, p again for the MXU
    temps = 4 * hqp * tn * 4
    if itemsize != 2:
        temps += n * kv_width * 4  # K and V of the slot cast for the MXU
    if quantized:
        lanes = _round_up(s, 128)
        slots += 2 * _DEPTH * pb * _round_up(hkv, 8) * lanes * 4
        temps += lanes * s * hkv * 2  # one-hot slot -> column expander
        # its three-part operand and product, for K's and V's planes
        temps += 4 * 3 * pb * _round_up(hkv, 8) * s * hkv * 4
    return whole_batch + slots + temps


def _tile_pages(pb: int, hq: int, rpp: int, lane_tiles: bool = False) -> int:
    """Pages of a block of `pb` folded at once: the most that divide the
    block and keep [Hq padded, columns] within `_MAX_TILE_SCORES`, never
    under one page of `rpp` columns. `lane_tiles`: a sub-tile's columns are
    whole lane tiles (its bits are read at that offset), else the block is
    one tile."""
    most = max(1, _MAX_TILE_SCORES // (_round_up(hq, 8) * rpp))
    tile = max(t for t in range(1, pb + 1) if pb % t == 0 and t <= most)
    return pb if lane_tiles and (tile * rpp) % 128 else tile


def _block_pages(
    b: int, hq: int, d: int, s: int, hkv: int, itemsize: int,
    quantized: bool, budget: int | None, rope_dim: int = 0,
    dv: int | None = None,
) -> int:
    """Pages per block, from the shapes alone: as many as reach
    `_BLOCK_BYTES` of K+V, at most `_MAX_BLOCK_PAGES`, no more key columns
    than `_MAX_BLOCK_COLUMNS`; then halved while the call would not fit
    `budget` (a large batch's q/acc blocks leave less for the slots). `dv`:
    the value's width where it is not the key's (`_footprint`)."""
    dv = d if dv is None else dv
    page_bytes = s * hkv * (d + rope_dim if rope_dim else d + dv) * itemsize
    pb = max(1, min(
        _MAX_BLOCK_PAGES, _BLOCK_BYTES // page_bytes,
        _MAX_BLOCK_COLUMNS // (s * hkv),
    ))
    while (
        budget is not None and pb > 1
        and _footprint(
            pb, b, hq, d, s, hkv, itemsize, quantized, rope_dim, dv
        ) > budget
    ):
        pb //= 2
    return pb


def _decode_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32 — layer of the stacked cache to read
    nrows_ref,  # [1] int32 — rows with history
    rows_ref,  # [B] int32 — those rows first, in batch order
    pt_ref,  # [B*MP] int32 — the page tables, flat
    len_ref,  # [B] int32 HISTORY lengths (tokens already in the cache)
    # then (positional, shape depends on `quantized`):
    #   q_ref,  # [B, HQP, D] VMEM (whole batch's queries, unscaled; the
    #           # head axis padded to a sublane tile)
    #   k_ref,  # [L, P, S*Hkv, D] in HBM/ANY (narrow dtype when quantized)
    #   v_ref,
    #   [ks_ref, vs_ref]  # [L, P, Hkv, S'] f32 scale planes (quantized)
    #   [bits_ref]  # [B, blocks * N] int32 VMEM (`token_bits`): which key
    #               # columns of each row's blocks the row attends
    # outputs (whole batch resident in VMEM; a row is written once):
    #   acc_ref,  # [B, HQP, D] f32 — UNNORMALIZED flash accumulator
    #   ml_ref,  # [B, HQP, 128] f32 — lane 0 running max, the rest the
    #            # running denominator
    # scratch:
    #   k_scr,  # [DEPTH, PB*S*Hkv, D] VMEM: a slot is a block of pages
    #   v_scr,
    #   [ks_scr, vs_scr]  # [DEPTH, PB*SUB, S'] f32 VMEM (quantized)
    #   sem,  # [2 or 4, DEPTH] DMA semaphores: [plane, slot]
    *refs,
    page_size: int,
    inv_scale: float,  # what the scores are multiplied by
    num_q_heads: int,
    num_kv_heads: int,
    max_pages: int,  # MP — row stride of pt_ref
    block_pages: int,
    tile_pages: int,  # pages of a block folded at once: they divide it
    quantized: bool,
    latent: bool,
    token_bits: bool = False,
    parts: tuple = (1, 1),
):
    bits_ref = None
    if token_bits:
        (q_ref, k_ref, v_ref, bits_ref, acc_ref, ml_ref,
         k_scr, v_scr, sem) = refs
        ks_ref = vs_ref = ks_scr = vs_scr = None
    elif quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref, ml_ref,
         k_scr, v_scr, ks_scr, vs_scr, sem) = refs
    else:
        (q_ref, k_ref, v_ref, acc_ref, ml_ref, k_scr, v_scr, sem) = refs
        ks_ref = vs_ref = ks_scr = vs_scr = None
    li = layer_ref[0]
    n_rows = nrows_ref[0]
    bsz, hqp, _ = q_ref.shape
    d = acc_ref.shape[2]  # the value's width: a latent's is its key's too
    dk = k_scr.shape[2]  # what of q the keys are scored by (a latent q
    # carries its rope part past it)
    hkv, s, pb = num_kv_heads, page_size, block_pages
    g = num_q_heads // hkv
    rpp = s * hkv  # cache rows (= key columns) of one page: r = slot*Hkv + h
    n = pb * rpp
    tn = tile_pages * rpp  # key columns folded at once
    sub = _round_up(hkv, 8)  # scale rows a page takes in its slot
    # What the MXU is fed: a bf16 pool under bf16 queries goes in as it is
    # and narrow pools convert exactly; q/sqrt(d) and the softmax weights
    # are rounded to bf16 on the way in, which is what the chip's default-
    # precision f32 dot made of them before (PR 25 measured that kernel
    # 1e-3 off a dense f32 reference on the chip, 4e-7 interpreted). The
    # products accumulate in f32; an f32 cache keeps f32 operands.
    narrow = jnp.dtype(k_scr.dtype).itemsize <= 2 and q_ref.dtype == jnp.bfloat16
    mxu = jnp.bfloat16 if narrow else jnp.float32

    def to_mxu(x):  # int8/fp8 rows convert through f32
        return x if x.dtype == mxu else x.astype(jnp.float32).astype(mxu)

    # Rows never visited (zero history) must read as the empty-history
    # state the caller's merge expects: acc=0, m=-inf, l=0. The slots start
    # finite: a short last block leaves pages unfetched, and 0 * stale NaN
    # would reach acc through the weights' masked columns.
    lane = jax.lax.broadcasted_iota(jnp.int32, (hqp, 128), 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ml_ref[...] = jnp.broadcast_to(
        jnp.where(lane == 0, -jnp.inf, 0.0), ml_ref.shape
    )
    k_scr[...] = jnp.zeros_like(k_scr)
    v_scr[...] = jnp.zeros_like(v_scr)
    if quantized:
        ks_scr[...] = jnp.zeros_like(ks_scr)
        vs_scr[...] = jnp.zeros_like(vs_scr)

    # Column c of a sub-tile is cache row c of it: key position c // Hkv
    # past its first, kv head c % Hkv. One dot scores every query head
    # against every column; `limit` keeps a head's own columns (position
    # where the heads match, else out of reach), so one compare against
    # the tokens left masks other heads and the tail together.
    col = jax.lax.broadcasted_iota(jnp.int32, (hqp, tn), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (hqp, tn), 0)
    col_pos = jax.lax.div(col, hkv)
    col_head = col - col_pos * hkv
    own = (col_head * g <= row) & (row < (col_head + 1) * g)
    limit = jnp.where(own, col_pos, jnp.int32(1 << 30))

    if quantized:
        # Scales arrive head-major, slot-minor ([Hkv, S'] a page) while the
        # columns run slot-major: a one-hot [S', S*Hkv] takes slot s to its
        # Hkv columns on the MXU (three bf16 parts of the f32 scale, each
        # product exact), and the head's own row is picked per column.
        sl = ks_scr.shape[2]
        e_slot = jax.lax.broadcasted_iota(jnp.int32, (sl, rpp), 0)
        e_col = jax.lax.broadcasted_iota(jnp.int32, (sl, rpp), 1)
        expand = (
            (e_slot * hkv <= e_col) & (e_col < (e_slot + 1) * hkv)
        ).astype(jnp.bfloat16)
        p_row = jax.lax.broadcasted_iota(jnp.int32, (sub, rpp), 0)
        p_col = jax.lax.broadcasted_iota(jnp.int32, (sub, rpp), 1)
        pick = p_row == p_col - jax.lax.div(p_col, hkv) * hkv

        def column_scales(plane):  # [PB*SUB, S'] f32 -> [1, N]
            hi = plane.astype(jnp.bfloat16).astype(jnp.float32)
            r1 = plane - hi
            mid = r1.astype(jnp.bfloat16).astype(jnp.float32)
            parts = jnp.concatenate([hi, mid, r1 - mid], axis=0)
            out = jax.lax.dot_general(
                parts.astype(jnp.bfloat16), expand,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [3*PB*SUB, S*Hkv]
            rows_ = pb * sub
            out = out[:rows_] + out[rows_ : 2 * rows_] + out[2 * rows_ :]
            return jnp.concatenate(
                [
                    jnp.sum(
                        jnp.where(pick, out[p * sub : (p + 1) * sub], 0.0),
                        axis=0, keepdims=True,
                    )
                    for p in range(pb)
                ],
                axis=1,
            )

    # one DMA plane per (cache/scale); a page and its scales land together.
    # A cache in `parts` (the wrapper's text) is a plane a part: part t of
    # layer li is entry `t x layers + li` and lands in the slot's lanes
    # `t x 128` on
    planes = [
        (src, dst, rpp, rpp,
         None if n_parts == 1 else (t * 128, t * (src.shape[0] // n_parts)))
        for src, dst, n_parts in ((k_ref, k_scr, parts[0]),
                                  (v_ref, v_scr, parts[1]))
        for t in range(n_parts)]
    if quantized:
        planes += [(ks_ref, ks_scr, sub, hkv, None),
                   (vs_ref, vs_scr, sub, hkv, None)]

    def block_copies(b, kb, slot, act):
        """Start or wait the DMAs of block `kb` of row `b`: one per plane
        and page that exists, into its place in the slot."""
        first = kb * pb

        def page_copies(p, _):
            page = pt_ref[b * max_pages + first + p]
            for pi, (src, dst, stride, rows_, part) in enumerate(planes):
                at = pl.multiple_of(p * stride, stride)
                source, into = src.at[li, page], dst.at[slot, pl.ds(at, rows_)]
                if part is not None:  # (its lanes of the slot, its layers)
                    source = src.at[li + part[1] if part[1] else li, page]
                    into = dst.at[slot, pl.ds(at, rows_), pl.ds(part[0], 128)]
                act(pltpu.make_async_copy(source, into, sem.at[pi, slot]))
            return 0

        pages = -(-len_ref[b] // s)
        jax.lax.fori_loop(0, jnp.minimum(pb, pages - first), page_copies, 0)

    copies = _PROBE != "body"

    @pl.when((n_rows > 0) if copies else False)
    def _():
        block_copies(rows_ref[0], 0, 0, lambda c: c.start())

    def row_body(ri, it0):
        b = rows_ref[ri]
        hist = len_ref[b]
        n_blk = -(-hist // (pb * s))
        # the block after this row's last: the next row's first
        b_next = rows_ref[jnp.minimum(ri + 1, bsz - 1)]
        q = (q_ref[b].astype(jnp.float32) * inv_scale).astype(mxu)  # [HQP, D]

        def block_body(kb, carry):
            m, l, acc = carry
            slot = jax.lax.rem(it0 + kb, _DEPTH)
            nslot = jax.lax.rem(it0 + kb + 1, _DEPTH)
            in_row = kb + 1 < n_blk

            @pl.when((in_row | (ri + 1 < n_rows)) if copies else False)
            def _():
                block_copies(
                    jnp.where(in_row, b, b_next),
                    jnp.where(in_row, kb + 1, 0),
                    nslot, lambda c: c.start(),
                )

            if copies:
                block_copies(b, kb, slot, lambda c: c.wait())
            if _PROBE == "copies":
                return carry

            # the block folded a sub-tile at a time, each its own score
            # product, softmax update and value product: no temporary is
            # wider than a sub-tile, and one's products run beside the
            # chain of another
            for t in range(pb // tile_pages):
                cols = slice(None) if tn == n else slice(t * tn, (t + 1) * tn)
                k_blk = to_mxu(k_scr[slot, cols])
                scores = jax.lax.dot_general(
                    q[:, :dk], k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [HQP, TN]
                if latent:
                    # the page in k_scr is the latent: scored here, summed
                    # as the value below; v_scr holds the shared rope key
                    scores += jax.lax.dot_general(
                        q[:, dk:], to_mxu(v_scr[slot, cols]),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                if quantized:
                    if t == 0:  # a block's scales expand at once
                        k_scales = column_scales(ks_scr[slot])
                    scores = scores * k_scales[:, cols]
                # the sub-tile's first key position (`if t`: a block of
                # one sub-tile traces the operations it always did)
                first = kb * (pb * s)
                if t:
                    first = first + t * (tile_pages * s)
                scores = jnp.where(limit < hist - first, scores, _MASKED)
                if token_bits:
                    # a row that attends CHOSEN tokens (ops/token_select.py):
                    # a block with none of them leaves sums of no meaning
                    # that the first chosen key's correction wipes
                    at = kb * n + t * tn if t else kb * n
                    at = pl.multiple_of(at, tn)
                    keep = bits_ref[pl.ds(b, 1), pl.ds(at, tn)]
                    scores = jnp.where(keep != 0, scores, _MASKED)
                m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
                p = jnp.exp(scores - m_new)
                corr = jnp.exp(m - m_new)
                l = l * corr + jnp.sum(p, axis=1, keepdims=True)
                if quantized:
                    if t == 0:
                        v_scales = column_scales(vs_scr[slot])
                    p = p * v_scales[:, cols]
                pv = jax.lax.dot_general(
                    p.astype(mxu),
                    k_blk if latent else to_mxu(v_scr[slot, cols]),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [HQP, D]
                m, acc = m_new, acc * corr + pv
            return m, l, acc

        # a row's running state stays in registers from block to block
        m, l, acc = jax.lax.fori_loop(
            0, n_blk, block_body,
            (
                jnp.full((hqp, 1), -jnp.inf, jnp.float32),
                jnp.zeros((hqp, 1), jnp.float32),
                jnp.zeros((hqp, d), jnp.float32),
            ),
        )
        acc_ref[b] = acc
        ml_ref[b] = jnp.where(lane == 0, m, l)
        return it0 + n_blk

    jax.lax.fori_loop(0, n_rows, row_body, 0)


def decode_work_list(
    page_tables: jax.Array,  # [B, MP] int32
    history_lens: jax.Array,  # [B] int32
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """What the decode kernel walks: (n_rows [1], rows [B], pages [B*MP]):
    the rows that have history, first and in batch order, and the page
    tables flat. The kernel takes a row's pages in blocks and derives
    every (row, block) from these and the history lengths with scalar
    arithmetic, so the list does not depend on how the kernel blocks.

    LAYER-INVARIANT: build it once per decode step and pass it to every
    layer's paged_decode_attention — inside the per-layer scan body XLA
    is not guaranteed to hoist the sort."""
    has_history = history_lens.astype(jnp.int32) > 0
    rows = jnp.argsort(~has_history, stable=True).astype(jnp.int32)
    n_rows = has_history.sum(dtype=jnp.int32).reshape(1)
    return n_rows, rows, page_tables.reshape(-1).astype(jnp.int32)


def decode_vmem_bytes(
    b: int, hq: int, d: int, s: int, hkv: int, itemsize: int,
    quantized: bool = False, budget: int | None = None, rope_dim: int = 0,
) -> int:
    """Kernel VMEM footprint estimate at the block size the kernel would
    take under `budget` (see `_footprint`). The caller routes to the XLA
    gather when even one page a block exceeds the budget, instead of
    letting Mosaic fail allocation."""
    pb = _block_pages(
        b, hq, d, s, hkv, itemsize, quantized, budget, rope_dim
    )
    return _footprint(pb, b, hq, d, s, hkv, itemsize, quantized, rope_dim)


def paged_decode_attention(
    q: jax.Array,  # [B, Hq, D] post-rope decode queries (D may be padded)
    k_cache: jax.Array,  # [L, P, S, Hkv, D] — full stacked cache
    v_cache: jax.Array,  # [L, P, S, Hkv, D]
    layer: jax.Array,  # scalar int32 layer index
    page_tables: jax.Array,  # [B, MP] int32
    history_lens: jax.Array,  # [B] int32 — tokens already written to pages
    *,
    scale_dim: int | None = None,
    scale: float | None = None,  # the scores' factor; default 1/sqrt(scale_dim)
    latent: bool = False,  # k_cache is key AND value, v_cache the rope key
    interpret: bool | None = None,
    mesh=None,
    work_list=None,  # precomputed decode_work_list (layer-invariant)
    k_scale: jax.Array | None = None,  # [L, P, Hkv, S'] f32 (quantized pools)
    v_scale: jax.Array | None = None,
    vmem_budget: int | None = None,  # the caller's, as in decode_vmem_bytes
    token_bits: jax.Array | None = None,  # [B, MP * S] bool: keys attended
    parts: tuple[int, int] = (1, 1),  # 128-lane parts of a K and a V row
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """History-only flash attention over the paged cache.

    Returns (acc [B, Hq, D] f32 unnormalized, m [B, Hq] f32, l [B, Hq] f32)
    for the caller to merge the current token (see module docstring).
    A sequence with history_lens == 0 yields acc=0, l=0, m=-inf — the merge
    then reduces to pure self-attention.

    With `k_scale`/`v_scale` the cache holds quantized rows; each page's
    scale plane DMAs alongside it and scales the scores' and the weights'
    columns after the dots.

    `latent` walks a latent cache (models/mla.py): `k_cache` [L, P, S, 1, C]
    holds the compressed latent, which is key and value at once, `v_cache`
    [L, P, S, 1, R] the rope key every head shares, and `q` is [B, Hq, C+R]
    (the absorbed latent query, then its rope part). A page is read once
    and used for both the C+R-wide score dots and the C-wide value sum;
    acc comes back [B, Hq, C]. Same work list, same block rule.

    `token_bits` walks the same pages and attends only the cached tokens
    it names (a bit a (row, position); models/keye_vl.py over GQA rows and
    models/dots3.py over a latent, whose indexers choose them): resident
    in VMEM as one int32 a key column.

    `parts` = (kp, vp) walks a cache whose key and value differ in width
    and are no multiple of 128 lanes a head (models/mimo_v2.py: 192 | 128):
    TWO KV heads' rows side by side are `kp` lane tiles of key and `vp` of
    value, each tile a pool entry of its own: `k_cache` [kp x L, P, S, Hkv /
    2, 128] holds part t of layer `layer` at `t x L + layer` (`v_cache`
    likewise), so every pool is 128 wide, nothing is padded and the page
    writer takes them as they are. A part lands in its lanes of the block's
    slot, so the body sees ONE pair-head of `kp x 128` key and `vp x 128`
    value columns: `q` is [B, Hq, kp x 128], a head's query in its own
    head's half of the pair and zeros in the other, `acc` comes back [B, Hq,
    vp x 128] with the head's output in its own half, and `Hkv / 2` pair-
    heads take the place of the KV heads everywhere else (the bits' columns,
    the block rule). The default is one part each: the code it was.

    `interpret` defaults to True off-TPU so tests run the same kernel on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    kp, vp = parts
    if parts != (1, 1) and (quantized or latent or mesh is not None or any(
            c.shape[4] != 128 or c.shape[0] % n
            for c, n in ((k_cache, kp), (v_cache, vp)))):
        raise ValueError(
            "parts: unquantized GQA pools [parts x L, P, S, Hkv / 2, 128] on "
            f"one chip; got {k_cache.shape}, {v_cache.shape} for {parts}")
    if token_bits is not None and (quantized or (
            mesh is not None and mesh.shape.get("tp", 1) > 1)):
        raise ValueError(
            "token_bits: an unquantized cache (GQA rows or a latent) on one "
            "chip; quantized pages and a tp mesh walk without bits")
    hkv, s = k_cache.shape[3], k_cache.shape[2]
    if work_list is None:
        work_list = decode_work_list(page_tables, history_lens)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        # Heads are embarrassingly parallel: shard_map the kernel over tp
        # (q/outputs on the head axis, caches on the kv-head axis) — each
        # shard walks the same pages for its own heads, no collectives.
        # The (replicated) work list rides along so shards don't re-sort.
        from jax.sharding import PartitionSpec as P

        def sharded(q_, k_, v_, layer_, pt_, hist_, n_, rows_, pages_, *scales):
            return paged_decode_attention(
                q_, k_, v_, layer_, pt_, hist_,
                scale_dim=scale_dim, scale=scale, latent=latent,
                interpret=interpret, mesh=None,
                work_list=(n_, rows_, pages_),
                k_scale=scales[0] if scales else None,
                v_scale=scales[1] if scales else None,
                vmem_budget=vmem_budget,
            )

        # a latent cache is one shared row a token: it replicates
        cache_spec = P() if latent else P(None, None, None, "tp", None)
        in_specs = [
            P(None, "tp", None),
            cache_spec,
            cache_spec,
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
    b, hq, dq = q.shape
    d, dv = k_cache.shape[4] * kp, v_cache.shape[4] * vp
    if latent and (quantized or hkv != 1 or dq != d + dv):
        raise ValueError(
            "a latent walk takes an unquantized one-row cache and "
            f"q [B, Hq, {d}+{dv}]; got q {q.shape}, Hkv={hkv}"
        )
    mp = page_tables.shape[1]
    n_rows, rows, pages = work_list
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    widths = (dv, None) if latent else (0, dv)  # (`rope_dim`, `dv`)
    pb = min(mp, _block_pages(
        b, hq, d, s, hkv, itemsize, quantized, vmem_budget, *widths,
    ))
    if token_bits is not None and (pb * s * hkv) % 128:
        # a block's bits are read at a lane-aligned offset: whole lane
        # tiles of key columns a block (7 pages of 64 -> 6)
        step = 128 // math.gcd(128, s * hkv)
        if pb >= step:
            pb = pb // step * step
    tile = _tile_pages(pb, hq, s * hkv, token_bits is not None)
    if tile < pb and (key := (b, hq, hkv, pb, tile)) not in _told_sub_tiles:
        # static a program: said once a shape, at trace time
        _told_sub_tiles.add(key)
        logging.getLogger(__name__).info(
            "paged_decode_attention: %d rows x %d / %d heads walk %d pages "
            "a block in sub-tiles of %d key columns (~%.1f MiB VMEM)",
            b, hq, hkv, pb, tile * s * hkv,
            _footprint(pb, b, hq, d, s, hkv, itemsize, quantized,
                       *widths) / 2**20)
    hqp = _round_up(hq, 8)
    if hqp != hq:  # whole sublane tiles of query heads; the pad is masked
        q = jnp.pad(q, ((0, 0), (0, hqp - hq), (0, 0)))
    # a page as [S*Hkv, D] rows (r = slot*Hkv + h): the same bytes, and
    # every dot operand a whole tile
    def rows_of(cache):
        return cache.reshape(*cache.shape[:2], s * hkv, cache.shape[4])

    def whole(*block):
        return pl.BlockSpec(
            block, lambda i, li, n, rw, pg, ln: (0,) * len(block)
        )

    in_specs = [
        whole(b, hqp, dq),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch_shapes = [
        pltpu.VMEM((_DEPTH, pb * s * hkv, d), k_cache.dtype),
        pltpu.VMEM((_DEPTH, pb * s * hkv, dv), v_cache.dtype),
    ]
    operands = [q, rows_of(k_cache), rows_of(v_cache)]
    if quantized:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        scale_slot = (_DEPTH, pb * _round_up(hkv, 8), k_scale.shape[3])
        scratch_shapes += [
            pltpu.VMEM(scale_slot, jnp.float32),
            pltpu.VMEM(scale_slot, jnp.float32),
        ]
        operands += [k_scale, v_scale]
    extra = {}
    if token_bits is not None:
        # a column a (position, kv head), padded to whole blocks
        cols = -(-mp // pb) * pb * s
        bits = jnp.pad(token_bits.astype(jnp.int32),
                       ((0, 0), (0, cols - token_bits.shape[1])))
        operands.append(jnp.repeat(bits, hkv, axis=1))
        in_specs.append(whole(b, cols * hkv))
        extra = dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024))
    scratch_shapes.append(  # a semaphore a DMA plane and slot
        pltpu.SemaphoreType.DMA((4 if quantized else kp + vp, _DEPTH))
    )
    wd = d if latent else dv  # the accumulator's width: the value's

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(1,),
        in_specs=in_specs,
        out_specs=[whole(b, hqp, wd), whole(b, hqp, 128)],
        scratch_shapes=scratch_shapes,
    )
    acc, ml = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=s,
            inv_scale=(
                1.0 / math.sqrt(scale_dim or d) if scale is None else scale
            ),
            num_q_heads=hq,
            num_kv_heads=hkv,
            max_pages=mp,
            block_pages=pb,
            tile_pages=tile,
            quantized=quantized,
            latent=latent,
            token_bits=token_bits is not None,
            parts=parts,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hqp, wd), jnp.float32),
            jax.ShapeDtypeStruct((b, hqp, 128), jnp.float32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode_attention",
        **extra,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        n_rows,
        rows,
        pages,
        history_lens.astype(jnp.int32),
        *operands,
    )
    return acc[:, :hq], ml[:, :hq, 0], ml[:, :hq, 1]
