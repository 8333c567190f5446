"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context prefill support, first-class for TPU (the reference has no
sequence parallelism at all — SURVEY.md §5.7 — it leans on paged KV +
disagg prefill; on TPU the ICI ring makes sequence parallelism natural, so
long prompts can be prefilling across an "sp" mesh axis instead of being
chunk-serialized on one chip).

Two interchangeable strategies over the same [B, T, H, D] contract, both
expressed as shard_map programs whose collectives XLA lowers onto ICI:

- **ring_attention**: Q stays put; K/V chunks rotate around the sp ring via
  `lax.ppermute`, with flash-style online-softmax accumulation per step.
  Communication O(T/sp) per step, overlapping compute; memory O(T/sp).
  (Liu et al., "Ring Attention with Blockwise Transformers", 2023 —
  PAPERS.md.)
- **ulysses_attention**: two `all_to_all`s re-shard sequence->heads, run
  dense local attention over the full sequence on a head subset, and shard
  back (Jacobs et al., "DeepSpeed Ulysses", 2023). Cheaper at moderate T
  when heads divide sp; requires Hq % sp == 0 and Hkv % sp == 0.

Both support GQA (Hq = G * Hkv) and causal masking, accumulate in f32, and
are validated against dense attention on an 8-device CPU mesh
(tests/test_context_parallel.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def dense_gqa_attention(
    q: jax.Array,  # [B, Tq, Hq, D]
    k: jax.Array,  # [B, Tk, Hkv, D]
    v: jax.Array,  # [B, Tk, Hkv, D]
    q_offset=0,  # absolute position of q[0] (for causal masking)
    k_offset=0,
    causal: bool = True,
) -> jax.Array:
    """Reference dense attention, GQA-grouped, f32 accumulation.

    Returns [B, Tq, Hq, D] in q.dtype.
    """
    b, tq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_offset + jnp.arange(tq)
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = k_pos[None, :] <= q_pos[:, None]  # [Tq, Tk]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, tq, hq, d).astype(q.dtype)


def _ring_shard(q, k, v, *, axis_name: str, causal: bool):
    """Per-shard body: local q chunk attends every k/v chunk as it passes by
    on the ring. Runs under shard_map; shapes are per-device."""
    sp = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, tl, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, tl, hkv, g, d).astype(jnp.float32) * scale
    q_pos = my * tl + jnp.arange(tl)  # absolute positions of local queries

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(s, carry):
        m, l, acc, k_cur, v_cur = carry

        # After s rotations we hold the chunk originally on device (my - s).
        chunk = (my - s) % sp
        k_pos = chunk * tl + jnp.arange(tl)

        def attend(m, l, acc):
            scores = jnp.einsum(
                "btkgd,bskd->bkgts", qg, k_cur.astype(jnp.float32)
            )  # [B, Hkv, G, Tl, Tl]
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                scores = jnp.where(mask[None, None, None], scores, -1e30)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * corr + jnp.einsum(
                "bkgts,bskd->bkgtd", p, v_cur.astype(jnp.float32)
            )
            return m_new, l_new, acc_new

        if causal:
            # An entirely-future chunk (chunk > my) is fully masked: skip its
            # einsums — otherwise ~half the ring's FLOPs are dead compute.
            m, l, acc = lax.cond(
                chunk <= my, attend, lambda m, l, acc: (m, l, acc), m, l, acc
            )
        else:
            m, l, acc = attend(m, l, acc)

        # Rotate K/V to the next device (the last step's rotate closes the
        # ring back to the owner — harmless, and keeps the loop uniform).
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    # pcast-to-varying: the carry is device-varying over sp (vma typing)
    def _vary(x):
        return lax.pcast(x, axis_name, to="varying")

    m0 = _vary(jnp.full((b, hkv, g, tl, 1), -jnp.inf, jnp.float32))
    l0 = _vary(jnp.zeros((b, hkv, g, tl, 1), jnp.float32))
    a0 = _vary(jnp.zeros((b, hkv, g, tl, d), jnp.float32))
    m, l, acc, _, _ = lax.fori_loop(0, sp, step, (m0, l0, a0, k, v))
    # Causal => every query row attends at least itself, so l > 0.
    out = acc / l
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(b, tl, hq, d).astype(q.dtype)
    )


def ring_attention(
    q: jax.Array,  # [B, T, Hq, D] — T sharded over `axis_name`
    k: jax.Array,  # [B, T, Hkv, D]
    v: jax.Array,  # [B, T, Hkv, D]
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    batch_axis: str | None = None,  # shard B over this mesh axis (dp)
    head_axis: str | None = None,  # shard heads over this mesh axis (tp)
) -> jax.Array:
    """Sequence-parallel causal attention over the sp ring. T must divide
    evenly by the sp axis size.

    When the ambient mesh also carries dp/tp axes, pass them as
    batch_axis/head_axis so the region stays batch- and head-sharded —
    omitting them would all-gather every head and batch row onto every
    device inside the shard_map (O(tp·dp) redundant attention work on the
    long-prompt path whose point is reducing per-chip memory)."""
    sp = mesh.shape[axis_name]
    if q.shape[1] % sp:
        raise ValueError(f"T={q.shape[1]} not divisible by sp={sp}")
    spec = P(batch_axis, axis_name, head_axis, None)
    fn = jax.shard_map(
        partial(_ring_shard, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def _ulysses_shard(q, k, v, *, axis_name: str, causal: bool):
    """seq-shard -> all_to_all -> head-shard dense attention -> all_to_all."""
    sp = lax.psum(1, axis_name)
    # [B, Tl, H, D] -> gather seq, scatter heads -> [B, T, H/sp, D]
    def to_heads(x):
        # split heads into sp groups; concat_dimension=seq, split=heads
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = dense_gqa_attention(qh, kh, vh, causal=causal)
    return to_seq(out)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: both head
    counts and T must divide the sp axis size."""
    sp = mesh.shape[axis_name]
    hq, hkv = q.shape[2], k.shape[2]
    if q.shape[1] % sp:
        raise ValueError(f"T={q.shape[1]} not divisible by sp={sp}")
    if hq % sp or hkv % sp:
        raise ValueError(
            f"heads (Hq={hq}, Hkv={hkv}) must divide sp={sp} for ulysses; "
            "use ring_attention otherwise"
        )
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(_ulysses_shard, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
