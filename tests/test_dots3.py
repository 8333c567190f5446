"""dots3-note-prev's language model (models/dots3.py): latent attention
under a learned indexer in the full layers, latent attention of another
geometry under a window whose cache is a ring in the slot pool, sigmoid
experts under a correction bias of which a chip may hold a share, at a
small size on seeded weights, against the plain reference the benchmark
brings (chipbench/references/dots3.py: float32, no absorbed form, a stable
sort for the selection, no cache, no kernels, expert by expert).

`dots3-tiny`: five layers (D, then F S twice), a page of 4 tokens, the 8 highest
tokens a query, a window of 9 keys in a ring of 48 rows, 4 index heads of
16, 8 experts top 2 and one shared.

Tolerances: everything runs in float32 here, so what separates the system
from the reference is the order of sums (the absorbed form against the
plain one among them): 3e-4 on log-probs of magnitude ~5, a hundred times
the observed 3e-6. A selection that differs in one token, a window one key
short, a dropped assignment or a stale ring row moves them by 1e-2 or
more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.models import dots3, mla
from dynamo_tpu.models.llama import StepGroup
from dynamo_tpu.models.registry import _dots3_adapter, get_model, list_presets
from dynamo_tpu.ops import token_select as ts
from test_falcon_h1 import _streams

TOL = 3e-4
PAGE = 4

ref = manifest._load(
    manifest.ROOT / "chipbench/references/dots3.py", "ref_dots3")


def hf_of(cfg, **more) -> dict:
    return {**ref.served_widths(cfg), "layer_types": list(cfg.layer_types),
            **more}


def test_presets_are_the_published_model_and_its_cut():
    assert {"dots3-note-prev", "dots3-note-prev-9l-8e",
            "dots3-tiny"} <= set(list_presets())
    full = get_model("dots3-note-prev").config
    cut = get_model("dots3-note-prev-9l-8e").config
    assert (full.num_layers, full.full_layers, full.state_layers) == (
        46, 13, 33)
    assert full.periods == ((0, 0),) + tuple(
        (i, 3) for i in range(1, 45, 4)) + ((45, 0),)
    assert (full.experts_held, full.vocab_size) == (None, 152064)
    assert (cut.num_layers, cut.full_layers, cut.state_layers) == (9, 3, 6)
    assert cut.periods == ((0, 0), (1, 3), (5, 3))
    assert get_model("dots3-tiny").config.periods == ((0, 0), (1, 1), (3, 1))
    assert (cut.experts_held, cut.experts_here, cut.n_routed_experts,
            cut.vocab_size) == ((0, 8), 8, 256, 19008)
    # every width of the cut is the published model's
    for name in ("hidden_size", "num_heads", "kv_lora_rank", "swa_num_heads",
                 "swa_kv_lora_rank", "index_heads", "index_head_dim",
                 "index_topk", "sliding_window", "moe_intermediate_size",
                 "num_experts_per_tok", "intermediate_size", "ring_tokens"):
        assert getattr(cut, name) == getattr(full, name), name
    # the bound on the ring: a window behind a query and a 512-token chunk
    assert cut.ring_tokens == 1088 and cut.ring_run == 576
    assert cut.ring_tokens % 64 == 0
    # a page of 64 tokens and a slot, in bytes (bf16; the rope key 64 wide
    # off the TPU, a 128-lane tile under the kernels)
    assert dots3.page_bytes(cut, 64) == 3 * 64 * (512 + 64 + 128) * 2
    assert dots3.state_bytes_per_slot(cut) == 6 * 1088 * 1088 * 2


@pytest.mark.parametrize("what", [
    "mesh", "kv_quantize", "speculation", "kv_tiers", "page_transfer",
    "embeddings"])
def test_what_the_family_cannot_serve_is_refused_with_its_sentence(what):
    adapter = get_model("dots3-tiny")
    assert adapter.state_in_place and adapter.state_layers == 2
    base = EngineConfig.for_tests(model="dots3-tiny")
    if what == "mesh":
        with pytest.raises(ValueError, match="one chip"):
            _dots3_adapter("dots3-tiny", adapter.config, mesh=object())
    elif what == "kv_quantize":
        with pytest.raises(ValueError, match="kv_quantize is not supported "
                                             "for dots3-note-prev"):
            adapter.init_kv(8, PAGE, kv_quantize="int8", state_slots=2)
    elif what == "speculation":
        with pytest.raises(ValueError, match="speculation is not supported "
                                             "for it .*rings"):
            JaxEngine(EngineConfig(**{**base.__dict__, "spec_ngram": 2}))
    elif what == "kv_tiers":
        with pytest.raises(ValueError, match="kv_tiers is not supported "
                                             "for it .*rings"):
            JaxEngine(EngineConfig(**{
                **base.__dict__, "host_kv_cache_bytes": 1 << 20}))
    elif what == "page_transfer":
        eng = JaxEngine(base)
        with pytest.raises(ValueError, match="handover is not supported "
                                             "for it .*rings"):
            eng._refuse_state_transfer("handover")
    else:
        with pytest.raises(ValueError, match="state slot"):
            JaxEngine(base).embed([[3, 4, 5]])


# -- (a) chunks + decode through pages and rings == one full forward ----------


def _serve(adapter, params, toks, chunks, t_bucket=16):
    """Prefill then decode one sequence through the caches the way the
    engine does: chunk by chunk, each padded to `t_bucket`."""
    forward = jax.jit(adapter.forward)
    kv = adapter.init_kv(64, PAGE, state_slots=2)
    pt = (jnp.asarray(np.arange(1, 49)[None], jnp.int32),
          jnp.asarray([[2, 2]], jnp.int32))
    pos, outs = 0, []
    for c in chunks:
        tb = max(c, t_bucket) if c > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, :c] = toks[pos : pos + c]
        logits, kv = forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + pos)[None].astype(np.int32)),
            jnp.asarray(np.arange(tb)[None] < c), kv, pt)
        outs.append(np.asarray(jax.nn.log_softmax(logits[0, :c])))
        pos += c
    return np.concatenate(outs), kv


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunks", [
    pytest.param([16, 16, 5] + [1] * 6, id="topk-and-window-passed-in-a-chunk"),
    pytest.param([4] + [1] * 12, id="topk-and-window-passed-during-decode"),
    pytest.param([16] * 7 + [11] + [1] * 5, id="the-ring-wraps-twice"),
])
def test_prefill_then_decode_through_pages_and_rings_is_the_reference(
        impl, chunks):
    """A sequence that passes `index_topk` (8 tokens), the window (9) and
    the ring's length (48 rows: 128 tokens wrap it twice), chunk by chunk
    and then token by token through the pools and the ring, against ONE
    full forward of the reference: the rules are by query token, so they
    agree."""
    adapter = get_model("dots3-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    n = sum(chunks)
    toks = np.random.default_rng(4).integers(3, 256, n)
    got, kv = _serve(adapter, params, toks, chunks)
    want = ref.log_probs(params, hf_of(adapter.config), toks, np.arange(n))
    np.testing.assert_allclose(got, want, atol=TOL)
    # the decode rows' count: min(8, t + 1) of t + 1 tokens, a FULL layer
    decoded = [t + 1 for t in range(n) if t >= n - chunks.count(1)]
    assert list(np.asarray(kv.walked)[:2]) == [
        3 * sum(min(8, c) for c in decoded), 3 * sum(decoded)]
    if n > 96:  # the ring wrapped: its rows hold the LAST 48 positions
        held = np.asarray(dots3.ring_positions(jnp.asarray([n - 1]), 48))[0]
        assert sorted(held) == list(range(n - 48, n))


def _engine(**overrides):
    base = EngineConfig.for_tests(
        model="dots3-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=2, decode_buckets=(1, 2),
    )
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


@pytest.mark.parametrize("scenario", [
    "three-chunks-then-fused-dispatches", "slot-reuse-after-a-finish",
    "forced-rollback", "preemption-recompute", "prefix-hit-refused"])
def test_engine_streams_are_the_reference(scenario):
    """The normal path (scheduler, pages and ring slots under one
    allocator, the step programs, launch-ahead on), teacher-forced against
    the reference on the chosen tokens' log-probs: a prompt over three
    chunks and fused 8-step dispatches (the ring wraps); five requests
    through two decode slots, so that every ring slot has a second and a
    third owner whose stale rows are never read; a neighbour aborted while
    a dispatch launched ahead is on the device, so that the survivors'
    pages, index keys AND RINGS were advanced by a dispatch that is thrown
    away, with ONE generation a slot; a pool so small that a row is
    preempted and recomputed; a PREFIX HIT, which this family refuses and
    counts (the hit's pages hold no window)."""
    rng = np.random.default_rng(2)
    events, only = None, None
    if scenario == "three-chunks-then-fused-dispatches":
        eng = _engine(max_seqs=1, decode_buckets=(1,))
        reqs = [("a", [int(x) for x in rng.integers(3, 250, 75)], 20)]
    elif scenario == "slot-reuse-after-a-finish":
        eng = _engine()
        reqs = [(f"r{i}", [int(x) for x in rng.integers(3, 250, 10 + 9 * i)],
                 6 + 4 * i) for i in range(5)]
    elif scenario == "forced-rollback":
        eng = _engine(max_seqs=4, decode_buckets=(1, 2, 4), decode_steps=4)
        reqs = [(f"h{i}", [int(x) for x in rng.integers(3, 250, 19 + 3 * i)],
                 24 + 2 * i) for i in range(3)]
        events = {5: lambda e: e.abort_request("h1")}
        only = ["h0", "h2"]
    elif scenario == "preemption-recompute":
        eng = _engine(num_pages=22, max_pages_per_seq=16, decode_steps=1)
        reqs = [(f"p{i}", [int(x) for x in rng.integers(3, 250, 24)], 20)
                for i in range(2)]
    else:
        eng = _engine(enable_prefix_caching=True)
        shared = [int(x) for x in rng.integers(3, 250, 40)]
        _streams(eng, [("cold", shared + [7, 8, 9], 10)])
        reqs = [("warm", shared + [7, 8, 9], 10)]
    toks, lps = _streams(eng, reqs, events)
    m = eng.metrics
    # one generation a slot: the pool is slots + 1 entries, and no row's
    # generation ever flipped
    assert eng.kv.ring.shape[1] == eng._state_slots + 1
    if scenario == "three-chunks-then-fused-dispatches":
        assert m.prefill_dispatches == 3
        assert any(k[0] == "decode_multi" and k[2] == 8
                   for k in eng.programs)
        assert 0 < m.walk_pages_named < 0.12 * m.walk_pages_live
        assert m.chunk_pages_read > m.chunk_pages_named > 0
        # the held experts the rows chose, counted a step and expert layer
        assert m.moe_experts_touched > 0
        assert not any(k[5] for k in eng.programs if len(k) > 5)
        assert m.state_pool_bytes == (
            eng.kv.ring.nbytes + eng.kv.ring_pe.nbytes)
    elif scenario == "slot-reuse-after-a-finish":
        assert m.mixed_dispatches > 0
        assert eng.allocator.slots_taken == 5
    elif scenario == "forced-rollback":
        assert m.overlap_rollbacks > 0
        assert m.state_restores == 0  # nothing to put back: benign in place
        reqs = [r for r in reqs if r[0] in only]
    elif scenario == "preemption-recompute":
        assert m.preemptions > 0
    else:
        eng.refresh_metrics() if hasattr(eng, "refresh_metrics") else None
        assert eng.allocator.prefix_hits_refused_state >= 1
        assert eng.allocator.stats.hit_tokens == 0
    hf = hf_of(eng.adapter.config)
    for rid, prompt, n in reqs:
        seq = list(prompt) + toks[rid]
        want = ref.log_probs(eng.params, hf, seq,
                             len(prompt) - 1 + np.arange(n))
        of_served = want[np.arange(n), np.asarray(toks[rid])]
        np.testing.assert_allclose(lps[rid], of_served, atol=TOL,
                                   err_msg=rid)
        assert (want.max(-1) - of_served).max() < TOL, rid


# -- (b) the window's two ends --------------------------------------------------


@pytest.mark.parametrize("impl,path,key,seen", [
    pytest.param(impl, path, key, seen, id=f"{name}-{path}-{impl}")
    for impl in ("xla", "pallas") for path in ("decode", "chunk")
    for key, seen, name in ((8, True, "t-minus-8-in"),
                            (9, False, "t-minus-9-out"),
                            (0, True, "its-own-token-in"))
    # the kernels' walk of the ring's pages interpreted is slow: its two
    # ends once each
    if impl == "xla" or (path, key) in (("decode", 8), ("chunk", 9))
])
def test_the_windows_two_ends(impl, path, key, seen):
    """A sliding query at `t` attends the keys `t - 8 .. t` (a window of 9,
    its own token among them: `t - 512 .. t` at the published 513):
    changing the cached row of `t - 8` moves its output, changing `t - 9`'s
    does not, through the decode path and through a chunk, past a wrap of
    the ring, in plain XLA and through the kernels' walk of the ring's
    pages."""
    cfg = dataclasses.replace(dots3.Dots3Config.tiny(), attention_impl=impl)
    geo = cfg.swa_geo
    t = 70  # the 48-row ring has wrapped
    rng = np.random.default_rng(t)
    n = t + 1
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.float32)
    c_rows = normal(1, n, geo.kv_lora_rank)
    kp = normal(1, n, geo.qk_rope_head_dim)
    ql = normal(1, n, geo.num_heads, geo.kv_lora_rank) * 0.3
    qp = normal(1, n, geo.num_heads, geo.qk_rope_head_dim) * 0.3
    cache = dots3.init_cache(cfg, 4, PAGE, 2)
    layer = jnp.int32(1)
    lo = t if path == "decode" else t - 5  # the query's own step

    def out(c_rows):
        rings = (cache.ring, cache.ring_pe)
        for a in list(range(0, lo, 16)) + [lo]:
            b = min(a + 16, lo) if a < lo else t + 1
            pos = jnp.arange(a, b, dtype=jnp.int32)[None]
            g = StepGroup(jnp.zeros_like(pos), pos, jnp.ones_like(pos, bool),
                          jnp.zeros((1, 1), jnp.int32),
                          state_rows=jnp.asarray([[2, 2]], jnp.int32))
            o, rings = dots3.window_attend(
                ql[:, a:b], qp[:, a:b], c_rows[:, a:b], kp[:, a:b], rings,
                layer, g, cfg)
        return np.asarray(o, np.float32)[0, -1]

    base = out(c_rows)
    moved = out(c_rows.at[0, t - key].add(1.0))
    assert (np.abs(moved - base).max() > 1e-3) == seen


# -- (b') a prompt piece in the plain form == the ring attended absorbed --------


@pytest.mark.parametrize("firsts,valid,filled", [
    pytest.param((0,), 16, None, id="a-piece-that-starts-at-position-0"),
    pytest.param((208,), 16, None, id="a-ring-that-wrapped-four-times"),
    pytest.param((64,), 11, None, id="a-piece-shorter-than-its-bucket"),
    pytest.param((32, 150), 16, None,
                 id="two-pieces-of-different-positions-in-one-group"),
    pytest.param((80,), 16, 7.0, id="a-slot-whose-last-owner-left-rows"),
])
def test_a_piece_in_the_plain_form_is_the_ring_attended_absorbed(
        firsts, valid, filled):
    """Under the kernels a window layer's prompt piece up-projects the keys
    in reach once and attends them as projected (`window_piece`), where
    the path without kernels writes the rows first and attends the whole
    ring absorbed (`ring_attention`): the same sums in another order. Each
    sequence is written into its slot's ring piece by piece up to the
    judged one; that one's invalid tail holds NaN (it reaches no valid
    row); a decode step after it reads the same ring through both paths,
    and the two rings hold the same rows where either was written."""
    b, t = len(firsts), 16
    cfgs = {impl: dataclasses.replace(
        dots3.Dots3Config.tiny(), attention_impl=impl)
        for impl in ("xla", "pallas")}
    geo = cfgs["xla"].swa_geo
    hn, c, n = geo.num_heads, geo.kv_lora_rank, geo.qk_nope_head_dim
    rr, vd = geo.qk_rope_head_dim, geo.v_head_dim
    rng = np.random.default_rng(sum(firsts) + valid)
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.float32)
    total = max(firsts) + t + 1
    wkv_b = normal(c, hn, n + vd) / np.sqrt(c)
    qn, qp = normal(b, total, hn, n) * 0.5, normal(b, total, hn, rr) * 0.5
    ck, kp = normal(b, total, c), normal(b, total, rr)
    layer = jnp.int32(1)
    slots = jnp.asarray([[2, 2], [1, 1]], jnp.int32)[:b]

    def step(rings, impl, seqs, lo, width, live):
        """Sequences `seqs` attend their `width` rows from `lo` on (the
        first `live` valid, the rest NaN) and write them into their rings:
        the plain form under the kernels, absorbed without them."""
        cfg = cfgs[impl]
        pos = jnp.asarray(lo)[:, None] + jnp.arange(width, dtype=jnp.int32)
        g = StepGroup(
            jnp.zeros_like(pos), pos,
            jnp.broadcast_to(jnp.arange(width) < live, pos.shape),
            jnp.zeros((len(seqs), 1), jnp.int32),
            state_rows=slots[jnp.asarray(seqs)])
        q, *cut = (
            jnp.stack([a[i, f:f + width] for i, f in zip(seqs, lo)]
                      ).at[:, live:].set(jnp.nan) for a in (qn, qp, ck, kp))
        if impl == "pallas" and width > 1:
            return dots3.window_piece(q, *cut, rings, layer, g, cfg, wkv_b)
        o_lat, rings = dots3.window_attend(
            jnp.einsum("bthn,chn->bthc", q, wkv_b[..., :n]), *cut, rings,
            layer, g, cfg)
        return jnp.einsum("bthc,chv->bthv", o_lat, wkv_b[..., n:]), rings

    def serve(impl):
        cache = dots3.init_cache(cfgs[impl], 4, PAGE, 2)
        rings = (cache.ring, cache.ring_pe)
        if filled is not None:
            rings = tuple(r + filled for r in rings)
        # every sequence up to its judged piece, a piece a call
        for i, f in enumerate(firsts):
            for lo in range(0, f, t):
                _, rings = step(rings, impl, [i], [lo], t, min(t, f - lo))
        seqs = list(range(b))
        o, rings = step(rings, impl, seqs, list(firsts), t, valid)
        o_d, rings = step(
            rings, impl, seqs, [f + valid for f in firsts], 1, 1)
        return (np.asarray(o)[:, :valid], np.asarray(o_d),
                [np.asarray(r)[1, 1:, :, :w] for r, w in zip(rings, (c, rr))])

    want, got = serve("xla"), serve("pallas")
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    assert np.isfinite(got[0]).all() and np.abs(want[0]).max() > 0.1
    for mine, theirs in zip(got[2], want[2]):
        np.testing.assert_array_equal(mine, theirs)


@pytest.mark.parametrize("first", [
    pytest.param(0, id="from-position-0"),
    pytest.param(5, id="fewer-keys-than-a-window"),
    pytest.param(70, id="past-a-wrap-of-the-ring"),
    pytest.param(64, id="a-piece-that-starts-on-a-ring-page"),
])
def test_a_plain_piece_attends_exactly_its_window(first):
    """With zero queries the softmax is uniform over the kept keys, and
    with the latent of position k the (k mod 16)-th unit vector under a
    `W_UV` that copies it the output NAMES them: a query at position p
    attends the keys at `max(0, p - 8) .. p` (a window of 9), 9 of them
    once it has them, its own among them and not the key 9 before it; a
    ring row at or past the piece's first position (a stale row from a
    wrap ago) is no key."""
    cfg = dataclasses.replace(
        dots3.Dots3Config.tiny(), attention_impl="pallas")
    geo = cfg.swa_geo
    hn, c, n = geo.num_heads, geo.kv_lora_rank, geo.qk_nope_head_dim
    rr, vd, t = geo.qk_rope_head_dim, geo.v_head_dim, 16
    assert cfg.sliding_window == 9 and 4 * t >= cfg.ring_reach
    total = first + t
    names = jnp.eye(c, dtype=jnp.float32)[jnp.arange(total) % vd][None]
    wkv_b = jnp.zeros((c, hn, n + vd)).at[:vd, :, n:].set(
        jnp.eye(vd)[:, None])
    cache = dots3.init_cache(cfg, 4, PAGE, 2)
    # what a wrap ago left: rows that would name every key at once
    rings = (cache.ring + 1.0, cache.ring_pe + 1.0)
    layer, slot = jnp.int32(1), jnp.asarray([[2, 2]], jnp.int32)

    def group(lo, hi):
        pos = jnp.arange(lo, hi, dtype=jnp.int32)[None]
        return StepGroup(jnp.zeros_like(pos), pos, jnp.ones_like(pos, bool),
                         jnp.zeros((1, 1), jnp.int32), state_rows=slot)

    for lo in range(0, first, t):
        hi = min(lo + t, first)
        rings = dots3.ring_write(
            rings, layer, names[:, lo:hi],
            jnp.zeros((1, hi - lo, geo.kv_rope_dim)), slot[:, 1],
            group(lo, hi).positions, jnp.ones((1, hi - lo), bool))
    o, _ = dots3.window_piece(
        jnp.zeros((1, t, hn, n)), jnp.zeros((1, t, hn, rr)),
        names[:, first:], jnp.zeros((1, t, rr)), rings, layer,
        group(first, total), cfg, wkv_b)
    o = np.asarray(o)[0]  # [T, H, v]
    for j in range(t):
        pos = first + j
        want = np.zeros(vd)
        kept = range(max(0, pos - 8), pos + 1)
        want[[k % vd for k in kept]] = 1.0 / len(kept)
        assert len(kept) == min(9, pos + 1) and (pos - 9) % vd not in [
            k % vd for k in kept]
        for h in range(hn):
            np.testing.assert_allclose(o[j, h], want, atol=1e-6)


# -- (b'') a full layer's prompt piece in the plain form ------------------------


def _full_block(rng, cfg, firsts, t, decode_rows=0):
    """A full layer's block on drawn inputs: (lp, the pools as the kernels
    keep them, groups [the pieces from `firsts`, then `decode_rows` decode
    rows], their joined rows x, the keys given to each group's queries)."""
    geo = cfg.full_geo
    normal = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.float32)
    lp = {name: (jnp.ones(shape) if name.endswith("norm") else
                 normal(*shape) / np.sqrt(shape[0])).astype(cfg.dtype)
          for name, shape in dots3._stack_shapes(cfg)["full"].items()}
    pages, mp = 80, 16
    kv = (normal(2, pages, PAGE, 1, geo.kv_lora_rank).astype(cfg.dtype),
          mla._pad_last(normal(2, pages, PAGE, 1, geo.qk_rope_head_dim),
                        128).astype(cfg.dtype))
    ki_pool = jnp.zeros((2, pages, PAGE, cfg.index_head_dim), cfg.dtype)
    groups, n = [], mp * PAGE
    for lo, width in (
            [(list(firsts), t)] + [([50 + i for i in range(decode_rows)], 1)]
            * bool(decode_rows)):
        pos = jnp.asarray(lo, jnp.int32)[:, None] + jnp.arange(
            width, dtype=jnp.int32)
        first = 1 + len(groups) * 40
        tables = jnp.asarray(
            first + np.arange(len(lo) * mp).reshape(len(lo), mp) % 39,
            jnp.int32)
        groups.append(StepGroup(
            jnp.zeros_like(pos), pos, jnp.ones(pos.shape, bool), tables))
    x = normal(sum(g.positions.size for g in groups), cfg.hidden_size)
    if len(groups) == 1:
        x = x.reshape(*groups[0].positions.shape, -1)
    return lp, kv, ki_pool, groups, x.astype(cfg.dtype), n


def _given(monkeypatch, rng, share):
    """The indexer's choice replaced by one key in `share` of a query's
    context (its own token always), whatever the path scores."""
    def chosen_keys(score, rows, n, positions, valid, topk):
        at = jnp.arange(n, dtype=jnp.int32)[None, None]
        drawn = jnp.asarray(
            rng.integers(0, share, size=(*positions.shape, n)) == 0)
        own = at == positions[..., None]
        return (drawn | own) & (at <= positions[..., None]) & valid[..., None]

    monkeypatch.setattr(dots3.keye, "chosen_keys", chosen_keys)


@pytest.mark.parametrize("firsts,valid,share,gate", [
    pytest.param((0,), 16, 2, True, id="no-history"),
    pytest.param((36,), 16, 2, True, id="a-history-that-is-no-whole-block"),
    pytest.param((40, 8), 16, 2, True,
                 id="two-pieces-of-different-histories-in-one-group"),
    pytest.param((20,), 11, 2, True, id="a-padded-tail-that-holds-nan"),
    pytest.param((44,), 16, 1, True, id="every-key-chosen"),
    pytest.param((44,), 16, 3, True, id="one-key-in-three-chosen"),
    pytest.param((36,), 16, 2, False, id="without-the-head-gate"),
])
def test_a_full_layers_piece_in_the_plain_form_is_attention_under_the_mask(
        monkeypatch, firsts, valid, share, gate):
    """Under the kernels a full layer's prompt piece attends in the plain
    form (`full_piece`: ops/flash_prefill.py `latent_plain_attention`,
    interpreted, a block of 2 pages of 4 a turn and 8 queries a softmax
    pass, so that a history is several turns), where the path without
    kernels writes the rows first and attends the gathered cache absorbed
    under the same keys (`mla._attend_xla(.., keep=chosen)`), both in
    float32 at `dots3-tiny`'s widths: the same sums in another order, the
    head gate on both. The piece's invalid tail holds NaN under the
    kernels and reaches no valid row."""
    from dynamo_tpu.ops import flash_prefill

    t = 16
    monkeypatch.setattr(flash_prefill, "PLAIN_BLOCK_PAGES", 2)
    monkeypatch.setattr(flash_prefill, "PLAIN_ROWS", 8)
    cfgs = {impl: dataclasses.replace(
        dots3.Dots3Config.tiny(), attention_impl=impl, headwise_gate=gate)
        for impl in ("xla", "pallas")}
    rng = np.random.default_rng(sum(firsts) + valid + share)
    lp, kv, ki_pool, (g,), x, _ = _full_block(rng, cfgs["pallas"], firsts, t)
    g = g._replace(valid=jnp.broadcast_to(
        jnp.arange(t) < valid, (len(firsts), t)))
    outs = {}
    for impl, cfg in cfgs.items():
        _given(monkeypatch, np.random.default_rng(7), share)
        pools = kv if impl == "pallas" else (
            kv[0], kv[1][..., :cfg.qk_rope_head_dim])
        rows = x.at[:, valid:].set(jnp.nan if impl == "pallas" else 0.0)
        out, _, _, staged, counted = dots3.full_attention(
            rows, lp, cfg, pools, ki_pool, jnp.int32(1), [g], [None])
        outs[impl] = np.asarray(out)[:, :valid], np.asarray(counted)
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0], atol=2e-5)
    np.testing.assert_array_equal(outs["pallas"][1], outs["xla"][1])
    assert np.isfinite(outs["pallas"][0]).all()
    assert np.abs(outs["xla"][0]).max() > 0.1


@pytest.mark.parametrize("decode_rows", [
    pytest.param(0, id="a-group-of-pieces-alone"),
    pytest.param(3, id="beside-a-group-of-decode-rows"),
])
def test_a_plain_full_piece_is_the_absorbed_one_and_stages_the_same_rows(
        monkeypatch, decode_rows):
    """`full_attention` under the kernels in bfloat16: a group of T > 1
    attends plain by the rule (`plain_full`), and absorbed where the rule
    is set aside (the parent's path: `full_attend`, `absorbed_query` and
    the value up-projection on all rows). The outputs agree within
    bfloat16, a decode group's rows to the letter's worth of the shared
    `wo`, and what each group stages for the landing after the layer
    loops, the latent rows, the rope keys as cached and the index keys,
    is the same to the letter, as is the count."""
    cfg = dataclasses.replace(
        dots3.Dots3Config.tiny(), attention_impl="pallas",
        dtype=jnp.bfloat16)
    rng = np.random.default_rng(decode_rows)
    lp, kv, ki_pool, groups, x, _ = _full_block(
        rng, cfg, (40, 8), 16, decode_rows)
    got = {}
    for form in ("rule", "absorbed"):
        _given(monkeypatch, np.random.default_rng(7), 2)
        if form == "absorbed":
            monkeypatch.setattr(dots3, "plain_full", lambda t, cfg: False)
        out, _, _, staged, counted = dots3.full_attention(
            x, lp, cfg, kv, ki_pool, jnp.int32(0), groups,
            [None] * len(groups))
        got[form] = (np.asarray(out, np.float32), staged, np.asarray(counted))
    (mine, staged, counted), (theirs, staged_a, counted_a) = (
        got["rule"], got["absorbed"])
    assert np.linalg.norm(mine - theirs) < 2e-2 * np.linalg.norm(theirs)
    assert np.linalg.norm(mine - theirs) > 0  # another order of sums
    np.testing.assert_array_equal(counted, counted_a)
    assert len(staged) == len(groups)
    for st, st_a in zip(staged, staged_a):
        assert len(st) == len(st_a) == 3
        for a, b in zip(st, st_a):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))


# -- (c) a rolled-back dispatch with ONE generation -----------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("ahead", ["decode-step", "chunk"])
def test_a_rolled_back_dispatch_leaves_every_window_intact(impl, ahead):
    """After a 60-token prompt (the ring has wrapped) a dispatch launched
    ahead with tokens that turn out wrong (a decode step, or a 16-token
    chunk) writes ring rows, page slots and index keys IN PLACE; the
    dispatch that replaces it reads the same logits and leaves every pool
    and the ring as one pass does, bit for bit: what the one thrown away
    wrote lies at positions the real one writes again, and what it
    overwrote in the ring lies more than a window behind every query to
    come."""
    adapter = get_model("dots3-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    n = 16 if ahead == "chunk" else 1
    toks = np.random.default_rng(5).integers(3, 256, 60 + n)
    pt = (jnp.asarray(np.arange(1, 49)[None], jnp.int32),
          jnp.asarray([[1, 1]], jnp.int32))
    forward = jax.jit(adapter.forward)

    def run(kv, ids, lo):
        tb = 16 if len(ids) > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, : len(ids)] = ids
        return forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + lo)[None].astype(np.int32)),
            jnp.asarray(np.arange(tb)[None] < len(ids)), kv, pt)

    kv = adapter.init_kv(64, PAGE, state_slots=2)
    for lo in range(0, 60, 16):
        _, kv = run(kv, toks[lo : min(lo + 16, 60)], lo)
    want, once = run(kv, toks[60:], 60)
    _, wrong = run(kv, [int(x) ^ 1 for x in toks[60:]], 60)
    got, twice = run(wrong, toks[60:], 60)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in ("k", "v", "ki", "ring", "ring_pe"):
        a, b = getattr(once, name), getattr(twice, name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        assert np.abs(np.asarray(getattr(wrong, name), np.float32)
                      - np.asarray(a, np.float32)).max() > 1e-3, name
    # a slot's new owner reads none of the old owner's rows: the same
    # prompt from position 0 in a ring that holds anything at all
    fresh = adapter.init_kv(64, PAGE, state_slots=2)
    dirty = fresh._replace(ring=fresh.ring + 7.0, ring_pe=fresh.ring_pe - 3.0)
    np.testing.assert_array_equal(
        np.asarray(run(dirty, toks[:16], 0)[0]),
        np.asarray(run(fresh, toks[:16], 0)[0]))


# -- (d) each assumed part has a case that fails with it left out --------------


@pytest.mark.parametrize("left_out", [
    "gate", "rescale", "index_rope", "correction_bias", "window"])
def test_each_assumed_part_moves_the_logits(left_out):
    """The head-wise gate, the lora rescale, the rope on the index
    vectors, the score-correction bias and the window's length are each
    part of what is compared: the reference with that part left out (the
    window one key short) is NOT what the program serves."""
    adapter = get_model("dots3-tiny")
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(6).integers(3, 256, 40)
    got, _ = _serve(adapter, params, toks, [16, 16, 8])
    hf = hf_of(adapter.config)
    how = {"gate": {"gate": False}, "rescale": {"rescale": False},
           "index_rope": {"index_rope": False},
           "correction_bias": {"moe": {"bias": False}},
           "window": {"window": 8}}[left_out]
    want = ref.log_probs(params, hf, toks, np.arange(40))
    other = ref.log_probs(params, hf, toks, np.arange(40), **how)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert np.abs(got - other).max() > 1e-2


# -- (e) the shares add up --------------------------------------------------------


@pytest.mark.parametrize("rows", [1, 5, 64, 300])
@pytest.mark.parametrize("shares", [4, 8])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(rows, shares):
    """The `shares` chips of an expert-parallel layer each route over all
    8 experts (sigmoid scores, the correction bias, the renormalised
    weights) and add their own experts' terms and the shared expert; what
    every chip computes alike (the router, the shared expert) is counted
    once: the sum of their routed parts and ONE shared expert is the uncut
    reference's expert layer at any row count."""
    whole = dataclasses.replace(dots3.Dots3Config.tiny(), experts_held=None)
    params = dots3.init_params(jax.random.key(3), whole)
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    assert float(jnp.abs(lp["router_bias"]).max()) > 0.01
    x = jnp.asarray(np.random.default_rng(rows).normal(
        size=(1, rows, whole.hidden_size)), jnp.float32)
    hf = hf_of(whole)
    want = ref.moe_branch(x[0], lp, hf)
    shared = ref.moe_branch(x[0], lp, hf, held=(0, 0))  # the shared alone
    per = whole.n_routed_experts // shares
    total, touched = 0.0, 0
    for s in range(shares):
        cfg = dataclasses.replace(whole, experts_held=(s * per, per))
        mine = {**lp, **{n: lp[n][s * per:(s + 1) * per]
                         for n in dots3.EXPERTS}}
        # a share's draw IS the whole model's experts at its place
        held = dots3.init_params(jax.random.key(3), cfg)["moe"]
        for n in dots3.EXPERTS:
            np.testing.assert_array_equal(held[n][0], mine[n])
        got, n = dots3.moe_ffn(x, mine, cfg)
        got = got[0]
        np.testing.assert_allclose(
            got, ref.moe_branch(x[0], mine, hf_of(cfg)), atol=2e-5)
        total = total + (got - shared)
        touched += int(n[0])  # (touched, passes beyond the first)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    # and so do the shares' counts of the experts their rows chose: every
    # expert some row chose is counted once, by the share that holds it
    _, topi = mla._gate(x[0], lp, whole.full_geo,
                        precision=jax.lax.Precision.HIGHEST)
    assert touched == np.unique(np.asarray(topi)).size


# -- (f) the kernels against plain XLA --------------------------------------------


def _latent_pools(rng, layers=2, pages=40, c=32, r=128):
    return (jnp.asarray(rng.normal(size=(layers, pages, PAGE, 1, c)),
                        jnp.float32),
            jnp.asarray(rng.normal(size=(layers, pages, PAGE, 1, r)),
                        jnp.float32))


@pytest.mark.parametrize("hist,topk", [
    pytest.param(37, 8, id="a-partly-filled-last-page"),
    pytest.param(45, 24, id="several-blocks"),
    pytest.param(5, 8, id="fewer-tokens-than-topk"),
])
def test_the_latent_walk_under_bits_is_dense_attention_under_the_mask(
        hist, topk):
    """ops/paged_attention.py `latent` + `token_bits` (interpreted): the
    walk over a ONE-ROW latent cache under a bit a cached token, against
    float32 attention in the absorbed form under the same mask."""
    from dynamo_tpu.ops.paged_attention import paged_decode_attention

    rng = np.random.default_rng(hist)
    k_pool, v_pool = _latent_pools(rng)
    b, hn, c, r = 3, 4, 32, 128
    q = jnp.asarray(rng.normal(size=(b, hn, c + r)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:b * 12].reshape(
        b, 12), jnp.int32)
    hists = jnp.asarray([hist, max(1, hist // 2), 0], jnp.int32)
    scores = jnp.asarray(rng.normal(size=(b, 48)), jnp.float32)
    chosen = ts.select_tokens(scores, hists, topk)
    acc, m, l = paged_decode_attention(
        q, k_pool, v_pool, jnp.int32(1), tables, hists, scale=0.25,
        latent=True, token_bits=chosen)
    lat = k_pool[1][tables].reshape(b, 48, c)
    rope = v_pool[1][tables].reshape(b, 48, r)
    sc = 0.25 * (jnp.einsum("bhc,bkc->bhk", q[..., :c], lat)
                 + jnp.einsum("bhr,bkr->bhk", q[..., c:], rope))
    sc = jnp.where(chosen[:, None], sc, -jnp.inf)
    want = jnp.einsum("bhk,bkc->bhc", jax.nn.softmax(sc, -1), lat)
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    assert float(jnp.abs(l[2]).max()) == 0.0  # no history: the empty state


@pytest.mark.parametrize("hist,cur,t", [
    pytest.param(32, 16, 16, id="whole-chunk"),
    pytest.param(20, 11, 16, id="padded-tail-and-a-partly-filled-page"),
    pytest.param(0, 16, 16, id="no-history"),
])
def test_the_latent_chunk_kernel_under_chosen_keys_is_the_same_mask_in_jnp(
        hist, cur, t):
    """ops/flash_prefill.py `latent_prefill_attention` with `chosen`
    (interpreted): each chunk query over the cached latent rows and the
    chunk's own that its mask names, against float32 attention in the
    absorbed form under the same mask."""
    from dynamo_tpu.ops.flash_prefill import latent_prefill_attention

    rng = np.random.default_rng(hist + cur)
    k_pool, v_pool = _latent_pools(rng)
    hn, c, r = 4, 32, 128
    hist_pages = -(-hist // PAGE)
    mp = 16
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:mp][None],
                         jnp.int32)
    ql = jnp.asarray(rng.normal(size=(1, t, hn, c)), jnp.float32) * 0.3
    qp = jnp.asarray(rng.normal(size=(1, t, hn, r)), jnp.float32) * 0.3
    lat_cur = jnp.asarray(rng.normal(size=(1, t, c)), jnp.float32)
    rope_cur = jnp.asarray(rng.normal(size=(1, t, r)), jnp.float32)
    n = mp * PAGE
    positions = hist + jnp.arange(t, dtype=jnp.int32)[None]
    valid = jnp.arange(t)[None] < cur
    scores = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    context = jnp.where(valid, positions + 1, 0)[0]
    chosen = ts.select_tokens(scores, context, 8)[None]
    got = latent_prefill_attention(
        ql, qp, lat_cur, rope_cur, k_pool, v_pool, jnp.int32(0), tables,
        jnp.asarray([hist], jnp.int32), jnp.asarray([cur], jnp.int32),
        chosen=chosen)
    # the same keys by position: the cached ones, then the chunk's own
    lat = k_pool[0][tables].reshape(1, n, c)
    rope = v_pool[0][tables].reshape(1, n, r)
    lat = jax.lax.dynamic_update_slice_in_dim(
        jnp.pad(lat, ((0, 0), (0, t), (0, 0))), lat_cur, hist, 1)[:, :n]
    rope = jax.lax.dynamic_update_slice_in_dim(
        jnp.pad(rope, ((0, 0), (0, t), (0, 0))), rope_cur, hist, 1)[:, :n]
    sc = (jnp.einsum("bthc,bkc->bhtk", ql, lat)
          + jnp.einsum("bthr,bkr->bhtk", qp, rope))
    sc = jnp.where(chosen[:, None], sc, -jnp.inf)
    want = jnp.einsum("bhtk,bkc->bthc", jax.nn.softmax(sc, -1), lat)
    np.testing.assert_allclose(got[0, :cur], want[0, :cur], atol=2e-5)
    assert hist_pages <= mp


@pytest.mark.parametrize("paired", [True, False])
@pytest.mark.parametrize("t", [1, 16])
def test_the_index_kernel_reads_both_pool_layouts(paired, t):
    """ops/index_scores.py `paged_index_scores` (interpreted) over keye's
    pair rows ([L / 2, P, S, 2 Di]) and over one key a row ([L, P, S, Di],
    `paired=False`): the same scores as `ts.index_scores` over the keys
    gathered by position."""
    from dynamo_tpu.ops.index_scores import paged_index_scores

    rng = np.random.default_rng(t + paired)
    layers, pages, di, nj, b, mp = 2, 30, 16, 4, 2, 12
    keys = jnp.asarray(rng.normal(size=(layers, pages, PAGE, di)),
                       jnp.float32)
    pool = (jnp.concatenate([keys[0::2], keys[1::2]], axis=-1) if paired
            else keys)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:b * mp]
                         .reshape(b, mp), jnp.int32)
    hist = jnp.asarray([33, 8], jnp.int32)
    qi = jnp.asarray(rng.normal(size=(b, t, nj, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, t, nj)), jnp.float32)
    own = jnp.asarray(rng.normal(size=(b, t, di)), jnp.float32)
    for layer in (0, 1):
        out = paged_index_scores(
            qi, w, pool, jnp.int32(layer), tables, hist,
            own if t > 1 else None, paired=paired)
        got = out[0] if t > 1 else out
        gathered = keys[layer][tables].reshape(b, mp * PAGE, di)
        want = ts.index_scores(qi, w, gathered)
        live = np.arange(mp * PAGE)[None, None] < np.asarray(hist)[:, None,
                                                               None]
        np.testing.assert_allclose(
            np.where(live, got, 0.0), np.where(live, want, 0.0), atol=2e-5)
        assert float(np.abs(np.where(live, 0.0, got)).max()) == 0.0
        if t > 1:
            np.testing.assert_allclose(
                out[1], ts.index_scores(qi, w, own), atol=2e-5)


def test_the_latent_helpers_keep_deepseeks_attention_as_it_was():
    """models/mla.py's attention block through the helpers this family
    shares (`latent_projections`, `absorbed_query`, `latent_output`) with
    no rescale and no gate IS the block DeepSeek-V2-Lite serves: the same
    numbers as the sum written out."""
    cfg = mla.MlaConfig.tiny()
    params = mla.init_params(jax.random.key(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 6, 64)),
                    jnp.float32)
    q, c_kv, kv_a, c_q = mla.latent_projections(x, lp, cfg)
    assert c_q is None and q.shape == (1, 6, 4, 24)
    np.testing.assert_allclose(q.reshape(1, 6, -1), x @ lp["wq"], atol=1e-5)
    np.testing.assert_allclose(kv_a, x @ lp["wkv_a"], atol=1e-5)
    assert c_kv.shape == (1, 6, 32)
    q_lat, w_uv = mla.absorbed_query(q, lp, cfg)
    wkv_b = lp["wkv_b"].reshape(32, 4, 32)
    np.testing.assert_allclose(
        q_lat, jnp.einsum("bthn,chn->bthc", q[..., :16], wkv_b[..., :16]),
        atol=1e-5)
    out = mla.latent_output(q_lat, w_uv, lp, cfg)
    want = jnp.einsum("bthc,chv->bthv", q_lat, wkv_b[..., 16:]).reshape(
        1, 6, -1) @ lp["wo"]
    np.testing.assert_allclose(out, want, atol=1e-4)
    gated = mla.latent_output(q_lat, w_uv, lp, cfg,
                              gate=jnp.zeros((1, 6, 4)))
    assert float(jnp.abs(gated).max()) == 0.0


def test_the_family_is_imported_lazily():
    import subprocess
    import sys

    code = ("import sys, dynamo_tpu.models.registry as r; "
            "r.get_model('keye-vl2-tiny'); r.get_model('mla-tiny'); "
            "print('dynamo_tpu.models.dots3' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                         "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip().endswith("False"), out.stderr[-2000:]
