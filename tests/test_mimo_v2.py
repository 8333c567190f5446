"""MiMo-V2.5's language model (models/mimo_v2.py) against its plain
reference (chipbench/references/mimo_v2.py) on seeded weights at small
sizes: prefill in pieces, then decode, with and without the kernels
(interpreted), through a ring that wraps and a dispatch launched ahead and
rolled back; the sink, the value scale, the partial rope and both thetas
against the reference and against their controls; a key wider than its
value in the walk, the write and the chunk kernel against plain einsums;
the shares adding up to the uncut layer; the sliced head; the adapter's
rules."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import mimo_v2 as ref
from dynamo_tpu.models import mimo_v2 as mm
from dynamo_tpu.models.registry import get_model, list_presets

PAGE = 4


def _hf(cfg, **over):
    return {
        **ref.served_widths(cfg), "partial_rotary_factor": 0.334,
        "hybrid_layer_pattern": [int(k != mm.FULL) for k in cfg.layer_types],
        "moe_layer_freq": [int(m) for m in cfg.moe_layers],
        "layer_ids": list(range(cfg.num_layers)), **over}


def _seeded(cfg, seed=0):
    """The tree of `mm.init_params` at its scales, drawn by numpy: XLA takes
    6 s a worker process to build the tiny tree's draws, leaf by leaf (the
    draws themselves, by published index, are judged in
    `test_the_published_preset_has_the_published_shapes`)."""
    rng = np.random.default_rng(seed)
    shapes = jax.eval_shape(lambda: mm.init_params(jax.random.key(0), cfg))

    def fill(path, s):
        name = path[-1].key
        if name.endswith("norm"):
            return jnp.ones(s.shape, s.dtype)
        scale = {"sink": 1.0, "embed": 1.0, "router_bias": mm.BIAS_SPREAD,
                 "w_router": mm.ROUTER_SPREAD / math.sqrt(s.shape[-2])}.get(
            name) or 1.0 / math.sqrt(s.shape[-2])
        return jnp.asarray(
            rng.standard_normal(s.shape, np.float32) * scale, s.dtype)

    return jax.tree_util.tree_map_with_path(fill, shapes)


@pytest.fixture(scope="module")
def tiny():
    cfg = mm.MimoV2Config.tiny()
    return cfg, _seeded(cfg)


def _serve(cfg, params, ids, piece, decode, rollback_at=None):
    """The program's logits at every position of `ids`: prefill in pieces
    of `piece`, then `decode` single steps; at `rollback_at` a dispatch is
    launched ahead with a wrong token, its result thrown away (the ring it
    wrote in place stays) and the real dispatch made after it."""
    total = len(ids) - decode
    cache = mm.init_cache(cfg, 64, PAGE, 2)
    tables = jnp.arange(1, 41, dtype=jnp.int32)[None]
    slot = jnp.array([[1, 1]], jnp.int32)
    fwd = jax.jit(lambda tok, pos, cache: mm.forward_hidden(
        params, cfg, tok, pos, jnp.ones(tok.shape, bool), cache, tables,
        slot))
    out = []
    for lo in range(0, total, piece):
        h, cache = fwd(jnp.asarray(ids[lo:lo + piece])[None],
                       jnp.arange(lo, lo + piece, dtype=jnp.int32)[None],
                       cache)
        out.append(mm.compute_logits(params, cfg, h)[0])
    for t in range(total, len(ids)):
        pos = jnp.full((1, 1), t, jnp.int32)
        if t == rollback_at:
            # launched ahead on a guess: a wrong token here AND one more
            # position after it; only the pools keep what it wrote
            _, ahead = fwd(jnp.asarray([[int(ids[t]) ^ 1]]), pos, cache)
            _, ahead = fwd(jnp.asarray([[7]]), pos + 1, ahead)
            cache = cache._replace(ring=ahead.ring, ring_v=ahead.ring_v,
                                   k=ahead.k, v=ahead.v)
        h, cache = fwd(jnp.asarray(ids[t:t + 1])[None], pos, cache)
        out.append(mm.compute_logits(params, cfg, h)[0])
    return np.asarray(jnp.concatenate(out)), cache


@pytest.mark.parametrize("impl,piece", [("xla", 16), ("pallas", 32)])
def test_system_agrees_with_the_reference_through_a_wrapped_ring(
        tiny, impl, piece):
    """96 tokens in pieces, then 8 decode steps with a rollback: the ring of
    40 rows wraps twice; logits against the reference's full forward."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    ids = np.random.default_rng(3).integers(1, cfg.vocab_size, 104)
    got, cache = _serve(cfg, params, ids, piece, 8, rollback_at=99)
    want = ref.log_probs(params, _hf(cfg), ids, np.arange(len(ids)))
    got = np.asarray(jax.nn.log_softmax(got, axis=-1))
    assert np.abs(got - want).max() < 2e-4
    # no window layer allocated a page, no pool pads a 192-wide key: a
    # token is 640 B x 4 bytes / 2 a KV head in this float32 cache
    kp, vp = cfg.parts
    assert (kp, vp) == (3, 2)
    assert cache.k.shape == (kp * cfg.full_layers, 64, PAGE, 2, 128)
    assert cache.v.shape == (vp * cfg.full_layers, 64, PAGE, 2, 128)
    assert cache.ring.shape == (kp * cfg.state_layers, 3, 40, 4, 128)
    assert cache.ring_v.shape == (vp * cfg.state_layers, 3, 40, 4, 128)
    assert cache.k.nbytes + cache.v.nbytes == 64 * mm.page_bytes(cfg, PAGE)
    assert cache.ring.nbytes + cache.ring_v.nbytes == (
        3 * mm.state_bytes_per_slot(cfg))
    # the device's count: 8 decode rows' windows, a window layer each (the
    # count of the dispatch launched ahead went with its result)
    named, live = int(cache.walked[0]), int(cache.walked[1])
    assert named == cfg.state_layers * 8 * cfg.sliding_window
    assert live == cfg.state_layers * sum(range(97, 105))


@pytest.mark.parametrize("fault,moved", [
    ({"sink": False}, True), ({"value_scale": False}, True),
    ({"rope_whole_head": True}, True), ({"thetas_swapped": True}, True),
    ({"window": 6}, True), ({"moe_how": {"bias": False}}, True),
    ({}, False)])
def test_the_reference_moves_under_each_fault_the_controls_plant(
        tiny, fault, moved):
    """No sink, no 0.707, the whole head rotated, the thetas swapped, a
    window off by one, the correction bias left out of the choice: each
    moves the reference's log-probs far past the agreement above (so the
    program, which agrees, has each of them right)."""
    cfg, params = tiny
    if "moe_how" in fault:  # a bias large enough to move a choice of 2 of 8
        params = {**params, "moe": {
            **params["moe"],
            "router_bias": params["moe"]["router_bias"] * 30}}
    ids = np.random.default_rng(3).integers(1, cfg.vocab_size, 40)
    at = np.arange(20, 40)
    base = ref.log_probs(params, _hf(cfg), ids, at)
    off = np.abs(ref.log_probs(params, _hf(cfg), ids, at, **fault)
                 - base).max()
    assert (off > 1e-2) == moved


@pytest.mark.parametrize("impl,fault", [
    ("xla", None), ("xla", "window_129"), ("xla", "sink_left_out"),
    ("xla", "thetas_swapped")])
def test_a_fault_planted_in_the_program_shows_at_depth(tiny, impl, fault):
    """`long_path` at 96 tokens (the ring wrapped twice): the program's
    window and full attention agree with the reference's, and each planted
    fault is seen by a distance (without the kernels here; under the
    interpreted kernels in tests/chipbench/test_chipbench_mimo_v2.py
    `test_the_harness_judges_the_tiny_program_and_a_planted_fault`)."""
    cfg, params = tiny
    hf = _hf(cfg, preset="mimo-v2.5-tiny", dtype="float32",
             attention_impl=impl, page_size=PAGE, judged=[16, 4])
    got = ref.long_path(params, hf, context=96, fault=fault)
    window, full = got["window_attn_distance"], got["full_attn_distance"]
    if fault is None:
        assert window < 1e-5 and full < 1e-5
    elif fault in ("sink_left_out", "window_129"):
        assert window > 0.01 and full < 1e-5  # the window layers' alone
    else:
        assert window > 0.01 and full > 0.01


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts of the four shares of two experts each add up to
    the uncut reference's FFN (there is no shared expert to count once)."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["moe"])
    x = jax.random.normal(jax.random.key(5), (24, cfg.hidden_size))
    hf = _hf(cfg)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_branch(x, lp, hf, held=(0, 8))
        total, touched = jnp.zeros_like(whole), 0
        for first in range(0, 8, 2):
            part = dataclasses.replace(cfg, experts_held=(first, 2))
            mine = {**lp, **{n: lp[n][first:first + 2] for n in mm.EXPERTS}}
            y, n = mm.moe_ffn(x, mine, part)
            total = total + y
            touched += int(n[0])
            np.testing.assert_allclose(
                y, ref.moe_branch(x, mine, hf, held=(first, 2)), atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert touched == 8  # 24 rows x 2 of 8: every expert chosen by some row


def test_the_head_is_untied_and_over_the_ids_held(tiny):
    cfg, params = tiny
    h = jax.random.normal(jax.random.key(1), (3, cfg.hidden_size))
    logits = mm.compute_logits(params, cfg, h)
    assert logits.shape == (3, cfg.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, h @ params["lm_head"], rtol=1e-5)
    assert params["embed"].shape == params["lm_head"].T.shape
    half = mm.MimoV2Config.tiny(vocab_size=128)
    sliced = _seeded(half)
    assert sliced["embed"].shape == (128, cfg.hidden_size)
    assert mm.compute_logits(sliced, half, h).shape == (3, 128)


def _plain(q, keys, vals, keep, sink=None):
    """softmax(q . k / sqrt(d)) v over `keep` [B, T, K], GQA, with a sink a
    head as one more column."""
    g = q.shape[2] // keys.shape[2]
    s = jnp.einsum("bthd,bkhd->bhtk", q, jnp.repeat(keys, g, axis=2))
    s = jnp.where(keep[:, None], s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink[None, :, None, None], (*s.shape[:3], 1))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :keys.shape[1]]
    return jnp.einsum("bhtk,bkhd->bthd", p, jnp.repeat(vals, g, axis=2))


@pytest.mark.parametrize("b,t,r,first,w,sink", [
    (1, 512, 640, 700, 128, True),  # four query tiles past their window
    (1, 384, 640, 0, 130, False),  # banded, no sink, a first piece
    (2, 256, 512, 900, 4096, True),  # a window past the piece: not banded
])
def test_the_banded_kernel_takes_wide_keys_and_a_sink(b, t, r, first, w,
                                                      sink):
    """ops/flash_prefill.py `ring_prefill_attention` (interpreted) with
    keys 256 wide (192 + zeros) beside values 128 wide, a sink a head and a
    window shorter than the piece, against plain attention, GQA 4 : 2."""
    from dynamo_tpu.models.dots3 import ring_positions
    from dynamo_tpu.ops.flash_prefill import ring_prefill_attention

    hq, hkv, d, dv = 4, 2, 256, 128
    ks = jax.random.split(jax.random.key(b * t), 6)
    q = jax.random.normal(ks[0], (b, t, hq, d)) / math.sqrt(d)
    k = jax.random.normal(ks[1], (b, t, hkv, d))
    v = jax.random.normal(ks[2], (b, t, hkv, dv))
    rk = jax.random.normal(ks[3], (b, hkv, r, d))
    rv = jax.random.normal(ks[4], (b, hkv, r, dv))
    sinks = jax.random.normal(ks[5], (hq,)) if sink else None
    start = jnp.asarray([first + 5 * i for i in range(b)], jnp.int32)
    pos = start[:, None] + jnp.arange(t)[None]
    valid = jnp.arange(t)[None] < t - 3  # a padded tail
    held = ring_positions(start - 1, r)
    got = ring_prefill_attention(
        q, k, v, rk, rv, pos, held, jnp.where(valid, pos, -1), window=w,
        sinks=sinks)
    assert got.shape == (b, t, hq, dv)
    keys = jnp.concatenate([jnp.swapaxes(rk, 1, 2), k], axis=1)
    vals = jnp.concatenate([jnp.swapaxes(rv, 1, 2), v], axis=1)
    kpos = jnp.concatenate([held, jnp.where(valid, pos, -1)], axis=1)
    keep = (kpos[:, None] >= 0) & (kpos[:, None] <= pos[..., None]) & (
        kpos[:, None] >= pos[..., None] - (w - 1))
    want = _plain(q, keys, vals, keep, sinks)
    np.testing.assert_allclose(got[:, :t - 3], want[:, :t - 3], atol=3e-5)


@pytest.mark.parametrize("bits", [False, True])
def test_the_walk_and_the_write_take_a_key_wider_than_its_value(bits):
    """ops/paged_attention.py `paged_decode_attention` with `parts` (3, 2)
    over pools in lane parts that ops/kv_update.py `paged_write` filled
    (K of 3 x L layers beside V of 2 x L), against a plain einsum over the
    same rows: 8 query heads over 4 KV heads of 192 | 128, layer 1 of 2."""
    from dynamo_tpu.ops.kv_update import paged_write
    from dynamo_tpu.ops.paged_attention import paged_decode_attention

    b, hq, hkv, dk, dv, s, mp, n_l = 3, 8, 4, 192, 128, 8, 6, 2
    ks = jax.random.split(jax.random.key(7), 4)
    lens = jnp.asarray([37, 0, 48], jnp.int32)
    k = jax.random.normal(ks[0], (n_l, b, 48, hkv, dk))
    v = jax.random.normal(ks[1], (n_l, b, 48, hkv, dv))
    q = jax.random.normal(ks[2], (b, hq, dk))
    tables = 1 + jnp.arange(b * mp, dtype=jnp.int32).reshape(b, mp)
    pools = [jnp.zeros((n * n_l, 1 + b * mp, s, hkv // 2, 128))
             for n in (3, 2)]
    pos = jnp.broadcast_to(jnp.arange(48)[None], (b, 48))
    # [parts, L, ..] -> parts x L: part t of layer l at t x L + l
    stage = lambda a: jnp.stack(  # noqa: E731
        [mm.pack(a[li]) for li in range(n_l)], axis=1).reshape(
        -1, b, 48, hkv // 2, 128)
    kc, vc = paged_write(*pools, stage(k), stage(v), tables, pos,
                         pos < lens[:, None])
    keep = jax.random.bernoulli(ks[3], 0.7, (b, mp * s)) if bits else None
    second = (jnp.arange(hq) // (hq // hkv)) % 2 == 1
    acc, m, l = paged_decode_attention(
        mm.widen(q, second), kc, vc, jnp.int32(1), tables, lens,
        scale=1 / math.sqrt(dk), parts=(3, 2), token_bits=keep)
    assert acc.shape == (b, hq, 2 * dv)
    got = mm.narrow(acc, second) / jnp.maximum(l, 1e-30)[..., None]
    mask = pos < lens[:, None]
    if bits:
        mask &= keep[:, :48]
    want = _plain(q[:, None] / math.sqrt(dk), k[1], v[1], mask[:, None])[:, 0]
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert float(jnp.abs(acc[1]).max()) == 0 and float(l[1].max()) == 0
    # `pack` / `unpack` are each other's inverse, and nothing is padded
    np.testing.assert_array_equal(mm.unpack(mm.pack(k[0]), dk), k[0])
    assert kc.size + vc.size == n_l * (1 + b * mp) * s * hkv * (dk + dv)


def test_the_sink_folds_into_a_walks_state_exactly():
    """`fold_sink`: a walk's (acc, m, l) and the sink give the softmax over
    [history, sink] with the sink's column dropped; an empty history gives
    (0, the sink, 1): the own token then stands against the sink alone."""
    b, hq, dv = 2, 4, 8
    rng = np.random.default_rng(2)
    s_hist = rng.normal(size=(b, hq, 6)).astype(np.float32)
    v_hist = rng.normal(size=(b, hq, 6, dv)).astype(np.float32)
    sink = rng.normal(size=(hq,)).astype(np.float32)
    m = s_hist.max(-1)
    p = np.exp(s_hist - m[..., None])
    acc, l = np.einsum("bhk,bhkd->bhd", p, v_hist), p.sum(-1)
    m[1], acc[1], l[1] = -np.inf, 0.0, 0.0  # a row with no history
    acc2, m2, l2 = jax.jit(mm.fold_sink)(acc, m, l, sink)
    cols = np.concatenate(
        [s_hist, np.broadcast_to(sink[None, :, None], (b, hq, 1))], axis=-1)
    w = np.exp(cols - cols.max(-1, keepdims=True))
    w = (w / w.sum(-1, keepdims=True))[..., :6]
    np.testing.assert_allclose(
        (acc2 / l2[..., None])[0],
        np.einsum("hk,hkd->hd", w[0], v_hist[0]), atol=1e-6)
    assert float(np.abs(acc2[1]).max()) == 0  # the sink's value is 0
    np.testing.assert_allclose(l2[1], 1.0)
    np.testing.assert_allclose(m2[1], sink)


def test_the_published_preset_has_the_published_shapes():
    """`mimo-v2.5` by `jax.eval_shape`: 308.8 B parameters, and the one-chip
    preset's 6.87 GB, pools and periods (ISSUE 56's arithmetic)."""
    def count(name):
        adapter = get_model(name, dtype="bfloat16")
        shapes = jax.eval_shape(
            lambda: adapter.init_params(jax.random.key(0)))
        return adapter, shapes

    def params(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    full, shapes = count("mimo-v2.5")
    assert 308e9 < params(shapes) < 310e9
    kinds = full.config.layer_types
    assert [i for i, k in enumerate(kinds) if k == mm.FULL] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert full.config.moe_layers == (False,) + (True,) * 47
    assert full.config.periods[:2] == ((0, 4), (5, 5))
    one, shapes = count("mimo-v2.5-7l-16e")
    assert abs(params(shapes) - 3.43e9) < 5e6
    assert abs(params(shapes["dense"]) + params(shapes["full"]) / 2
               - 290.5e6) < 1e5  # layer 0
    assert abs(params(shapes["moe"]) / 6 + params(shapes["swa"]) / 5
               - 498.1e6) < 1e5  # a window expert layer
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    assert abs(nbytes - 6.87e9) < 1e7
    cfg = one.config
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.swa_num_kv_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.rotary_dim, cfg.sliding_window, cfg.ring_tokens,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (
        4096, 64, 4, 8, 192, 128, 64, 128, 640, 16384, 2048, 256, (0, 16),
        19072)
    assert cfg.published_ids == (0, 6, 7, 8, 9, 10, 11)
    assert cfg.periods == ((0, 5), (6, 0)) and cfg.ring_run == 513
    assert one.state_layers == 5 and one.state_in_place
    assert one.state_slot_bytes == 5 * 640 * 5120  # 16.4 MB a slot
    assert mm.page_bytes(cfg, 64) == 2 * 64 * 2560  # the full layers alone
    # a held layer is drawn by its PUBLISHED index: layer 6 of the cut is
    # what the whole model holds at layer 6
    small = dataclasses.replace(
        mm.MimoV2Config.tiny(), layer_ids=(0, 6, 7, 8, 9, 10, 11))
    whole = dataclasses.replace(
        mm.MimoV2Config.tiny(), layer_ids=None,
        layer_types=(mm.FULL,) + (mm.SLIDING,) * 6,
        moe_layers=(False,) + (True,) * 6)
    # (one leaf of each tree: XLA drops the other draws)
    a, b = (jax.jit(lambda c=c: mm.init_params(jax.random.key(0), c)[
        "swa"]["wk"])() for c in (small, whole))
    np.testing.assert_array_equal(a[0], b[5])
    assert not np.array_equal(a[0], b[0])


def test_the_adapter_refuses_what_would_move_pages_without_the_rings():
    adapter = get_model("mimo-v2.5-tiny")
    assert {"mimo-v2.5", "mimo-v2.5-7l-16e", "mimo-v2.5-tiny"} <= set(
        list_presets())
    assert [what for what, _ in adapter.refuses] == [
        "kv_tiers", "speculation", "page_transfer"]
    assert all("rings" in why for _, why in adapter.refuses)
    assert not adapter.step_twins and adapter.walk_pages is mm.walk_count
    with pytest.raises(ValueError, match="kv_quantize"):
        adapter.init_kv(8, 4, kv_quantize="int8", state_slots=1)
    with pytest.raises(ValueError, match="whole number of pages"):
        adapter.init_kv(8, 3, state_slots=1)
    with pytest.raises(ValueError, match="a full layer first"):
        dataclasses.replace(adapter.config,
                            layer_types=(mm.SLIDING,) * 7)
    with pytest.raises(ValueError, match="whole lane tiles"):
        dataclasses.replace(adapter.config, head_dim=160)
    with pytest.raises(ValueError, match="parts"):
        from dynamo_tpu.ops.paged_attention import paged_decode_attention

        paged_decode_attention(
            jnp.zeros((1, 8, 384)), jnp.zeros((3, 4, 8, 2, 128)),
            jnp.zeros((2, 4, 8, 2, 64)), jnp.int32(0),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            parts=(3, 2))
