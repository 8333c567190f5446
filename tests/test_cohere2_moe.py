"""Command A+'s language model (models/cohere2_moe.py) against its plain
reference (chipbench/references/command_a_plus.py) on seeded weights at
small sizes: prefill in pieces, then decode, with and without the kernels
(interpreted), through a ring that wraps and a dispatch launched ahead and
rolled back; the shares adding up to the uncut layer; the tied head; the
banded kernel alone; the adapter's rules."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import command_a_plus as ref
from dynamo_tpu.models import cohere2_moe as c2
from dynamo_tpu.models import mla
from dynamo_tpu.models.registry import get_model, list_presets

PAGE = 4


def _hf(cfg, **over):
    return {**ref.served_widths(cfg), "layer_types": list(cfg.layer_types),
            **over}


@pytest.fixture(scope="module")
def tiny():
    cfg = c2.Cohere2MoeConfig.tiny()
    return cfg, c2.init_params(jax.random.key(0), cfg)


def _serve(cfg, params, ids, piece, decode, rollback_at=None):
    """The program's logits at every position of `ids`: prefill in pieces
    of `piece`, then `decode` single steps; at `rollback_at` a dispatch is
    launched ahead with a wrong token, its result thrown away (the ring it
    wrote in place stays) and the real dispatch made after it."""
    total = len(ids) - decode
    cache = c2.init_cache(cfg, 64, PAGE, 2)
    tables = jnp.arange(1, 41, dtype=jnp.int32)[None]
    slot = jnp.array([[1, 1]], jnp.int32)
    fwd = jax.jit(lambda tok, pos, cache: c2.forward_hidden(
        params, cfg, tok, pos, jnp.ones(tok.shape, bool), cache, tables,
        slot))
    out = []
    for lo in range(0, total, piece):
        h, cache = fwd(jnp.asarray(ids[lo:lo + piece])[None],
                       jnp.arange(lo, lo + piece, dtype=jnp.int32)[None],
                       cache)
        out.append(c2.compute_logits(params, cfg, h)[0])
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
        out.append(c2.compute_logits(params, cfg, h)[0])
    return np.asarray(jnp.concatenate(out)), cache


@pytest.mark.parametrize("impl,piece", [
    ("xla", 16), ("pallas", 16), ("pallas", 32)])
def test_system_agrees_with_the_reference_through_a_wrapped_ring(
        tiny, impl, piece):
    """96 tokens in pieces, then 8 decode steps with a rollback: the ring of
    48 rows wraps twice; logits against the reference's full forward."""
    cfg, params = tiny
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    ids = np.random.default_rng(3).integers(1, cfg.vocab_size, 104)
    got, cache = _serve(cfg, params, ids, piece, 8, rollback_at=99)
    want = ref.log_probs(params, _hf(cfg), ids, np.arange(len(ids)))
    got = np.asarray(jax.nn.log_softmax(got, axis=-1))
    assert np.abs(got - want).max() < 2e-4
    # no window layer allocated a page: its rows live in the ring alone
    assert cache.k.shape[0] == cfg.full_layers == 2
    assert cache.ring.shape[:3] == (cfg.state_layers, 3, cfg.ring_tokens)
    # the device's count: 8 decode rows' windows, a sliding layer each
    # (the count of the dispatch launched ahead went with its result)
    named, live = int(cache.walked[0]), int(cache.walked[1])
    assert named == cfg.state_layers * 8 * cfg.sliding_window
    assert live == cfg.state_layers * sum(range(97, 105))


@pytest.mark.parametrize("fault,moved", [
    ({"window": 8}, True), ({"rope_full": True}, True),
    ({"moe": {"mean": False}}, True), ({}, False)])
def test_the_reference_moves_under_each_fault_the_controls_plant(
        tiny, fault, moved):
    """A window off by one, a rope on the full layer, a missing 1/4: each
    moves the reference's log-probs far past the agreement above."""
    cfg, params = tiny
    ids = np.random.default_rng(3).integers(1, cfg.vocab_size, 40)
    at = np.arange(20, 40)
    base = ref.log_probs(params, _hf(cfg), ids, at)
    off = np.abs(ref.log_probs(params, _hf(cfg), ids, at, **fault)
                 - base).max()
    assert (off > 1e-2) == moved


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The routed parts of the four shares of two experts each, and the
    shared experts counted once, add up to the uncut reference's FFN."""
    cfg, params = tiny
    lp = jax.tree.map(lambda w: w[1], params["layers"])
    x = jax.random.normal(jax.random.key(5), (24, cfg.hidden_size))
    hf = _hf(cfg)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe_branch(x, lp, hf, held=(0, 8))
        shared = ref.moe_branch(x, lp, hf, held=(0, 0))
        total = shared
        touched = 0
        for first in range(0, 8, 2):
            part = dataclasses.replace(cfg, experts_held=(first, 2))
            mine = {**lp, **{n: lp[n][first:first + 2] for n in c2.EXPERTS}}
            y, n = c2.moe_ffn(x, mine, part)
            total = total + (y - shared)
            touched += int(n[0])
            # the share's part is what the reference gives the same share
            np.testing.assert_allclose(
                y, ref.moe_branch(x, mine, hf, held=(first, 2)), atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert touched == 8  # 24 rows x 2 of 8: every expert chosen by some row


def test_the_sigmoid_rule_takes_the_highest_scores_ties_to_the_lower_index():
    geo = c2.Cohere2MoeConfig.tiny().moe_geo
    x = jnp.eye(4, 64)
    w = jnp.zeros((64, 8)).at[0, 5].set(3.0).at[0, 2].set(3.0).at[
        1, 7].set(1.0)
    topw, topi = mla._gate(x, {"w_router": w}, geo)
    assert topi[0].tolist() == [2, 5]  # a tie: the lower index first
    assert topi[1].tolist() == [7, 0]  # then the flat scores, lowest index
    np.testing.assert_allclose(topw.sum(-1), 1.0, rtol=1e-6)
    s = jax.nn.sigmoid(jnp.asarray([1.0, 0.0]))
    np.testing.assert_allclose(topw[1], s / s.sum(), rtol=1e-6)


def test_the_head_is_the_embedding_over_the_ids_held(tiny):
    cfg, params = tiny
    assert "lm_head" not in params
    h = jax.random.normal(jax.random.key(1), (3, cfg.hidden_size))
    logits = c2.compute_logits(params, cfg, h)
    assert logits.shape == (3, cfg.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, h @ params["embed"].T, rtol=1e-5)
    half = c2.Cohere2MoeConfig.tiny(vocab_size=128)
    sliced = c2.init_params(jax.random.key(0), half)
    assert sliced["embed"].shape == (128, cfg.hidden_size)
    assert c2.compute_logits(sliced, half, h).shape == (3, 128)


@pytest.mark.parametrize("b,t,r,first", [
    (1, 32, 48, 40),  # one query tile, the ring wrapped
    (2, 256, 1024, 700),  # two query tiles over two ring tiles of 512
    (1, 16, 48, 0),  # a prompt's first piece: no ring row holds a key
])
def test_the_banded_kernel_agrees_with_plain_attention(b, t, r, first):
    """ops/flash_prefill.py `ring_prefill_attention` (interpreted) against
    softmax attention over the same keys under the window, GQA 4 : 2."""
    from dynamo_tpu.models.dots3 import ring_positions
    from dynamo_tpu.ops.flash_prefill import ring_prefill_attention

    hq, hkv, d, w = 4, 2, 16, 37
    ks = jax.random.split(jax.random.key(b * t), 5)
    q = jax.random.normal(ks[0], (b, t, hq, d)) / 4
    k, v = (jax.random.normal(kk, (b, t, hkv, d)) for kk in ks[1:3])
    rk, rv = (jax.random.normal(kk, (b, hkv, r, d)) for kk in ks[3:5])
    start = jnp.asarray([first + 5 * i for i in range(b)], jnp.int32)
    pos = start[:, None] + jnp.arange(t)[None]
    valid = jnp.arange(t)[None] < t - 3  # a padded tail
    held = ring_positions(start - 1, r)
    got = ring_prefill_attention(
        q, k, v, rk, rv, pos, held, jnp.where(valid, pos, -1), window=w)
    keys = jnp.concatenate([jnp.swapaxes(rk, 1, 2), k], axis=1)
    vals = jnp.concatenate([jnp.swapaxes(rv, 1, 2), v], axis=1)
    kpos = jnp.concatenate([held, jnp.where(valid, pos, -1)], axis=1)
    keep = (kpos[:, None] >= 0) & (kpos[:, None] <= pos[..., None]) & (
        kpos[:, None] >= pos[..., None] - (w - 1))
    s = jnp.einsum("bthd,bkhd->bhtk", q, jnp.repeat(keys, 2, axis=2))
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhtk,bkhd->bthd", p, jnp.repeat(vals, 2, axis=2))
    np.testing.assert_allclose(got[:, :t - 3], want[:, :t - 3], atol=2e-5)


def test_a_decode_rows_walk_names_the_ring_pages_in_reach():
    """`ring_walk`: the pages that hold the window's positions, in position
    order, a bit a ring row inside the window; short contexts keep the rows
    no position has reached out."""
    cfg = dataclasses.replace(c2.Cohere2MoeConfig.tiny(), sliding_window=9,
                              ring_tokens=48)
    pos = jnp.asarray([[3], [50], [0]], jnp.int32)
    valid = jnp.asarray([[True], [True], [False]])
    tables, hist, bits = c2.ring_walk(
        pos, valid, jnp.asarray([1, 2, 0], jnp.int32), cfg, PAGE)
    assert tables.shape == (3, 3) and bits.shape == (3, 12)
    # row 1 at position 50: window 42..49 starts in ring page 10 of slot 2
    assert tables[1].tolist() == [2 * 12 + 10, 2 * 12 + 11, 2 * 12 + 0]
    assert int(hist[1]) == 50 - 40 and int(bits[1].sum()) == 8
    assert bits[1].tolist() == [False] * 2 + [True] * 8 + [False] * 2
    # row 0 at position 3: three keys behind it, none before position 0
    assert int(bits[0].sum()) == 3 and int(hist[2]) == 0


def test_the_published_preset_has_the_published_shapes():
    """`command-a-plus` by `jax.eval_shape`: 218 B parameters, and the
    one-chip preset's 9.47 GB (ISSUE 52's arithmetic)."""
    def count(name):
        adapter = get_model(name, dtype="bfloat16")
        shapes = jax.eval_shape(
            lambda: adapter.init_params(jax.random.key(0)))
        return adapter, sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(shapes))

    full, n = count("command-a-plus")
    assert 217e9 < n < 219e9
    assert full.config.layer_types.count(c2.FULL) == 8
    one, n = count("command-a-plus-4l-16e")
    assert abs(n - (4 * 1149.8e6 + 134.2e6)) < 1e6
    cfg = one.config
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.ring_tokens, cfg.intermediate_size,
            cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (
        4096, 128, 8, 128, 4096, 4608, 4096, 128, (0, 16), 32768)
    assert one.state_layers == 3 and one.state_in_place
    assert one.state_slot_bytes == 3 * 4608 * 4096  # 56.6 MB a slot
    assert c2.page_bytes(cfg, 64) == 64 * 4096  # the full layer alone


def test_the_adapter_refuses_what_would_move_pages_without_the_rings():
    adapter = get_model("command-a-plus-tiny")
    assert {"command-a-plus", "command-a-plus-4l-16e",
            "command-a-plus-tiny"} <= set(list_presets())
    assert [what for what, _ in adapter.refuses] == [
        "kv_tiers", "speculation", "page_transfer"]
    assert all("rings" in why for _, why in adapter.refuses)
    assert not adapter.step_twins and adapter.walk_pages is c2.walk_count
    with pytest.raises(ValueError, match="kv_quantize"):
        adapter.init_kv(8, 4, kv_quantize="int8", state_slots=1)
    with pytest.raises(ValueError, match="whole number of pages"):
        adapter.init_kv(8, 5, state_slots=1)
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(adapter.config,
                            layer_types=(c2.FULL, c2.SLIDING))
