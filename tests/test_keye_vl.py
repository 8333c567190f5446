"""Keye-VL-2.0's language model (models/keye_vl.py, ops/token_select.py):
GQA under a learned indexer that chooses tokens, softmax-routed experts of
which a chip may hold a share, at a small size on seeded weights, against
the plain reference the benchmark brings (chipbench/references/
keye_vl.py: float32, a stable sort for the selection, no cache, no
kernels, expert by expert).

`keye-vl2-tiny`: a page of 4 tokens, the 8 highest tokens a query, 4 index
heads of 8, 2 query heads a KV head, 8 experts top 2, two layers.

Tolerances: everything runs in float32 here, so what separates the system
from the reference is the order of sums: 2e-4 on log-probs of magnitude
~4, a hundred times the observed 2e-6. A selection that differs in one
token, a dropped assignment or a stale index key moves them by 1e-2 or
more.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.models import keye_vl as kvm
from dynamo_tpu.models.registry import (
    _keye_vl_adapter, get_model, list_presets)
from dynamo_tpu.ops import sparse_chunk as sc
from dynamo_tpu.ops import token_select as ts
from dynamo_tpu.ops.paged_attention import paged_decode_attention
from test_falcon_h1 import _streams

TOL = 2e-4
PAGE = 4

ref = manifest._load(
    manifest.ROOT / "chipbench/references/keye_vl.py", "ref_keye_vl")


def hf_of(cfg, **more) -> dict:
    return {**ref.served_widths(cfg), **more}


@pytest.fixture(scope="module")
def tiny():
    adapter = get_model("keye-vl2-tiny")
    return adapter, adapter.init_params(jax.random.key(0))


def test_presets_are_the_published_model_and_its_cut():
    assert {"keye-vl2-30b-a3b", "keye-vl2-30b-a3b-8l-16e",
            "keye-vl2-tiny"} <= set(list_presets())
    full = get_model("keye-vl2-30b-a3b").config
    cut = get_model("keye-vl2-30b-a3b-8l-16e").config
    assert (full.num_layers, full.experts_held) == (48, None)
    assert (cut.num_layers, cut.experts_held, cut.experts_here) == (
        8, (0, 16), 16)
    assert dataclasses.replace(
        cut, num_layers=48, experts_held=None) == full
    w = ref.served_widths(cut)
    assert (w["hidden_size"], w["num_attention_heads"],
            w["num_key_value_heads"], w["head_dim"]) == (2048, 32, 4, 128)
    assert (w["moe_intermediate_size"], w["num_local_experts"],
            w["num_experts"], w["num_experts_per_tok"]) == (768, 128, 16, 8)
    assert w["sa_config"]["topk"] == 2048 and w["vocab_size"] == 151936
    assert w["rope_scaling"]["mrope_section"] == [16, 24, 24]
    # a page of 64 tokens, 8 layers: K, V and the index keys
    assert kvm.page_bytes(cut, 64) == 1_114_112
    shapes = jax.eval_shape(lambda: kvm.init_cache(cut, 9000, 64))
    assert sum(np.prod(a.shape) * a.dtype.itemsize
               for a in (shapes.k, shapes.v, shapes.ki)) == 9000 * 1_114_112


def test_a_mesh_and_quantised_pages_are_refused():
    adapter = get_model("keye-vl2-tiny")
    with pytest.raises(ValueError, match="one chip"):
        _keye_vl_adapter("keye-vl2-tiny", adapter.config, mesh=object())
    with pytest.raises(ValueError, match="kv_quantize is not supported"):
        adapter.init_kv(8, PAGE, kv_quantize="int8")
    base = EngineConfig.for_tests(model="keye-vl2-tiny")
    with pytest.raises(ValueError, match="speculation is not supported"):
        JaxEngine(EngineConfig(**{**base.__dict__, "spec_ngram": 2}))
    with pytest.raises(ValueError, match="kv_tiers is not supported"):
        JaxEngine(EngineConfig(**{
            **base.__dict__, "host_kv_cache_bytes": 1 << 20}))


# -- (a) chunks + decode through the engine == one full forward --------------


def _serve(adapter, params, toks, chunks, t_bucket=16):
    """Prefill then decode one sequence through the cache the way the
    engine does: chunk by chunk, each padded to `t_bucket`."""
    forward = jax.jit(adapter.forward)
    kv = adapter.init_kv(64, PAGE)
    pt = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
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
    pytest.param([16, 16, 5] + [1] * 6, id="topk-passed-inside-a-chunk"),
    pytest.param([4] + [1] * 12, id="topk-passed-during-decode"),
    pytest.param([16, 16, 16, 11] + [1] * 3, id="long-row"),
])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        impl, chunks):
    """A sequence that passes `topk` (8 tokens) inside a prompt chunk, or
    during decode, chunk by chunk and then token by token through the
    pools, against ONE full forward of the reference: the rule is by
    query token, so the three agree."""
    adapter = get_model("keye-vl2-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    n = sum(chunks)
    toks = np.random.default_rng(4).integers(3, 256, n)
    got, kv = _serve(adapter, params, toks, chunks)
    want = ref.log_probs(params, hf_of(adapter.config), toks, np.arange(n))
    np.testing.assert_allclose(got, want, atol=TOL)
    # the decode rows' count: min(8, t + 1) of t + 1 tokens, a layer each
    decoded = [t + 1 for t in range(n) if t >= n - chunks.count(1)]
    assert list(np.asarray(kv.walked)[:2]) == [
        2 * sum(min(8, c) for c in decoded), 2 * sum(decoded)]


def _engine(**overrides):
    base = EngineConfig.for_tests(
        model="keye-vl2-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=2, decode_buckets=(1, 2),
    )
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


@pytest.mark.parametrize("scenario", [
    "three-chunks-then-fused-dispatches", "slot-reuse-after-a-finish",
    "forced-rollback", "preemption-recompute", "prefix-hit"])
def test_engine_streams_are_the_reference(scenario):
    """The normal path (scheduler, pages with their index keys, the step
    programs, launch-ahead on), teacher-forced against the reference on
    the chosen tokens' log-probs: a prompt over three chunks and fused
    8-step dispatches; five requests through two decode slots with mixed
    steps all the way; a neighbour aborted while a dispatch launched ahead
    is on the device, so the survivors' pages and index keys were advanced
    by a dispatch that is thrown away; a pool so small that a row is
    preempted and recomputed on other pages (a freed page's keys are never
    read again); a PREFIX HIT, which this family accepts: the second
    request reads the first one's pages, index keys included, and its
    log-probs are a cold run's."""
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
        toks, lps = _streams(eng, [("cold", shared + [7, 8, 9], 10)])
        before = eng.allocator.stats.hit_tokens
        reqs = [("warm", shared + [7, 8, 9], 10)]
        toks2, lps2 = _streams(eng, reqs)
        assert eng.allocator.stats.hit_tokens - before >= 40
        assert toks2["warm"] == toks["cold"]
        np.testing.assert_allclose(lps2["warm"], lps["cold"], atol=TOL)
        toks, lps = toks2, lps2
    if scenario != "prefix-hit":
        toks, lps = _streams(eng, reqs, events)
    m = eng.metrics
    if scenario == "three-chunks-then-fused-dispatches":
        assert m.prefill_dispatches == 3
        assert any(k[0] == "decode_multi" and k[2] == 8
                   for k in eng.programs)
        # every decode row attended 8 of its 76-95 tokens (counted on the
        # device, read back beside each dispatch's ids)
        assert 0 < m.walk_pages_named < 0.12 * m.walk_pages_live
        assert m.chunk_pages_read > m.chunk_pages_named > 0
        # one prefill-carrying program a shape (`STEP_TWINS` False)
        assert not any(k[5] for k in eng.programs if len(k) > 5)
        assert not any(k[0] == "prefill_nosample" for k in eng.programs)
    elif scenario == "slot-reuse-after-a-finish":
        assert m.mixed_dispatches > 0
    elif scenario == "forced-rollback":
        assert m.overlap_rollbacks > 0
        reqs = [r for r in reqs if r[0] in only]
    elif scenario == "preemption-recompute":
        assert m.preemptions > 0
    hf = hf_of(eng.adapter.config)
    for rid, prompt, n in reqs:
        seq = list(prompt) + toks[rid]
        want = ref.log_probs(eng.params, hf, seq,
                             len(prompt) - 1 + np.arange(n))
        of_served = want[np.arange(n), np.asarray(toks[rid])]
        np.testing.assert_allclose(lps[rid], of_served, atol=TOL,
                                   err_msg=rid)
        assert (want.max(-1) - of_served).max() < TOL, rid


# -- (b) the selected set is the reference's, ties included -------------------


@pytest.mark.parametrize("case", ["seeded", "planted-ties", "all-equal",
                                  "signed-zeros"])
def test_the_selection_is_the_references_at_every_position(case):
    """`select_tokens` (no sort: the k-th key by bisection over the bits,
    ties by bisection over the position) against the reference's stable
    descending sort, at every position of a 70-token row."""
    n, topk = 72, 8
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(n, n)).astype(np.float32)
    if case == "planted-ties":
        scores = np.round(scores * 2) / 2  # a few distinct values
    elif case == "all-equal":
        scores[:] = 0.25
    elif case == "signed-zeros":
        scores = np.where(rng.random((n, n)) < 0.5, 0.0, -0.0).astype(
            np.float32)
        scores[:, ::7] = 1.0
    pos = np.arange(70, dtype=np.int32)
    mine = np.asarray(jax.jit(lambda s, c: ts.select_tokens(s, c, topk))(
        jnp.asarray(scores[:70]), jnp.asarray(pos + 1)))
    theirs = np.asarray(ref.selected_tokens(
        jnp.asarray(scores[:70]), jnp.asarray(pos), topk))
    np.testing.assert_array_equal(mine, theirs)
    assert list(mine.sum(1)) == [min(topk, t + 1) for t in pos]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_programs_selection_is_the_references_on_a_long_row(impl, tiny):
    """The benchmark's own judgement (`sparse_path` of the reference
    module, which `compare` runs at 12,288 tokens on the chip) at 96
    tokens: the program's index keys through its cache layout, its
    scores, selection, chunk kernel and decode walk on the reference's
    hidden states agree with the reference on every judged query, and the
    planted fault (two cached tokens changing places) is seen by the
    attention's distance alone."""
    adapter, params = tiny
    hf = hf_of(adapter.config, preset="keye-vl2-tiny", dtype="float32",
               attention_impl=impl, sparse_context=96, judged=[16, 4],
               page_size=PAGE)
    got = ref.sparse_path(params, hf, context=96)
    assert got["selected_tokens_agreement_min"] == 1.0
    assert got["sparse_attn_distance"] < 1e-5
    bad = ref.sparse_path(params, hf, context=96, fault="wrong_token")
    assert bad["selected_tokens_agreement"] == 1.0
    assert bad["sparse_attn_distance"] > 0.01


# -- (c) attention over a selection == dense attention under the same mask ---


def _pools(rng, layers=2, pages=40, hkv=2, d=16):
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return f32(layers, pages, PAGE, hkv, d), f32(layers, pages, PAGE, hkv, d)


@pytest.mark.parametrize("hist,topk", [
    pytest.param([50, 9, 0], 8, id="past-topk"),
    pytest.param([7, 3, 0], 8, id="under-topk-is-dense"),
])
def test_the_decode_walk_under_bits_is_dense_attention_under_the_mask(
        hist, topk):
    rng = np.random.default_rng(0)
    kp, vp = _pools(rng)
    b, mp, hq, d = len(hist), 16, 4, 16
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, 40))[:mp] for _ in range(b)
    ]).astype(np.int32))
    hist = jnp.asarray(hist, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    scores = jnp.asarray(rng.normal(size=(b, mp * PAGE)), jnp.float32)
    bits = ts.select_tokens(scores, hist, topk)
    acc, m, l = paged_decode_attention(q, kp, vp, 1, tables, hist,
                                       token_bits=bits)
    kh = kp[1][tables].reshape(b, mp * PAGE, 2, d)
    vh = vp[1][tables].reshape(b, mp * PAGE, 2, d)
    want = ts.masked_attention((q / math.sqrt(d))[:, None], kh, vh,
                               bits[:, None])[:, 0]
    live = np.asarray(hist) > 0
    np.testing.assert_allclose(
        np.asarray(acc / jnp.maximum(l, 1e-30)[..., None])[live],
        np.asarray(want)[live], atol=2e-5)
    if int(hist.max()) <= topk:  # every cached token: the plain walk
        dense = paged_decode_attention(q, kp, vp, 1, tables, hist)
        for a, b_ in zip(dense, (acc, m, l)):
            np.testing.assert_allclose(np.asarray(a)[live],
                                       np.asarray(b_)[live], atol=2e-5)
    # a row with no history reads as the empty state the merge expects
    assert float(l[2].max()) == 0.0 and np.isneginf(np.asarray(m[2])).all()


@pytest.mark.parametrize("hist,cur,t", [
    pytest.param([20, 8], [24, 10], 24, id="two-rows-one-padded"),
    pytest.param([0, 36], [5, 16], 16, id="a-first-chunk-beside-a-late-one"),
])
def test_the_chunk_kernel_under_token_bits_is_the_same_mask_in_jnp(
        hist, cur, t):
    rng = np.random.default_rng(1)
    kp, vp = _pools(rng)
    b, mp, hq, hkv, d = len(hist), 16, 4, 2, 16
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, 40))[:mp] for _ in range(b)
    ]).astype(np.int32))
    hist = jnp.asarray(hist, jnp.int32)
    valid = jnp.asarray(np.arange(t)[None] < np.asarray(cur)[:, None])
    pos = hist[:, None] + jnp.arange(t)[None]
    q, k, v = f32(b, t, hq, d), f32(b, t, hkv, d), f32(b, t, hkv, d)
    chosen = jax.vmap(lambda s, c: ts.select_tokens(s, c, 8))(
        f32(b, t, mp * PAGE), jnp.where(valid, pos + 1, 0))
    out = sc.token_chunk_attention(q, k, v, kp, vp, 1, tables, chosen,
                                   hist, valid)
    at = jnp.where(valid, pos, mp * PAGE)
    rows = jnp.arange(b)[:, None]
    kh = kp[1][tables].reshape(b, mp * PAGE, hkv, d).at[rows, at].set(
        k, mode="drop")
    vh = vp[1][tables].reshape(b, mp * PAGE, hkv, d).at[rows, at].set(
        v, mode="drop")
    want = ts.masked_attention(q, kh, vh, chosen)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)],
                               atol=2e-5)


# -- (d) the shares add up; no assignment is dropped --------------------------


@pytest.mark.parametrize("rows", [1, 5, 64, 300])
@pytest.mark.parametrize("shares", [4, 8])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(rows, shares):
    """The `shares` chips of an expert-parallel layer each route over all
    8 experts and add their own experts' terms; what every chip computes
    alike (the router) is counted once: the sum of their outputs is the
    uncut reference's expert layer at any row count. A control through
    models/moe.py's capacity dispatch at a tight capacity DROPS
    assignments and differs."""
    whole = kvm.KeyeVLConfig.tiny()
    params = kvm.init_params(jax.random.key(3), whole)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(np.random.default_rng(rows).normal(
        size=(1, rows, whole.hidden_size)), jnp.float32)
    want = ref.moe_branch(x[0], lp, hf_of(whole))
    per = whole.n_routed_experts // shares
    total = 0.0
    for s in range(shares):
        cfg = dataclasses.replace(whole, experts_held=(s * per, per))
        mine = {**lp, **{n: lp[n][s * per:(s + 1) * per]
                         for n in kvm.EXPERTS}}
        # a share's draw IS the whole model's experts at its place
        held = kvm.init_params(jax.random.key(3), cfg)["layers"]
        for n in kvm.EXPERTS:
            np.testing.assert_array_equal(held[n][0], mine[n])
        got = kvm.moe_ffn(x, mine, cfg)[0][0]  # (out, extra passes)
        np.testing.assert_allclose(
            got, ref.moe_branch(x[0], mine, hf_of(cfg)), atol=2e-5)
        total = total + got
    np.testing.assert_allclose(total, want, atol=5e-5)
    if rows >= 64 and shares == 4:
        from dynamo_tpu.models.moe import top_k_gating

        # models/moe.py's dispatch at a capacity of half the mean load
        cap = max(1, rows * 2 // whole.n_routed_experts // 2)
        dispatch, _ = top_k_gating(x[0] @ lp["w_router"], 2, cap)
        assert float(dispatch.sum()) < rows * 2  # assignments dropped


# -- (e) a rolled-back dispatch; a freed page ---------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_rolled_back_step_leaves_pages_and_index_keys_as_one_pass(impl):
    """After a 37-token prompt a decode step launched ahead with a token
    that turns out wrong writes a page slot and an index key; the step
    that replaces it leaves every pool as one pass does, bit for bit:
    nothing a step thrown away wrote is ever read."""
    adapter = get_model("keye-vl2-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(5).integers(3, 256, 38)
    pt = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
    forward = jax.jit(adapter.forward)

    def run(kv, ids, lo):
        tb = 16 if len(ids) > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, : len(ids)] = ids
        return forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + lo)[None].astype(np.int32)),
            jnp.asarray(np.arange(tb)[None] < len(ids)), kv, pt)

    kv = adapter.init_kv(64, PAGE)
    for lo in (0, 16, 32):
        _, kv = run(kv, toks[lo : min(lo + 16, 37)], lo)
    want, once = run(kv, toks[37:38], 37)
    _, wrong = run(kv, [int(toks[37]) ^ 1], 37)
    got, twice = run(wrong, toks[37:38], 37)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for name in ("k", "v", "ki"):
        a, b = getattr(once, name), getattr(twice, name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        assert np.abs(np.asarray(getattr(wrong, name))
                      - np.asarray(a)).max() > 1e-3, name
    # a page's new owner reads none of the old owner's index keys: the
    # same step over pools whose OTHER pages hold anything at all
    dirty = once._replace(ki=once.ki.at[:, 20:].set(7.0),
                          k=once.k.at[:, 20:].set(7.0))
    np.testing.assert_array_equal(
        np.asarray(run(dirty, toks[37:38], 37)[0][:, :1]),
        np.asarray(run(once, toks[37:38], 37)[0][:, :1]))


# -- (g) mrope ----------------------------------------------------------------


def test_equal_position_components_are_plain_rope_and_unequal_differ(tiny):
    adapter, params = tiny
    cfg = adapter.config
    t = 12
    toks = jnp.asarray(np.random.default_rng(8).integers(3, 256, (1, t)),
                       jnp.int32)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    pt = jnp.asarray(np.arange(1, 9)[None], jnp.int32)
    valid = jnp.ones((1, t), bool)

    def hidden(rope_positions):
        return kvm.forward_hidden(
            params, cfg, toks, pos, valid, adapter.init_kv(16, PAGE), pt,
            rope_positions=rope_positions)[0]

    plain = hidden(None)
    np.testing.assert_allclose(hidden(jnp.stack([pos] * 3)), plain,
                               atol=1e-6)
    image = jnp.stack([pos, pos // 3, pos % 3])  # a grid's (t, h, w)
    assert float(jnp.abs(hidden(image) - plain).max()) > 1e-3
    # and the reference rotates the same way
    hf = hf_of(cfg)
    h_ref = ref.hidden_states(params, hf, toks[0], positions=image[:, 0])
    import chipbench.reference as dense

    want = dense._rms(h_ref, params["final_norm"], cfg.rms_norm_eps)
    np.testing.assert_allclose(hidden(image)[0], want, atol=TOL)
