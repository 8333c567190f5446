"""MiniCPM-SALA (models/minicpm_sala.py, ops/sparse_select.py): block-sparse
attention that switches on with the context, selecting pages inside the
page walk, beside lightning linear-attention layers in the state pool, at
a small size on seeded weights, against the plain reference the benchmark
brings (chipbench/references/minicpm_sala.py: float32, the recurrence
token by token, the selection by query position, no cache, no kernels).

`minicpm-sala-tiny`: a block = a page = 4 tokens with 4 compressed keys a
page (kernel 2, stride 1), 6 blocks of a context of 32 or more (block 0,
the blocks of the last 8 tokens, the rest chosen), published layer
indices 9, 10, 11, 16, 17.

Tolerances: everything runs in float32 here, so what separates the
system from the reference is the order of sums: 2e-4 on log-probs of
magnitude ~4, fifty times the observed 3e-6. A selection that differs in
one block, a scalar left out or a stale state moves them by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.models import minicpm_sala as sala
from dynamo_tpu.models.llama import KVPages, attention_block
from dynamo_tpu.models.nemotron_h import ssd_chunk_scan
from dynamo_tpu.models.registry import (
    _minicpm_sala_adapter, get_model, list_presets)
from dynamo_tpu.ops import sparse_select as ss
from dynamo_tpu.ops import ssm_state
from test_falcon_h1 import _streams

TOL = 2e-4

ref = manifest._load(
    manifest.ROOT / "chipbench/references/minicpm_sala.py", "ref_minicpm_sala")


def hf_of(cfg, **more) -> dict:
    return {**ref.served_widths(cfg), **more}


def _serve(adapter, params, toks, chunks, t_bucket=16, slot=1, slots=4):
    """Prefill then decode one sequence through both caches the way the
    engine does: chunk by chunk, each padded to `t_bucket`, the state
    read at one generation of its slot and written at the other. One
    jitted program a shape (eagerly, every call compiles the layer loops
    anew)."""
    forward = jax.jit(adapter.forward)
    kv = adapter.init_kv(64, 4, state_slots=slots)
    pt = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
    stride, gen, pos, outs = slots + 1, 0, 0, []
    for c in chunks:
        tb = max(c, t_bucket) if c > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, :c] = toks[pos : pos + c]
        rows = jnp.asarray(
            [[gen * stride + slot, (1 - gen) * stride + slot]], jnp.int32)
        logits, kv = forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + pos)[None].astype(np.int32)),
            jnp.asarray(np.arange(tb)[None] < c), kv, (pt, rows))
        outs.append(np.asarray(jax.nn.log_softmax(logits[0, :c])))
        gen, pos = 1 - gen, pos + c
    return np.concatenate(outs)


@pytest.fixture(scope="module")
def tiny():
    adapter = get_model("minicpm-sala-tiny")
    return adapter, adapter.init_params(jax.random.key(0))


def test_presets_are_the_published_model_and_its_cut():
    full = get_model("minicpm-sala-9b").config
    assert (full.hidden_size, full.intermediate_size, full.vocab_size) == (
        4096, 16384, 73448)
    assert (full.num_heads, full.num_kv_heads, full.head_dim) == (32, 2, 128)
    assert (full.lightning_heads, full.lightning_head_dim) == (32, 128)
    assert (full.num_layers, full.sparse_layers, full.state_layers) == (
        32, 8, 24)
    assert full.sparse == ss.SparseDims(32, 16, 64, 1, 2048, 64, 8192)
    assert (full.scale_emb, full.scale_depth, full.dim_model_base,
            full.mup_denominator) == (12, 1.4, 256, 32)
    cut = get_model("minicpm-sala-9b-16l").config
    assert cut.layer_indices == tuple(range(9, 25))
    assert [i for i, m in zip(cut.layer_indices, cut.mixer_types)
            if m == sala.SPARSE] == [9, 16, 17, 22]
    assert (cut.sparse_layers, cut.state_layers) == (4, 12)
    # S | L x 6 | S | S | L x 4 | S | L L: the lightning layers that follow
    # each sparse layer (none ahead of the first), one body a kind
    assert cut.blocks == (0, 6, 0, 4, 2) and full.blocks == (
        0, 8, 6, 0, 4, 6, 0, 0, 0)
    assert cut.published(sala.LIGHTNING) == (
        10, 11, 12, 13, 14, 15, 18, 19, 20, 21, 23, 24)
    assert dataclasses.replace(cut, mixer_types=full.mixer_types,
                               layer_indices=full.layer_indices) == full
    assert sala.state_bytes_per_slot(cut) == 12 * 32 * 128 * 128 * 4
    assert sala.page_bytes(cut, 64) == 4 * 2 * 128 * 2 * (2 * 64 + 4)
    for name in ("minicpm-sala-9b", "minicpm-sala-9b-16l",
                 "minicpm-sala-tiny"):
        assert name in list_presets()


# -- (a) chunks + decode through both caches == one full forward ------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunks", [
    pytest.param([16, 16, 5] + [1] * 23, id="crosses-inside-a-chunk"),
    pytest.param([16, 12] + [1] * 32, id="crosses-during-decode"),
])
def test_prefill_then_decode_through_both_caches_is_the_reference(
        impl, chunks):
    """A prompt in chunks of 16, the last one padded, then decoded tokens,
    60 tokens in all: the context passes `dense_len` 32 inside the third
    chunk (positions 32-36), or during decode; logits against the
    reference's one full pass, every position."""
    adapter = get_model("minicpm-sala-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(0).integers(3, 256, sum(chunks))
    got = _serve(adapter, params, toks, chunks, t_bucket=16)
    hf = hf_of(adapter.config)
    want = ref.log_probs(params, hf, toks, np.arange(len(toks)))
    np.testing.assert_allclose(got, want, atol=TOL)
    # seeded at a trained block's scale: the log-probs are not flat
    assert want.std() > 0.5 and want.max(-1).mean() > -4.5
    # and the selection is not a formality: dense attention past
    # dense_len is another model
    dense = ref.log_probs(params, hf, toks, np.arange(len(toks)),
                          select=False)
    assert np.abs(dense[:31] - want[:31]).max() < TOL
    assert np.abs(dense[32:] - want[32:]).max() > 100 * TOL


def _engine(**overrides):
    base = EngineConfig.for_tests(
        model="minicpm-sala-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=2, decode_buckets=(1, 2),
    )
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


@pytest.mark.parametrize("scenario", [
    "three-chunks-then-fused-dispatches", "slot-reuse-after-a-finish",
    "forced-rollback", "preemption-recompute"])
def test_engine_streams_are_the_reference(scenario):
    """The normal path (scheduler, pages and state slots under one
    allocator, the step programs, launch-ahead on), teacher-forced
    against the reference on the chosen tokens' log-probs: a prompt over
    three chunks of 32 (75 = 32 + 32 + 11: it crosses `dense_len` at the
    second chunk's first token) and fused 8-step dispatches past it; five
    requests through two decode slots with mixed steps all the way, some
    crossing during decode; a neighbour aborted while a dispatch launched
    ahead is on the device, so the survivors' pages, compressed keys AND
    state were advanced by a dispatch that is thrown away; a pool so
    small that a row is preempted and recomputed."""
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
    else:
        eng = _engine(num_pages=22, max_pages_per_seq=16, decode_steps=1)
        reqs = [(f"p{i}", [int(x) for x in rng.integers(3, 250, 24)], 20)
                for i in range(2)]
    toks, lps = _streams(eng, reqs, events)
    m = eng.metrics
    if scenario == "three-chunks-then-fused-dispatches":
        assert m.prefill_dispatches == 3
        assert any(k[0] == "decode_multi" and k[2] == 8
                   for k in eng.programs)
        # every decode row stood past dense_len: 6 of its 19-24 blocks
        # (counted on the device, read back beside each dispatch's ids)
        assert 0 < m.walk_pages_named < 0.4 * m.walk_pages_live
        # one prefill-carrying program a shape (`STEP_TWINS` False): no
        # history-free twin for first chunks, none that samples nothing
        assert not any(k[5] for k in eng.programs if len(k) > 5)
        assert not any(k[0] == "prefill_nosample" for k in eng.programs)
    elif scenario == "slot-reuse-after-a-finish":
        assert m.state_resets == 5 and m.mixed_dispatches > 0
    elif scenario == "forced-rollback":
        assert m.overlap_rollbacks > 0 and m.state_restores > 0
        reqs = [r for r in reqs if r[0] in only]
    else:
        assert m.preemptions > 0 and m.state_resets > 2
    assert m.overlap_hits > 0
    assert eng.allocator.num_free_slots == eng.allocator.state_slots
    hf = hf_of(eng.adapter.config)
    for rid, prompt, n in reqs:
        seq = list(prompt) + toks[rid]
        want = ref.log_probs(eng.params, hf, seq,
                             len(prompt) - 1 + np.arange(n))
        of_served = want[np.arange(n), np.asarray(toks[rid])]
        np.testing.assert_allclose(lps[rid], of_served, atol=TOL,
                                   err_msg=rid)
        # greedy: the served token is the reference's best, or within
        # rounding of it
        assert (want.max(-1) - of_served).max() < TOL, rid


# -- (b) the selected set is the reference's, every position, each KV head --


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_selection_and_the_walk_are_the_references(impl, tiny):
    """The program's routines on the reference's own q, k, v of every
    sparse layer, a KV head a row, through the adapter's cache: one chunk
    of 48 tokens (it crosses `dense_len` 32 inside), then 48 decode steps
    each selecting 6 of 12-24 blocks: every position's selected set, per
    KV head and layer, IS the reference's, and the attention output the
    reference's."""
    adapter, params = tiny
    res = ref.sparse_path(
        params, hf_of(adapter.config, preset="minicpm-sala-tiny",
                      dtype="float32", attention_impl=impl),
        context=96, queries=48)
    assert res["selected_pages_agreement_min"] == 1.0
    assert res["sparse_selection_matched_share"] == 1.0
    assert res["sparse_attn_distance"] < 1e-5


# -- (c) the walk over a list == dense attention under the same mask --------


def _one_row_cache(hist, d=128, pages=16, s=4, seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(1, pages, s, 1, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, pages, s, 1, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, d)), jnp.float32)
    cur = jnp.asarray(rng.normal(size=(2, 1, 1, 1, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[None, :12],
                         jnp.int32)
    return q, KVPages(k=k, v=v), cur[0], cur[1], tables


@pytest.mark.parametrize("hist,blocks", [
    (37, (0, 3, 5, 8, 9)), (40, (0, 2, 7, 8, 9, 10)), (41, (0, 1, 9, 10)),
    (23, None), (24, None), (3, None)])
def test_the_walk_over_a_list_is_dense_attention_under_the_same_mask(
        hist, blocks):
    """The decode kernel (interpreted) over `decode_lists` of a selected
    set, the current token merged, against dense scores masked to the
    same blocks; with every block selected (6 or fewer here) it IS dense
    attention. `hist` 40 and 24: the query opens a block, which holds no
    cached token and is left out of the walk."""
    dims = ss.SparseDims(2, 1, 4, 1, 8, 6, 32)
    q, kv, k_cur, v_cur, tables = _one_row_cache(hist)
    own = hist // 4
    sel = np.zeros((1, 12), bool)
    sel[0, list(blocks) if blocks else range(own + 1)] = True
    sel[0, own] = True
    pages, lens = ss.decode_lists(
        jnp.asarray(sel), tables, jnp.asarray([hist], jnp.int32), dims)
    assert pages.shape == (1, 8) and int(lens[0]) == (
        4 * (int(sel[0, : -(-hist // 4)].sum()) - 1) + hist - 4 * ((hist - 1) // 4))
    cfg = sala.MiniCPMSALAConfig.tiny()
    acfg = dataclasses.replace(cfg.attn_cfg, head_dim=128,
                               attention_impl="pallas")
    got, _, _ = attention_block(
        q, k_cur, v_cur, kv, jnp.int32(0), pages, lens[:, None],
        jnp.ones((1, 1), bool), acfg)
    # the same, densely: the cached keys in position order, then the
    # current one
    keys = jnp.concatenate(
        [kv.k[0, tables[0]].reshape(1, -1, 128), k_cur[:, :, 0]], axis=1)
    vals = jnp.concatenate(
        [kv.v[0, tables[0]].reshape(1, -1, 128), v_cur[:, :, 0]], axis=1)
    pos = np.concatenate([np.arange(48), [hist]])
    keep = sel[0, pos // 4] & (np.arange(49) < 48) & (pos < hist)
    keep[48] = True
    sc = jnp.einsum("btgd,bkd->btgk", q, keys) / np.sqrt(128.0)
    p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
    want = jnp.einsum("btgk,bkd->btgd", p, vals).reshape(1, 1, -1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if blocks is None:  # every block: dense attention over the history
        assert keep[:hist].all()


# -- (d) lightning: recurrence == chunked form == the state kernel ----------


@pytest.mark.parametrize("published", [9, 10, 24, 31])
def test_lightning_recurrence_chunked_form_and_kernel_agree(published):
    """`S_t = lambda S_(t-1) + k_t^T v_t`, `o_t = q_t S_t` token by
    token in numpy, against the chunked scan the Mamba-2 models call (dt
    1, a constant A = log lambda) and the interpreted state kernel one
    token at a time; the decay is the PUBLISHED layer's."""
    cfg = sala.MiniCPMSALAConfig.tiny()
    nh, d, t = cfg.lightning_heads, cfg.lightning_head_dim, 21
    rng = np.random.default_rng(published)
    q, k, v = (rng.normal(size=(1, t, nh, d)).astype(np.float32)
               for _ in range(3))
    log_decay = cfg.log_decay(published)
    hf = hf_of(cfg)
    np.testing.assert_allclose(log_decay, ref.log_decay(hf, published),
                               rtol=1e-6)
    want_decay = -2.0 ** (-8.0 * np.arange(1, nh + 1) / nh) * (
        1 - published / 31 + 1e-5)
    np.testing.assert_allclose(log_decay, want_decay, rtol=1e-6)
    lam = np.exp(want_decay)
    s = np.zeros((nh, d, d), np.float32)
    want = []
    for i in range(t):
        s = s * lam[:, None, None] + v[0, i][:, :, None] * k[0, i][:, None, :]
        want.append(np.einsum("hpn,hn->hp", s, q[0, i]))
    y, s_end = ssd_chunk_scan(
        jnp.asarray(v), jnp.ones((1, t, nh)), log_decay, jnp.asarray(k),
        jnp.asarray(q), jnp.zeros((1, nh, d, d)), cfg.chunk_size)
    np.testing.assert_allclose(y[0], np.stack(want), atol=2e-5)
    np.testing.assert_allclose(s_end[0], s, atol=2e-5)
    pool = jnp.zeros((1, 3, nh, d, d), jnp.float32)
    one = jnp.ones((1,), jnp.int32)
    for i in range(t):
        y1, pool = ssm_state.ssm_decode_step(
            pool, jnp.int32(0), one, one, jnp.asarray(v[:, i]),
            jnp.exp(log_decay)[None], jnp.asarray(k[:, i]),
            jnp.asarray(q[:, i]), use_kernel=True, interpret=True)
        np.testing.assert_allclose(y1[0], want[i], atol=2e-5)
    np.testing.assert_allclose(pool[0, 1], s, atol=2e-5)


# -- (e) a dispatch rolled back and replayed leaves what one pass leaves ----


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_rolled_back_step_leaves_pages_compressed_keys_and_state(impl):
    """After a 37-token prompt a decode step launched ahead with a token
    that turns out wrong writes a page slot, a compressed key and the
    state's other generation; the step that replaces it (reading the
    generation the prompt left) leaves every pool as one pass does, bit
    for bit: nothing a step thrown away wrote is ever read."""
    adapter = get_model("minicpm-sala-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(5).integers(3, 256, 38)
    pt = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
    forward = jax.jit(adapter.forward)

    def run(kv, ids, lo, gen, n=None):
        n = len(ids) if n is None else n
        tb = 16 if len(ids) > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, : len(ids)] = ids
        rows = jnp.asarray([[gen * 5 + 1, (1 - gen) * 5 + 1]], jnp.int32)
        return forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + lo)[None].astype(np.int32)),
            jnp.asarray(np.arange(tb)[None] < n), kv, (pt, rows))[1]

    kv = adapter.init_kv(64, 4, state_slots=4)
    for lo, gen in ((0, 0), (16, 1), (32, 0)):
        kv = run(kv, toks[lo : min(lo + 16, 37)], lo, gen)
    once = run(kv, toks[37:38], 37, 1)
    wrong = run(kv, [int(toks[37]) ^ 1], 37, 1)
    twice = run(wrong, toks[37:38], 37, 1)
    for name in ("k", "v", "kc", "ssm"):
        a, b = getattr(once, name), getattr(twice, name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
        assert np.abs(np.asarray(getattr(wrong, name))
                      - np.asarray(a)).max() > 1e-3, name


# -- (f) the muP scalars are each read --------------------------------------


@pytest.mark.parametrize("name,value", [
    ("scale_emb", 6.0), ("scale_depth", 1.0), ("dim_model_base", 32),
    ("mup_denominator", 16)])
def test_every_scalar_is_applied(tiny, name, value):
    """Each scalar changed in the PROGRAM alone (the weights and the
    reference keep the configuration's) moves the logits far past the
    tolerance: none is dropped, none folded into a weight the reference
    also reads. `mup_denominator` also moves the lightning decay."""
    adapter, params = tiny
    assert name in sala.SCALARS
    toks = np.random.default_rng(3).integers(3, 256, 21)
    want = ref.log_probs(params, hf_of(adapter.config), toks, np.arange(21))
    other = _minicpm_sala_adapter("other", dataclasses.replace(
        adapter.config, **{name: value}))
    got = _serve(other, params, toks, [16, 5], t_bucket=16)
    assert np.abs(got - want).max() > 100 * TOL, name
    same = _serve(adapter, params, toks, [16, 5], t_bucket=16)
    np.testing.assert_allclose(same, want, atol=TOL)


# -- what refuses, refuses loudly -------------------------------------------


def test_a_mesh_and_a_wrong_page_size_are_refused():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(ValueError, match="MiniCPM-SALA runs on one chip"):
        get_model("minicpm-sala-tiny", mesh=mesh)
    adapter = get_model("minicpm-sala-tiny")
    with pytest.raises(ValueError, match="a selection block is a page"):
        adapter.init_kv(64, 8, state_slots=2)
    with pytest.raises(ValueError, match="kv_quantize"):
        adapter.init_kv(64, 4, kv_quantize="int8", state_slots=2)


@pytest.mark.parametrize("positions,named,live", [
    ([30], 8, 8), ([31], 6, 8), ([32], 5, 8), ([33], 6, 9),
    ([95, 17], 6 + 5, 24 + 5)])
def test_pages_walked_counts_what_the_lists_name(positions, named, live):
    """The device's count of a decode step's walks is taken from the
    lists the walk is GIVEN (`decode_lists`' tokens a list), beside the
    pages the rows hold: under `dense_len` (32 tokens of context) the two
    are equal, past it a list names `topk` 6 blocks less the query's own
    where that holds no cached token; a padding row counts nothing."""
    cfg = sala.MiniCPMSALAConfig.tiny()
    rng = np.random.default_rng(0)
    n = len(positions) + 1  # and a padding row
    q = jnp.asarray(rng.normal(size=(n, 1, 2, 16)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(n, 32 * 4, 16)), jnp.float32)
    tables = jnp.tile(jnp.arange(1, 33, dtype=jnp.int32)[None], (n, 1))
    pos = jnp.asarray([*positions, 77], jnp.int32)[:, None]
    _, _, lens = sala.decode_selection(q, kc, tables, pos, cfg)
    valid = jnp.arange(n) < len(positions)
    assert sala.pages_walked(lens, pos[:, 0], valid, 4).tolist() == [
        named, live]
