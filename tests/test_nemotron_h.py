"""Nemotron-H (models/nemotron_h.py): Mamba-2 layers beside attention and
sparse experts, at a small size on seeded weights, against the plain
reference the benchmark brings (chipbench/references/nemotron_h.py:
float32, token-by-token recurrence, no cache, no chunks, no kernels).

Tolerances: everything runs in float32 here, so what separates the
system from the reference is the order of sums (the chunked form of the
recurrence, the sorted expert dispatch, XLA:CPU's matmul blocking by row
count): 2e-4 on log-probs of magnitude ~5, a hundred times the observed
4e-6, a thousand times under what a wrong state (stale, doubled, of
another sequence) moves them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import manifest
from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.llama import StepGroup
from dynamo_tpu.models.registry import get_model, list_presets
from dynamo_tpu.ops import ssm_state

TOL = 2e-4

ref = manifest._load(
    manifest.ROOT / "chipbench/references/nemotron_h.py", "ref_nemotron_h")
ref.PROBE = False


def hf_of(cfg) -> dict:
    return {
        **ref.served_widths(cfg),
        "hybrid_override_pattern": cfg.pattern,
        "norm_eps": cfg.rms_norm_eps, "norm_topk_prob": cfg.norm_topk_prob,
        "experts_held_first": (cfg.experts_held or (0, 0))[0],
    }


@pytest.fixture(scope="module")
def tiny():
    adapter = get_model("nemotron-h-tiny")
    return adapter, adapter.init_params(jax.random.key(0))


def test_presets_are_the_published_model_and_its_cut():
    assert {"nemotron3-nano", "nemotron3-nano-28l-16e",
            "nemotron-h-tiny"} <= set(list_presets())
    full = nh.NemotronHConfig.nemotron3_nano()
    assert (full.num_layers, full.count("M"), full.count("E"),
            full.count("*")) == (52, 23, 23, 6)
    assert full.segments == [("MEMEM*E", 5), ("MEMEMEM*EMEMEMEME", 1)]
    assert (full.mamba.d_inner, full.mamba.conv_dim,
            full.mamba.in_proj_dim) == (
        4096, 6144, 10304)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(jax.eval_shape(
        lambda: nh.init_params(jax.random.key(0), full))))
    # 31.6 B published; the experts' 1856 -> 1920 lane padding adds 1.0 B
    assert 31.4e9 < n - 23 * 128 * 2 * 2688 * 64 < 31.8e9
    cut = nh.NemotronHConfig.nemotron3_nano_1chip()
    assert cut.pattern == "MEMEM*E" * 4 and cut.segments == [("MEMEM*E", 4)]
    assert cut.experts_held == (0, 16) and cut.n_routed_experts == 128
    assert nh.state_bytes_per_slot(cut) == 12 * (3 * 6144 * 2 + 64 * 64 * 128 * 4)
    tiny_cfg = nh.NemotronHConfig.tiny()
    assert set(tiny_cfg.pattern) == {"M", "E", "*"}
    assert tiny_cfg.segments == [("ME*", 2), ("ME", 1)]  # a scan and a tail


@pytest.mark.parametrize("t,chunk,valid", [
    (32, 8, 32), (32, 8, 19), (16, 16, 16), (8, 16, 3), (64, 8, 64)])
def test_chunked_scan_is_the_token_by_token_recurrence(t, chunk, valid):
    rng = np.random.default_rng(t + valid)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (b, t, h)), jnp.float32)
    dt = dt * (jnp.arange(t) < valid)[None, :, None]  # padding: dt 0
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, t, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, t, g, n)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32)
    y, s = nh.ssd_chunk_scan(x, dt, a, bm, cm, s0, chunk)
    y_ref, s_ref = nh.ssm_recurrence(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(y[:, :valid], y_ref[:, :valid], atol=1e-4)
    np.testing.assert_allclose(s, s_ref, atol=1e-4)
    # padding tokens did not advance the state
    _, s_valid = nh.ssm_recurrence(
        x[:, :valid], dt[:, :valid], a, bm[:, :valid], cm[:, :valid], s0)
    np.testing.assert_allclose(s, s_valid, atol=1e-4)


@pytest.mark.parametrize("h,p,n,g,block_bytes,block", [
    # Nemotron-3-Nano's shape class: a block is the whole row
    pytest.param(4, 16, 16, 2, None, 4, id="one-block-a-row"),
    # Falcon-H1-34B's: a block is one group of the row's heads
    pytest.param(4, 16, 32, 2, 2 * 16 * 32 * 4, 2, id="a-block-a-group"),
    pytest.param(8, 8, 128, 2, 4 * 8 * 128 * 4, 4, id="two-groups-of-four"),
    # a group too large for a block: a whole fraction of it
    pytest.param(4, 16, 32, 1, 2 * 16 * 32 * 4, 2, id="half-a-group"),
    pytest.param(4, 16, 16, 2, 16 * 16 * 4, 1, id="a-head-a-block"),
])
def test_state_kernels_interpreted_match_plain_jnp(
        monkeypatch, h, p, n, g, block_bytes, block):
    if block_bytes is not None:
        monkeypatch.setattr(ssm_state, "STATE_BLOCK_BYTES", block_bytes)
    assert ssm_state.head_block(h, h // g, p, n) == block
    rng = np.random.default_rng(3)
    layers, entries, b = 3, 7, 4
    pool = jnp.asarray(rng.normal(size=(layers, entries, h, p, n)),
                       jnp.float32)
    u = jnp.asarray(rng.normal(size=(b, h, p)), jnp.float32)
    dec = jnp.asarray(rng.uniform(0.3, 1, (b, h)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, g, n)), jnp.float32)
    ridx, widx = jnp.array([1, 2, 5, 0]), jnp.array([4, 3, 5, 0])
    y0, p0 = ssm_state.ssm_decode_step(
        pool, 1, ridx, widx, u, dec, bm, cm, use_kernel=False)
    y_def, new_def = ssm_state.ssm_decode_reference(
        pool[1, ridx], u, dec, bm, cm)
    np.testing.assert_array_equal(y0, y_def)
    y1, p1 = ssm_state.ssm_decode_step(
        pool, jnp.int32(1), ridx, widx, u, dec, bm, cm, use_kernel=True,
        interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-5)
    np.testing.assert_allclose(p1, p0, atol=1e-6)
    np.testing.assert_allclose(p1[1, widx[:3]], new_def[:3], atol=1e-6)
    # the read entries of rows that write elsewhere, and every other
    # layer, are as they were
    np.testing.assert_array_equal(p1[1, 1:3], pool[1, 1:3])
    np.testing.assert_array_equal(p1[0::2], pool[0::2])
    rows = jnp.asarray(rng.normal(size=(b, h, p, n)), jnp.float32)
    w0 = ssm_state.write_rows(pool, 2, widx, rows, use_kernel=False)
    w1 = ssm_state.write_rows(pool, jnp.int32(2), widx, rows,
                              use_kernel=True)
    np.testing.assert_array_equal(w1[:, 1:], w0[:, 1:])  # 0: the null slot
    # the row reader is the writer's mirror: one DMA a row
    np.testing.assert_array_equal(
        ssm_state.read_rows(w1, jnp.int32(2), widx[:3], use_kernel=True),
        ssm_state.read_rows(w1, 2, widx[:3]))
    np.testing.assert_array_equal(
        ssm_state.read_rows(w1, 2, widx[:3]), rows[:3])


def _serve(adapter, params, toks, chunks, t_bucket=None, slot=1, slots=4):
    """Prefill then decode one sequence through both caches the way the
    engine does: chunk by chunk, each padded to `t_bucket`, the state
    read at one generation of its slot and written at the other."""
    kv = adapter.init_kv(64, 4, state_slots=slots)
    pt = jnp.asarray(np.arange(1, 33)[None], jnp.int32)
    stride, gen, pos, outs = slots + 1, 0, 0, []
    for c in chunks:
        tb = max(c, t_bucket or c) if c > 1 else 1
        tok = np.zeros((1, tb), np.int32)
        tok[0, :c] = toks[pos : pos + c]
        valid = np.zeros((1, tb), bool)
        valid[0, :c] = True
        rows = jnp.asarray(
            [[gen * stride + slot, (1 - gen) * stride + slot]], jnp.int32)
        logits, kv = adapter.forward(
            params, jnp.asarray(tok),
            jnp.asarray((np.arange(tb) + pos)[None].astype(np.int32)),
            jnp.asarray(valid), kv, (pt, rows))
        outs.append(np.asarray(jax.nn.log_softmax(logits[0, :c])))
        gen, pos = 1 - gen, pos + c
    return np.concatenate(outs)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_both_caches_is_the_reference(impl):
    """A prompt over three chunks, the last one padded (16 + 16 + 5 of
    16), then eight decoded tokens: logits against the reference's one
    full pass. `pallas` runs the state kernels and the attention kernels
    interpreted."""
    adapter = get_model("nemotron-h-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(0).integers(3, 256, 45)
    got = _serve(adapter, params, toks, [16, 16, 5] + [1] * 8, t_bucket=16)
    want = ref.log_probs(params, hf_of(adapter.config), toks, np.arange(45))
    np.testing.assert_allclose(got, want, atol=TOL)


def test_mixed_step_is_the_reference(tiny):
    """One fused mixed step: a prompt chunk (continuing from its slot)
    beside two decode rows, each row from its own slot."""
    adapter, params = tiny
    cfg = adapter.config
    rng = np.random.default_rng(1)
    seqs = [rng.integers(3, 256, n) for n in (30, 12, 9)]
    kv = adapter.init_kv(64, 4, state_slots=4)
    stride = 5
    pts = [jnp.asarray(np.arange(1 + 8 * i, 9 + 8 * i)[None], jnp.int32)
           for i in range(3)]

    def rows(gen, slot):
        return jnp.asarray(
            [[gen * stride + slot, (1 - gen) * stride + slot]], jnp.int32)

    def run(i, lo, hi, gen, kv):
        t = hi - lo
        return adapter.forward_hidden(
            params, jnp.asarray(seqs[i][None, lo:hi], jnp.int32),
            jnp.asarray((np.arange(t) + lo)[None], jnp.int32),
            jnp.ones((1, t), bool), kv, (pts[i], rows(gen, i + 1)))[1]

    kv = run(0, 0, 16, 0, kv)  # the prompt's first chunk
    kv = run(1, 0, 11, 0, kv)  # the decode rows' prompts
    kv = run(2, 0, 8, 0, kv)
    prompt = (
        jnp.asarray(seqs[0][None, 16:30], jnp.int32),
        jnp.asarray((np.arange(14) + 16)[None], jnp.int32),
        jnp.ones((1, 14), bool), (pts[0], rows(1, 1)),
    )
    decode = (
        jnp.asarray([[seqs[1][11]], [seqs[2][8]]], jnp.int32),
        jnp.asarray([[11], [8]], jnp.int32), jnp.ones((2, 1), bool),
        (jnp.concatenate(pts[1:]),
         jnp.concatenate([rows(1, 2), rows(1, 3)])),
    )
    h_p, h_d, _ = adapter.forward_hidden_mixed(params, prompt, decode, kv)
    hf = hf_of(cfg)
    lp = lambda h: np.asarray(jax.nn.log_softmax(  # noqa: E731
        adapter.compute_logits(params, h)))
    np.testing.assert_allclose(
        lp(h_p[0]), ref.log_probs(params, hf, seqs[0], np.arange(16, 30)),
        atol=TOL)
    for i, row in ((1, 0), (2, 1)):
        n = len(seqs[i])
        np.testing.assert_allclose(
            lp(h_d[row]), ref.log_probs(params, hf, seqs[i], [n - 1]),
            atol=TOL)


def _engine(**overrides):
    base = EngineConfig.for_tests(
        model="nemotron-h-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=2, decode_buckets=(1, 2),
    )
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


def _streams(eng, reqs):
    for rid, prompt, n in reqs:
        eng.add_request(rid, prompt, SamplingParams(
            max_tokens=n, temperature=0.0, ignore_eos=True, logprobs=0))
    toks, lps = {}, {}
    while eng.has_work:
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            lps.setdefault(o.request_id, []).extend(o.logprobs or ())
    return toks, lps


def _assert_streams_are_the_reference(eng, reqs, toks, lps):
    hf = hf_of(eng.adapter.config)
    for rid, prompt, n in reqs:
        seq = list(prompt) + toks[rid]
        at = len(prompt) - 1 + np.arange(n)
        want = ref.log_probs(eng.params, hf, seq, at)
        of_served = want[np.arange(n), np.asarray(toks[rid])]
        np.testing.assert_allclose(lps[rid], of_served, atol=TOL,
                                   err_msg=rid)
        # greedy: the served token is the reference's best, or within
        # rounding of it
        assert (want.max(-1) - of_served).max() < TOL, rid


@pytest.mark.parametrize("scenario", [
    "three-chunks-then-fused-dispatches", "slot-reuse-after-a-finish",
    "preemption-recompute"])
def test_engine_streams_are_the_reference(scenario):
    """The normal path (scheduler, both caches under one allocator, the
    step programs, launch-ahead on), teacher-forced against the
    reference on the chosen tokens' log-probs: a prompt over three
    chunks of 32 with the last one padded (75 = 32 + 32 + 11) and fused
    8-step dispatches; five requests through two decode slots, so that
    every slot has a second and a third owner who must start from zeros
    (mixed steps all the way); and a pool so small that a row is
    preempted and recomputed from position 0 in another slot."""
    rng = np.random.default_rng(2)
    if scenario == "three-chunks-then-fused-dispatches":
        eng = _engine(max_seqs=1, decode_buckets=(1,))
        reqs = [("a", [int(x) for x in rng.integers(3, 250, 75)], 20)]
    elif scenario == "slot-reuse-after-a-finish":
        eng = _engine()
        reqs = [(f"r{i}", [int(x) for x in rng.integers(3, 250, 10 + 9 * i)],
                 6 + 4 * i) for i in range(5)]
    else:
        eng = _engine(num_pages=14, max_pages_per_seq=12, decode_steps=1)
        reqs = [(f"p{i}", [int(x) for x in rng.integers(3, 250, 12)], 20)
                for i in range(2)]
    toks, lps = _streams(eng, reqs)
    m = eng.metrics
    if scenario == "three-chunks-then-fused-dispatches":
        assert m.prefill_dispatches == 3
        assert any(k[0] == "decode_multi" and k[2] == 8
                   for k in eng.programs)
    elif scenario == "slot-reuse-after-a-finish":
        assert m.state_resets == 5 and m.mixed_dispatches > 0
        assert m.state_slots_live <= eng.allocator.state_slots == 3
    else:
        assert m.preemptions > 0 and m.state_resets > 2
    assert m.overlap_hits > 0
    assert eng.allocator.num_free_slots == eng.allocator.state_slots
    _assert_streams_are_the_reference(eng, reqs, toks, lps)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny):
    """The cut's tie to the model: four chips hold two of the eight
    routed experts each. The routed parts that all the shares give, with
    the shared expert (which every chip computes alike) counted once,
    add up to what the uncut reference gives for the whole layer."""
    adapter, _ = tiny
    whole = dataclasses.replace(adapter.config, experts_held=None)
    params = nh.init_params(jax.random.key(1), whole)
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    x = jnp.asarray(
        np.random.default_rng(4).normal(size=(24, 64)), jnp.float32)
    # the uncut reference's layer, less its residual
    want = ref.moe_block(x, lp, hf_of(whole))[0] - x
    h = ref.dense._rms(x, lp["norm"], whole.rms_norm_eps)
    shared = nh._relu2(h @ lp["ws_up"]) @ lp["ws_down"]
    total = shared
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, experts_held=(first, 2))
        held = {**lp, "we_up": lp["we_up"][first : first + 2],
                "we_down": lp["we_down"][first : first + 2]}
        part = nh.moe_ffn(h[None], held, share)[0][0] - shared
        assert float(jnp.abs(part).max()) > 1e-3  # every share adds its own
        total = total + part
    np.testing.assert_allclose(total, want, atol=1e-5)
    # and the layer that holds every expert is the reference's too
    np.testing.assert_allclose(
        nh.moe_ffn(h[None], lp, whole)[0][0], want, atol=1e-5)


# -- what refuses, refuses loudly -------------------------------------------


@pytest.mark.parametrize("overrides,says", [
    (dict(kv_quantize="int8"), "kv_quantize"),
    (dict(host_kv_cache_bytes=1 << 20), "KVBM offload"),
    (dict(spec_ngram=3), "speculative decoding"),
    (dict(spec_draft_model="tiny"), "speculative decoding"),
])
def test_engine_refuses_what_would_serve_half_a_sequence(overrides, says):
    with pytest.raises(ValueError, match="state-space layers") as err:
        _engine(**overrides)
    assert says in str(err.value)


def test_registry_and_transfer_surface_refuse_a_state_model():
    adapter = get_model("nemotron-h-tiny")
    with pytest.raises(ValueError, match="kv_quantize is not supported"):
        adapter.init_kv(8, 4, kv_quantize="int8", state_slots=2)
    with pytest.raises(ValueError, match="one chip"):
        get_model("nemotron-h-tiny", mesh=object())
    with pytest.raises(ValueError, match="state slot"):
        nh.forward_groups(
            adapter.init_params(jax.random.key(0)), adapter.config,
            [StepGroup(jnp.zeros((1, 1), jnp.int32),
                       jnp.zeros((1, 1), jnp.int32), jnp.ones((1, 1), bool),
                       jnp.zeros((1, 4), jnp.int32))],
            adapter.init_kv(8, 4, state_slots=2))
    eng = _engine()
    for call in (
        lambda: eng.extract_pages([1]),
        lambda: eng.inject_pages([1], None, None),
        lambda: eng.serve_blocks([1]),
        lambda: eng.allocate_for_remote_prefill("x", [1, 2, 3], None),
        lambda: eng.add_prefilled(None, 0),
    ):
        with pytest.raises(ValueError, match="without its recurrent state"):
            call()
    with pytest.raises(ValueError, match="embeddings"):
        eng.embed([[1, 2, 3]])


def test_prefix_hit_is_refused_and_counted():
    """Pages of a shared prefix are there, the state at their boundary is
    not: no hit, counted, and the second stream is the first's."""
    eng = _engine(enable_prefix_caching=True)
    prompt = [int(x) for x in np.random.default_rng(6).integers(3, 250, 40)]
    first = _streams(eng, [("a", prompt, 6)])
    cached = []
    eng.add_request("b", prompt, SamplingParams(
        max_tokens=6, temperature=0.0, ignore_eos=True, logprobs=0))
    toks = []
    while eng.has_work:
        for o in eng.step():
            toks.extend(o.new_token_ids)
            if o.cached_tokens is not None:
                cached.append(o.cached_tokens)
    assert toks == first[0]["a"]
    assert cached == [0]
    assert eng.metrics.prefix_hits_refused_state == 1
    assert eng.allocator.stats.hit_tokens == 0


def test_memory_report_counts_the_state_pool_beside_the_pages():
    eng = _engine()
    rep = eng.memory_report()["totals"]
    cfg = eng.adapter.config
    entries = 2 * (eng.allocator.state_slots + 1)
    assert rep["state_pool_bytes"] == entries * nh.state_bytes_per_slot(cfg)
    assert rep["state_pool_bytes"] == eng.metrics.state_pool_bytes
    pages = 2 * cfg.count("*") * 256 * 4 * cfg.num_kv_heads * cfg.head_dim * 4
    # (and the device's running count, six int32: `HybridCache.walked`)
    assert rep["kv_pool_bytes"] == eng.metrics.kv_pool_bytes == pages + 6 * 4
    assert eng.metrics.state_slots == eng.allocator.state_slots == 3
