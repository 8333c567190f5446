"""Falcon-H1 (models/falcon_h1.py): a Mamba-2 mixer and rotary GQA
attention side by side in every layer, at a small size on seeded weights,
against the plain reference the benchmark brings
(chipbench/references/falcon_h1.py: float32, token-by-token recurrence, no
cache, no chunks, no kernels, every multiplier where the published block
puts it).

Tolerances: everything runs in float32 here, so what separates the
system from the reference is the order of sums (the chunked form of the
recurrence, XLA:CPU's matmul blocking by row count): 2e-4 on log-probs of
magnitude ~4, fifty times the observed 4e-6. A multiplier left out moves
them by 2e-2 at the least (`test_every_multiplier_is_applied`), a wrong
state (stale, doubled, of another sequence) by more.
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
from dynamo_tpu.models import falcon_h1 as fh
from dynamo_tpu.models import nemotron_h as nh
from dynamo_tpu.models.llama import StepGroup
from dynamo_tpu.models.registry import (
    _falcon_h1_adapter, get_model, list_presets)
from dynamo_tpu.ops import ssm_state
from test_nemotron_h import _serve  # one sequence through both caches

TOL = 2e-4

ref = manifest._load(
    manifest.ROOT / "chipbench/references/falcon_h1.py", "ref_falcon_h1")


def hf_of(cfg) -> dict:
    return ref.served_widths(cfg)


@pytest.fixture(scope="module")
def tiny():
    adapter = get_model("falcon-h1-tiny")
    return adapter, adapter.init_params(jax.random.key(0))


def test_presets_are_the_published_model_and_its_cut():
    assert {"falcon-h1-34b", "falcon-h1-34b-6l",
            "falcon-h1-tiny"} <= set(list_presets())
    full = fh.FalconH1Config.falcon_h1_34b()
    m = full.mamba
    assert (full.num_layers, full.state_layers, full.attn_cfg.num_layers) == (
        72, 72, 72)
    # d_inner is mamba_d_ssm = 32 heads x 128, NOT mamba_expand x hidden
    assert (m.d_inner, m.conv_dim, m.in_proj_dim) == (4096, 5120, 9248)
    assert m.conv_state_shape == (120, 128)
    assert m.ssm_state_shape == (32, 128, 256)
    shapes = jax.eval_shape(lambda: fh.init_params(jax.random.key(0), full))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 33.5e9 < n < 33.7e9  # 33.6 B published
    per_layer = sum(int(np.prod(x.shape[1:]))
                    for x in jax.tree.leaves(shapes["layers"]))
    assert per_layer == 31_457_280 + 68_351_072 + 330_301_440 + 2 * 5120
    assert shapes["embed"].shape == (261120, 5120)
    assert shapes["lm_head"].shape == (5120, 261120)
    scale = np.asarray(full.in_proj_scale)
    assert scale.shape == (9248,)
    for lo, hi, c in ((0, 4096, 0.3535533905932738), (4096, 8192, 0.25),
                      (8192, 8704, 0.1767766952966369), (8704, 9216, 0.5),
                      (9216, 9248, 0.3535533905932738)):
        assert np.all(scale[lo:hi] == np.float32(c))
    acfg = full.attn_cfg
    assert (acfg.num_heads, acfg.num_kv_heads, acfg.head_dim, acfg.use_rope,
            acfg.rope_theta) == (20, 4, 128, True, 1e11)
    cut = get_model("falcon-h1-34b-6l").config
    assert cut == dataclasses.replace(full, num_layers=6)
    # a slot: 6 layers x (32 x 128 x 256 float32 + 3 conv rows of 5120 bf16)
    assert nh.state_bytes_per_slot(cut) == 6 * (4_194_304 + 3 * 5120 * 2)
    assert get_model("falcon-h1-34b-6l").state_slot_bytes == 25_350_144
    tiny_cfg = fh.FalconH1Config.tiny()
    assert all(getattr(tiny_cfg, k) != 1.0 for k in fh.MULTIPLIERS)
    assert tiny_cfg.num_heads // tiny_cfg.num_kv_heads == 2


def test_the_head_block_at_both_served_shapes():
    """The rule in `ssm_decode_step`'s docstring: whole groups, at most 2
    MiB of float32 state a block, so four state buffers take 8 MiB of
    VMEM at both shapes."""
    # Nemotron-3-Nano: 64 heads x 64 x 128, 8 heads a group: the whole row
    assert ssm_state.head_block(64, 8, 64, 128) == 64
    assert 64 * 64 * 128 * 4 == ssm_state.STATE_BLOCK_BYTES
    # Falcon-H1-34B: 32 heads x 128 x 256, 16 heads a group: one group
    assert ssm_state.head_block(32, 16, 128, 256) == 16
    assert 16 * 128 * 256 * 4 == ssm_state.STATE_BLOCK_BYTES
    # a group that does not fit: a whole fraction of it
    assert ssm_state.head_block(8, 8, 512, 256) == 4
    assert ssm_state.head_block(8, 4, 128, 1024) == 4
    assert ssm_state.head_block(4, 2, 16, 16) == 4  # the tiny presets


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_through_both_caches_is_the_reference(impl):
    """A prompt over three chunks, the last one padded (16 + 16 + 5 of
    16), then eight decoded tokens, every layer reading its pages AND its
    slot: logits against the reference's one full pass."""
    adapter = get_model("falcon-h1-tiny", attention_impl=impl)
    params = adapter.init_params(jax.random.key(0))
    toks = np.random.default_rng(0).integers(3, 256, 45)
    got = _serve(adapter, params, toks, [16, 16, 5] + [1] * 8, t_bucket=16)
    want = ref.log_probs(params, hf_of(adapter.config), toks, np.arange(45))
    np.testing.assert_allclose(got, want, atol=TOL)
    # seeded at a trained block's scale: the log-probs are not flat
    assert want.std() > 0.5 and want.max(-1).mean() > -4.5


_ONES = {"ssm_multipliers": (1.0,) * 5, "mlp_multipliers": (1.0, 1.0)}


@pytest.mark.parametrize("name", fh.MULTIPLIERS)
def test_every_multiplier_is_applied(tiny, name):
    """Each of the nine multipliers set to 1 in the PROGRAM alone (the
    weights and the reference keep the configuration's) moves the logits
    far past the tolerance: none is dropped, and none is folded into a
    weight the reference also reads."""
    adapter, params = tiny
    cfg = adapter.config
    assert len(fh.MULTIPLIERS) == 9
    toks = np.random.default_rng(3).integers(3, 256, 21)
    want = ref.log_probs(params, hf_of(cfg), toks, np.arange(21))
    without = _falcon_h1_adapter("without", dataclasses.replace(
        cfg, **{name: _ONES.get(name, 1.0)}))
    got = _serve(without, params, toks, [16, 5], t_bucket=16)
    assert np.abs(got - want).max() > 100 * TOL, name
    # and each entry of the two lists on its own
    if name in _ONES:
        have = getattr(cfg, name)
        for i in range(len(have)):
            one = tuple(1.0 if j == i else c for j, c in enumerate(have))
            got = _serve(_falcon_h1_adapter("without", dataclasses.replace(
                cfg, **{name: one})), params, toks, [16, 5], t_bucket=16)
            assert np.abs(got - want).max() > 100 * TOL, (name, i)


def test_mixed_step_is_the_reference(tiny):
    """One fused mixed step: a prompt chunk (continuing from its slot and
    its pages) beside two decode rows, each row from its own."""
    adapter, params = tiny
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
    hf = hf_of(adapter.config)
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
        model="falcon-h1-tiny", num_pages=256, max_pages_per_seq=48,
        prefill_chunk=32, max_seqs=2, decode_buckets=(1, 2),
    )
    return JaxEngine(EngineConfig(**{**base.__dict__, **overrides}))


def _streams(eng, reqs, events=None):
    for rid, prompt, n in reqs:
        eng.add_request(rid, prompt, SamplingParams(
            max_tokens=n, temperature=0.0, ignore_eos=True, logprobs=0))
    toks, lps, steps = {}, {}, 0
    while eng.has_work:
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            lps.setdefault(o.request_id, []).extend(o.logprobs or ())
        steps += 1
        if events and steps in events:
            events[steps](eng)
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
    "forced-rollback", "preemption-recompute"])
def test_engine_streams_are_the_reference(scenario):
    """The normal path (scheduler, both caches under one allocator, the
    step programs, launch-ahead on), teacher-forced against the
    reference on the chosen tokens' log-probs: a prompt over three
    chunks of 32 with the last one padded (75 = 32 + 32 + 11) and fused
    8-step dispatches; five requests through two decode slots, so that
    every slot and its pages have a second and a third owner (mixed steps
    all the way); a neighbour aborted while a dispatch launched ahead is
    on the device, so that the survivors' state AND staged pages were
    advanced by a dispatch that is thrown away; and a pool so small that
    a row is preempted and recomputed from position 0 in another slot."""
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
        reqs = [(f"h{i}", [int(x) for x in rng.integers(3, 250, 9 + 3 * i)],
                 20 + 2 * i) for i in range(3)]
        events = {5: lambda e: e.abort_request("h1")}
        only = ["h0", "h2"]
    else:
        eng = _engine(num_pages=14, max_pages_per_seq=12, decode_steps=1)
        reqs = [(f"p{i}", [int(x) for x in rng.integers(3, 250, 12)], 20)
                for i in range(2)]
    toks, lps = _streams(eng, reqs, events)
    m = eng.metrics
    if scenario == "three-chunks-then-fused-dispatches":
        assert m.prefill_dispatches == 3
        assert any(k[0] == "decode_multi" and k[2] == 8
                   for k in eng.programs)
    elif scenario == "slot-reuse-after-a-finish":
        assert m.state_resets == 5 and m.mixed_dispatches > 0
        assert m.state_slots_live <= eng.allocator.state_slots == 3
    elif scenario == "forced-rollback":
        assert m.overlap_rollbacks > 0 and m.state_restores > 0
        reqs = [r for r in reqs if r[0] in only]
    else:
        assert m.preemptions > 0 and m.state_resets > 2
    assert m.overlap_hits > 0
    assert eng.allocator.num_free_slots == eng.allocator.state_slots
    _assert_streams_are_the_reference(eng, reqs, toks, lps)


# -- what refuses, refuses loudly, and says what is true of this family -----


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


@pytest.mark.parametrize("preset,family", [
    ("falcon-h1-tiny", "Falcon-H1"), ("nemotron-h-tiny", "Nemotron-H")])
def test_registry_refusals_are_true_of_both_families(preset, family):
    """The `kv_quantize` refusal argued from Nemotron-3-Nano's ratio
    ("pages are a twentieth of the state"), false where a row's pages
    pass its state at 2,063 tokens: it says what holds for any state
    model, and names the family."""
    adapter = get_model(preset)
    with pytest.raises(ValueError, match="kv_quantize is not supported") as e:
        adapter.init_kv(8, 4, kv_quantize="int8", state_slots=2)
    assert family in str(e.value) and "twentieth" not in str(e.value)
    assert "float32" in str(e.value)
    with pytest.raises(ValueError, match="one chip") as e:
        get_model(preset, mesh=object())
    assert family in str(e.value)


def test_forward_and_transfer_surface_refuse_half_a_sequence():
    adapter = get_model("falcon-h1-tiny")
    with pytest.raises(ValueError, match="state slot"):
        fh.forward_groups(
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


def test_prefix_hit_is_refused_and_counted():
    """Pages of a shared prefix are there in every layer, the state at
    their boundary is not: no hit, counted, and the second stream is the
    first's."""
    eng = _engine(enable_prefix_caching=True)
    prompt = [int(x) for x in np.random.default_rng(6).integers(3, 250, 40)]
    first = _streams(eng, [("a", prompt, 6)])
    again = _streams(eng, [("b", prompt, 6)])
    assert again[0]["b"] == first[0]["a"]
    assert eng.metrics.prefix_hits_refused_state == 1
    assert eng.allocator.stats.hit_tokens == 0


def test_memory_report_counts_both_pools_of_every_layer():
    eng = _engine()
    rep = eng.memory_report()["totals"]
    cfg = eng.adapter.config
    entries = 2 * (eng.allocator.state_slots + 1)
    assert eng.adapter.state_layers == cfg.num_layers == 3
    assert rep["state_pool_bytes"] == entries * nh.state_bytes_per_slot(cfg)
    assert rep["state_pool_bytes"] == eng.metrics.state_pool_bytes
    # as many page layers as state layers
    pages = 2 * cfg.num_layers * 256 * 4 * cfg.num_kv_heads * cfg.head_dim * 4
    assert rep["kv_pool_bytes"] == eng.metrics.kv_pool_bytes == pages
    assert eng.metrics.state_slots == eng.allocator.state_slots == 3
    # the flight record of a step carries the rows' live tokens (the page
    # bytes of `paged_attn_hbm_share` come from them)
    _streams(eng, [("a", [5, 6, 7, 8, 9, 10, 11, 12, 13], 12)])
    recs = [r for r in eng.flight.snapshot() if r.get("n_decode")]
    assert recs and recs[0]["active_pages"] > 0
