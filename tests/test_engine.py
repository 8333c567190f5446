"""JaxEngine end-to-end on CPU: continuous batching, prefix caching,
chunked prefill, preemption, sampling, and consistency with the raw model."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import FinishReason, SamplingParams


@pytest.fixture(scope="module")
def engine_factory():
    def make(**overrides):
        base = EngineConfig.for_tests()
        cfg = EngineConfig(**{**base.__dict__, **overrides})
        return JaxEngine(cfg)

    return make


def _greedy(max_tokens=8):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens)


def test_single_request_greedy(engine_factory):
    eng = engine_factory()
    eng.add_request("r1", [5, 17, 42, 99, 3], _greedy(6))
    out = eng.run_to_completion()
    assert len(out["r1"]) == 6

    # Same prompt again must produce identical tokens (greedy determinism)
    eng2 = engine_factory()
    eng2.add_request("x", [5, 17, 42, 99, 3], _greedy(6))
    assert eng2.run_to_completion()["x"] == out["r1"]


def test_engine_matches_raw_model(engine_factory):
    """Engine greedy output == hand-rolled forward loop on the same params."""
    from dynamo_tpu.models.llama import forward, init_kv_pages

    eng = engine_factory()
    prompt = [7, 1, 3, 9, 2, 8, 4, 4, 0, 6, 11, 13]  # 12 tokens, 3 pages
    eng.add_request("r", prompt, _greedy(5))
    got = eng.run_to_completion()["r"]

    cfg = eng.adapter.config
    kv = init_kv_pages(cfg, 64, 4)
    pt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    toks = list(prompt)
    ref = []
    for step in range(5):
        arr = jnp.asarray([toks], jnp.int32)
        pos = jnp.arange(len(toks), dtype=jnp.int32)[None]
        kv0 = init_kv_pages(cfg, 64, 4)
        logits, _ = forward(eng.params, cfg, arr, pos,
                            jnp.ones((1, len(toks)), bool), kv0, pt)
        tok = int(np.asarray(logits)[0, -1].argmax())
        ref.append(tok)
        toks.append(tok)
    assert got == ref


def test_concurrent_requests_isolated(engine_factory):
    """Batched decode must equal each request run alone."""
    eng = engine_factory()
    prompts = {
        "a": [1, 2, 3, 4, 5],
        "b": [9, 8, 7],
        "c": [11, 4, 11, 4, 11, 4, 2],
    }
    for rid, p in prompts.items():
        eng.add_request(rid, p, _greedy(4))
    batched = eng.run_to_completion()

    for rid, p in prompts.items():
        solo_eng = engine_factory()
        solo_eng.add_request("solo", p, _greedy(4))
        assert solo_eng.run_to_completion()["solo"] == batched[rid], rid


def test_chunked_prefill_long_prompt(engine_factory):
    """Prompt longer than prefill_chunk is prefilled over multiple steps."""
    eng = engine_factory(prefill_chunk=8, max_pages_per_seq=16, num_pages=128)
    prompt = list(np.random.default_rng(0).integers(1, 200, 25))
    eng.add_request("long", [int(x) for x in prompt], _greedy(3))
    out = eng.run_to_completion()
    assert len(out["long"]) == 3

    # consistency with single-chunk prefill
    eng2 = engine_factory(prefill_chunk=32, max_pages_per_seq=16, num_pages=128)
    eng2.add_request("one", [int(x) for x in prompt], _greedy(3))
    assert eng2.run_to_completion()["one"] == out["long"]


def test_prefix_cache_hit_same_output(engine_factory):
    """Second request sharing a long prefix reuses pages AND matches the
    no-cache output exactly."""
    eng = engine_factory()
    base = [3, 1, 4, 1, 5, 9, 2, 6]  # 2 full pages
    eng.add_request("p1", base + [10, 11], _greedy(4))
    first = eng.run_to_completion()["p1"]
    hits_before = eng.allocator.stats.hit_tokens
    eng.add_request("p2", base + [10, 11], _greedy(4))
    second = eng.run_to_completion()["p2"]
    assert second == first
    assert eng.allocator.stats.hit_tokens > hits_before

    cold = engine_factory(enable_prefix_caching=False)
    cold.add_request("p3", base + [10, 11], _greedy(4))
    assert cold.run_to_completion()["p3"] == first


def test_register_pages_resumes_where_it_stopped(engine_factory):
    """`_register_pages` runs for every row of every dispatch: it offers
    each filled block to the allocator once, not all of them from block 0
    each step (ISSUE 30: ~4,100 ctypes calls a dispatch at 64 rows of
    4k-token contexts), and a request that is preempted and re-admitted
    starts over on its new pages."""
    eng = engine_factory(max_pages_per_seq=16, num_pages=128, decode_steps=1)
    ps = eng.config.page_size
    calls = []
    register = eng.allocator.register
    eng.allocator.register = lambda page, *a: (
        calls.append(page), register(page, *a))[1]
    prompt = [int(x) for x in np.random.default_rng(3).integers(1, 200, 21)]
    eng.add_request("r", prompt, _greedy(24))
    req = eng.scheduler.waiting[0]
    while len(req.output_tokens) < 12:
        eng.step()
    blocks = req.num_computed_tokens // ps
    assert req.registered_blocks == blocks >= 7
    assert len(calls) == blocks  # once a block, a dozen dispatches in

    assert eng.scheduler._preempt_youngest(excluding=None)
    eng.allocator.clear_cache()  # its old pages lose their addresses
    del calls[:]
    eng.run_to_completion()
    # the last token is never computed, and the step that ends a request
    # releases its pages before it would register them
    full = (len(prompt) + 24 - 2) // ps
    assert req.registered_blocks == full
    assert len(calls) == len(set(calls)) == full
    assert eng.allocator.stats.stored_blocks == blocks + full


def test_eos_stops_generation(engine_factory):
    eng = engine_factory()
    eng.add_request("r", [5, 17, 42, 99, 3], _greedy(6))
    ref = eng.run_to_completion()["r"]
    eos = ref[2]

    eng2 = engine_factory(eos_token_ids=(eos,))
    eng2.add_request("r", [5, 17, 42, 99, 3], _greedy(6))
    outs = []
    finish = None
    while eng2.has_work:
        for o in eng2.step():
            outs.extend(o.new_token_ids)
            if o.finish_reason:
                finish = o.finish_reason
    assert outs == ref[:3]
    assert finish == FinishReason.STOP


def test_sampling_with_temperature_varies_and_respects_topk(engine_factory):
    eng = engine_factory()
    sp = SamplingParams(temperature=1.5, top_k=5, max_tokens=12, seed=1)
    eng.add_request("s", [5, 17, 42], sp)
    out = eng.run_to_completion()["s"]
    assert len(out) == 12
    # top-k=5 on a random tiny model: sampled ids must come from the top-5
    # at each step — verify the first step's choice against raw logits.
    from dynamo_tpu.models.llama import forward, init_kv_pages

    cfg = eng.adapter.config
    kv0 = init_kv_pages(cfg, 64, 4)
    pt = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    logits, _ = forward(eng.params, cfg, jnp.asarray([[5, 17, 42]], jnp.int32),
                        jnp.arange(3, dtype=jnp.int32)[None],
                        jnp.ones((1, 3), bool), kv0, pt)
    top5 = set(np.asarray(logits)[0, -1].argsort()[-5:].tolist())
    assert out[0] in top5


def test_preemption_under_page_pressure(engine_factory):
    """More decode growth than pages: youngest preempted, all finish."""
    eng = engine_factory(num_pages=12, max_seqs=4, admission_watermark=0.0)
    for i in range(3):
        eng.add_request(f"r{i}", [10 + i, 20 + i, 30 + i, 40 + i], _greedy(10))
    out = eng.run_to_completion()
    assert all(len(out[f"r{i}"]) == 10 for i in range(3))
    # Preempted-then-recomputed streams must equal unpressured solo runs.
    for i in range(3):
        solo = engine_factory(num_pages=64)
        solo.add_request("s", [10 + i, 20 + i, 30 + i, 40 + i], _greedy(10))
        assert solo.run_to_completion()["s"] == out[f"r{i}"], f"r{i}"


def test_metrics_surface(engine_factory):
    eng = engine_factory()
    eng.add_request("m", [1, 2, 3, 4, 5, 6], _greedy(4))
    eng.step()
    m = eng.metrics
    assert m.kv_total_pages == eng.config.num_pages - 1
    assert m.kv_active_pages > 0
    eng.run_to_completion()
    assert eng.metrics.generated_tokens == 4


def test_seeded_sampling_reproducible(engine_factory):
    """(prompt, seed) reproduces exactly, regardless of batch composition."""
    sp = SamplingParams(temperature=1.0, max_tokens=6, seed=123)
    eng = engine_factory()
    eng.add_request("solo", [5, 6, 7], sp)
    solo = eng.run_to_completion()["solo"]

    eng2 = engine_factory()
    eng2.add_request("other", [9, 9, 9], SamplingParams(temperature=1.3, max_tokens=6, seed=7))
    eng2.add_request("same", [5, 6, 7], sp)
    batched = eng2.run_to_completion()
    assert batched["same"] == solo

    # different seed -> (almost surely) different stream
    eng3 = engine_factory()
    eng3.add_request("d", [5, 6, 7], SamplingParams(temperature=1.0, max_tokens=6, seed=124))
    assert eng3.run_to_completion()["d"] != solo


def test_impossible_requests_finish_instead_of_hanging(engine_factory):
    """Liveness: requests that can never progress are finished, not spun on."""
    # (a) prompt larger than the whole pool
    eng = engine_factory(num_pages=4, max_pages_per_seq=8)
    eng.add_request("big", list(range(14)), _greedy(4))  # needs 4 pages, pool has 3
    outs = {}
    for _ in range(50):
        if not eng.has_work:
            break
        for o in eng.step():
            outs[o.request_id] = o.finish_reason
    assert not eng.has_work, "engine hung on impossible prompt"
    assert outs["big"] == FinishReason.LENGTH

    # (b) sole sequence exhausts the pool mid-decode
    eng2 = engine_factory(num_pages=4, max_pages_per_seq=8, admission_watermark=0.0)
    eng2.add_request("grow", [1, 2, 3], _greedy(40))
    n = 0
    for _ in range(100):
        if not eng2.has_work:
            break
        for o in eng2.step():
            n += len(o.new_token_ids)
    assert not eng2.has_work, "engine hung on pool exhaustion"
    assert 0 < n < 40  # stopped early at pool capacity


def test_prompt_at_max_context_rejected(engine_factory):
    eng = engine_factory()  # max_context = 32
    with pytest.raises(ValueError):
        eng.add_request("edge", list(range(32)), _greedy(2))
    eng.add_request("ok", list(range(31)), _greedy(2))
    out = eng.run_to_completion()
    assert len(out["ok"]) >= 1


def test_multi_step_decode_matches_single_step(engine_factory):
    """decode_steps=K fuses K decode iterations into one dispatch with
    on-device token feedback; outputs must be identical to K=1 stepping,
    including mixed finish points (eos overshoot dropped on host)."""
    prompts = {
        "a": [5, 17, 42, 99, 3],
        "b": [1, 2, 3],
        "c": [9, 9, 1, 4, 6, 2, 7],
    }

    def run(k):
        eng = engine_factory(decode_steps=k)
        for rid, p in prompts.items():
            mt = {"a": 11, "b": 3, "c": 7}[rid]
            eng.add_request(rid, p, _greedy(mt))
        return eng.run_to_completion()

    single, fused = run(1), run(8)
    assert single == fused


def test_multi_step_decode_sampled_matches(engine_factory):
    """Seeded sampling is step-indexed (counters ride the scan), so fused
    and single stepping draw identical tokens."""
    sp = SamplingParams(temperature=0.8, top_p=0.9, top_k=12, seed=7,
                       max_tokens=9)

    def run(k):
        eng = engine_factory(decode_steps=k)
        eng.add_request("s", [3, 1, 4, 1, 5], sp)
        return eng.run_to_completion()["s"]

    assert run(1) == run(6)


def test_pallas_engine_under_tp_mesh(engine_factory):
    """The Pallas kernels run shard_mapped over a tp mesh (heads are
    embarrassingly parallel): greedy output must match the single-chip
    xla engine exactly."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    prompt = [5, 17, 42, 9, 3, 7, 11, 2]
    ref = engine_factory()
    ref.add_request("r", prompt, _greedy(6))
    expected = ref.run_to_completion()["r"]

    eng = engine_factory(tp=2, attention_impl="pallas")
    assert eng.mesh is not None and eng.mesh.shape["tp"] == 2
    eng.add_request("m", prompt, _greedy(6))
    got = eng.run_to_completion()["m"]
    assert got == expected


def test_sp_ring_prefill_matches_single_chip(engine_factory):
    """Engine-level sequence parallelism: a long first-chunk prefill runs
    ring attention over the sp mesh axis; greedy output must match the
    unsharded engine exactly."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    prompt = list(range(1, 29))  # fills most of a 32-token chunk

    ref = engine_factory(prefill_chunk=32, max_pages_per_seq=16, num_pages=64)
    ref.add_request("r", prompt, _greedy(5))
    expected = ref.run_to_completion()["r"]

    eng = engine_factory(
        sp=2, prefill_chunk=32, max_pages_per_seq=16, num_pages=64
    )
    assert eng.mesh is not None and eng.mesh.shape["sp"] == 2
    eng.add_request("s", prompt, _greedy(5))
    assert eng.run_to_completion()["s"] == expected


def test_multihost_init_single_process():
    """jax.distributed bring-up (num_hosts=1 smoke) — in a subprocess,
    since initialize() must precede any XLA backend use and this suite
    process has long since initialized it."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = f"""
import jax
jax.config.update("jax_platforms", "cpu")
from dynamo_tpu.parallel.mesh import init_multihost
n = init_multihost("127.0.0.1:{port}", num_hosts=1, host_id=0)
assert n == len(jax.devices()) >= 1
assert init_multihost("127.0.0.1:{port}", 1, 0) == n  # idempotent
try:
    init_multihost("127.0.0.1:9", 2, 1)
except RuntimeError:
    pass
else:
    raise AssertionError("conflicting re-init must raise")
print("MULTIHOST_OK", n)
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd="/root/repo",
    )
    assert "MULTIHOST_OK" in out.stdout, out.stderr


def test_long_context_chunked_prefill_thousands_of_tokens(engine_factory):
    """Long-context serving at real scale for the test model: a ~3k-token
    prompt walks 12 prefill chunks and ~48 KV pages, and the greedy
    continuation must match a one-shot (single-chunk) prefill of the same
    prompt bit-for-bit (SURVEY §5.7; the reference reaches long context
    through vLLM's chunked prefill — this pins ours through the paged
    path at depth, not just the 2-chunk smoke above)."""
    import numpy as np

    rng = np.random.default_rng(7)
    prompt = [int(x) for x in rng.integers(1, 250, 3000)]

    chunked = engine_factory(
        prefill_chunk=256, page_size=64, max_pages_per_seq=64,
        num_pages=80, max_seqs=4,
    )
    chunked.add_request("lc", list(prompt), _greedy(8))
    out_chunked = chunked.run_to_completion()["lc"]

    oneshot = engine_factory(
        prefill_chunk=4096, page_size=64, max_pages_per_seq=64,
        num_pages=80, max_seqs=4,
    )
    oneshot.add_request("lc", list(prompt), _greedy(8))
    assert oneshot.run_to_completion()["lc"] == out_chunked
    assert len(out_chunked) == 8


def test_adaptive_prefill_budget_engine_e2e():
    """Engine-level: adaptive policy serves a saturation burst correctly
    (same tokens as fixed; the policy only changes dispatch granularity)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    def serve(policy):
        base = EngineConfig.for_tests()
        cfg = EngineConfig(**{
            **base.__dict__, "num_pages": 96,
            "prefill_token_budget": 16,
            "prefill_budget_policy": policy,
        })
        eng = JaxEngine(cfg)
        for i in range(6):
            eng.add_request(
                f"q{i}", [2 + i, 3, 5, 8, 13],
                SamplingParams(temperature=0.0, max_tokens=6),
            )
        return eng.run_to_completion()

    fixed = serve("fixed")
    adaptive = serve("adaptive")
    assert fixed == adaptive  # identical greedy outputs per request
    assert all(len(v) == 6 for v in adaptive.values())


def test_step_phase_timing_metrics():
    """EngineMetrics accumulates per-phase wall time and dispatch counts
    (the host-loop observability plane — exported via metrics_service)."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    eng = JaxEngine(EngineConfig.for_tests())
    eng.add_request("t0", [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=4))
    eng.run_to_completion()
    m = eng.metrics.to_dict()
    assert m["prefill_dispatches"] >= 1
    assert m["decode_dispatches"] >= 1
    assert m["time_prefill_ms"] > 0 and m["time_decode_ms"] > 0
    assert m["time_schedule_ms"] >= 0


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_a_thinning_batch_keeps_the_decode_program_it_has(
        engine_factory, decode_steps):
    """Rows that leave one by one (streams cut at once and aborted as
    their closes arrive) do not walk the batch down through every smaller
    row bucket, one first call each: the dispatch pads up to the program
    that has already run. The survivors' tokens are those of a run that
    never had the neighbours' bucket to fall back on."""
    prompts = {f"r{i}": [3 + i, 17, 42, 9 + i, 5] for i in range(4)}

    def run(abort: bool):
        eng = engine_factory(decode_steps=decode_steps)
        for rid, p in prompts.items():
            eng.add_request(rid, p, _greedy(16))
        out: dict = {rid: [] for rid in prompts}
        steps = 0
        while eng.has_work:
            for o in eng.step():
                out[o.request_id].extend(o.new_token_ids)
            steps += 1
            if abort and steps == 3:
                eng.abort_request("r2")
                eng.abort_request("r3")
                seen = set(eng.programs)
        return eng, out, (seen if abort else None)

    eng, out, seen = run(abort=True)
    decode = [k for k in eng.programs if k[0].startswith("decode")]
    assert decode and {k[1] for k in decode} == {4}  # never a 2-row program
    assert set(eng.programs) == seen  # no first call after the aborts
    _, alone, _ = run(abort=False)
    for rid in ("r0", "r1"):
        assert out[rid] == alone[rid] and len(out[rid]) == 16
