"""Mixed prefill+decode steps (EngineConfig.mixed_steps, ISSUE 5): one
fused step carries a bounded prefill chunk plus the current decode batch,
so decode rows emit a token every step while a prompt backlog drains.
Token streams must be BIT-EXACT vs the XOR (prefill-priority) scheduler —
same kernels, same per-request order — across chunked prompts, sampling,
logprobs, penalties, bias, preemption-resume, and the overlapped decode
pipeline; and the compiled-program family must stay finite."""

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.telemetry import phases, promlint


@pytest.fixture(scope="module")
def engine_factory():
    def make(**overrides):
        base = EngineConfig.for_tests()
        cfg = EngineConfig(**{**base.__dict__, **overrides})
        return JaxEngine(cfg)

    return make


def _drive(eng, late=(), late_at=5):
    """Run to completion, injecting `late` requests after `late_at`
    steps — the shape that forces mixed (or XOR prefill) scheduling
    against a running decode wave."""
    out = {}
    steps = 0
    added = not late
    while eng.has_work:
        for o in eng.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
        if steps == late_at and not added:
            for rid, prompt, s in late:
                eng.add_request(rid, prompt, s)
            added = True
    return out


def _chunked_late(rng, n=2, max_tokens=6):
    """Prompts longer than prefill_chunk (16) => multi-chunk prefill."""
    return [
        (
            f"late{i}",
            [int(x) for x in rng.integers(1, 200, 24 + 2 * i)],
            SamplingParams(max_tokens=max_tokens, ignore_eos=True),
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("decode_steps,late_at", [
    pytest.param(1, 5, id="one-step-dispatches"),
    pytest.param(8, 2, id="fused-scan-of-8"),  # the benchmark cells' shape
])
def test_mixed_greedy_bitexact_chunked_prompts(
        engine_factory, decode_steps, late_at):
    """The headline contract: greedy streams identical, mixed on vs off,
    with chunked prompts arriving against a decode wave — and the on-arm
    really scheduled mixed steps, whose one pass over the weights carried
    decode rows beside the chunk."""
    rng = np.random.default_rng(5)
    late = _chunked_late(rng)
    base = [
        ("a", [1, 2, 3], SamplingParams(max_tokens=20, ignore_eos=True)),
        ("b", [4, 5, 6, 7], SamplingParams(max_tokens=20, ignore_eos=True)),
    ]

    def run(mixed):
        eng = engine_factory(mixed_steps=mixed, decode_steps=decode_steps)
        for rid, p, s in base:
            eng.add_request(rid, p, s)
        return _drive(eng, late, late_at=late_at), eng.metrics

    ref, m_off = run(False)
    got, m_on = run(True)
    assert got == ref
    assert m_on.mixed_dispatches > 0
    assert m_on.mixed_shared_rows > 0
    assert m_off.mixed_dispatches == 0 and m_off.mixed_shared_rows == 0


@pytest.mark.parametrize("late_at,floats_bitwise", [
    # one prompt a chunk: [1, 32] prompt rows beside 2-4 decode rows
    pytest.param((4, 8), True, id="one-prompt-a-chunk"),
    # both prompts in one chunk: [2, 32] prompt rows beside 2 decode rows
    pytest.param((4, 4), False, id="two-prompts-a-chunk"),
])
def test_mixed_parity_sampled_logprobs_bias(
        engine_factory, late_at, floats_bitwise):
    """Sampled rows, logprob reporting and logit_bias ride the fused
    program's combined row space: the tokens match XOR exactly. The
    reported logprobs match bit for bit wherever the compiler runs a
    matmul's rows the same way at both row counts, and to rounding
    elsewhere: the fused step's matmuls take the decode rows beside the
    prompt rows, and XLA:CPU picks a dot's implementation, hence a row's
    summation order, by the dot's shape (here: the [rows, 64] x [64, 32]
    k and v projections change at 51 rows; the second case's 66 rows
    cross that, the first case's 34-36 do not)."""
    rng = np.random.default_rng(9)
    late = [
        (
            "late-lp",
            [int(x) for x in rng.integers(1, 200, 26)],
            SamplingParams(max_tokens=5, ignore_eos=True, logprobs=1),
        ),
        (
            "late-s",
            [int(x) for x in rng.integers(1, 200, 20)],
            SamplingParams(temperature=1.1, seed=7, max_tokens=5,
                           ignore_eos=True),
        ),
    ]

    def run(mixed):
        eng = engine_factory(mixed_steps=mixed, decode_steps=1)
        eng.add_request(
            "s", [5, 6, 7],
            SamplingParams(temperature=0.8, top_p=0.9, seed=42,
                           max_tokens=16, ignore_eos=True),
        )
        eng.add_request(
            "lp", [8, 9],
            SamplingParams(max_tokens=16, ignore_eos=True, logprobs=2,
                           logit_bias=((3, 4.0),)),
        )
        out, lps = {}, {}
        steps = 0
        while eng.has_work:
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
                if o.logprobs:
                    lps.setdefault(o.request_id, []).extend(o.logprobs)
            steps += 1
            for (rid, p, s), at in zip(late, late_at):
                if steps == at:
                    eng.add_request(rid, p, s)
        return out, lps, eng.metrics.mixed_dispatches

    ref_out, ref_lps, _ = run(False)
    got_out, got_lps, n_mixed = run(True)
    assert got_out == ref_out
    if floats_bitwise:
        assert got_lps == ref_lps
    else:
        assert got_lps.keys() == ref_lps.keys()
        for rid, ref in ref_lps.items():
            assert got_lps[rid] == pytest.approx(ref, abs=1e-5)
    assert n_mixed > 0


def test_mixed_parity_with_penalties(engine_factory):
    """Penalty history rides the fused program's combined row space
    (build_output_counts over decode + prefill rows)."""
    rng = np.random.default_rng(13)
    late = [
        (
            "late-pen",
            [int(x) for x in rng.integers(1, 200, 22)],
            SamplingParams(max_tokens=4, ignore_eos=True,
                           presence_penalty=0.7),
        )
    ]

    def run(mixed):
        eng = engine_factory(mixed_steps=mixed, decode_steps=1)
        eng.add_request(
            "pen", [5, 6, 7],
            SamplingParams(max_tokens=14, ignore_eos=True,
                           repetition_penalty=1.5, frequency_penalty=0.4),
        )
        return _drive(eng, late, late_at=4), eng.metrics.mixed_dispatches

    ref, _ = run(False)
    got, n_mixed = run(True)
    assert got == ref
    assert n_mixed > 0


def test_mixed_parity_heterogeneous_piece_buckets(engine_factory):
    """Pieces landing in DIFFERENT T buckets (a mid-prompt tail beside a
    short whole prompt) must run under exactly the program variants the
    XOR scheduler would pick — the fused step carries one bucket group
    and dispatches the rest through the plain prefill path. The tiny
    default config can't exercise this (every piece buckets to 32), so
    this test widens the chunk to 64."""
    rng = np.random.default_rng(41)
    late = [
        (
            "two-chunk",  # 64-token chunk + 26-token tail (bucket 32)
            [int(x) for x in rng.integers(1, 200, 90)],
            SamplingParams(max_tokens=4, ignore_eos=True),
        ),
        (
            "one-piece",  # 50 tokens -> bucket 64, first_chunk
            [int(x) for x in rng.integers(1, 200, 50)],
            SamplingParams(max_tokens=4, ignore_eos=True),
        ),
    ]

    def run(mixed, overlap=True):
        eng = engine_factory(
            mixed_steps=mixed, overlap_decode=overlap, decode_steps=1,
            prefill_chunk=64, page_size=4, max_pages_per_seq=32,
            num_pages=128,
        )
        eng.add_request("w", [1, 2, 3],
                        SamplingParams(max_tokens=24, ignore_eos=True))
        return _drive(eng, late), eng.metrics

    ref, _ = run(False)
    for overlap in (False, True):
        got, m = run(True, overlap)
        assert got == ref, f"overlap={overlap}"
        assert m.mixed_dispatches > 0


def test_mixed_parity_under_preemption_resume(engine_factory):
    """Page pressure preempts mid-wave; the folded request re-prefills
    through mixed steps and the streams still match XOR bit-for-bit."""

    def run(mixed):
        eng = engine_factory(
            mixed_steps=mixed, decode_steps=1,
            num_pages=12, max_pages_per_seq=8,
        )
        eng.add_request("p1", [1, 2, 3, 4, 5, 6, 7, 8],
                        SamplingParams(max_tokens=16, ignore_eos=True))
        eng.add_request("p2", [9, 10, 11, 12, 13, 14, 15, 16],
                        SamplingParams(max_tokens=16, ignore_eos=True))
        return _drive(eng)

    assert run(True) == run(False)


def test_mixed_overlap_interaction(engine_factory):
    """Overlap + mixed: a matching in-flight speculation is consumed as
    the decode half of the mixed step (mixed steps count as decode steps
    for the pipeline), a composition change rolls it back, and the
    streams never contain stale tokens — they match the fully
    synchronous engine exactly."""
    rng = np.random.default_rng(21)
    late = _chunked_late(rng, n=2)
    base = [
        ("a", [1, 2, 3], SamplingParams(max_tokens=24, ignore_eos=True)),
        # finishes right around the arrival: composition change
        ("b", [4, 5, 6], SamplingParams(max_tokens=7, ignore_eos=True)),
    ]

    def run(overlap):
        eng = engine_factory(
            mixed_steps=True, overlap_decode=overlap, decode_steps=1
        )
        for rid, p, s in base:
            eng.add_request(rid, p, s)
        return _drive(eng, late), eng.metrics

    ref, _ = run(False)
    got, m = run(True)
    assert got == ref
    # the pipeline engaged across mixed steps...
    assert m.overlap_dispatches > 0 and m.overlap_hits > 0
    # ...and every dispatch was either consumed or rolled back
    assert m.overlap_hits + m.overlap_rollbacks == m.overlap_dispatches
    assert m.mixed_dispatches > 0


def test_mixed_speculation_rides_through_backlog(engine_factory):
    """While a long prompt drains chunk by chunk, the decode rows are
    stable — the engine must keep speculating (decode_rows_stable), so
    overlap hits accumulate DURING the mixed phase, not just after."""
    rng = np.random.default_rng(2)
    eng = engine_factory(mixed_steps=True, decode_steps=1)
    eng.add_request("w", [1, 2, 3], SamplingParams(max_tokens=30, ignore_eos=True))
    out = {}

    def pump(n=None):
        while eng.has_work if n is None else n:
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
            if n is not None:
                n -= 1

    pump(4)
    hits_before = eng.metrics.overlap_hits
    # 28-token prompt: 2 chunks => at least one mixed step with no piece
    # completing (the mid-prompt chunk), where speculation must engage
    eng.add_request(
        "long", [int(x) for x in rng.integers(1, 200, 28)],
        SamplingParams(max_tokens=4, ignore_eos=True),
    )
    pump()
    assert eng.metrics.overlap_hits > hits_before
    assert eng.metrics.mixed_dispatches > 0
    sync = engine_factory(mixed_steps=True, overlap_decode=False,
                          decode_steps=1)
    sync.add_request("w", [1, 2, 3], SamplingParams(max_tokens=30, ignore_eos=True))
    ref = sync.run_to_completion()
    assert out["w"] == ref["w"]


def test_mixed_off_never_schedules_mixed(engine_factory):
    """--no-mixed-steps: the scheduler never emits mixed batches and the
    jit cache holds no mixed programs — the XOR path is untouched."""
    rng = np.random.default_rng(8)
    eng = engine_factory(mixed_steps=False, decode_steps=1)
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=12, ignore_eos=True))
    _drive(eng, _chunked_late(rng))
    assert eng.metrics.mixed_dispatches == 0
    assert not any(k[0] == "mixed" for k in eng._jit_cache)


def test_compile_cache_family_stays_finite(engine_factory):
    """Acceptance: no per-request shapes. Every _get_step_fn cache key
    stays inside the finite family — mixed keys are (b_decode_bucket,
    t_prefill_bucket, b_prefill_bucket) with bucketed members — and
    re-running the same workload shape with NEW requests adds no keys."""
    rng = np.random.default_rng(17)
    # overlap off => the fused mixed program (the overlap split path
    # dispatches the pure prefill/decode programs instead)
    eng = engine_factory(
        mixed_steps=True, decode_steps=1, overlap_decode=False
    )

    def wave(tag):
        for i in range(3):
            eng.add_request(
                f"{tag}w{i}", [int(x) for x in rng.integers(1, 200, 2 + i)],
                SamplingParams(max_tokens=14, ignore_eos=True),
            )
        late = [
            (
                f"{tag}l{i}",
                [int(x) for x in rng.integers(1, 200, 18 + 3 * i)],
                SamplingParams(max_tokens=4, ignore_eos=True),
            )
            for i in range(3)
        ]
        _drive(eng, late)

    wave("x")
    keys = set(eng._jit_cache)
    cfg = eng.config
    known_kinds = {
        "prefill", "prefill_nosample", "decode", "decode_multi", "mixed",
        "spec_verify", "embed",
    }
    pow2 = {1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024}
    for key in keys:
        if not isinstance(key[0], str) or key[0] not in known_kinds:
            continue  # extract/inject helper entries
        kind, b, t = key[0], key[1], key[2]
        if kind == "mixed":
            b_pre = key[9]
            assert b in cfg.decode_buckets, key
            assert t in pow2 and t <= max(cfg.prefill_chunk, 32), key
            assert b_pre in pow2 and b_pre <= cfg.max_seqs, key
    assert any(k[0] == "mixed" for k in keys)
    # same shapes, different requests => zero new programs
    wave("y")
    assert set(eng._jit_cache) == keys


def test_bucket_t_guard_rejects_oversized_piece(engine_factory):
    """Satellite bugfix: the T bucket used to cap by silently rounding
    DOWN (truncating the valid mask); oversized pieces must raise."""
    eng = engine_factory()
    cap = max(eng.config.prefill_chunk, 32)
    assert eng._bucket_t(cap) == cap
    with pytest.raises(ValueError, match="T-bucket cap"):
        eng._bucket_t(cap + 1)


@pytest.mark.parametrize("buckets,piece,bucket", [
    (None, 33, 64), (None, 100, 128), ((32, 128), 33, 128),
    ((32, 128), 32, 32), ((128,), 5, 128), ((16, 64, 128), 17, 64)])
def test_named_t_buckets_take_the_place_of_the_powers_of_two(
        engine_factory, buckets, piece, bucket):
    """`EngineConfig.prefill_buckets` (`--prefill-buckets`): a piece is
    padded to the first named bucket that holds it, so a model whose step
    programs are dear to load keeps few of them."""
    eng = engine_factory(prefill_chunk=128, prefill_buckets=buckets)
    assert eng._bucket_t(piece) == bucket


@pytest.mark.parametrize("buckets", [(64, 32, 128), (32, 64), (0, 128),
                                     (32, 32, 128)])
def test_t_buckets_that_do_not_ascend_to_the_chunk_are_refused(buckets):
    base = EngineConfig.for_tests()
    with pytest.raises(ValueError, match="prefill_buckets"):
        EngineConfig(**{**base.__dict__, "prefill_chunk": 128,
                        "prefill_buckets": buckets})


def test_two_t_buckets_serve_the_streams_of_the_default_ones(engine_factory):
    """Padding a tail piece further changes no token: the same requests
    under the default buckets and under (32, 128), and only those two T
    among the second engine's step programs."""
    rng = np.random.default_rng(3)
    reqs = [(f"r{i}", [int(x) for x in rng.integers(3, 200, n)],
             SamplingParams(max_tokens=6, temperature=0.0))
            for i, n in enumerate((150, 40, 70))]
    outs = []
    for buckets in (None, (32, 128)):
        eng = engine_factory(prefill_chunk=128, prefill_buckets=buckets,
                             num_pages=256, max_pages_per_seq=48)
        for rid, prompt, sp in reqs[:1]:
            eng.add_request(rid, prompt, sp)
        outs.append(_drive(eng, late=reqs[1:], late_at=3))
        ts = {k[2] for k in eng.programs
              if k[0] in ("mixed", "prefill", "prefill_nosample")}
    assert outs[0] == outs[1]
    assert ts <= {32, 128} and 128 in ts


def test_decode_stall_histogram_observed(engine_factory):
    """dynamo_tpu_phase_decode_stall_ms: gaps between a running request's
    token emissions with a prefill-carrying dispatch in between land in
    the histogram (both schedulers), and the exposition passes promlint."""
    phases.phase_histograms.reset()
    rng = np.random.default_rng(31)
    for mixed in (False, True):
        eng = engine_factory(mixed_steps=mixed, decode_steps=1)
        eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=16, ignore_eos=True))
        _drive(eng, _chunked_late(rng))
    text = "\n".join(phases.expose_lines()) + "\n"
    assert "# TYPE dynamo_tpu_phase_decode_stall_ms histogram" in text
    assert "dynamo_tpu_phase_decode_stall_ms_count" in text
    assert promlint.lint(text) == []
    phases.phase_histograms.reset()


def test_mixed_outputs_marked_for_span_attribute(engine_factory):
    """StepOutputs emitted by a mixed step carry mixed=True (the engine
    span's `mixed` attribute rides this through output_to_dict)."""
    from dynamo_tpu.engine.async_engine import output_to_dict

    rng = np.random.default_rng(23)
    eng = engine_factory(mixed_steps=True, decode_steps=1)
    eng.add_request("a", [1, 2, 3], SamplingParams(max_tokens=16, ignore_eos=True))
    flagged = []
    steps = 0
    added = False
    while eng.has_work:
        before = eng.metrics.mixed_dispatches
        outs = eng.step()
        for o in outs:
            if eng.metrics.mixed_dispatches > before:
                flagged.append(o.mixed)
            d = output_to_dict(o)
            assert d.get("mixed", False) == o.mixed
        steps += 1
        if steps == 4 and not added:
            eng.add_request(
                "late", [int(x) for x in rng.integers(1, 200, 20)],
                SamplingParams(max_tokens=4, ignore_eos=True),
            )
            added = True
    assert flagged and all(flagged)
