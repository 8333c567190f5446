"""The fused decode scan (`EngineConfig.decode_steps` > 1, program kind
`decode_multi`): K decode iterations in one dispatch with the sampled
token fed back on device and every finish condition applied on the host
afterwards. It is what every benchmark cell decodes with, beside mixed
steps and a dispatch launched ahead. Contract: each request's stream is
the one `decode_steps=1` gives, whatever overshoot the scan computed
past a stop or a budget, and that overshoot leaves nothing behind."""

import time

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import EngineMetrics, JaxEngine
from dynamo_tpu.engine.request import SamplingParams


@pytest.fixture(scope="module")
def engine_factory():
    def make(**overrides):
        return JaxEngine(EngineConfig.for_tests(**overrides))

    return make


def _collect(eng, reqs, late=None, late_after=2):
    """Serve `reqs` step by step (`late` joins after `late_after`
    steps); returns ({rid: tokens}, {rid: logprobs})."""
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    toks, lps, n = {}, {}, 0
    while eng.has_work:
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            if o.logprobs:
                lps.setdefault(o.request_id, []).extend(o.logprobs)
        n += 1
        if late is not None and n == late_after:
            eng.add_request(*late)
            late = None
    return toks, lps


def _fused(eng) -> bool:
    return eng.compiles_by_kind.get("decode_multi", 0) > 0


_STYLES = {
    "greedy": lambda i: SamplingParams(
        temperature=0.0, max_tokens=5 + 4 * (i % 3), ignore_eos=True
    ),
    "sampled": lambda i: SamplingParams(
        temperature=0.8, top_p=0.9, top_k=20, seed=300 + i,
        max_tokens=5 + 4 * (i % 3), ignore_eos=True,
    ),
    "penalty": lambda i: SamplingParams(
        temperature=0.7, seed=400 + i, repetition_penalty=1.3,
        frequency_penalty=0.2, max_tokens=6 + 3 * (i % 2), ignore_eos=True,
    ),
    "bias": lambda i: SamplingParams(
        temperature=0.0, logit_bias=((3, 4.0), (7, -2.0)),
        max_tokens=6 + 3 * (i % 2), ignore_eos=True,
    ),
    "min_tokens": lambda i: SamplingParams(
        temperature=0.0, min_tokens=6, max_tokens=9,
    ),
    "logprobs": lambda i: SamplingParams(
        temperature=0.0 if i % 2 else 0.8, seed=500 + i, logprobs=2,
        max_tokens=7 + 3 * (i % 2), ignore_eos=True,
    ),
}


def _workload(styles):
    """Six rows cycling through `styles`, with staggered max_tokens so
    rows end in the middle of a scan of 8."""
    rng = np.random.default_rng(11)
    return [
        (
            f"{styles[i % len(styles)]}{i}",
            [int(x) for x in rng.integers(1, 200, 3 + (i % 4))],
            _STYLES[styles[i % len(styles)]](i),
        )
        for i in range(6)
    ]


# -- (a) row mixes: bit-exact against one step a dispatch -----------------


@pytest.mark.parametrize(
    "styles",
    [("penalty",), ("bias", "min_tokens"),
     ("greedy", "sampled", "penalty", "bias"), ("logprobs", "greedy")],
    ids=["penalty", "bias_min_tokens", "mixed_rows", "logprobs"],
)
def test_fused_scan_bitexact_vs_single_step(engine_factory, styles):
    reqs = _workload(styles)
    ref = _collect(engine_factory(decode_steps=1, overlap_decode=False), reqs)
    eng = engine_factory(decode_steps=8, overlap_decode=False)
    got = _collect(eng, reqs)
    assert got == ref  # tokens AND per-token logprobs
    assert _fused(eng), "the fused scan never ran"
    if "logprobs" in styles:
        assert all(len(v) == len(got[0][rid]) for rid, v in got[1].items())


# -- (b) a stop sampled inside the scan ------------------------------------


def test_stop_inside_scan_leaves_no_overshoot_behind(engine_factory):
    """The scan computes up to K-1 tokens past a stop. The stream ends
    on the stop, only accepted tokens are counted and content-addressed,
    and later requests that reuse the cached pages read nothing of the
    overshoot."""
    prompt = [9, 4, 9, 1, 7, 7, 2, 9, 5]
    geom = dict(num_pages=128, max_pages_per_seq=16)
    draw = dict(temperature=0.8, seed=3, max_tokens=24)
    probe = _collect(
        engine_factory(decode_steps=1, overlap_decode=False, **geom),
        [("p", prompt, SamplingParams(ignore_eos=True, **draw))],
    )[0]["p"]
    # token i >= 1 is step (i - 1) % 8 of a scan: one that first shows
    # before the scan's last step, so the scan runs on past it
    at = next(
        i for i in range(2, 24)
        if probe.index(probe[i]) == i and (i - 1) % 8 < 7
    )
    stop = SamplingParams(stop_token_ids=(probe[at],), **draw)
    stored = []
    eng = JaxEngine(
        EngineConfig.for_tests(
            decode_steps=8, overlap_decode=False, **geom),
        on_kv_event=lambda e: stored.extend(
            e.token_blocks if e.kind == "stored" else ()
        ),
    )
    got = _collect(eng, [("s", prompt, stop)])[0]["s"]
    assert got == probe[: at + 1]
    assert _fused(eng)
    assert eng.metrics.generated_tokens == at + 1
    # every registered page holds accepted tokens only, in order
    seen = [t for block in stored for t in block]
    assert seen and seen == (prompt + got)[: len(seen)]
    # the same prompt again, now from the prefix cache
    assert _collect(eng, [("s2", prompt, stop)])[0]["s2"] == got
    assert eng.allocator.stats.hit_tokens > 0
    # and a request that continues past the stop over the cached pages
    cont = prompt + got
    ref = _collect(
        engine_factory(decode_steps=1, overlap_decode=False, **geom),
        [("c", cont, SamplingParams(max_tokens=6, ignore_eos=True))],
    )[0]["c"]
    assert _collect(
        eng, [("c", cont, SamplingParams(max_tokens=6, ignore_eos=True))]
    )[0]["c"] == ref


# -- (c) a budget that runs out inside the scan ----------------------------


@pytest.mark.parametrize("k", [4, 16])
def test_max_tokens_runs_out_mid_scan(engine_factory, k):
    """max_tokens that is no multiple of K ends one row inside a scan
    while the others go on: exact lengths, never rounded to K."""
    geom = dict(num_pages=128, max_pages_per_seq=16)
    reqs = [
        ("a", [1, 2, 3], SamplingParams(max_tokens=6, ignore_eos=True)),
        ("b", [4, 5, 6], SamplingParams(max_tokens=19, ignore_eos=True)),
        ("c", [7, 8, 9], SamplingParams(
            temperature=0.8, seed=5, max_tokens=11, ignore_eos=True)),
    ]
    eng = engine_factory(decode_steps=k, overlap_decode=False, **geom)
    got = _collect(eng, reqs)[0]
    assert {r: len(t) for r, t in got.items()} == {"a": 6, "b": 19, "c": 11}
    assert got == _collect(
        engine_factory(decode_steps=1, overlap_decode=False, **geom), reqs
    )[0]
    assert _fused(eng)
    assert eng.metrics.generated_tokens == 36


# -- (d) a long wave -------------------------------------------------------


def test_long_wave_at_sixteen_steps(engine_factory):
    """48 tokens at decode_steps=16: a handful of host visits, the
    stream of one step a dispatch."""
    reqs = [("w", [5, 17, 42], SamplingParams(max_tokens=48, ignore_eos=True))]
    geom = dict(num_pages=128, max_pages_per_seq=16)  # room for 51 tokens
    ref = _collect(
        engine_factory(decode_steps=1, overlap_decode=False, **geom), reqs
    )
    eng = engine_factory(decode_steps=16, overlap_decode=False, **geom)
    assert _collect(eng, reqs) == ref
    # 47 decoded tokens: 16 + 16 + 8 + 4 + 2 + 1 at the most
    assert eng.metrics.decode_dispatches <= 6
    assert ("decode_multi", 1, 16) in {k[:3] for k in eng.programs}


# -- (e) preemption by recompute -------------------------------------------


def test_fused_scan_under_preemption(engine_factory):
    """A pool too small for both rows: the scan that cannot pre-grow its
    pages yields to the one-step path, which preempts; the folded row
    re-prefills and rejoins. Streams as with one step a dispatch."""

    def run(k):
        eng = engine_factory(
            decode_steps=k, overlap_decode=False,
            num_pages=12, max_pages_per_seq=8,
        )
        got = _collect(eng, [
            ("p1", [1, 2, 3, 4, 5, 6, 7, 8],
             SamplingParams(max_tokens=16, ignore_eos=True)),
            ("p2", [9, 10, 11, 12, 13, 14, 15, 16],
             SamplingParams(max_tokens=16, ignore_eos=True)),
        ])[0]
        return got, eng

    (ref, _), (got, eng) = run(1), run(8)
    assert got == ref
    assert eng.metrics.preemptions > 0 and _fused(eng)


# -- (f) beside mixed steps: the shape of every benchmark cell -------------


@pytest.mark.parametrize("overlap", [True, False], ids=["ahead", "sync"])
@pytest.mark.parametrize("prompt_len", [6, 21], ids=["one-chunk", "chunked"])
def test_fused_scan_beside_mixed_steps(engine_factory, prompt_len, overlap):
    """A prompt arrives in the middle of a wave of fused scans and is
    prefilled in mixed steps (prefill_chunk is 16): every stream equals
    the loop that neither fuses steps nor mixes."""
    rng = np.random.default_rng(3)
    reqs = [
        ("g", [1, 2, 3], SamplingParams(max_tokens=30, ignore_eos=True)),
        ("s", [4, 5, 6, 7], SamplingParams(
            temperature=0.8, top_p=0.9, seed=9, max_tokens=27,
            ignore_eos=True)),
    ]
    late = (
        "late", [int(x) for x in rng.integers(1, 200, prompt_len)],
        SamplingParams(max_tokens=12, ignore_eos=True),
    )
    geom = dict(num_pages=128, max_pages_per_seq=16)
    ref = _collect(
        engine_factory(mixed_steps=False, decode_steps=1,
                       overlap_decode=False, **geom),
        reqs, late=late,
    )[0]
    eng = engine_factory(
        mixed_steps=True, decode_steps=8, overlap_decode=overlap, **geom
    )
    got = _collect(eng, reqs, late=late)[0]
    assert got == ref
    assert eng.metrics.mixed_dispatches >= (2 if prompt_len > 16 else 1)
    assert _fused(eng)
    if overlap:
        assert eng.metrics.overlap_hits > 0


# -- (g) how many steps a dispatch fuses -----------------------------------


def _decoding(eng, reqs):
    """Admit `reqs` and step until each is a decode row."""
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    while eng.scheduler.waiting or any(
        r.num_computed_tokens < len(r.prompt_tokens)
        for r in eng.scheduler.running
    ):
        eng.step()
    return list(eng.scheduler.running)


_LONG = SamplingParams(max_tokens=64, ignore_eos=True)


def test_pick_yields_to_an_admissible_head_only(engine_factory):
    """One step while a request waits that could be admitted now; the
    whole scan while the one that waits has no slot to take."""
    eng = engine_factory(decode_steps=8, overlap_decode=False, max_seqs=2,
                         num_pages=128, max_pages_per_seq=16)
    rows = _decoding(eng, [("a", [1, 2, 3], _LONG)])
    assert eng._pick_decode_steps(rows) == 8
    eng.add_request("b", [4, 5, 6], _LONG)
    assert eng.scheduler.can_admit_head()
    assert eng._pick_decode_steps(rows) == 1
    rows = _decoding(eng, [])
    eng.add_request("c", [7, 8, 9], _LONG)  # both slots taken
    assert len(rows) == 2 and not eng.scheduler.can_admit_head()
    assert eng._pick_decode_steps(rows) == 8


def test_pick_snaps_down_under_the_context_cap(engine_factory):
    """Room for 6 more tokens under max_pages_per_seq is a scan of 4:
    the program family stays powers of two."""
    eng = engine_factory(decode_steps=8, overlap_decode=False)
    (row,) = _decoding(eng, [("a", list(range(1, 27)), _LONG)])
    cap = eng.config.max_pages_per_seq * eng.config.page_size
    assert min(cap, eng.config.max_context) - row.num_tokens + 1 == 6
    assert eng._pick_decode_steps([row]) == 4


@pytest.mark.parametrize("left, k", [(3, 4), (5, 8), (1, 1)])
def test_pick_rounds_the_longest_completion_up(engine_factory, left, k):
    """The tail of a wave is ONE dispatch: the longest remaining
    completion rounded up to a power of two, overshoot dropped."""
    eng = engine_factory(decode_steps=8, overlap_decode=False)
    rows = _decoding(eng, [
        ("a", [1, 2, 3], SamplingParams(max_tokens=1 + left, ignore_eos=True)),
        ("b", [4, 5, 6], SamplingParams(max_tokens=2, ignore_eos=True)),
    ])
    assert [r.sampling.max_tokens - len(r.output_tokens) for r in rows] == [
        left, 1]
    assert eng._pick_decode_steps(rows) == k


def test_pick_is_one_and_takes_nothing_from_a_dry_pool(engine_factory):
    eng = engine_factory(decode_steps=8, overlap_decode=False)
    rows = _decoding(eng, [("a", [1, 2, 3], _LONG), ("b", [4, 5, 6], _LONG)])
    alloc = eng.allocator
    taken = alloc.allocate(alloc.num_free - 1)  # one page left, two needed
    pages = [list(r.pages) for r in rows]
    assert eng._pick_decode_steps(rows) == 1
    assert [list(r.pages) for r in rows] == pages and alloc.num_free == 1
    alloc.free(taken)
    assert eng._pick_decode_steps(rows) == 8
    assert all(
        len(r.pages) * eng.config.page_size >= r.num_tokens + 7 for r in rows
    )


def test_pick_honours_tokens_still_to_come(engine_factory):
    """Launched ahead of its batch, a dispatch counts what the one on
    the device adds first: against the budget and in the pages."""
    eng = engine_factory(decode_steps=8, overlap_decode=False,
                         num_pages=128, max_pages_per_seq=16)
    (row,) = _decoding(eng, [
        ("a", [1, 2, 3], SamplingParams(max_tokens=13, ignore_eos=True)),
    ])
    assert len(row.output_tokens) == 1  # 12 to go
    assert eng._pick_decode_steps([row], [8]) == 4
    ps = eng.config.page_size
    assert len(row.pages) == -(-(row.num_tokens + 8 + 3) // ps)
    assert eng._pick_decode_steps([row]) == 8


# -- (h)-(i) what a delivery of K tokens looks like to telemetry -----------


def test_slo_spreads_a_delivery_over_its_tokens(engine_factory):
    """Eight tokens delivered at once after 0.8 s are eight gaps of
    0.1 s to the ITL sketch, not one of 0.8 s."""

    class Sketch:
        def __init__(self):
            self.seen = []

        def observe(self, metric, value):
            self.seen.append((metric, value))

    eng = engine_factory(decode_steps=8)
    eng.add_request("o", [1, 2, 3], _LONG)
    req = eng.scheduler.waiting[0]
    eng.slo = Sketch()
    eng._slo_marks[req.request_id] = [None, 0.0, 0, time.perf_counter() - 0.8]
    eng._observe_slo(req, 8, finished=False)
    ((metric, gap_ms),) = eng.slo.seen
    assert metric == "itl_ms" and 100.0 <= gap_ms < 200.0


def test_flight_record_of_a_fused_dispatch(engine_factory):
    """One record a dispatch: the batch's kind, its rows and bucket, and
    K tokens a row in the token delta."""
    eng = engine_factory(decode_steps=8, overlap_decode=False)
    _collect(eng, [
        (f"r{i}", [1 + i, 2, 3], SamplingParams(max_tokens=17, ignore_eos=True))
        for i in range(3)
    ])
    fused = [r for r in eng.flight.snapshot() if r.get("tokens") == 8 * 3]
    assert len(fused) == 2  # 1 at prefill + 8 + 8 = 17 a row
    for rec in fused:
        assert (rec["kind"], rec["n_decode"], rec["b_decode"]) == (
            "decode", 3, 4)
        assert rec["step_ms"] > 0 and rec["sync_ms"] > 0


# -- (j) the on-device window is gone, and stays gone ----------------------


def test_no_window_in_any_export():
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.metrics_service import MetricsService
    from dynamo_tpu.telemetry.flight import _DELTA_FIELDS

    frame = EngineMetrics().to_dict()
    assert not [k for k in frame if "kstep" in k]
    assert not [f for f in EngineMetrics.TIMING_FIELDS if "kstep" in f]
    assert not [f for pair in _DELTA_FIELDS for f in pair if "kstep" in f]
    svc = MetricsService(object())
    frame.update(instance_id="w1", model="tiny", component="backend",
                 role="decode")
    svc.aggregator._latest["w1"] = (frame, time.monotonic())
    for text in (FrontendMetrics().expose(), svc.expose()):
        assert "dynamo_tpu_" in text and "kstep" not in text


def test_config_refuses_the_window():
    with pytest.raises(TypeError, match="decode_kstep"):
        EngineConfig(decode_kstep=2)


def test_cli_refuses_the_window(capsys):
    from dynamo_tpu.cli.run import build_parser

    argv = ["run", "in=http", "out=jax", "--model", "tiny"]
    assert build_parser().parse_args(argv + ["--decode-steps", "8"])
    with pytest.raises(SystemExit):
        build_parser().parse_args(argv + ["--decode-kstep", "8"])
    assert "--decode-kstep" in capsys.readouterr().err
