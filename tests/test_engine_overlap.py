"""Overlapped decode loop (EngineConfig.overlap_decode): the speculative
next-step dispatch with on-device token feedback and one-step-lagged
async readback must produce BIT-IDENTICAL per-request token streams to
the synchronous path, and roll back cleanly whenever the batch changes
underneath it (finish, mid-wave admission, preemption)."""

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams


@pytest.fixture(scope="module")
def engine_factory():
    def make(**overrides):
        base = EngineConfig.for_tests()
        cfg = EngineConfig(**{**base.__dict__, **overrides})
        return JaxEngine(cfg)

    return make


def _mixed_workload():
    """Mixed greedy/sampled requests with stop tokens and staggered
    max_tokens so finishes land mid-wave (the rollback-heavy shape the
    issue's parity criterion names)."""
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(6):
        prompt = [int(x) for x in rng.integers(1, 200, 3 + (i % 4))]
        sampled = i % 2 == 1
        reqs.append(
            (
                f"r{i}",
                prompt,
                SamplingParams(
                    temperature=0.8 if sampled else 0.0,
                    top_p=0.9 if sampled else 1.0,
                    seed=100 + i,
                    max_tokens=4 + 3 * (i % 3),  # 4/7/10: mid-wave length
                    stop_token_ids=(13,) if i in (2, 5) else (),
                ),
            )
        )
    return reqs


def _run(eng, reqs):
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    return eng.run_to_completion()


def test_overlap_parity_mixed_workload(engine_factory):
    """The headline contract: identical per-request streams, overlap on
    vs off, across fused-step depths."""
    reqs = _mixed_workload()
    for k in (1, 2, 8):
        ref = _run(engine_factory(overlap_decode=False, decode_steps=k), reqs)
        eng = engine_factory(overlap_decode=True, decode_steps=k)
        got = _run(eng, reqs)
        assert got == ref, f"decode_steps={k}"
        if k == 1:
            # long k=1 waves are where the pipeline must actually engage
            assert eng.metrics.overlap_hits > 0


def test_overlap_parity_across_decode_steps(engine_factory):
    """Overlapped k=1 must also match synchronous k=8 (the token stream
    is defined by the requests, not the dispatch shape)."""
    reqs = _mixed_workload()
    ref = _run(engine_factory(overlap_decode=False, decode_steps=8), reqs)
    assert _run(engine_factory(overlap_decode=True, decode_steps=1), reqs) == ref


def test_overlap_engages_and_collapses_sync(engine_factory):
    """Steady-state wave: speculation consumed nearly every step, and the
    one-step-lagged readback makes sync cheaper than the blocking path."""
    eng = engine_factory(overlap_decode=True, decode_steps=1)
    eng.add_request("w", [5, 17, 42], SamplingParams(max_tokens=24, ignore_eos=True))
    eng.run_to_completion()
    m = eng.metrics
    assert m.overlap_dispatches > 10
    assert m.overlap_hits == m.overlap_dispatches - m.overlap_rollbacks
    # the phase split is populated (the bench's overlap visibility)
    assert m.time_decode_dispatch_ms > 0 and m.time_decode_host_ms > 0


def test_midwave_prefill_keeps_the_dispatch_ahead(engine_factory):
    """A prompt admitted mid-overlap leaves the decode rows as they
    were: the dispatch launched ahead lands as the decode half beside
    the prefill, the row that joins rides the next one (its first token
    fed from the host), nothing is thrown away, and the streams are
    those of the synchronous engine fed the same arrival order."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, decode_steps=1)
        eng.add_request("a", [1, 2, 3, 4], SamplingParams(max_tokens=12, ignore_eos=True))
        eng.add_request("b", [9, 8, 7], SamplingParams(max_tokens=12, ignore_eos=True))
        out = {}
        steps = 0
        late_added = False
        while eng.has_work:
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
            steps += 1
            if steps == 6 and not late_added:
                # arrives mid-wave: next schedule() admits -> prefill
                eng.add_request(
                    "late", [3, 1, 4, 1, 5],
                    SamplingParams(max_tokens=6, ignore_eos=True),
                )
                late_added = True
        return out, eng.metrics

    ref, _ = run(False)
    got, m = run(True)
    assert got == ref
    assert m.overlap_rollbacks == 0 and m.overlap_hits > 0


def test_rollback_on_finish(engine_factory):
    """A request hitting max_tokens mid-wave changes the batch; survivors
    must continue with identical streams (the speculated dispatch that
    included the finished row is discarded as overshoot)."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, decode_steps=1)
        eng.add_request("short", [1, 2, 3], SamplingParams(max_tokens=3, ignore_eos=True))
        eng.add_request("long", [4, 5, 6], SamplingParams(max_tokens=14, ignore_eos=True))
        return _run(eng, [])

    assert run(True) == run(False)


def test_overlap_under_preemption(engine_factory):
    """Page pressure forces preemption-by-recompute mid-wave; the folded
    request re-prefills and rejoins. Streams must match sync exactly."""

    def run(overlap):
        eng = engine_factory(
            overlap_decode=overlap, decode_steps=1,
            num_pages=12, max_pages_per_seq=8,  # 12 pages DO preempt here
        )
        eng.add_request("p1", [1, 2, 3, 4, 5, 6, 7, 8],
                        SamplingParams(max_tokens=16, ignore_eos=True))
        eng.add_request("p2", [9, 10, 11, 12, 13, 14, 15, 16],
                        SamplingParams(max_tokens=16, ignore_eos=True))
        return _run(eng, [])

    assert run(True) == run(False)


def test_overlap_with_logprobs_and_bias(engine_factory):
    """Logprob reporting and logit_bias ride the speculated dispatch
    (penalties force the sync path); values must match sync."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, decode_steps=1)
        eng.add_request(
            "lp", [5, 6, 7],
            SamplingParams(max_tokens=8, ignore_eos=True, logprobs=2,
                           logit_bias=((3, 5.0),)),
        )
        toks, lps = [], []
        while eng.has_work:
            for o in eng.step():
                toks.extend(o.new_token_ids)
                if o.logprobs:
                    lps.extend(o.logprobs)
        return toks, lps

    assert run(True) == run(False)


def test_penalties_fall_back_to_sync(engine_factory):
    """Penalty history needs the pending step's tokens host-side, so the
    engine must not speculate — and streams still match."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, decode_steps=1)
        eng.add_request(
            "pen", [5, 6, 7],
            SamplingParams(max_tokens=8, ignore_eos=True,
                           repetition_penalty=1.5),
        )
        out = _run(eng, [])
        return out, eng.metrics.overlap_dispatches

    (ref, _), (got, n_spec) = run(False), run(True)
    assert got == ref
    assert n_spec == 0


def test_abort_mid_overlap(engine_factory):
    """Aborting a request between steps invalidates the speculation via
    the identity check; the survivor's stream is unaffected."""
    eng = engine_factory(overlap_decode=True, decode_steps=1)
    eng.add_request("keep", [1, 2, 3], SamplingParams(max_tokens=10, ignore_eos=True))
    eng.add_request("kill", [7, 8, 9], SamplingParams(max_tokens=10, ignore_eos=True))
    out = {}
    steps = 0
    while eng.has_work:
        for o in eng.step():
            out.setdefault(o.request_id, []).extend(o.new_token_ids)
        steps += 1
        if steps == 4:
            assert eng.abort_request("kill")
    solo = engine_factory(overlap_decode=False, decode_steps=1)
    solo.add_request("keep", [1, 2, 3], SamplingParams(max_tokens=10, ignore_eos=True))
    assert solo.run_to_completion()["keep"] == out["keep"]


def test_drain_overlap_is_idempotent(engine_factory):
    eng = engine_factory(overlap_decode=True, decode_steps=1)
    eng.drain_overlap()  # nothing in flight: no-op
    eng.add_request("d", [1, 2, 3], SamplingParams(max_tokens=6, ignore_eos=True))
    toks = []
    for _ in range(2):  # prefill, then first decode + speculation
        for o in eng.step():
            toks.extend(o.new_token_ids)
    assert eng._inflight is not None
    eng.drain_overlap()
    assert eng._inflight is None
    assert eng.metrics.overlap_rollbacks == 1
    # the wave still completes correctly after a forced drain
    toks.extend(eng.run_to_completion()["d"])
    ref = engine_factory(overlap_decode=False)
    ref.add_request("d", [1, 2, 3], SamplingParams(max_tokens=6, ignore_eos=True))
    assert toks == ref.run_to_completion()["d"]


# -- a dispatch in flight across an admission (ISSUE 28) -------------------
#
# More requests than slots and every end a max_tokens end: the engine
# launches, behind a dispatch still on the device, the batch schedule()
# WILL return: the row certain to end gone, its successor admitted (its
# first piece in a mixed step), a prompt's last piece joined.


def _queue_workload(n: int, prompt_len: int, sampled_every: int = 2):
    """`n` requests, `prompt_len` +- 2 prompt tokens, staggered budgets;
    every `sampled_every`-th is seeded-sampled, the rest greedy; none
    can stop early, so every end is one the host can know ahead."""
    rng = np.random.default_rng(23)
    reqs = []
    for i in range(n):
        plen = prompt_len + int(rng.integers(-2, 3))
        sampled = i % sampled_every == 1
        reqs.append(
            (
                f"q{i}",
                [int(x) for x in rng.integers(1, 200, plen)],
                SamplingParams(
                    temperature=0.8 if sampled else 0.0,
                    top_p=0.9 if sampled else 1.0,
                    seed=300 + i,
                    max_tokens=5 + 2 * (i % 4),
                    ignore_eos=True,
                ),
            )
        )
    return reqs


_QUEUE = dict(
    max_seqs=4, decode_buckets=(1, 2, 4), num_pages=128,
    max_pages_per_seq=16,
)


def _steps(eng, reqs, between=None):
    """Run to completion step by step; returns (streams, per-step
    emissions). `between(eng, step_no)` runs after every step."""
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    streams, log, n = {}, [], 0
    while eng.has_work:
        outs = eng.step()
        for o in outs:
            streams.setdefault(o.request_id, []).extend(o.new_token_ids)
        log.append([
            (o.request_id, o.new_token_ids, o.finish_reason) for o in outs
        ])
        n += 1
        if between is not None:
            between(eng, n)
    return streams, log


@pytest.mark.parametrize("prompt_len", [6, 21], ids=["one-chunk", "chunked"])
@pytest.mark.parametrize("decode_steps", [1, 4])
def test_admission_parity(engine_factory, prompt_len, decode_steps):
    """Twice the requests the slots hold, max_tokens ends: greedy and
    seeded-sampled streams equal the synchronous loop's, for prompts of
    one chunk and of two (prefill_chunk is 16)."""
    reqs = _queue_workload(8, prompt_len)
    ref, _ = _steps(
        engine_factory(overlap_decode=False, decode_steps=decode_steps,
                       **_QUEUE), reqs)
    eng = engine_factory(
        overlap_decode=True, decode_steps=decode_steps, **_QUEUE)
    got, _ = _steps(eng, reqs)
    assert got == ref
    assert all(len(got[rid]) == s.max_tokens for rid, _, s in reqs)
    assert eng.metrics.overlap_rollbacks == 0


@pytest.mark.parametrize("prompt_len", [6, 21], ids=["one-chunk", "chunked"])
def test_queue_never_empty_across_admissions(engine_factory, prompt_len):
    """Engagement: while requests wait for a slot, no decode-carrying
    dispatch after the first is launched with the queue empty: every
    decode or mixed step was on the device before its batch was
    scheduled, but the first and the one behind the burst's last pure
    prefill (a step with no decode row launches nothing ahead). Once
    nobody waits, nothing is launched behind a dispatch that ends a row
    (whoever takes the slot must not wait behind it): the tail is not
    counted here."""
    eng = engine_factory(overlap_decode=True, decode_steps=1, **_QUEUE)
    seen = {}

    def while_queued(e, _n):
        if e.scheduler.waiting:
            seen.update(e.metrics.to_dict())

    _steps(eng, _queue_workload(12, prompt_len), between=while_queued)
    assert seen["mixed_dispatches"] >= 6  # the admissions themselves
    assert seen["overlap_hits"] >= (
        seen["decode_dispatches"] + seen["mixed_dispatches"] - 2)
    m = eng.metrics
    assert m.overlap_rollbacks == 0
    # every dispatch launched ahead was the batch that came
    assert m.overlap_dispatches == m.overlap_hits


def test_successor_rides_the_dispatch_after_its_predecessors_last(
    engine_factory,
):
    """The step that reads a row's last token is followed, with nothing
    in between, by the step that reads its successor's first: step for
    step the overlapped loop emits what the synchronous loop emits, so
    no request is admitted a dispatch later (or out of order)."""
    reqs = _queue_workload(8, 6)
    _, ref_log = _steps(
        engine_factory(overlap_decode=False, decode_steps=1, **_QUEUE), reqs)
    eng = engine_factory(overlap_decode=True, decode_steps=1, **_QUEUE)
    _, log = _steps(eng, reqs)
    assert log == ref_log
    first = {}
    last = {}
    for i, outs in enumerate(log):
        for rid, toks, fin in outs:
            first.setdefault(rid, i)
            if fin is not None:
                last[rid] = i
    # q4..q7 wait for a slot; each rides the step right after some end
    for rid in ("q4", "q5", "q6", "q7"):
        assert first[rid] - 1 in last.values(), (rid, first, last)


def _until(eng, cond, limit=200):
    """Step until `cond(eng)` holds right after a step."""
    streams = {}
    for _ in range(limit):
        for o in eng.step():
            streams.setdefault(o.request_id, []).extend(o.new_token_ids)
        if cond(eng):
            return streams
    raise AssertionError("condition never held")


def _three(eng):
    eng.add_request("lead", [1, 2, 3, 4], SamplingParams(max_tokens=4, ignore_eos=True))
    eng.add_request("keep", [5, 6, 7], SamplingParams(max_tokens=14, ignore_eos=True))
    eng.add_request("next", [9, 8, 7, 6, 5], SamplingParams(max_tokens=6, ignore_eos=True))


_PAIR = dict(max_seqs=2, decode_buckets=(1, 2), decode_steps=1)


def _finish(eng, streams):
    while eng.has_work:
        for o in eng.step():
            streams.setdefault(o.request_id, []).extend(o.new_token_ids)
    return streams


def _early_admitted(eng):
    """`next` admitted ahead of its slot: a mixed step is on the device
    with its first piece while `lead` has just ended."""
    infl = eng._inflight
    return (
        infl is not None and infl.pieces
        and infl.pieces[0].request.request_id == "next"
    )


@pytest.mark.parametrize("event", ["abort-leaver", "abort-successor",
                                   "preempt"])
def test_event_between_admission_and_consume(engine_factory, event):
    """An abort of the row about to end, an abort of the successor
    admitted early, and a preemption between that admission and the
    consume each cost exactly one rollback, and leave the scheduler and
    the allocator as the synchronous loop's: no page leaked, no row too
    many, the other streams untouched."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, **_PAIR)
        usable = eng.allocator.num_free
        _three(eng)
        sched = eng.scheduler
        if event == "abort-leaver":
            # the dispatch on the device still carries `lead`: one token
            # short of its budget, so the NEXT launch ahead would drop it
            s = _until(eng, lambda e: any(
                r.request_id == "lead" and len(r.output_tokens) == 2
                for r in sched.running))
            assert eng.abort_request("lead")
        else:
            # `lead` has just ended; overlapped, `next` is admitted
            # already and its first piece is on the device
            s = _until(eng, lambda e: all(
                r.request_id != "lead" for r in sched.running))
            if overlap:
                assert _early_admitted(eng)
                assert [r.request_id for r in sched.running] == ["keep", "next"]
            if event == "abort-successor":
                assert eng.abort_request("next")
            else:
                keep = next(r for r in sched.running if r.request_id == "keep")
                assert sched._preempt_youngest(excluding=None)
                assert keep in sched.waiting
        rb0 = eng.metrics.overlap_rollbacks
        for o in eng.step():
            s.setdefault(o.request_id, []).extend(o.new_token_ids)
        state = (
            len(sched.running), len(sched.waiting), eng.allocator.num_free
        )
        assert len(sched.running) <= 2
        rolled = eng.metrics.overlap_rollbacks - rb0
        _finish(eng, s)
        assert eng.allocator.num_free == usable and not sched.running
        return s, state, rolled

    ref, ref_state, _ = run(False)
    got, state, rolled = run(True)
    assert rolled == 1
    assert state == ref_state
    assert got == ref


def test_small_pool_falls_back(engine_factory):
    """Where the free pool cannot hold the successor without the pages
    of the row about to end (they are still being written), nobody is
    admitted early and nothing is launched behind that dispatch: the
    old path, the same streams."""
    cfg = dict(_PAIR, num_pages=6)  # 5 usable pages of 4 tokens

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, **cfg)
        for rid, prompt, n in (("lead", [1, 2, 3, 4], 4), ("keep", [5, 6, 7], 6),
                               ("next", [9, 8, 7, 6, 5], 3)):
            eng.add_request(
                rid, prompt, SamplingParams(max_tokens=n, ignore_eos=True))
        sched = eng.scheduler
        s = _until(eng, lambda e: all(
            r.request_id != "lead" for r in sched.running))
        # lead's two pages came back only now; `next` needs two
        early = [r.request_id for r in sched.running]
        infl = eng._inflight
        return _finish(eng, s), early, infl, eng.metrics

    ref, _, _, _ = run(False)
    got, early, infl, m = run(True)
    assert early == ["keep"] and infl is None
    assert got == ref and m.overlap_rollbacks == 0 and m.overlap_hits > 0


# -- takers of free slots (PR 30: a step that ends about when a client is
# back must not decide the batches by the millisecond) --------------------


def test_launch_ahead_behind_a_mixed_step_with_a_leaver(engine_factory):
    """A row ends in a fused mixed step and nobody waits for its slot:
    the next decode dispatch is launched ahead all the same (the rows
    that stay and the prompt that joined), so the device stays covered
    while the runner waits for the slot's taker; a pure decode dispatch
    with a leaver still launches nothing. Streams are the synchronous
    engine's."""

    def run(overlap):
        eng = engine_factory(overlap_decode=overlap, decode_steps=1, max_seqs=3)
        for rid, prompt, n in (("a", [1, 2, 3], 2), ("short", [4, 5, 6], 3),
                               ("long", [7, 8, 9, 1], 12)):
            eng.add_request(
                rid, prompt, SamplingParams(max_tokens=n, ignore_eos=True))
        out, seen = {}, []
        for step in range(3):
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
            infl = eng._inflight
            seen.append(None if infl is None else
                        tuple(r.request_id for r in infl.reqs))
            if step == 1:  # `a` is gone, its slot free, nobody waited
                eng.add_request(
                    "new", [2, 7, 1, 8],
                    SamplingParams(max_tokens=5, ignore_eos=True))
        while eng.has_work:
            for o in eng.step():
                out.setdefault(o.request_id, []).extend(o.new_token_ids)
        return out, seen, eng.metrics

    ref, _, _ = run(False)
    got, seen, m = run(True)
    assert got == ref
    # after the decode step in which `a` ended: nothing launched ahead;
    # after the mixed step in which `short` ended: long and new are
    assert seen[1] is None and seen[2] == ("long", "new")
    assert m.mixed_dispatches >= 1 and m.overlap_rollbacks == 0


class _Landed:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


def test_takers_wait_s(engine_factory):
    """An allowance only while a fused scan launched ahead is still on
    the device and a free slot has no taker: 3/4 of a decode dispatch."""
    import dataclasses

    eng = engine_factory(overlap_decode=True, decode_steps=8, max_seqs=4)
    for rid, prompt in (("a", [1, 2, 3]), ("b", [4, 5, 6, 7])):
        eng.add_request(rid, prompt, SamplingParams(max_tokens=40, ignore_eos=True))
    assert eng.takers_wait_s() == 0.0  # nothing launched
    while eng._inflight is None or eng._inflight.k_steps < 2:
        eng.step()
    assert eng._decode_wall_s > 0.0  # a decode dispatch was timed
    infl = eng._inflight
    eng._inflight = dataclasses.replace(infl, token_ids=_Landed(False))
    eng._decode_wall_s = 0.1
    assert eng.takers_wait_s() == pytest.approx(0.075)  # two slots free
    assert eng.takers_wait_s(queued=1) == pytest.approx(0.075)
    assert eng.takers_wait_s(queued=2) == 0.0  # both takers are in
    eng.add_request("c", [9, 9], SamplingParams(max_tokens=4))
    assert eng.takers_wait_s(queued=1) == 0.0  # c waits for the other
    eng._inflight = dataclasses.replace(infl, token_ids=_Landed(True))
    assert eng.takers_wait_s() == 0.0  # landed: the device would idle
    eng._inflight = dataclasses.replace(
        infl, token_ids=_Landed(False), k_steps=1)
    assert eng.takers_wait_s() == 0.0  # one token: over too soon
    eng._inflight = infl
    # a dispatch read late does not pass for a short one
    eng._decode_wall_s = 0.1
    eng.step()
    assert eng._decode_wall_s >= 0.09
    eng.run_to_completion()


@pytest.mark.parametrize(
    "room, queued, arrives_ms, lands_ms, allowed, waits",
    [
        (0, 0, None, None, 1.0, 0.0),   # no free slot, or nothing ahead
        (1, 1, None, None, 1.0, 0.0),   # the taker is in the inbox
        (1, 0, None, None, 1.0, 0.2),   # nobody comes: the cap
        (2, 1, None, None, 1.0, 0.2),   # one of two takers: the cap
        (1, 0, None, None, 0.08, 0.08),  # the engine allows less
        (1, 0, 15, None, 1.0, 0.0),     # the taker arrives: at once
        (1, 0, None, 15, 1.0, 0.0),     # the dispatch lands: at once
    ],
    ids=["no-room", "taker-queued", "cap", "one-of-two", "allowance",
         "arrival", "landed"],
)
def test_runner_awaits_takers(monkeypatch, room, queued, arrives_ms,
                              lands_ms, allowed, waits):
    """The runner holds a step back only for takers of free slots under
    a dispatch launched ahead, no longer than the engine allows and no
    longer than TAKERS_WAIT_S."""
    import threading
    import time

    from dynamo_tpu.engine import async_engine

    monkeypatch.setattr(async_engine, "TAKERS_WAIT_S", 0.2)
    state = {"room": room}

    class Eng:
        def takers_wait_s(self, queued):
            return allowed if state["room"] > queued else 0.0

    runner = async_engine.AsyncEngineRunner(Eng())
    runner._pending = [("req", None)] * queued

    def later(ms, fn):
        def go():
            time.sleep(ms / 1000.0)
            fn()
            runner._wake.set()
        threading.Thread(target=go, daemon=True).start()

    if arrives_ms is not None:
        def arrive():
            with runner._lock:
                runner._pending.append(("req", None))
        later(arrives_ms, arrive)
    if lands_ms is not None:
        later(lands_ms, lambda: state.update(room=0))
    t = time.perf_counter()
    runner._await_takers()
    dt = time.perf_counter() - t
    if waits:
        assert waits - 0.02 <= dt < waits + 0.5
    else:
        assert dt < 0.07
    # an engine without the hook (a test double) is never waited on
    t = time.perf_counter()
    async_engine.AsyncEngineRunner(object())._await_takers()
    assert time.perf_counter() - t < 0.05


# -- a model with state-space layers: a rolled-back dispatch must not -------
# -- advance a surviving row's recurrent state (docs/engine.md) -------------

_HYBRID = dict(
    model="nemotron-h-tiny", num_pages=256, max_pages_per_seq=32,
    prefill_chunk=16, max_seqs=4, decode_buckets=(1, 2, 4),
)


def _drive(eng, reqs, events=None):
    """Run to completion; `events[n]` is called after step n. Per request
    (tokens, logprobs)."""
    for rid, prompt, s in reqs:
        eng.add_request(rid, prompt, s)
    toks, lps, steps = {}, {}, 0
    while eng.has_work:
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
            lps.setdefault(o.request_id, []).extend(o.logprobs or ())
        steps += 1
        if events and steps in events:
            events[steps](eng)
    return toks, lps


def _hybrid_reqs(n=3, max_tokens=20, prompt=9, **kw):
    rng = np.random.default_rng(11)
    return [
        (f"h{i}", [int(x) for x in rng.integers(3, 250, prompt + 3 * i)],
         SamplingParams(max_tokens=max_tokens + 2 * i, ignore_eos=True,
                        logprobs=0, **kw))
        for i in range(n)
    ]


def _same_streams(got, ref, only=None):
    """Tokens equal; log-probs to 1e-4 (float32: a row's matmul sums may
    be ordered by how many rows share the matmul, `_run_mixed`)."""
    for rid in only or ref[0]:
        assert got[0][rid] == ref[0][rid], rid
        np.testing.assert_allclose(
            got[1][rid], ref[1][rid], atol=1e-4, err_msg=rid)


def _in_place(monkeypatch):
    """The build this PR must not be: a dispatch launched ahead updates a
    row's state where it is read, and nothing is committed."""
    def rows(self, pt, reqs):
        out = np.zeros((pt.shape[0], 2), np.int32)
        for i, r in enumerate(reqs):
            out[i] = r.state_slot
        return (pt, out)

    monkeypatch.setattr(JaxEngine, "_row_tables", rows)
    monkeypatch.setattr(JaxEngine, "_commit_state", lambda self, reqs: None)


@pytest.mark.parametrize("decode_steps", [1, 4])
@pytest.mark.parametrize("event", ["abort", "preempt", "sampled-stop"])
def test_hybrid_rollback_leaves_surviving_state_untouched(
        engine_factory, event, decode_steps):
    """Launch-ahead on, a model with Mamba-2 layers: an abort of a
    neighbour, a preemption, and a stop nobody foresaw each roll back a
    dispatch that had already advanced every row's recurrent state by
    1-4 tokens. The surviving rows' tokens AND log-probs are the
    synchronous loop's."""
    reqs = _hybrid_reqs()
    solo = _drive(
        engine_factory(**_HYBRID, overlap_decode=False,
                       decode_steps=decode_steps), reqs)
    events, only = None, None
    if event == "abort":
        events = {5: lambda e: e.abort_request("h1")}
        only = ["h0", "h2"]
    elif event == "preempt":
        events = {5: lambda e: e.scheduler._preempt_youngest(excluding=None)}
    else:  # h1 stops on a token it samples mid-wave
        reqs[1] = (reqs[1][0], reqs[1][1], SamplingParams(
            max_tokens=22, logprobs=0, stop_token_ids=(solo[0]["h1"][6],)))
    ref = solo
    if event != "abort":  # the event is part of what the rows compute
        ref = _drive(engine_factory(**_HYBRID, overlap_decode=False,
                                    decode_steps=decode_steps), reqs, events)
    eng = engine_factory(**_HYBRID, overlap_decode=True,
                         decode_steps=decode_steps)
    got = _drive(eng, reqs, events)
    m = eng.metrics
    assert m.overlap_rollbacks > 0 and m.state_restores > 0
    assert m.overlap_hits > 0
    _same_streams(got, ref, only)
    assert eng.allocator.num_free_slots == eng.allocator.state_slots


def test_hybrid_rolled_back_mixed_step_runs_its_chunk_again(engine_factory):
    """A mixed step launched ahead carries the next chunk of a long
    prompt beside the decode rows; an abort rolls it back after it has
    advanced the prompt's slot by a chunk. The chunk runs again from the
    state the last taken dispatch left, and the stream is the
    synchronous loop's."""
    rng = np.random.default_rng(5)
    reqs = _hybrid_reqs(n=2, max_tokens=30)
    long = ("long", [int(x) for x in rng.integers(3, 250, 75)],
            SamplingParams(max_tokens=8, ignore_eos=True, logprobs=0))

    def run(overlap):
        eng = engine_factory(**_HYBRID, overlap_decode=overlap,
                             decode_steps=1)
        seen = {"rolled_mixed": 0}

        def late(e):
            e.add_request(*long)

        def watch_abort(e):
            infl = e._inflight
            if infl is not None and infl.pieces:
                seen["rolled_mixed"] += 1
            e.abort_request("h0")

        out = _drive(eng, reqs, {4: late, 7: watch_abort})
        return out, eng.metrics, seen

    ref, _, _ = run(False)
    got, m, seen = run(True)
    assert seen["rolled_mixed"] == 1  # the dispatch rolled back was mixed
    assert m.overlap_rollbacks > 0 and m.state_restores > 0
    _same_streams(got, ref, ["long", "h1"])


def test_hybrid_rollback_test_fails_on_an_in_place_build(
        engine_factory, monkeypatch):
    """The control of the tests above: with the state updated where it is
    read and no commit, the same abort leaves the survivors' streams
    wrong (their state advanced twice)."""
    reqs = _hybrid_reqs()
    ref = _drive(engine_factory(**_HYBRID, overlap_decode=False,
                                decode_steps=4), reqs)
    _in_place(monkeypatch)
    eng = engine_factory(**_HYBRID, overlap_decode=True, decode_steps=4)
    got = _drive(eng, reqs, {5: lambda e: e.abort_request("h1")})
    assert eng.metrics.overlap_rollbacks > 0
    wrong = [
        rid for rid in ("h0", "h2")
        if got[0][rid] != ref[0][rid]
        or not np.allclose(got[1][rid], ref[1][rid], atol=1e-3)
    ]
    assert wrong
