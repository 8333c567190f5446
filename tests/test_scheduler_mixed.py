"""Mixed-schedule property test (ISSUE 5): randomized arrivals, finishes
and page-pressure preemptions driven through a pure-scheduler simulation
(no model, no device). The mixed schedule must preserve exactly what the
XOR schedule guarantees — per-request token order, sequential prefill
chunks, and page accounting — while actually interleaving decode progress
into prefill backlogs (the property XOR cannot have)."""

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.engine.request import Request, RequestState, SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler


def _cfg(mixed: bool) -> EngineConfig:
    return EngineConfig(
        model="tiny", num_pages=16, page_size=4, max_pages_per_seq=8,
        decode_buckets=(1, 2, 4, 8), prefill_chunk=8, max_seqs=6,
        admission_watermark=0.0, dtype="float32",
        enable_prefix_caching=False, mixed_steps=mixed,
    )


def _check_page_accounting(s: Scheduler, alloc: PageAllocator, usable: int):
    """No page is owned twice, and every page is either owned or free."""
    live_pages = []
    for r in s.running:
        live_pages.extend(r.pages)
    assert len(live_pages) == len(set(live_pages)), "page owned twice"
    assert 0 not in live_pages, "null page handed to a request"
    assert alloc.num_free + len(live_pages) == usable, (
        f"leak: free={alloc.num_free} live={len(live_pages)} "
        f"usable={usable}"
    )


def _simulate(mixed: bool, seed: int, steps: int = 500):
    """Drive the scheduler the way the engine does, with deterministic
    'tokens' (the per-request emission index) so order is checkable."""
    cfg = _cfg(mixed)
    alloc = PageAllocator(cfg.num_pages, cfg.page_size)
    s = Scheduler(cfg, alloc)
    usable = alloc.num_free
    rng = np.random.default_rng(seed)
    emissions: dict[str, list[int]] = {}
    budgets: dict[str, int] = {}
    was_decode: set[str] = set()
    arrivals = 0
    stats = {"mixed": 0, "decode_during_backlog": 0, "preemptions": 0}

    def emit(req: Request):
        idx = req.num_emitted + len(req.output_tokens)
        req.output_tokens.append(idx)
        emissions.setdefault(req.request_id, []).append(idx)
        if idx + 1 >= req.sampling.max_tokens:
            s.finish(req)

    for _ in range(steps):
        if arrivals < 30 and rng.random() < 0.3:
            rid = f"r{arrivals}"
            plen = int(rng.integers(1, 20))
            req = Request(
                request_id=rid,
                prompt_tokens=list(range(1, plen + 1)),
                sampling=SamplingParams(max_tokens=int(rng.integers(1, 12))),
            )
            s.add_request(req)
            budgets[rid] = req.sampling.max_tokens
            arrivals += 1
        preempted_before = {
            r.request_id for r in s.waiting if r.request_id in was_decode
        }
        batch = s.schedule()
        assert not s.doomed, f"doomed under seed {seed}: {s.doomed}"
        preempted_after = {
            r.request_id for r in s.waiting if r.request_id in was_decode
        }
        stats["preemptions"] += len(preempted_after - preempted_before)
        _check_page_accounting(s, alloc, usable)
        if batch is None:
            if arrivals >= 30 and not s.has_work:
                break
            continue
        if batch.kind == "mixed":
            stats["mixed"] += 1
        # prefill half: chunks must be sequential and page-backed
        for piece in batch.prefill:
            req = piece.request
            assert piece.start == req.num_computed_tokens, "chunk skipped"
            assert piece.length >= 1
            assert len(req.pages) * cfg.page_size >= (
                piece.start + piece.length
            ), "prefill chunk writes past its pages"
            req.num_computed_tokens += piece.length
            if req.prefill_done:
                req.state = RequestState.DECODE
                was_decode.add(req.request_id)
                emit(req)
        # decode half: one token per row, pages already grown
        backlog = any(
            r.state == RequestState.PREFILL for r in s.running
        )
        for req in batch.decode:
            assert req.state == RequestState.DECODE
            assert len(req.pages) * cfg.page_size >= req.num_tokens, (
                "decode writes past its pages"
            )
            req.num_computed_tokens += 1
            emit(req)
            if backlog:
                stats["decode_during_backlog"] += 1
    assert not s.has_work, f"work left after {steps} steps (seed {seed})"
    assert alloc.num_free == usable, "pages leaked at drain"
    return emissions, budgets, stats


@pytest.mark.parametrize("seed", [3, 11, 29, 47])
def test_mixed_schedule_preserves_order_and_pages(seed):
    """The property: under randomized arrivals/finishes/preemptions the
    mixed schedule emits every request's tokens 0..max_tokens-1 exactly
    once, in order (preemption-by-recompute folds included), with page
    accounting clean at every step — identical guarantees to XOR — AND
    decode rows actually progress while a prefill backlog exists."""
    xor_em, xor_budget, xor_stats = _simulate(False, seed)
    mix_em, mix_budget, mix_stats = _simulate(True, seed)
    # identical arrival stream => identical final streams
    assert mix_em == xor_em
    for rid, toks in mix_em.items():
        assert toks == list(range(mix_budget[rid])), rid
    assert mix_stats["mixed"] > 0
    # the stall-free property itself: decode progressed during backlog
    assert mix_stats["decode_during_backlog"] > 0
    # XOR by construction cannot interleave (prefill has priority)
    assert xor_stats["mixed"] == 0 and xor_stats["decode_during_backlog"] == 0


def test_preemption_happens_under_pressure():
    """The property test must actually cover preemption-by-recompute:
    at least one seed preempts (otherwise the claim above is vacuous)."""
    total = 0
    for seed in (3, 11, 29, 47):
        for mixed in (True, False):
            _, _, stats = _simulate(mixed, seed)
            total += stats["preemptions"]
    assert total >= 1


def test_mixed_piece_cap_keeps_combined_rows_in_family():
    """Adaptive budget clamp (satellite): with running decodes, a grown
    prefill budget may never pack more pieces than the decode bucket
    family admits for the combined row space."""
    cfg = EngineConfig(
        model="tiny", num_pages=128, page_size=4, max_pages_per_seq=8,
        decode_buckets=(1, 2, 4), prefill_chunk=8, max_seqs=16,
        prefill_token_budget=8, prefill_budget_policy="adaptive",
        prefill_budget_max=96, admission_watermark=0.0, dtype="float32",
        enable_prefix_caching=False, mixed_steps=True,
    )
    alloc = PageAllocator(cfg.num_pages, cfg.page_size)
    s = Scheduler(cfg, alloc)
    # two decoding requests
    for i in range(2):
        r = Request(
            request_id=f"d{i}", prompt_tokens=[1, 2, 3],
            sampling=SamplingParams(max_tokens=32),
        )
        s.add_request(r)
    batch = s.schedule()
    for piece in batch.prefill:
        piece.request.num_computed_tokens += piece.length
        piece.request.state = RequestState.DECODE
        piece.request.output_tokens.append(0)
    # now a burst of short prompts: the adaptive budget would pack many
    # pieces, but the mixed row cap (bucket[-1]=4 minus 2 decodables)
    # must bound the piece count
    for i in range(8):
        r = Request(
            request_id=f"p{i}", prompt_tokens=[1, 2, 3, 4, 5],
            sampling=SamplingParams(max_tokens=4),
        )
        s.add_request(r)
    batch = s.schedule()
    assert batch is not None and batch.kind == "mixed"
    assert len(batch.prefill) <= 2  # 4 (bucket cap) - 2 decodables
    assert len(batch.prefill) + len(batch.decode) <= cfg.decode_buckets[-1]


# -- the batch that comes next (Scheduler.next_batch, ISSUE 28) -----------


def _sched(**over):
    cfg = EngineConfig(**{**_cfg(True).__dict__, **over})
    alloc = PageAllocator(cfg.num_pages, cfg.page_size)
    return Scheduler(cfg, alloc), alloc


def _req(rid: str, plen: int, max_tokens: int) -> Request:
    return Request(
        request_id=rid, prompt_tokens=list(range(1, plen + 1)),
        sampling=SamplingParams(max_tokens=max_tokens),
    )


def _apply(batch, s: Scheduler) -> None:
    """What the engine does with a batch once its ids are read: pieces
    computed (a completed prompt samples its first token), one token a
    decode row, a request at its budget finished."""
    def emit(req):
        req.output_tokens.append(7)
        if len(req.output_tokens) + req.num_emitted >= req.sampling.max_tokens:
            s.finish(req)

    for piece in batch.prefill:
        req = piece.request
        req.num_computed_tokens += piece.length
        if req.prefill_done:
            req.state = RequestState.DECODE
            emit(req)
    for req in batch.decode:
        req.num_computed_tokens += 1
        emit(req)


def _same(a, b) -> bool:
    return (
        a is not None and b is not None and a.kind == b.kind
        and len(a.decode) == len(b.decode)
        and all(x is y for x, y in zip(a.decode, b.decode))
        and [(p.request.request_id, p.start, p.length) for p in a.prefill]
        == [(p.request.request_id, p.start, p.length) for p in b.prefill]
    )


def test_next_batch_admits_the_successor_beside_the_leavers_pages():
    """The early admission's slot and page arithmetic: the row certain
    to end counts as gone for the slot, its pages do not count as free;
    the successor's pages come from the pool as it stands and the batch
    is the mixed step `schedule()` then returns."""
    s, alloc = _sched(max_seqs=2, num_pages=16)
    short, long_, nxt = _req("short", 6, 3), _req("long", 6, 9), _req("n", 5, 4)
    for r in (short, long_, nxt):
        s.add_request(r)
    _apply(s.schedule(), s)  # both prompts; "n" waits for a slot
    _apply(s.schedule(), s)  # short has 2 of its 3 tokens
    batch = s.schedule()
    assert batch.kind == "decode" and s.waiting == [nxt]
    free0, held = alloc.num_free, list(short.pages)
    assert s.ends_within(short, 1) and not s.ends_within(long_, 1)
    ahead = s.next_batch(batch.decode, 1)
    # admitted now: one row more than the slots, briefly; its two pages
    # (5 + 1 tokens) came from the free pool, short's are still its own
    assert s.waiting == [] and s.running == [short, long_, nxt]
    assert nxt.state == RequestState.PREFILL and len(nxt.pages) == 2
    assert alloc.num_free == free0 - 2 and short.pages == held
    assert ahead.kind == "mixed" and ahead.decode == (long_,)
    assert [(p.request, p.start, p.length) for p in ahead.prefill] == [
        (nxt, 0, 5)
    ]
    _apply(batch, s)  # short ends, its pages return
    assert s.running == [long_, nxt]
    assert _same(s.schedule(), ahead)
    # the piece completes the prompt: "n" joins at its place in running
    joined = s.next_batch(ahead.decode, 1, ahead.prefill)
    assert joined.kind == "decode" and joined.decode == (long_, nxt)
    _apply(ahead, s)
    assert _same(s.schedule(), joined)


def test_next_batch_unknown_where_the_pool_needs_the_leavers_pages():
    """No early admission the pool cannot pay without the pages that
    are still being written: next_batch says it cannot know the batch,
    admits nobody, and schedule() admits at its old place."""
    s, alloc = _sched(max_seqs=2, num_pages=6)  # 5 usable pages
    short, long_, nxt = _req("short", 6, 3), _req("long", 6, 9), _req("n", 5, 4)
    for r in (short, long_, nxt):
        s.add_request(r)
    for _ in range(2):
        _apply(s.schedule(), s)
    batch = s.schedule()
    assert alloc.num_free < 2  # "n" needs 2 pages
    assert s.next_batch(batch.decode, 1) is None
    assert s.waiting == [nxt] and s.running == [short, long_]
    _apply(batch, s)
    after = s.schedule()
    assert after.kind == "mixed" and after.prefill[0].request is nxt


def test_next_batch_unknown_where_a_freed_slot_stays_empty():
    """A row ends and nobody waits for its slot: whoever arrives next
    takes it at the next schedule(), so no batch is named (the engine
    launches nothing behind that dispatch, and the arrival's first
    chunk does not wait behind one). With the slots still full, or
    nothing ending, the batch is named."""
    s, _ = _sched(max_seqs=2, num_pages=16)
    short, long_ = _req("short", 6, 3), _req("long", 6, 9)
    for r in (short, long_):
        s.add_request(r)
    _apply(s.schedule(), s)
    batch = s.schedule()
    same = s.next_batch(batch.decode, 1)  # nothing ends in this one
    assert same.kind == "decode" and same.decode == (short, long_)
    _apply(batch, s)
    batch = s.schedule()
    assert s.ends_within(short, 1)
    assert s.next_batch(batch.decode, 1) is None
    _apply(batch, s)
    assert s.schedule().decode == (long_,)


def test_next_batch_behind_a_mixed_step_is_named_with_a_slot_left_empty():
    """A row ends in a MIXED step and nobody waits for its slot: the
    batch is named all the same (the rows that stay, the prompt that
    joins): one token a row is over before a client is back, and the
    runner waits for the takers under the dispatch launched ahead
    (`AsyncEngineRunner._await_takers`). The batch schedule() returns
    once the step is read is that batch."""
    s, _ = _sched(max_seqs=3, num_pages=16)
    short, long_ = _req("short", 6, 3), _req("long", 6, 9)
    for r in (short, long_):
        s.add_request(r)
    _apply(s.schedule(), s)
    _apply(s.schedule(), s)
    new = _req("new", 4, 5)
    s.add_request(new)
    batch = s.schedule()
    assert batch.kind == "mixed" and batch.prefill[0].request is new
    assert s.ends_within(short, 1)
    # a pure decode step with the same leaver names nothing
    assert s.next_batch(batch.decode, 1) is None
    ahead = s.next_batch(batch.decode, 1, batch.prefill)
    assert ahead.kind == "decode" and ahead.decode == (long_, new)
    _apply(batch, s)
    assert _same(s.schedule(), ahead)


def test_next_batch_first_token_is_the_last():
    """A prompt whose first token is its whole budget never joins the
    decode rows, and frees its slot for the request behind it."""
    s, _ = _sched(max_seqs=2, num_pages=16)
    a, one, nxt = _req("a", 4, 9), _req("one", 4, 1), _req("n", 4, 4)
    s.add_request(a)
    _apply(s.schedule(), s)
    s.add_request(one)
    s.add_request(nxt)
    batch = s.schedule()
    assert batch.kind == "mixed" and batch.prefill[0].request is one
    ahead = s.next_batch(batch.decode, 1, batch.prefill)
    assert ahead.decode == (a,) and ahead.prefill[0].request is nxt
    _apply(batch, s)
    assert _same(s.schedule(), ahead)


def _simulate_ahead(seed: int, steps: int = 400):
    """The scheduler driven as the overlapped engine drives it: after
    every batch, the batch that comes next is asked for BEFORE the
    batch's effects are applied. Returns (compared, agreed, emissions,
    budgets)."""
    s, alloc = _sched(num_pages=40)
    usable = alloc.num_free
    rng = np.random.default_rng(seed)
    emissions: dict[str, list[int]] = {}
    budgets: dict[str, int] = {}
    arrivals = compared = agreed = 0
    ahead = None
    for _ in range(steps):
        arrived = False
        if arrivals < 40 and rng.random() < 0.35:
            r = _req(f"r{arrivals}", int(rng.integers(1, 20)),
                     int(rng.integers(1, 12)))
            s.add_request(r)
            budgets[r.request_id] = r.sampling.max_tokens
            arrivals += 1
            arrived = True
        pre0 = s.preemptions
        roomy = alloc.num_free >= len(s.running)  # no row stalls for a page
        batch = s.schedule()
        assert not s.doomed
        assert len(s.running) <= s.config.max_seqs
        _check_page_accounting(s, alloc, usable)
        if ahead is not None and not arrived and roomy and (
            s.preemptions == pre0
        ):
            compared += 1
            agreed += _same(batch, ahead)
            assert _same(batch, ahead), (seed, batch, ahead)
        ahead = None
        if batch is None:
            if arrivals >= 40 and not s.has_work:
                break
            continue
        if batch.decode:
            ahead = s.next_batch(batch.decode, 1, batch.prefill)
            _check_page_accounting(s, alloc, usable)
        for piece in batch.prefill:
            req = piece.request
            assert piece.start == req.num_computed_tokens
            req.num_computed_tokens += piece.length
            if req.prefill_done:
                req.state = RequestState.DECODE
        for req in [p.request for p in batch.prefill] + list(batch.decode):
            if req.state != RequestState.DECODE:
                continue
            if req in batch.decode:
                req.num_computed_tokens += 1
            idx = req.num_emitted + len(req.output_tokens)
            req.output_tokens.append(idx)
            emissions.setdefault(req.request_id, []).append(idx)
            if idx + 1 >= req.sampling.max_tokens:
                s.finish(req)
    assert not s.has_work and alloc.num_free == usable
    return compared, agreed, emissions, budgets


@pytest.mark.parametrize("seed", [3, 11, 29, 47])
def test_next_batch_is_the_batch_schedule_returns(seed):
    """The property the engine's dispatch ahead rests on: with every end
    a max_tokens end, no arrival in between and room to grow, the batch
    next_batch names is the batch schedule() then returns: same kind,
    same request objects in the same rows, same pieces. Early admission
    leaks no page and never leaves more rows than slots at a schedule."""
    compared, agreed, emissions, budgets = _simulate_ahead(seed)
    assert compared > 20 and agreed == compared
    for rid, toks in emissions.items():
        assert toks == list(range(budgets[rid])), rid
