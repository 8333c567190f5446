"""Continuous-batching scheduler.

Replaces what the reference gets for free from vLLM's scheduler (and mirrors
its own mocker's simulation of it — /root/reference lib/llm/src/mocker/
scheduler.rs:197): admission with KV watermark, chunked prefill, decode
batching, and preemption-by-recompute under page pressure.

TPU-first twist: the scheduler's output is always one of a *finite family of
shapes* — a prefill chunk of exactly `prefill_chunk` tokens or a decode batch
padded to a bucket — so the engine runs a handful of XLA programs total.

Policy (one `schedule()` call = one engine step):
1. Admit waiting requests while pages + decode slots allow (prefix-cache
   lookups happen here, so admission cost reflects true page need).
2. If any running request still needs prefill: schedule one prefill chunk
   (packing multiple small prompts up to the token budget).
3. Otherwise schedule a decode batch over all running sequences, growing
   page tables by one page where the next token would overflow; preempt
   the youngest sequences if pages run out.

`next_batch()` says, while a dispatch is still on the device, which batch
the next `schedule()` call returns once it is read back — the contract the
engine's overlapped decode pipeline relies on (docs/engine.md).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Literal, Optional

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.engine.request import FinishReason, Request, RequestState
from dynamo_tpu.telemetry import phases
from dynamo_tpu.tokens import TokenBlockSequence

logger = logging.getLogger(__name__)


class QueueFullError(RuntimeError):
    """Bounded admission (EngineConfig.max_waiting): the waiting queue is
    at capacity. The runner answers 'overloaded' with a Retry-After hint
    instead of queueing forever (docs/operations.md)."""


@dataclass(frozen=True)
class PrefillPiece:
    """One request's token span inside a prefill chunk."""

    request: Request
    start: int  # absolute token index where this piece begins
    length: int


@dataclass(frozen=True)
class ScheduledBatch:
    """`mixed` carries BOTH a prefill chunk and the decode batch — one
    engine step (one fused XLA program) in which every decode row emits
    a token while the prefill backlog drains (EngineConfig.mixed_steps)."""

    kind: Literal["prefill", "decode", "mixed"]
    prefill: tuple[PrefillPiece, ...] = ()
    decode: tuple[Request, ...] = ()

    @property
    def num_tokens(self) -> int:
        if self.kind == "prefill":
            return sum(p.length for p in self.prefill)
        if self.kind == "mixed":
            return sum(p.length for p in self.prefill) + len(self.decode)
        return len(self.decode)


class _After:
    """The running set as a dispatch still on the device will leave it,
    as far as the host knows before reading what it sampled: `gone`
    requests it is certain to finish, and `computed` (by `id(request)`)
    the prompt tokens in pages once its pieces have run. With neither,
    the running set as it is now."""

    def __init__(self, gone=(), computed=None):
        self._gone = {id(r) for r in gone}
        self._computed = computed or {}

    def computed(self, req: Request) -> int:
        return self._computed.get(id(req), req.num_computed_tokens)

    def state(self, req: Request) -> RequestState:
        if id(req) in self._gone:
            return RequestState.FINISHED
        if id(req) in self._computed:
            done = self._computed[id(req)] >= len(req.prompt_tokens)
            return RequestState.DECODE if done else RequestState.PREFILL
        return req.state


_NOW = _After()


class Scheduler:
    def __init__(self, config: EngineConfig, allocator: PageAllocator):
        self.config = config
        self.allocator = allocator
        #: emit `mixed` steps when both prefill work and running decodes
        #: exist (config.mixed_steps; the engine overrides this to False
        #: on multi-process SPMD meshes and under spec_ngram)
        self.mixed_enabled = config.mixed_steps
        self.waiting: list[Request] = []
        self.running: list[Request] = []
        #: content chains per live request (prefix registration + routing)
        self.chains: dict[str, TokenBlockSequence] = {}
        #: requests that can never make progress (engine finishes them
        #: with the given reason) — guarantees step() liveness instead
        #: of a silent busy-spin
        self.doomed: list[tuple[Request, str, FinishReason]] = []
        #: deadline-expired requests dropped pre-admission (observability)
        self.deadline_drops = 0
        #: pages of finished hold_pages requests, awaiting extraction
        self.held: dict[str, list[int]] = {}
        #: preemption-by-recompute count (page pressure) — exported as
        #: the dynamo_tpu_worker_preemptions_total fleet counter
        self.preemptions = 0
        #: admission wait of every admitted request, traced or not,
        #: counted where the wait ends (`_admit`): the sum and the count
        #: (EngineMetrics.queue_wait_ms_total / .admissions), and the
        #: waits since the engine last took them for its flight record
        self.queue_wait_ms_total = 0.0
        self.admissions = 0
        self._admit_waits: list[float] = []

    def take_admit_waits(self) -> list[float]:
        """The waits (ms) of the requests admitted since the last call:
        one engine step's admissions, for its flight record."""
        waits, self._admit_waits = self._admit_waits, []
        return waits

    # -- queue interface ---------------------------------------------------

    def add_request(self, request: Request) -> None:
        # Need ceil((len+1)/ps) pages <= max_pages_per_seq, i.e. room for the
        # prompt plus at least one generated token.
        if len(request.prompt_tokens) >= self.config.max_context:
            raise ValueError(
                f"prompt of {len(request.prompt_tokens)} tokens exceeds max "
                f"context {self.config.max_context} (one slot is reserved for "
                "generation)"
            )
        cap = self.config.max_waiting
        if cap is not None and len(self.waiting) >= cap:
            raise QueueFullError(
                f"waiting queue full ({len(self.waiting)}/{cap} requests); "
                "retry later or on another instance"
            )
        request.state = RequestState.WAITING
        self.waiting.append(request)

    def abort_request(self, request_id: str) -> Optional[Request]:
        for q in (self.waiting, self.running):
            for r in q:
                if r.request_id == request_id:
                    q.remove(r)
                    self._release(r)
                    self.chains.pop(request_id, None)
                    return r
        return None

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def num_waiting(self) -> int:
        return len(self.waiting)

    def can_admit_head(self) -> bool:
        """Whether the waiting-queue head could be admitted right now
        (cheap page-count check; used by the engine to decide if fused
        decode should yield to admission latency)."""
        if not self.waiting or len(self.running) >= self.config.max_seqs:
            return False
        req = self.waiting[0]
        need = -(-(len(req.prompt_tokens) + 1) // self.config.page_size)
        return self.allocator.num_free - need >= self._watermark_pages()

    def num_running(self) -> int:
        return len(self.running)

    def ends_within(self, req: Request, k: int) -> bool:
        """Whether `req` is certain to finish within its next `k` sampled
        tokens whatever they are: the token budget or the context runs
        out (the engine's `_finish_reason_for` length legs). A sampled
        stop is not certain, so it is not counted."""
        s = req.sampling
        return (
            len(req.output_tokens) + req.num_emitted + k >= s.max_tokens
            or req.num_tokens + k >= self.config.max_context
        )

    def next_batch(
        self, decode, k: int, prefill=()
    ) -> Optional[ScheduledBatch]:
        """The batch that comes next: what `schedule()` returns once the
        dispatch now on the device (decode rows `decode`, `k` tokens
        each; prompt pieces `prefill`) has been read back, as far as the
        host can know it before reading what was sampled. Three things
        change the batch and are known ahead: a row certain to end in
        that dispatch (`ends_within`) leaves; a waiting request that is
        admissible once those rows are gone is admitted NOW, its pages
        taken from the free pool as it stands (the leavers' pages are
        still being written), so its first piece is in the batch; a
        piece that completes its prompt joins the decode rows, in
        `running` order. Request-side events the host cannot know (a
        sampled stop, an abort, a preemption) are caught when the engine
        compares this batch with the scheduled one.

        None where the batch cannot be known: rows leave and their
        slots are not all taken now. Whoever takes one changes the
        batch: a request the pool can pay for only with the leavers'
        pages (`schedule()` admits it), or one that has not arrived yet
        (a client that gets its last token sends its next prompt), whose
        first chunk must not wait behind a dispatch built without it.
        Behind a mixed step (`prefill`) with nobody waiting the batch is
        named all the same: one token a row is over before a client is
        back, so no taker is lost by the launch, and the loop waits for
        them under it (`AsyncEngineRunner._await_takers`) where it would
        else meet them, or not, by the millisecond.

        Reads only replicated scheduler state, so every process of a
        multi-process mesh computes the same batch. Page growth of the
        decode rows is the caller's (the engine pre-grows them)."""
        gone = [r for r in decode if self.ends_within(r, k)]
        computed = {}
        for p in prefill:
            computed[id(p.request)] = p.start + p.length
            if p.start + p.length >= len(
                p.request.prompt_tokens
            ) and self.ends_within(p.request, 1):
                gone.append(p.request)  # its first token is its last
        self._admit(gone=len(gone))
        slots_left = len(self.running) - len(gone) < self.config.max_seqs
        if gone and slots_left and (self.waiting or not prefill):
            return None
        after = _After(gone, computed)
        pieces = self._schedule_prefill(after)
        rows = tuple(
            r for r in self.running if after.state(r) == RequestState.DECODE
        )[: self.config.decode_buckets[-1]]
        return self._compose(pieces, rows)

    def _compose(
        self, prefill: Optional[ScheduledBatch], decode: tuple
    ) -> Optional[ScheduledBatch]:
        """One step from its halves: a `mixed` batch when both exist and
        mixed steps are on, else prefill first (the XOR policy)."""
        if prefill is not None and decode and self.mixed_enabled:
            return ScheduledBatch(
                kind="mixed", prefill=prefill.prefill, decode=decode
            )
        if prefill is not None:
            return prefill
        if decode:
            return ScheduledBatch(kind="decode", decode=decode)
        return None

    # -- the step ----------------------------------------------------------

    def schedule(self) -> Optional[ScheduledBatch]:
        self._admit()
        prefill = self._schedule_prefill()
        decode = None
        if prefill is None or self.mixed_enabled:
            # Beside a prefill chunk the decode batch piggybacks: one
            # `mixed` step instead of a decode-stalling prefill step.
            # _schedule_decode's side effects (page growth, preemption of
            # the youngest DECODE victim) apply exactly as they would on
            # the decode step the XOR policy runs after the backlog.
            decode = self._schedule_decode()
        return self._compose(
            prefill, decode.decode if decode is not None else ()
        )

    def _watermark_pages(self) -> int:
        return int(self.allocator.num_pages * self.config.admission_watermark)

    def _drop_expired_waiting(self) -> None:
        """Deadline-expired requests leave the waiting queue BEFORE
        admission: prefill flops are never spent on a client whose
        deadline already passed (docs/operations.md). Error finishes
        ride the doomed drain."""
        if not any(r.deadline for r in self.waiting):
            return
        now = time.time()
        for req in [r for r in self.waiting if r.deadline and now > r.deadline]:
            self.waiting.remove(req)
            self._release(req)  # waiting requests hold no pages; defensive
            self.chains.pop(req.request_id, None)
            self.deadline_drops += 1
            self.doomed.append(
                (req, "deadline expired before admission",
                 FinishReason.ERROR)
            )

    def _admit(self, gone: int = 0) -> None:
        """Admit from the head of the queue while slots and pages allow.
        `gone` rows of `running` are certain to end in the dispatch on
        the device (`next_batch`): their slots count as free, their
        pages do not."""
        ps = self.config.page_size
        self._drop_expired_waiting()
        while (
            self.waiting
            and len(self.running) - gone < self.config.max_seqs
        ):
            req = self.waiting[0]
            # A prompt that can never fit the pool (even with everything else
            # evicted) would block the queue head forever: doom it instead.
            min_need = -(-(len(req.prompt_tokens) + 1) // ps)
            if min_need > (self.allocator.num_pages - 1) - self._watermark_pages():
                self.waiting.pop(0)
                self.doomed.append(
                    (req, f"prompt needs {min_need} pages; pool has "
                          f"{self.allocator.num_pages - 1}",
                     FinishReason.LENGTH)
                )
                continue
            chain = self.chains.get(req.request_id)
            if chain is None:
                chain = TokenBlockSequence(
                    req.prompt_tokens, block_size=ps, salt=self.config.model
                )
                self.chains[req.request_id] = chain
            # Probe the prefix cache to size the true page need. Multimodal
            # prompts bypass it: their placeholder token ids don't identify
            # the image content, so content-addressing would alias
            # different images onto the same hashes.
            use_cache = (
                self.config.enable_prefix_caching and req.mm_embeds is None
            )
            cached_blocks = (
                self.allocator.match_length(chain.sequence_hashes())
                if use_cache
                else 0
            )
            total_pages = -(-(len(req.prompt_tokens) + 1) // ps)
            need = total_pages - cached_blocks
            if self.allocator.num_free - need < self._watermark_pages():
                break  # head-of-line blocking by design (FIFO fairness)
            cached_pages = (
                self.allocator.lookup(chain.sequence_hashes())
                if use_cache
                else []
            )
            # A fully-cached prompt must still recompute its last token so
            # there are logits to sample from: cap the reuse.
            max_reuse = (len(req.prompt_tokens) - 1) // ps
            while len(cached_pages) > max_reuse:
                self.allocator.free([cached_pages.pop()])
            if self.allocator.state_slots and not (
                self.allocator.num_free_slots
            ):
                # every state slot is held (rows certain to end still hold
                # theirs while `next_batch` admits ahead): wait, as for pages
                self.allocator.free(cached_pages)
                break
            fresh = self.allocator.allocate(total_pages - len(cached_pages))
            if fresh is None:
                self.allocator.free(cached_pages)
                break
            if self.allocator.state_slots:
                # one admission, both kinds of cache: the sequence's state
                # slot for its life in `running`, read from generation 0
                # (it starts at position 0, so from zeros)
                req.state_slot = self.allocator.allocate_slot()
                req.state_gen = 0
            req.pages = cached_pages + fresh
            req.registered_blocks = len(cached_pages)  # looked up by hash
            req.num_cached_prompt_tokens = len(cached_pages) * ps
            req.num_computed_tokens = req.num_cached_prompt_tokens
            req.state = RequestState.PREFILL
            self.waiting.pop(0)
            self.running.append(req)
            # admission latency, per request (preempted requests re-enter
            # the queue and observe their re-admission wait too).
            # arrival_time defaults to 0.0 for directly-constructed
            # Requests (unit tests, tools) — an epoch-sized wait there is
            # garbage, not a measurement
            if req.arrival_time:
                wait_ms = max(
                    0.0, (time.time() - req.arrival_time) * 1000.0
                )
                self.queue_wait_ms_total += wait_ms
                self.admissions += 1
                self._admit_waits.append(round(wait_ms, 3))
                if req.trace_id is not None:
                    # traced request: the wait rides the first StepOutput
                    # onto the engine.generate span (timeline breakdown)
                    # and stamps the histogram bucket's exemplar
                    req.queue_wait_ms = wait_ms
                phases.observe(
                    "queue_wait_ms", wait_ms, trace_id=req.trace_id
                )

    def _mixed_max_pieces(self, at: _After = _NOW) -> Optional[int]:
        """Piece-count cap for a step that will carry the decode batch:
        the engine samples mixed steps over one combined row space of
        BUCKETED halves (decode bucket + prefill-piece bucket), so the
        cap must be computed in bucket space — the largest power-of-two
        piece bucket that still fits beside the decode bucket inside
        decode_buckets[-1]. (A raw-count cap would let the piece bucket
        round UP past the family.) Always >= 1 so a full decode bucket
        can never starve prefill; that floor is the one case where the
        combined rows exceed the family by the single-piece bucket.
        None = no decodables, no cap."""
        if not self.mixed_enabled:
            return None
        n_dec = sum(
            1 for r in self.running if at.state(r) == RequestState.DECODE
        )
        if not n_dec:
            return None
        cap = self.config.decode_buckets[-1]
        b_dec = self.config.decode_bucket_for(n_dec)
        b_pre = 1
        while b_pre * 2 + b_dec <= cap:
            b_pre *= 2
        return b_pre

    def _prefill_step_budget(self, at: _After = _NOW) -> int:
        """Token budget for this prefill step. Adaptive policy: grow
        toward the whole un-prefilled backlog (capped) so a saturation
        burst drains in a few large dispatches — see EngineConfig
        docstrings and docs/PERF.md (saturation-TTFT section)."""
        base = self.config.effective_prefill_budget
        if self.config.prefill_budget_policy != "adaptive":
            return base
        pending = sum(
            len(r.prompt_tokens) - at.computed(r)
            for r in self.running
            if at.state(r) == RequestState.PREFILL
        )
        cap = self.config.effective_prefill_budget_max
        budget = max(base, min(pending, cap))
        max_pieces = self._mixed_max_pieces(at)
        if max_pieces is not None:
            # A mixed step's combined row count must stay inside the
            # finite shape family: clamp the GROWN budget so it can never
            # pack more pieces than the row-space cap admits (the base
            # budget always stays available).
            budget = min(
                budget, max(base, max_pieces * self.config.prefill_chunk)
            )
        return budget

    def _schedule_prefill(
        self, at: _After = _NOW
    ) -> Optional[ScheduledBatch]:
        # `at` is the running set the pieces are cut from: as it is now,
        # or as the dispatch on the device leaves it (next_batch).
        # Each piece is capped at prefill_chunk tokens; the step budget
        # spans sequences. The engine groups same-bucket pieces into one
        # batched [B, T] program, so packing many prompts here turns into
        # fewer, larger dispatches rather than serial B=1 launches.
        budget = self._prefill_step_budget(at)
        ps = self.config.page_size
        max_pieces = self._mixed_max_pieces(at)
        pieces: list[PrefillPiece] = []
        for req in self.running:
            if at.state(req) != RequestState.PREFILL or budget <= 0:
                continue
            if max_pieces is not None and len(pieces) >= max_pieces:
                break  # mixed row-space cap (see _mixed_max_pieces)
            start = at.computed(req)
            remaining = len(req.prompt_tokens) - start
            take = min(remaining, self.config.prefill_chunk, budget)
            if take < remaining:
                # Mid-prompt chunks end on page boundaries so every chunk
                # STARTS page-aligned — the Pallas write path lands chunk
                # KV as whole-page DMA runs (ops/kv_update.py invariant).
                take = (take // ps) * ps
            if take <= 0:
                continue
            pieces.append(PrefillPiece(request=req, start=start, length=take))
            budget -= take
        if not pieces:
            return None
        return ScheduledBatch(kind="prefill", prefill=tuple(pieces))

    def _schedule_decode(self) -> Optional[ScheduledBatch]:
        decodable = [r for r in self.running if r.state == RequestState.DECODE]
        if not decodable:
            return None
        ps = self.config.page_size
        scheduled: list[Request] = []
        # Oldest first; preemption victims are taken from the youngest.
        for req in decodable:
            if req.state != RequestState.DECODE:
                continue  # preempted by an earlier iteration of this loop
            have = len(req.pages) * ps
            # Writing this step's KV at position num_tokens-1 needs
            # have >= num_tokens; grow exactly when it would not fit.
            # (num_tokens can never exceed max_context here: _accept_token
            # finishes requests at the boundary, so growth is always legal.)
            if req.num_tokens > have:
                got = self.allocator.allocate(1)
                if got is None:
                    if self._preempt_youngest(excluding=req, scheduled=scheduled):
                        got = self.allocator.allocate(1)
                    if got is None:
                        if not scheduled and len(self.running) == 1:
                            # Sole sequence and the pool is exhausted: no
                            # future step can free pages — doom it rather
                            # than busy-spin (engine finishes it as LENGTH).
                            self.running.remove(req)
                            self._release(req)
                            self.chains.pop(req.request_id, None)
                            self.doomed.append(
                                (req, "kv pool exhausted with no preemption "
                                      "victim",
                                 FinishReason.LENGTH)
                            )
                        continue  # stalled this step; others may progress
                req.pages.extend(got)
            scheduled.append(req)
        if not scheduled:
            return None
        cap = self.config.decode_buckets[-1]
        return ScheduledBatch(kind="decode", decode=tuple(scheduled[:cap]))

    def _preempt_youngest(
        self, excluding: Request, scheduled: Optional[list[Request]] = None
    ) -> bool:
        victims = [
            r
            for r in self.running
            if r is not excluding and r.state == RequestState.DECODE
        ]
        if not victims:
            return False
        victim = victims[-1]
        if scheduled is not None and victim in scheduled:
            # Already picked for this step's batch — pull it back out, or it
            # would decode against an empty page table (the null page).
            scheduled.remove(victim)
        logger.warning(
            "preempting %s (recompute) under page pressure", victim.request_id
        )
        self.preemptions += 1
        self._release(victim)
        # Recompute-from-scratch: prompt grows to include generated tokens.
        victim.state = RequestState.WAITING
        victim.num_emitted += len(victim.output_tokens)
        victim.prompt_tokens = victim.all_tokens
        victim.output_tokens = []
        victim.num_computed_tokens = 0
        victim.num_cached_prompt_tokens = 0
        # draft-model speculation: the draft pool's KV for this request
        # lived in the released pages — the re-admission prefill rebuilds
        # both pools from scratch
        victim.spec_draft_pos = 0
        self.running.remove(victim)
        self.waiting.insert(0, victim)
        self.chains.pop(victim.request_id, None)
        return True

    # -- completion --------------------------------------------------------

    def finish(self, request: Request) -> None:
        request.state = RequestState.FINISHED
        if request in self.running:
            self.running.remove(request)
        if request.hold_pages and request.pages:
            self.held[request.request_id] = request.pages
            request.pages = []
            self._release(request)  # its state slot, if it has one
        else:
            self._release(request)
        self.chains.pop(request.request_id, None)

    def release_held(self, request_id: str) -> None:
        pages = self.held.pop(request_id, None)
        if pages:
            self.allocator.free(pages)

    def add_prefilled(self, request: Request, chain: TokenBlockSequence) -> None:
        """Admit a request whose prompt KV is already resident (written into
        request.pages by a remote prefill transfer) straight into decode."""
        request.state = RequestState.DECODE
        request.num_computed_tokens = len(request.prompt_tokens)
        self.chains[request.request_id] = chain
        self.running.append(request)

    def _release(self, request: Request) -> None:
        if request.pages:
            self.allocator.free(request.pages)
            request.pages = []
        if request.state_slot:
            self.allocator.free_slot(request.state_slot)
            request.state_slot = 0
