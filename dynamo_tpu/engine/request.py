"""Engine-facing request/response types.

The engine speaks tokens-in/tokens-out (the preprocessor upstream owns
templates+tokenization; the backend op downstream owns detokenization) —
same split as the reference's PreprocessedRequest contract
(/root/reference lib/llm/src/preprocessor.rs:156, backend.rs:278).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Optional, Sequence


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0  # 0 => greedy
    top_p: float = 1.0
    top_k: int = 0  # 0 => disabled
    max_tokens: int = 256
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False
    seed: Optional[int] = None
    #: -1 = off; 0 = chosen-token logprob only; N>0 = chosen + top-N
    #: alternatives per emitted token (OpenAI logprobs/top_logprobs)
    logprobs: int = -1
    #: OpenAI penalties over the output-token history (0 = off)
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    #: multiplicative repetition penalty over GENERATED tokens only —
    #: prompt tokens are deliberately not penalized, unlike HF's
    #: RepetitionPenaltyLogitsProcessor (1 = off; reference exposes it
    #: via nvext)
    repetition_penalty: float = 1.0
    #: OpenAI logit_bias: additive per-token-id biases applied in the
    #: sampler (before temperature). Bounded by sampling.BIAS_SLOTS
    #: minus the min_tokens ban slots.
    logit_bias: tuple[tuple[int, float], ...] = ()
    #: suppress eos/stop-token finishes until this many output tokens
    #: (reference: protocols/common.rs min_tokens) — implemented as
    #: sampler-level bans, so the banned ids are never emitted
    min_tokens: int = 0


class FinishReason(str, enum.Enum):
    STOP = "stop"  # eos / stop token
    LENGTH = "length"  # max_tokens or context limit
    CANCELLED = "cancelled"
    ERROR = "error"


class RequestState(str, enum.Enum):
    WAITING = "waiting"
    PREFILL = "prefill"
    DECODE = "decode"
    PREEMPTED = "preempted"
    FINISHED = "finished"


@dataclass
class Request:
    """One in-flight generation inside the engine."""

    request_id: str
    prompt_tokens: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    arrival_time: float = 0.0
    #: absolute end-to-end deadline (epoch seconds; None = none). The
    #: scheduler drops expired requests BEFORE admission; the runner
    #: error-finishes expired streams mid-decode (docs/operations.md)
    deadline: Optional[float] = None
    #: multimodal (llava-style): projected image embeddings [n, H] replacing
    #: the placeholder prompt tokens at mm_positions (absolute indices)
    mm_embeds: Optional["object"] = None  # np.ndarray
    mm_positions: tuple[int, ...] = ()

    # -- engine-managed state ---------------------------------------------
    state: RequestState = RequestState.WAITING
    output_tokens: list[int] = field(default_factory=list)
    pages: list[int] = field(default_factory=list)
    #: leading blocks of `pages` that carry their content address: where
    #: `JaxEngine._register_pages` resumes instead of walking from block 0
    #: every step. Set wherever `pages` is assigned (admission).
    registered_blocks: int = 0
    #: tokens whose KV is already in pages (prefix-cache hits + prefilled)
    num_computed_tokens: int = 0
    #: prompt tokens served from the prefix cache at admission
    num_cached_prompt_tokens: int = 0
    #: tokens already emitted before a preemption folded them into the prompt
    #: (keeps the max_tokens budget correct across recompute)
    num_emitted: int = 0
    finish_reason: Optional[FinishReason] = None
    #: a model with state-space layers: the slot of the state pool that
    #: holds this sequence's recurrent state while it is in `running` (0 =
    #: none: the null slot), and which of the slot's two generations holds
    #: the state as the last dispatch TAKEN left it (engine/engine.py
    #: `_row_tables`, `_commit_state`)
    state_slot: int = 0
    state_gen: int = 0
    #: disaggregated serving: keep pages allocated after finish so a prefill
    #: worker can extract their KV for transfer (released via release_held)
    hold_pages: bool = False
    #: speculative decoding (engine-managed): incremental n-gram -> last
    #: start position index over the token sequence, plus a persistent
    #: copy of that sequence (all_tokens rebuilds a list per call) and the
    #: next unindexed n-gram start
    spec_index: Optional[dict] = None
    spec_ctx: Optional[list] = None
    spec_indexed_upto: int = 0
    #: draft-model speculation (EngineConfig.spec_draft_model): number of
    #: tokens whose DRAFT KV is committed (positions [0, spec_draft_pos)).
    #: The draft prefill rides the target prefill; each spec step's
    #: catch-up window re-feeds the tokens accepted since. Reset to 0 on
    #: preemption-by-recompute (pages are released; the re-admission
    #: prefill rebuilds both pools).
    spec_draft_pos: int = 0
    #: distributed-tracing enrichment (set by AsyncEngineRunner only
    #: while tracing is ON; None otherwise — the default token path is
    #: untouched): the request's trace id stamps phase-histogram
    #: exemplars, and the measured queue wait / prefill-induced stall
    #: ride the first/last StepOutput onto the engine.generate span so
    #: the assembled trace's timeline breakdown can attribute them
    trace_id: Optional[str] = None
    queue_wait_ms: Optional[float] = None
    stall_accum_ms: float = 0.0

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_tokens) + len(self.output_tokens)

    @property
    def all_tokens(self) -> list[int]:
        return self.prompt_tokens + self.output_tokens

    @property
    def prefill_done(self) -> bool:
        return self.num_computed_tokens >= len(self.prompt_tokens)

    @property
    def is_finished(self) -> bool:
        return self.state == RequestState.FINISHED


@dataclass(frozen=True)
class StepOutput:
    """Per-request result of one engine step."""

    request_id: str
    new_token_ids: tuple[int, ...]
    finish_reason: Optional[FinishReason] = None
    #: set on the first output of a request (TTFT accounting)
    is_first: bool = False
    #: per-token logprob of each new token (when sampling.logprobs >= 0)
    logprobs: Optional[tuple[float, ...]] = None
    #: per-token top-N alternatives [(token_id, logprob), ...]
    top_logprobs: Optional[tuple[tuple[tuple[int, float], ...], ...]] = None
    #: prompt tokens served from the prefix cache (first output only —
    #: OpenAI usage.prompt_tokens_details.cached_tokens)
    cached_tokens: Optional[int] = None
    #: emitted by a mixed prefill+decode step (EngineConfig.mixed_steps) —
    #: surfaces as the `mixed` attribute on the engine.generate trace span
    mixed: bool = False
    #: emitted by a speculative verify step (spec_ngram or
    #: spec_draft_model) — surfaces as the `spec` attribute on the
    #: engine.generate trace span
    spec: bool = False
    #: tracing enrichment (first output of a TRACED request only; None
    #: otherwise — the wire shape is unchanged when tracing is off):
    #: admission-to-schedule wait, for the trace timeline breakdown
    queue_wait_ms: Optional[float] = None
    #: tracing enrichment (final output of a traced request): total
    #: prefill-induced decode stall this request experienced
    stall_ms: Optional[float] = None
