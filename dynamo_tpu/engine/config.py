"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence


@dataclass(frozen=True)
class EngineConfig:
    """Static configuration of one engine worker.

    Shape-affecting knobs (page_size, buckets, max_pages_per_seq) define the
    finite program family XLA compiles; everything dynamic is masked inside
    those shapes (no data-dependent shapes under jit).
    """

    model: str = "llama3-8b"
    #: KV pages on device (page 0 reserved as the null page)
    num_pages: int = 2048
    #: tokens per page == router token-block size (hashes align 1:1)
    page_size: int = 64
    #: max pages a single sequence may hold (=> max context length)
    max_pages_per_seq: int = 64
    #: decode batch buckets (padded up to the next bucket)
    decode_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    #: per-sequence prefill chunk length (a prompt is processed in chunks of
    #: at most this many tokens; also the max prefill T bucket)
    prefill_chunk: int = 512
    #: the T buckets a prompt piece is padded to, ascending, the last one
    #: `prefill_chunk` (None => powers of two from 32 up to it). Every
    #: bucket is a step program a decode bucket, a piece-row bucket and a
    #: sampling variant: a model whose programs are dear to load names few
    #: (`--prefill-buckets 32 512`) and pays for them in padding
    prefill_buckets: Optional[tuple[int, ...]] = None
    #: total prefill tokens per step across sequences (None => 4×chunk).
    #: Pieces of the same length bucket run as ONE batched [B, T] program —
    #: this is what lets many short/medium prompts prefill in one dispatch.
    prefill_token_budget: Optional[int] = None
    #: "fixed" spends at most `effective_prefill_budget` tokens per prefill
    #: step; "adaptive" grows the step budget toward the whole un-prefilled
    #: backlog (capped at `prefill_budget_max`) so an arrival burst drains
    #: in O(1) large dispatches instead of O(backlog) small ones — the
    #: saturation-TTFT cliff (docs/PERF.md: c=64 p50 2,232 ms was backlog
    #: drain at the default budget). An unloaded engine still takes the
    #: small fixed budget, keeping the per-step decode stall short.
    prefill_budget_policy: str = "fixed"
    #: adaptive-policy ceiling (None => 4× the effective budget). Bounds
    #: the worst-case single prefill dispatch, which is exactly the
    #: longest decode stall (ITL spike) a running sequence can observe.
    prefill_budget_max: Optional[int] = None
    #: max sequences resident (decode slots)
    max_seqs: int = 64
    #: decode steps fused per dispatch (lax.scan with on-device token
    #: feedback): one host⇄device sync per `decode_steps` tokens/seq.
    #: Finish conditions are applied on the host afterwards — up to K-1 speculative
    #: tokens past a stop are computed and dropped. 1 = classic stepping.
    decode_steps: int = 8
    #: overlapped decode loop: after dispatching step N, dispatch step
    #: N+1 ahead (the batch the scheduler will return next: a row at its
    #: token budget gone, its successor admitted, a finished prompt
    #: joined; sampled ids fed back on device) and read step N's ids back
    #: one step lagged via an async copy — host postprocessing and array
    #: staging hide under device compute. Rolled back (overshoot
    #: discarded, like decode_multi's post-stop tokens) when a sampled
    #: stop, an abort or a preemption changes the batch (docs/engine.md).
    #: Runs on multi-process SPMD meshes: decode ids
    #: are replicated on-device, the rollback decision is a pure
    #: function of the (broadcast) event log, so every lockstep host
    #: overlaps and rolls back identically — the lagged readback is the
    #: ONLY per-window host sync. Forced off when spec_ngram > 0
    #: (prompt-lookup drafts need host tokens). Token streams are
    #: bit-identical to the synchronous path (pinned by
    #: tests/test_engine_overlap.py and test_engine_multihost.py).
    overlap_decode: bool = True
    #: stall-free mixed prefill+decode steps (Sarathi-style piggybacking):
    #: when both a prefill backlog and running decodes exist, the
    #: scheduler emits ONE `mixed` step carrying a bounded prefill chunk
    #: plus the current decode batch, and the engine dispatches both as a
    #: single XLA program — decode rows emit a token every step even while
    #: a prompt burst drains, collapsing the burst-drain ITL tail the
    #: XOR (prefill-priority) policy pays (docs/PERF.md saturation
    #: section, lever 4). Greedy token streams are bit-exact vs the XOR
    #: scheduler (same kernels, same per-request order — pinned by
    #: tests/test_engine_mixed.py). Runs on multi-process SPMD meshes
    #: (the mixed/XOR choice is a deterministic function of the
    #: replicated scheduler state, so lockstep replicas agree). Forced
    #: off when spec_ngram > 0 (the verify program owns the decode
    #: batch).
    mixed_steps: bool = True
    #: speculative decoding by prompt lookup (draft-free n-gram
    #: speculation): propose this many draft tokens per decode step from
    #: the last occurrence of the sequence's trailing n-gram, verify all
    #: of them in ONE forward pass, accept the longest matching prefix
    #: plus the model's own token at the first mismatch. 0 = off. Greedy
    #: requests only; mixed batches with sampling/logprob/penalty
    #: requests fall back to the normal decode path for that step.
    spec_ngram: int = 0
    #: trailing n-gram length the lookup matches on
    spec_ngram_match: int = 2
    #: draft-model speculative decoding: a SECOND (small) model from the
    #: same registry family proposes spec_draft_tokens greedy drafts per
    #: decode step, and one fused program runs draft catch-up + proposal
    #: + target verify + ON-DEVICE acceptance (bit-exact greedy; exact
    #: rejection sampling for temperature>0 — accept draft x with prob
    #: min(1, p_target(x)/q(x)) where q is the deterministic draft's
    #: point mass, resample the residual otherwise, which preserves the
    #: target sampling distribution exactly). Unlike spec_ngram, the
    #: draft path COMPOSES with overlap_decode (the next spec dispatch
    #: chains off the previous one's on-device outputs) and mixed_steps
    #: (the verify program runs as the decode leg beside the prefill
    #: chunk). None = off. The draft must share the target's vocabulary
    #: (same tokenizer family); `--spec-draft` on the CLI.
    spec_draft_model: Optional[str] = None
    #: drafts proposed (and verified) per spec step; the fused program's
    #: verify window is spec_draft_tokens+1 wide
    spec_draft_tokens: int = 4
    #: checkpoint dir for the draft weights (None = the draft adapter's
    #: default checkpoint, else random init — random drafts accept at
    #: chance and immediately hit the acceptance cooldown)
    spec_draft_checkpoint: Optional[str] = None
    #: adaptive fallback: when a spec step's draft acceptance rate drops
    #: below this, decode reverts to the fused multi-step path for
    #: spec_cooldown_steps before probing speculation again (lookup-miss
    #: workloads must not pay s+1-wide verifies per single token)
    spec_min_accept_rate: float = 0.2
    spec_cooldown_steps: int = 16
    #: admission watermark: keep this fraction of pages free when admitting
    admission_watermark: float = 0.02
    #: bounded admission (docs/operations.md "Overload & draining"):
    #: cap on the scheduler's WAITING queue. None (default) keeps the
    #: historical unbounded queue; with a cap, add_request raises
    #: QueueFullError once `max_waiting` requests are already queued —
    #: the worker answers "overloaded" (HTTP 429 + Retry-After at the
    #: frontend) instead of queueing a request it cannot serve within
    #: any reasonable deadline. `--max-waiting` on the CLI.
    max_waiting: Optional[int] = None
    #: eos token ids (from the model card/tokenizer)
    eos_token_ids: tuple[int, ...] = ()
    #: dtype name for params/KV ("bfloat16" | "float32")
    dtype: str = "bfloat16"
    #: weight-only quantization: None | "int8" (per-output-channel scales;
    #: halves the HBM weight traffic decode is bound by)
    quantize: Optional[str] = None
    #: KV-cache page quantization: None | "int8" | "fp8". Pages store the
    #: narrow dtype with per-(page, kv-head, slot) f32 scale planes;
    #: dequant is folded into the Pallas page-walk kernels (and the XLA
    #: gather fallback), halving KV HBM traffic in the history-dominated
    #: decode regime and ~doubling effective cache capacity. Not
    #: supported for MLA (shared-latent cache) models.
    kv_quantize: Optional[str] = None
    #: decode attention: "auto" (pallas on TPU single-chip, else xla),
    #: "xla", "pallas", or "hybrid" (pallas kernels with decode falling
    #: back to the XLA gather past LlamaConfig.pallas_decode_max_batch)
    attention_impl: str = "auto"
    #: mesh layout
    dp: int = 1
    tp: int = 1
    #: sequence/context parallel: long first-chunk prefills run ring
    #: attention over this many devices (parallel/context.py)
    sp: int = 1
    #: expert parallel: MoE experts shard over this many devices (dense
    #: models ignore it)
    ep: int = 1
    #: combined topology knob: "tp=N,dp=M[,ep=K][,sp=J]" (the
    #: vLLM-style `--topology` flag; docs/migrating.md). Parsed in
    #: __post_init__ and OVERRIDES the individual dp/tp/sp/ep fields;
    #: unnamed axes keep their defaults. The product must match the
    #: devices the mesh is built over (make_mesh validates). "" = use
    #: the individual fields.
    topology: str = ""
    #: test/bench knob: treat a single-process mesh as multi-host —
    #: the engine takes the multi-controller SPMD code paths
    #: (addressable-shard readbacks, replicated decode outputs,
    #: lockstep-safe scheduling) without a real fabric. Lets CPU tests
    #: and bench.py exercise the cross-host decode pipeline
    #: deterministically. No effect on real multi-process meshes
    #: (already multi-host).
    force_multihost: bool = False
    #: random seed for sampling
    seed: int = 0
    #: enable content-addressed prefix caching
    enable_prefix_caching: bool = True
    #: live fleet telemetry (docs/observability.md "Fleet view & SLO
    #: accounting"): per-request TTFT/ITL/e2e quantile sketches + SLA
    #: counters on the engine, the live tokens/s gauge, and the worker's
    #: fleet-frame publishing. Host-side metrics only — the token path
    #: is identical either way; off (`--no-fleet-telemetry`) skips the
    #: bookkeeping entirely (bench.py `slo_overhead` prices it <1%).
    fleet_telemetry: bool = True
    #: flight recorder (docs/observability.md "Debugging a slow or stuck
    #: worker"): an always-on bounded ring of per-step records — batch
    #: kind/buckets, page-pool deltas, dispatch/sync/host ms, overlap
    #: hits/rollbacks, compile events, queue depths — served at
    #: GET /v1/debug/flight and shipped in the worker's metrics frames.
    #: Host-side only; off (`--no-flight-recorder`) is bit-identical on
    #: the token path (bench.py `flight_overhead` prices it <1%).
    flight_recorder: bool = True
    #: flight ring capacity (records, one per engine step)
    flight_ring: int = 512
    #: stall watchdog (telemetry/watchdog.py): per-request progress
    #: monitor diagnosing wedged streams (structured JSONL diagnosis +
    #: dynamo_tpu_stalls_total{cause}); runs on the worker event loop
    stall_watchdog: bool = True
    #: a stream is "stalled" after stall_factor × the live ITL-p95
    #: estimate with no emission, floored at stall_min_s (first compiles
    #: legitimately take seconds)
    stall_factor: float = 32.0
    stall_min_s: float = 5.0
    #: admission-wait budget: a request with NO first emission after
    #: this many seconds is diagnosed as cause="queue_wait"
    stall_queue_wait_s: float = 120.0
    #: None (default) = diagnose-only. A number hard-finishes streams
    #: stalled past it with an error frame instead of hanging the
    #: client (`--stall-hard-deadline`)
    stall_hard_deadline_s: Optional[float] = None
    #: KVBM tiering (dynamo_tpu/kvbm): host-DRAM tier byte budget (0 = off)
    host_kv_cache_bytes: int = 0
    #: disk tier byte budget (0 = off; needs disk_kv_cache_dir)
    disk_kv_cache_bytes: int = 0
    #: directory for the disk tier's block files
    disk_kv_cache_dir: Optional[str] = None

    def __post_init__(self):
        if self.topology:
            # Parse before the sp validation below so a topology-set sp
            # goes through the same checks as an explicitly-set one.
            from dynamo_tpu.parallel.mesh import parse_topology

            for axis, n in parse_topology(self.topology).items():
                object.__setattr__(self, axis, n)
        if self.prefill_buckets is not None:
            object.__setattr__(
                self, "prefill_buckets", tuple(self.prefill_buckets))
            if (list(self.prefill_buckets) != sorted(set(self.prefill_buckets))
                    or self.prefill_buckets[-1] != max(self.prefill_chunk, 32)
                    or self.prefill_buckets[0] < 1):
                raise ValueError(
                    f"prefill_buckets {self.prefill_buckets} must ascend to "
                    f"prefill_chunk ({max(self.prefill_chunk, 32)})"
                )
        if self.prefill_chunk % self.page_size != 0:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) must be a multiple of "
                f"page_size ({self.page_size}) — chunks start page-aligned "
                "so the KV write path can land whole-page DMA runs"
            )
        if self.sp > 1 and (32 % self.sp != 0 or self.prefill_chunk % self.sp):
            # Prefill T buckets are powers of two from 32 up to
            # prefill_chunk; sp must divide every one of them or the ring
            # path silently never engages.
            raise ValueError(
                f"sp ({self.sp}) must be a power of two <= 32 that divides "
                f"prefill_chunk ({self.prefill_chunk}) — prefill length "
                "buckets must shard evenly over the sequence-parallel axis"
            )
        if (
            self.prefill_token_budget is not None
            and self.prefill_token_budget < self.page_size
        ):
            raise ValueError(
                f"prefill_token_budget ({self.prefill_token_budget}) must be "
                f">= page_size ({self.page_size}): mid-prompt chunks round "
                "down to page boundaries, so a smaller budget could never "
                "schedule any prefill work"
            )
        if self.kv_quantize not in (None, "int8", "fp8"):
            raise ValueError(
                f"kv_quantize must be None, 'int8' or 'fp8', got "
                f"{self.kv_quantize!r}"
            )
        if self.spec_draft_model is not None and self.spec_ngram > 0:
            raise ValueError(
                "spec_draft_model and spec_ngram are mutually exclusive "
                "speculation modes — configure one of them"
            )
        if self.spec_draft_model is not None and self.spec_draft_tokens < 1:
            raise ValueError(
                f"spec_draft_tokens must be >= 1, got "
                f"{self.spec_draft_tokens}"
            )
        if self.prefill_budget_policy not in ("fixed", "adaptive"):
            raise ValueError(
                "prefill_budget_policy must be 'fixed' or 'adaptive', got "
                f"{self.prefill_budget_policy!r}"
            )
        if (
            self.prefill_budget_max is not None
            and self.prefill_budget_max < self.effective_prefill_budget
        ):
            raise ValueError(
                f"prefill_budget_max ({self.prefill_budget_max}) must be >= "
                f"the effective budget ({self.effective_prefill_budget}) — "
                "adaptive only ever grows the step budget"
            )

    @property
    def max_context(self) -> int:
        return self.max_pages_per_seq * self.page_size

    @property
    def effective_prefill_budget(self) -> int:
        return self.prefill_token_budget or 4 * self.prefill_chunk

    @property
    def effective_prefill_budget_max(self) -> int:
        """Adaptive-policy ceiling (the single source of the 4× default)."""
        return self.prefill_budget_max or 4 * self.effective_prefill_budget

    def decode_bucket_for(self, n: int) -> int:
        for b in self.decode_buckets:
            if n <= b:
                return b
        return self.decode_buckets[-1]

    @staticmethod
    def for_tests(**overrides) -> "EngineConfig":
        defaults = dict(
            model="tiny",
            num_pages=64,
            page_size=4,
            max_pages_per_seq=8,
            decode_buckets=(1, 2, 4, 8),
            prefill_chunk=16,
            max_seqs=8,
            dtype="float32",
        )
        defaults.update(overrides)
        return EngineConfig(**defaults)
