"""Async bridge over JaxEngine + simple test engines.

The engine's step loop is synchronous (device dispatch); AsyncEngineRunner
runs it on a dedicated thread and exposes the universal AsyncEngine
interface: `generate(context, preprocessed) -> async iterator of
{token_ids, finish_reason}` (the reference's AsyncEngine::generate —
engine.rs:207). Echo engines mirror engines.rs EchoFull/EchoCore for
tests/CLI.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Any, AsyncIterator, Optional, Protocol

from dynamo_tpu import telemetry
from dynamo_tpu.engine.engine import JaxEngine, phase
from dynamo_tpu.engine.request import SamplingParams, StepOutput
from dynamo_tpu.engine.scheduler import QueueFullError
from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
from dynamo_tpu.runtime.context import (
    CANCELLED,
    Context,
    queue_get_or_cancelled,
)
from dynamo_tpu.runtime.overload import (
    OverloadedError,
    estimate_retry_after_s,
)
from dynamo_tpu.testing import faults

logger = logging.getLogger(__name__)

#: longest the loop holds a step back for the takers of free decode
#: slots (`AsyncEngineRunner._await_takers`): over a client's way back
#: on one host (20-37 ms p5-p95, 48 ms p98 on the chip) with its tail
#: (a taker later than this changed the batches of one run in eight at
#: 60 ms); the engine allows three quarters of a decode dispatch, which
#: is about this in the benchmark's cells (8 steps of 15-18 ms) and less
#: for a small model
TAKERS_WAIT_S = 0.1


class AsyncEngine(Protocol):
    async def generate(
        self, context: Context, request: PreprocessedRequest
    ) -> AsyncIterator[dict]: ...


def output_to_dict(out: StepOutput) -> dict:
    """The one wire shape for engine stream items."""
    d = {
        "token_ids": list(out.new_token_ids),
        "finish_reason": out.finish_reason.value if out.finish_reason else None,
    }
    if out.logprobs is not None:
        d["logprobs"] = list(out.logprobs)
    if out.top_logprobs is not None:
        d["top_logprobs"] = [
            [[tid, lp] for tid, lp in alts] for alts in out.top_logprobs
        ]
    if out.cached_tokens is not None:
        d["cached_tokens"] = out.cached_tokens
    if out.mixed:
        d["mixed"] = True
    if out.spec:
        d["spec"] = True
    # tracing enrichment (traced requests only — these keys are absent
    # from the wire when tracing is off, keeping it bit-identical):
    # measured queue wait / prefill-induced stall for the engine span
    if out.queue_wait_ms is not None:
        d["queue_wait_ms"] = out.queue_wait_ms
    if out.stall_ms is not None:
        d["stall_ms"] = out.stall_ms
    return d


def _sampling_from(req: PreprocessedRequest) -> SamplingParams:
    return SamplingParams(
        temperature=req.temperature,
        top_p=req.top_p,
        top_k=req.top_k,
        max_tokens=req.max_tokens,
        stop_token_ids=tuple(req.stop_token_ids),
        ignore_eos=req.ignore_eos,
        seed=req.seed,
        logprobs=getattr(req, "logprobs", -1),
        frequency_penalty=getattr(req, "frequency_penalty", 0.0),
        presence_penalty=getattr(req, "presence_penalty", 0.0),
        repetition_penalty=getattr(req, "repetition_penalty", 1.0) or 1.0,
        logit_bias=tuple(
            (int(t), float(b))
            for t, b in (getattr(req, "logit_bias", None) or ())
        ),
        min_tokens=int(getattr(req, "min_tokens", 0) or 0),
    )


class AsyncEngineRunner:
    """Thread-backed continuous-batching loop around a JaxEngine.

    With the engine's overlapped decode pipeline (EngineConfig
    .overlap_decode), each `eng.step()` returns step N's outputs while
    step N+1 is already in flight on device — so this loop streams
    tokens to clients (and drains admissions/aborts for the next step)
    exactly in the window the device is computing. When the queue
    drains, any dangling speculative dispatch is discarded before the
    thread sleeps so its device buffers free promptly."""

    def __init__(self, engine: JaxEngine):
        self.engine = engine
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._queues: dict[str, asyncio.Queue] = {}
        self._pending: list[tuple[PreprocessedRequest, SamplingParams]] = []
        self._aborts: list[str] = []
        self._ops: list[tuple] = []
        #: request_id -> absolute epoch deadline; the engine thread
        #: error-finishes expired streams mid-decode (the scheduler
        #: already drops expired WAITING requests pre-admission)
        self._deadlines: dict[str, float] = {}
        #: request_id -> trace id, populated ONLY while tracing is on:
        #: _add_pending stamps it onto the engine-side Request so phase
        #: exemplars and the breakdown enrichment know their trace
        self._trace_ids: dict[str, str] = {}
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        #: stall watchdog (telemetry/watchdog.py, config.stall_watchdog):
        #: built in start() — it needs the running event loop, which is
        #: deliberately NOT the engine thread it watches
        self.watchdog = None

    def _start_watchdog(self) -> None:
        cfg = getattr(self.engine, "config", None)
        if cfg is None or not getattr(cfg, "stall_watchdog", False):
            return
        import weakref

        from dynamo_tpu.telemetry.watchdog import StallWatchdog

        eng = self.engine

        def itl_ms():
            """Live ITL-p95 estimate from the SLO plane (None cold)."""
            slo = getattr(eng, "slo", None)
            if slo is None:
                return None
            sk = slo.sketches.get("itl_ms")
            return sk.quantile(0.95) if sk is not None and sk.count else None

        self.watchdog = StallWatchdog(
            itl_estimate_ms=itl_ms,
            flight=getattr(eng, "flight", None),
            stall_factor=cfg.stall_factor,
            stall_min_s=cfg.stall_min_s,
            queue_wait_budget_s=cfg.stall_queue_wait_s,
            hard_deadline_s=cfg.stall_hard_deadline_s,
            on_wedged=self._wedge_request,
        )
        self.watchdog.start()
        try:
            eng._watchdog_ref = weakref.ref(self.watchdog)
        except AttributeError:
            pass  # non-JaxEngine test doubles need not carry the slot

    def _wedge_request(self, request_id: str, info: dict) -> None:
        """Hard-deadline action (config.stall_hard_deadline_s): error-
        finish the wedged stream through its output queue — the client
        unblocks even while the engine thread is stuck — and enqueue an
        abort for whenever the engine recovers."""
        self._post(
            request_id,
            {
                "error": (
                    f"stall watchdog: {info.get('cause')} for "
                    f"{info.get('stalled_s')}s; stream error-finished by "
                    "hard deadline"
                )
            },
        )
        self._post(request_id, None)
        with self._lock:
            self._aborts.append(request_id)
        self._wake.set()

    def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._start_watchdog()
        self._thread = threading.Thread(target=self._run, daemon=True, name="engine")
        self._thread.start()

    def stop(self) -> None:
        self._stop = True
        self._wake.set()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self._thread is not None:
            self._thread.join(timeout=10)

    # -- engine thread -----------------------------------------------------

    def _drain_inbox(self):
        with self._lock:
            pending, self._pending = self._pending, []
            aborts, self._aborts = self._aborts, []
            ops, self._ops = self._ops, []
        return pending, aborts, ops

    def _run_ops(self, ops) -> None:
        for fn, fut in ops:
            try:
                res = fn(self.engine)
                self._loop.call_soon_threadsafe(
                    lambda f=fut, r=res: f.done() or f.set_result(r)
                )
            except Exception as e:
                self._loop.call_soon_threadsafe(
                    lambda f=fut, err=e: f.done() or f.set_exception(err)
                )

    def _emit(self, outputs) -> None:
        wd = self.watchdog
        clock = getattr(self.engine.metrics, "dry_clock", None)
        with phase(
            self.engine.metrics, "engine.emit", "time_emit_ms",
            posted=len(outputs),
        ):
            for out in outputs:
                if clock is not None:
                    # posting a fused dispatch's tokens takes
                    # milliseconds (the event loop's thread contends for
                    # the interpreter): a boundary of the dry clock each
                    clock.poll()
                if wd is not None and out.new_token_ids:
                    # engine-side progress mark: a wedged engine thread
                    # stops exactly these, which is what the watchdog
                    # detects
                    wd.progress(out.request_id)
                self._post(out.request_id, output_to_dict(out))
                if out.finish_reason is not None:
                    if wd is not None:
                        wd.done(out.request_id)
                    self._post(out.request_id, None)

    def _idle_wait(self) -> None:
        """Nothing to run: sleep until woken (`engine.wait` in a capture:
        device idle under it is nobody's fault)."""
        with phase(None, "engine.wait"):
            self._wake.wait(timeout=0.05)
        self._wake.clear()

    def _add_pending(self, req, sampling) -> None:
        """Admit one queued request on the engine thread; a full waiting
        queue answers 'overloaded' with a Retry-After hint priced from
        the live SLO sketches (docs/operations.md)."""
        eng = self.engine
        kwargs = {}
        deadline = getattr(req, "deadline", None)
        if deadline:
            # only deadline-carrying requests pass the kwarg — engines
            # without deadline support (test doubles, older externals)
            # keep their add_request signature working
            kwargs["deadline"] = deadline
        try:
            req_obj = eng.add_request(
                req.request_id, req.token_ids, sampling,
                mm_embeds=req.mm_embeds,
                mm_positions=req.mm_positions,
                **kwargs,
            )
            tid = self._trace_ids.get(req.request_id)
            if tid is not None and req_obj is not None:
                try:
                    # traced request: the engine-side Request carries its
                    # trace id (exemplars + breakdown enrichment). Set by
                    # attribute so engines with narrower add_request
                    # signatures (test doubles, externals) are untouched.
                    req_obj.trace_id = tid
                except (AttributeError, TypeError):
                    pass
        except QueueFullError as e:
            eng.metrics.overload_rejects += 1
            sched = getattr(eng, "scheduler", None)
            self._post(
                req.request_id,
                {
                    "error": str(e),
                    "overloaded": True,
                    "retry_after_s": estimate_retry_after_s(
                        getattr(eng, "slo", None),
                        queue_depth=(
                            sched.num_waiting() if sched is not None else 0
                        ),
                    ),
                },
            )
            self._post(req.request_id, None)
        except Exception as e:
            self._post(req.request_id, {"error": str(e)})
            self._post(req.request_id, None)

    def _expire_deadlines(self) -> None:
        """Mid-decode deadline enforcement (engine thread): abort expired
        streams and error-finish them — pages free via the abort path,
        and the cost already sunk is the only cost paid."""
        if not self._deadlines:
            return
        now = time.time()
        with self._lock:
            expired = [r for r, d in self._deadlines.items() if now > d]
            for rid in expired:
                del self._deadlines[rid]
        eng = self.engine
        for rid in expired:
            if eng.abort_request(rid):
                try:
                    eng._runner_deadline_expired += 1
                except AttributeError:
                    pass  # non-JaxEngine test doubles
            wd = self.watchdog
            if wd is not None:
                wd.done(rid)
            self._post(rid, {"token_ids": [], "finish_reason": "error"})
            self._post(rid, None)

    def _await_takers(self) -> None:
        """Hold the next step back while it is free to: a decode slot
        has no taker and a dispatch launched ahead keeps the device busy
        (`JaxEngine.takers_wait_s`). A client that got its last token
        sends its next prompt a round trip later; a step that ends about
        then (a fused mixed step of a short chunk does) would take the
        prompt now or a whole decode dispatch later by the millisecond,
        and every later batch follows from it. Waits until the free
        slots have takers in the inbox, the dispatch lands, something
        else wants the loop, or the shorter of the engine's allowance
        and `TAKERS_WAIT_S`."""
        wait_s = getattr(self.engine, "takers_wait_s", None)
        if wait_s is None:
            return
        allowed = self._takers_allowance(wait_s)
        if allowed <= 0:
            return
        deadline = time.perf_counter() + min(allowed, TAKERS_WAIT_S)
        # the engine HAS work here, so this wait is on the dry clock
        # (`dry_wait_ms`); `_idle_wait`'s is not: nothing is held up
        with phase(getattr(self.engine, "metrics", None), "engine.wait"):
            while not self._stop and time.perf_counter() < deadline:
                # short naps: whether the dispatch has landed is polled
                self._wake.wait(timeout=0.002)
                self._wake.clear()
                if self._takers_allowance(wait_s) <= 0:
                    return

    def _takers_allowance(self, wait_s) -> float:
        with self._lock:
            if self._aborts or self._ops:
                return 0.0
            queued = len(self._pending)
        return wait_s(queued)

    def _run(self) -> None:
        eng = self.engine
        while not self._stop:
            self._await_takers()
            with phase(eng.metrics, "engine.intake", "time_intake_ms") as ph:
                pending, aborts, ops = self._drain_inbox()
                self._run_ops(ops)
                for req, sampling in pending:
                    self._add_pending(req, sampling)
                for rid in aborts:
                    eng.abort_request(rid)
                self._expire_deadlines()
                ph.note(added=len(pending))
            if not eng.has_work:
                drain = getattr(eng, "drain_overlap", None)
                if drain is not None:
                    drain()
                self._idle_wait()
                continue
            wd = self.watchdog
            if wd is not None:
                wd.step_begin()  # a dispatch that never returns is the
                # cause="engine_stuck" signal
            try:
                # fault-injection hook (dynamo_tpu/testing/faults.py): an
                # injected delay stalls the loop (watchdog fodder); an
                # injected error is swallowed like a real step failure
                faults.fire_sync("engine.step")
                outputs = eng.step()
            except Exception:
                logger.exception("engine step failed")
                continue
            finally:
                if wd is not None:
                    wd.step_end()
            self._emit(outputs)

    def _post(self, request_id: str, item) -> None:
        q = self._queues.get(request_id)
        if q is not None and self._loop is not None:
            self._loop.call_soon_threadsafe(q.put_nowait, item)

    # -- async side --------------------------------------------------------

    async def submit(self, fn):
        """Run fn(engine) on the engine thread (the only thread allowed to
        touch the allocator/scheduler/KV); awaitable result. Used by the
        disaggregation path for page reservation, KV injection, and
        prefilled-request admission."""
        fut = asyncio.get_running_loop().create_future()
        with self._lock:
            self._ops.append((fn, fut))
        self._wake.set()
        return await fut

    def watch_request(self, request_id: str) -> asyncio.Queue:
        """Open the output queue for a request admitted out of band (e.g.
        via add_prefilled on the engine thread)."""
        q: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = q
        return q

    def unwatch_request(self, request_id: str) -> None:
        self._queues.pop(request_id, None)
        with self._lock:
            self._deadlines.pop(request_id, None)

    def track_deadline(self, request_id: str, deadline) -> None:
        """Deadline enforcement for requests admitted out of band (the
        disaggregated decode path): drain() untracks on stream end."""
        if deadline:
            with self._lock:
                self._deadlines[request_id] = deadline
            self._wake.set()

    async def generate(
        self, context: Context, request: PreprocessedRequest
    ) -> AsyncIterator[dict]:
        # The engine thread itself is contextvar-free; this async-side
        # span brackets the whole engine residency (submit -> finish) and
        # marks the first token. Phase costs (queue wait, per-dispatch
        # prefill/decode) land in the telemetry phase histograms from the
        # scheduler/step loop.
        with telemetry.span(
            "engine.generate", service="engine",
            attrs={
                "request_id": request.request_id,
                "input_tokens": len(request.token_ids),
            },
        ) as sp:
            q = self.watch_request(request.request_id)
            deadline = getattr(request, "deadline", None)
            if sp.trace_id:
                # tracing on: let the engine thread stamp this request's
                # Request/StepOutputs with the trace (cleaned in drain)
                self._trace_ids[request.request_id] = sp.trace_id
            with self._lock:
                self._pending.append((request, _sampling_from(request)))
                if deadline:
                    self._deadlines[request.request_id] = deadline
            self._wake.set()
            generated = 0
            mixed_seen = False
            spec_seen = False
            async for item in self.drain(context, request.request_id, q):
                if generated == 0:
                    sp.add_event("first_token")
                if not mixed_seen and item.get("mixed"):
                    # at least one token rode a mixed prefill+decode step
                    mixed_seen = True
                    sp.set_attr("mixed", True)
                if not spec_seen and item.get("spec"):
                    # at least one token rode a speculative verify step
                    spec_seen = True
                    sp.set_attr("spec", True)
                qw = item.get("queue_wait_ms")
                if qw is not None:
                    # measured admission wait (timeline breakdown input)
                    sp.set_attr("queue_wait_ms", round(float(qw), 3))
                stall = item.get("stall_ms")
                if stall is not None:
                    sp.set_attr("decode_stall_ms", round(float(stall), 3))
                generated += len(item.get("token_ids", ()))
                yield item
            sp.set_attr("generated_tokens", generated)

    async def drain(
        self, context: Context, request_id: str, q: asyncio.Queue
    ) -> AsyncIterator[dict]:
        """Stream a watched request's output queue: the single place that
        knows the cancel/sentinel/error protocol (used by generate and the
        disaggregated decode path). Also the single place every streamed
        request enters/leaves the stall watchdog — with its current
        trace/span ids, so a stall diagnosis can name the wedged trace."""
        wd = self.watchdog
        if wd is not None:
            sp = telemetry.current_span()
            wd.track(
                request_id,
                {"trace_id": sp.trace_id, "span_id": sp.span_id}
                if sp is not None
                else None,
            )
        ended = False  # the engine said its last word on this request
        try:
            while True:
                if context.cancelled:
                    return
                # race the queue against cancellation: a client that
                # disconnects while its request still sits in the
                # WAITING queue (no items ever arrive) must abort it —
                # a bare q.get() would hold the slot forever
                item = await queue_get_or_cancelled(context, q)
                if item is CANCELLED:
                    continue  # loop re-checks context.cancelled -> abort
                ended = item is None or "error" in item
                if item is None:
                    return
                if "error" in item:
                    if item.get("overloaded"):
                        raise OverloadedError(
                            item["error"], item.get("retry_after_s")
                        )
                    raise RuntimeError(item["error"])
                yield item
        finally:
            if not ended:
                # cancelled, or the consumer went away: a client that
                # hangs up mid-stream fails the frontend's next write and
                # this generator is CLOSED where it stands (at the yield),
                # never resumed to see `context.cancelled`; without the
                # abort the engine decodes the stream to its last token
                # for nobody
                with self._lock:
                    self._aborts.append(request_id)
                self._wake.set()
            if wd is not None:
                wd.done(request_id)
            with self._lock:
                self._deadlines.pop(request_id, None)
            self._queues.pop(request_id, None)
            self._trace_ids.pop(request_id, None)

    async def embed(self, prompts, normalize: bool = True):
        """Embedding vectors via the engine thread (shares the page pool
        and jit cache with the serving loop)."""
        return await self.submit(lambda eng: eng.embed(prompts, normalize))

    @property
    def metrics(self):
        return self.engine.metrics


class SpmdEngineRunner(AsyncEngineRunner):
    """Leader-side runner for one replica of a cross-host lockstep group
    (engine/spmd.py): admissions, aborts, and cache clears ride the
    driver's broadcast so every host's scheduler replica stays identical;
    the jitted steps execute SPMD over the shared mesh.

    Contract differences from the base runner:
    - submit(fn) ops MUST be read-only (metrics snapshots, hit queries) —
      a mutating op would desync the replicas. The one mutating op the
      worker needs, prefix-cache clear, has clear_kv().
    - multimodal requests are refused (embeddings cannot ride the JSON
      event broadcast yet).
    """

    def __init__(self, engine, driver):
        super().__init__(engine)
        self.driver = driver
        self._clears: list[asyncio.Future] = []

    async def clear_kv(self) -> int:
        """Replicated prefix-cache clear; resolves to freed page count."""
        fut = asyncio.get_running_loop().create_future()
        with self._lock:
            self._clears.append(fut)
        self._wake.set()
        return await fut

    async def embed(self, prompts, normalize: bool = True):
        # engine.embed dispatches leader-only jitted SPMD programs and
        # allocates scratch pages — the followers would never join the
        # collectives (cross-host hang) and the allocators would desync.
        raise RuntimeError(
            "embeddings are not supported on a cross-host SPMD group yet"
        )

    def _run(self) -> None:
        drv = self.driver
        eng = self.engine
        while not self._stop:
            with phase(eng.metrics, "engine.intake", "time_intake_ms") as ph:
                pending, aborts, ops = self._drain_inbox()
                with self._lock:
                    clears, self._clears = self._clears, []
                self._run_ops(ops)  # read-only by contract
                submitted: list[str] = []
                for req, sampling in pending:
                    if req.mm_embeds is not None:
                        self._post(
                            req.request_id,
                            {
                                "error": "multimodal requests are not "
                                "supported on a cross-host SPMD group yet"
                            },
                        )
                        self._post(req.request_id, None)
                        continue
                    drv.submit(req.request_id, list(req.token_ids), sampling)
                    submitted.append(req.request_id)
                for rid in aborts:
                    drv.abort(rid)
                if clears:
                    drv.clear_cache()
                ph.note(added=len(submitted))
            if not (drv._pending or eng.has_work):
                self._idle_wait()
                continue
            try:
                outputs = drv.step()
            except Exception as e:  # broadcast-layer failure (the driver
                # already swallows engine.step errors symmetrically)
                logger.exception("lockstep step failed")
                self._fail_clears(clears, e)
                # This round's admissions were popped from the driver's
                # pending queue before the broadcast died — they reached
                # neither the engine nor the followers. Fail them (only
                # the ones actually submitted; refused multimodal ones
                # already got their error); their clients would otherwise
                # wait forever.
                for rid in submitted:
                    self._post(rid, {"error": f"lockstep step failed: {e}"})
                    self._post(rid, None)
                drv.submit_errors.clear()
                continue
            for rid, err in drv.submit_errors:
                self._post(rid, {"error": err})
                self._post(rid, None)
            drv.submit_errors.clear()
            for fut in clears:
                self._loop.call_soon_threadsafe(
                    lambda f=fut, n=drv.last_cleared: f.done()
                    or f.set_result(n)
                )
            self._emit(outputs)
        # release the followers' serve() loops, then fail any flush
        # still waiting (it would otherwise await forever)
        try:
            drv.shutdown()
        except Exception:  # noqa: BLE001 — best-effort during teardown
            logger.warning("lockstep shutdown broadcast failed", exc_info=True)
        with self._lock:
            leftovers, self._clears = self._clears, []
        self._fail_clears(
            leftovers, RuntimeError("engine runner stopped")
        )

    def _fail_clears(self, clears, exc: Exception) -> None:
        for fut in clears:
            self._loop.call_soon_threadsafe(
                lambda f=fut, e=exc: f.done() or f.set_exception(e)
            )


def fake_embedding(tokens, dim: int = 32):
    """Deterministic stand-in embedding for echo/mock engines: a hashed
    bag-of-tokens projection, L2-normalized. Lets the /v1/embeddings path
    be exercised end-to-end with no model."""
    import numpy as np
    import xxhash

    vec = np.zeros(dim, np.float32)
    for pos, tok in enumerate(tokens):
        h = xxhash.xxh64_intdigest(f"{tok}".encode(), seed=7)
        vec[h % dim] += 1.0 + 0.01 * (pos % 7)
    norm = float(np.linalg.norm(vec))
    return vec / norm if norm > 0 else vec


class EchoEngine:
    """Echoes the prompt tokens back, one per step (engines.rs EchoCore)."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay

    async def generate(self, context, request: PreprocessedRequest):
        n = min(len(request.token_ids), request.max_tokens)
        for i, tok in enumerate(request.token_ids[:n]):
            if context.cancelled:
                return
            if self.delay:
                await asyncio.sleep(self.delay)
            yield {
                "token_ids": [tok],
                "finish_reason": "stop" if i == n - 1 else None,
            }

    async def embed(self, prompts, normalize: bool = True):
        import numpy as np

        return np.stack([fake_embedding(p) for p in prompts])
