"""Overload protection & graceful degradation (docs/operations.md
"Overload & draining"): the deterministic fault-injector matrix, bounded
admission (QueueFullError -> OverloadedError -> HTTP 429 + Retry-After),
the SLO-burn shedder, end-to-end deadlines (pre-admission drop +
mid-decode expiry + the deadline_guard wrapper), pre-admission client
disconnect, the disagg dead-letter cap, push-router retry backoff,
graceful drain, and the everything-off bit-identity pin."""

from __future__ import annotations

import asyncio
import time
from dataclasses import replace

import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.engine.scheduler import QueueFullError
from dynamo_tpu.preprocessor.preprocessor import PreprocessedRequest
from dynamo_tpu.runtime.context import Context
from dynamo_tpu.runtime.overload import (
    OverloadedError,
    deadline_guard,
    estimate_retry_after_s,
)
from dynamo_tpu.testing import faults


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def tiny_cfg():
    return EngineConfig.for_tests()


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    """Every test starts and ends with fault injection OFF."""
    faults.uninstall()
    yield
    faults.uninstall()


def _pre(rid, prompt=None, max_tokens=4, deadline=None, **kw):
    return PreprocessedRequest(
        request_id=rid,
        token_ids=prompt or [5, 17, 42, 99],
        max_tokens=max_tokens,
        temperature=0.0,
        ignore_eos=True,
        deadline=deadline,
        **kw,
    )


# -- fault injector (satellite 6: the fast deterministic fault matrix) ------


@pytest.mark.parametrize("point", faults.HOOK_POINTS)
@pytest.mark.parametrize("kind", ["drop", "error", "delay"])
def test_fault_matrix_every_point_every_kind(point, kind):
    """Every hook point x drop/delay/error behaves identically at the
    async AND sync entries: the chaos harness can aim any fault anywhere."""
    expected = {
        "drop": ConnectionError,
        "error": faults.FaultError,
    }.get(kind)

    async def fire_async(inj):
        t0 = time.perf_counter()
        if expected is not None:
            with pytest.raises(expected):
                await faults.fire(point)
        else:
            await faults.fire(point)
            assert time.perf_counter() - t0 >= 0.02
        assert inj.fired[(point, kind)] == 1
        assert inj.log[0][:2] == (point, kind)

    inj = faults.install(seed=3)
    inj.add_rule(point, kind, delay_ms=25.0)
    run(fire_async(inj))

    inj = faults.install(seed=3)
    inj.add_rule(point, kind, delay_ms=25.0)
    t0 = time.perf_counter()
    if expected is not None:
        with pytest.raises(expected):
            faults.fire_sync(point)
    else:
        faults.fire_sync(point)
        assert time.perf_counter() - t0 >= 0.02
    assert inj.fired[(point, kind)] == 1


def test_fault_hooks_are_noops_without_injector():
    faults.uninstall()
    faults.fire_sync("engine.step")
    run(faults.fire("fabric.call", op="kv.get"))


def test_corrupt_queue_payload_rejected_never_lands():
    """Corrupt kind on the fabric plane (ISSUE 12 satellite): a flipped
    byte in a queue.push frame fails the codec's xxh3 check server-side
    — the push ERRORS (the corrupt item never lands in the queue), the
    session drops, and the reconnecting client's later pushes land."""
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.fabric import FabricServer

    async def main():
        server = FabricServer(port=0)
        await server.start()
        rt = await DistributedRuntime.create(server.address)
        fab = rt.fabric
        try:
            await fab.queue_push("q", {"h": 1}, b"payload")
            assert await fab.queue_len("q") == 1
            inj = faults.install(seed=0)
            inj.add_rule("fabric.call", "corrupt", times=1)
            with pytest.raises(Exception):
                await asyncio.wait_for(
                    fab.queue_push("q", {"h": 2}, b"evil"), 10
                )
            assert inj.fired[("fabric.call", "corrupt")] == 1
            faults.uninstall()
            # the client session re-establishes; good pushes land again
            for _ in range(50):
                try:
                    await asyncio.wait_for(
                        fab.queue_push("q", {"h": 3}, b"fine"), 2
                    )
                    break
                except Exception:
                    await asyncio.sleep(0.1)
            # exactly the two GOOD items — the corrupt one never landed
            assert await fab.queue_len("q") == 2
        finally:
            faults.uninstall()
            await rt.close()
            await server.stop()

    run(main())


def test_rule_times_cap_and_ctx_match():
    inj = faults.install(seed=0)
    inj.add_rule("fabric.call", "error", times=2, op="queue.pop")

    async def go():
        # wrong op never fires
        await inj.fire("fabric.call", op="kv.get")
        for _ in range(2):
            with pytest.raises(faults.FaultError):
                await inj.fire("fabric.call", op="queue.pop")
        # budget exhausted: passes through
        await inj.fire("fabric.call", op="queue.pop")

    run(go())
    assert inj.fired[("fabric.call", "error")] == 2


def test_partition_normalizes_to_persistent_drop():
    rule = faults.FaultRule(point="transfer.send", kind="partition", prob=0.3,
                           times=5)
    assert rule.kind == "drop" and rule.prob == 1.0 and rule.times is None


def test_seeded_probability_is_deterministic():
    def fire_pattern(seed):
        inj = faults.FaultInjector(seed=seed)
        inj.add_rule("engine.step", "error", prob=0.5)
        pattern = []
        for _ in range(32):
            try:
                inj.fire_sync("engine.step")
                pattern.append(0)
            except faults.FaultError:
                pattern.append(1)
        return pattern

    assert fire_pattern(7) == fire_pattern(7)
    assert fire_pattern(7) != fire_pattern(8)  # astronomically unlikely tie
    assert 0 < sum(fire_pattern(7)) < 32


def test_unknown_point_and_kind_rejected_at_install():
    with pytest.raises(ValueError, match="unknown hook point"):
        faults.FaultRule(point="typo.site", kind="drop")
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.FaultRule(point="engine.step", kind="explode")


def test_parse_spec_round_trip_and_errors(monkeypatch):
    rules = faults.parse_spec(
        "transfer.land:error:1.0:times=2;engine.step:delay:0.5:delay_ms=200"
    )
    assert [(r.point, r.kind, r.prob) for r in rules] == [
        ("transfer.land", "error", 1.0), ("engine.step", "delay", 0.5),
    ]
    assert rules[0].times == 2 and rules[1].delay_ms == 200.0
    with pytest.raises(ValueError):
        faults.parse_spec("engine.step")  # no kind
    with pytest.raises(ValueError):
        faults.parse_spec("no.such.point:drop")
    with pytest.raises(ValueError):
        faults.parse_spec("engine.step:drop:1.0:bogus=1")

    monkeypatch.setenv("DYNTPU_FAULTS", "ingress.call:error:1.0:times=1")
    monkeypatch.setenv("DYNTPU_FAULTS_SEED", "11")
    inj = faults.install_from_env()
    assert inj is not None and faults.get_injector() is inj
    assert inj.rules[0].point == "ingress.call"
    monkeypatch.delenv("DYNTPU_FAULTS")
    faults.uninstall()
    assert faults.install_from_env() is None


# -- bounded admission ------------------------------------------------------


def test_scheduler_waiting_queue_cap(tiny_cfg):
    eng = JaxEngine(replace(tiny_cfg, max_waiting=2))
    sp = SamplingParams(temperature=0.0, max_tokens=2, ignore_eos=True)
    eng.add_request("a", [1, 2, 3], sp)
    eng.add_request("b", [1, 2, 3], sp)
    with pytest.raises(QueueFullError):
        eng.add_request("c", [1, 2, 3], sp)
    assert len(eng.scheduler.waiting) == 2
    # capacity frees as requests admit/finish
    eng.run_to_completion()
    eng.add_request("c", [1, 2, 3], sp)


def test_runner_overload_surfaces_retry_after(tiny_cfg):
    """A full waiting queue answers OverloadedError (not a hang, not a
    plain error) with a clamped Retry-After hint, while admitted work
    keeps streaming."""
    from dynamo_tpu.engine.async_engine import AsyncEngineRunner

    cfg = replace(tiny_cfg, max_seqs=1, max_waiting=1, overlap_decode=False)
    eng = JaxEngine(cfg)
    # keep "run" on the engine long enough that "wait" is still queued
    # when "shed" knocks, even with a warm compile cache. 300ms: the
    # fused K-step decode retires up to decode_steps=8 tokens per paced
    # step, so "run" (24 tokens ≈ 3 steps) must still be mid-flight at
    # the 0.4s probe — at 30ms it occasionally finished first.
    faults.install(seed=0).add_rule("engine.step", "delay", delay_ms=300.0)

    async def go():
        runner = AsyncEngineRunner(eng)
        runner.start()
        try:
            async def consume(rid, max_tokens):
                out = []
                async for item in runner.generate(
                    Context(), _pre(rid, max_tokens=max_tokens)
                ):
                    out.extend(item.get("token_ids", ()))
                return out

            def occupancy():
                # read-only length peeks from the test thread: cheap
                # enough to poll every 10ms, which matters — a
                # runner.submit round-trip pays a whole paced step and
                # would burn "run"'s lifetime on bookkeeping
                return (len(eng.scheduler.running),
                        len(eng.scheduler.waiting))

            # sequence the admissions: "run" must hold the single seat
            # BEFORE "wait" joins the queue — submitting both at once
            # races their inbox order, and a first-admitted "wait"
            # finishes fast and frees the queue before the probe
            t_run = asyncio.create_task(consume("run", 24))  # occupies max_seqs
            for _ in range(500):
                if occupancy()[0] >= 1:
                    break
                await asyncio.sleep(0.01)
            t_wait = asyncio.create_task(consume("wait", 4))  # fills max_waiting
            for _ in range(500):
                if occupancy()[1] >= 1:
                    break
                await asyncio.sleep(0.01)
            assert occupancy() == (1, 1)
            with pytest.raises(OverloadedError) as ei:
                await consume("shed", 4)
            assert ei.value.retry_after_s is not None
            assert 1.0 <= ei.value.retry_after_s <= 30.0
            assert len(await t_run) == 24
            assert len(await t_wait) == 4
            assert eng.metrics.overload_rejects == 1
        finally:
            runner.stop()

    run(go())


def test_http_max_inflight_answers_429_with_retry_after():
    import aiohttp

    from dynamo_tpu.engine.async_engine import EchoEngine
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.service import local_pipeline
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.telemetry import promlint

    async def main():
        card = ModelDeploymentCard(
            name="echo-model", tokenizer={"kind": "byte"}, context_length=512
        )
        manager = ModelManager()
        manager.add("echo-model", local_pipeline(card, EchoEngine(delay=0.05)))
        svc = HttpService(
            manager, host="127.0.0.1", port=0, max_inflight=1
        )
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        body = {
            "model": "echo-model",
            "messages": [{"role": "user", "content": "hello there"}],
            "max_tokens": 32,
        }
        try:
            async with aiohttp.ClientSession() as s:

                async def one():
                    async with s.post(
                        f"{base}/v1/chat/completions", json=body
                    ) as r:
                        return r.status, dict(r.headers), await r.json()

                results = await asyncio.gather(*(one() for _ in range(4)))
                statuses = sorted(r[0] for r in results)
                assert statuses.count(429) >= 1, statuses
                assert statuses.count(200) >= 1, statuses
                for status, headers, payload in results:
                    if status == 429:
                        assert int(headers["Retry-After"]) >= 1
                        assert "max-inflight" in payload["error"]
                # the shed shows up, by reason, in the exposition — and
                # the exposition still lints clean with the new family
                async with s.get(f"{base}/metrics") as r:
                    text = await r.text()
                assert 'dynamo_tpu_shed_total{reason="frontend_inflight"}' in text
                assert promlint.lint(text) == []
        finally:
            await svc.stop()

    run(main())


# -- the SLO-burn shedder ---------------------------------------------------


class _BurningTracker:
    """Stand-in SloTracker pinned at a chosen short-window burn rate."""

    def __init__(self, burn):
        self.windows = (60.0, 600.0)
        self._burn = burn
        self.sketches = {}
        self.count = 0

    def burn_rate(self, window_s):
        assert window_s == 60.0  # the SHORT window is the one that sheds
        return self._burn


def test_burn_shedder_ramps_and_respects_priority():
    from dynamo_tpu.frontend.admission import AdmissionController
    from dynamo_tpu.frontend.metrics import FrontendMetrics

    metrics = FrontendMetrics()
    metrics.slo["chat"] = _BurningTracker(burn=3.0)

    # rng=1.0-epsilon: only a 100% shed fraction sheds. burn 3.0 over
    # threshold 1.0 -> frac = min(1, 2.0) = 1.0 -> shed.
    ctrl = AdmissionController(
        metrics, burn_threshold=1.0, rng=lambda: 0.999
    )
    decision = ctrl.check("chat", priority=0)
    assert decision is not None and decision.reason == "burn"
    assert decision.retry_after_s >= 1.0
    # priority >= 1 rides through the same burn
    assert ctrl.check("chat", priority=1) is None
    # marginal overshoot + unlucky-free rng: admitted
    ctrl = AdmissionController(
        metrics, burn_threshold=2.9, rng=lambda: 0.999
    )
    assert ctrl.check("chat", priority=0) is None
    # healthy burn: admitted even with rng=0
    metrics.slo["chat"] = _BurningTracker(burn=0.5)
    ctrl = AdmissionController(metrics, burn_threshold=1.0, rng=lambda: 0.0)
    assert ctrl.check("chat", priority=0) is None
    assert metrics.shed_total == {"burn": 1}
    # threshold 0 reads as "shed best-effort whenever burning at all" —
    # full shed, never a ZeroDivisionError on the request path
    metrics.slo["chat"] = _BurningTracker(burn=0.1)
    ctrl = AdmissionController(metrics, burn_threshold=0.0, rng=lambda: 0.999)
    assert ctrl.check("chat", priority=0).reason == "burn"
    assert ctrl.check("chat", priority=1) is None


def test_priority_header_parsing():
    from dynamo_tpu.frontend.admission import AdmissionController

    assert AdmissionController.priority_from({"x-priority": "2"}) == 2
    assert AdmissionController.priority_from({}) == 0
    assert AdmissionController.priority_from({"x-priority": "vip"}) == 0


def test_estimate_retry_after_clamps():
    from dynamo_tpu.telemetry.slo import SloTracker

    assert estimate_retry_after_s(None) == 1.0
    tracker = SloTracker()
    assert estimate_retry_after_s(tracker) == 1.0  # cold sketch
    for _ in range(32):
        tracker.observe("itl_ms", 2000.0)
    # 2s p95 ITL x 30 queued = 60s, clamped to the 30s ceiling
    assert estimate_retry_after_s(tracker, queue_depth=30) == 30.0
    t2 = SloTracker()
    for _ in range(32):
        t2.observe("itl_ms", 0.01)
    # pathologically fast sketch still never says "retry immediately"
    assert estimate_retry_after_s(t2, queue_depth=1) == 1.0


# -- end-to-end deadlines ---------------------------------------------------


def test_scheduler_drops_expired_before_admission(tiny_cfg):
    """An already-dead request must never reach prefill: it error-
    finishes out of the waiting queue and the pool stays untouched."""
    eng = JaxEngine(tiny_cfg)
    free_before = eng.allocator.num_free
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    eng.add_request("dead", [1, 2, 3, 4], sp, deadline=time.time() - 5.0)
    eng.add_request("live", [1, 2, 3, 4], sp, deadline=time.time() + 600.0)
    done = eng.run_to_completion()
    assert done["live"] and len(done["live"]) == 8
    assert done["dead"] == []
    assert eng.scheduler.deadline_drops == 1
    assert eng.metrics.deadline_expired == 1
    assert eng.allocator.num_free == free_before
    # the step that drained it reported an ERROR finish, not LENGTH
    assert eng.scheduler.doomed == []


def test_runner_expires_stream_mid_decode(tiny_cfg):
    """A deadline that lapses DURING decode error-finishes the stream
    (client unblocks) and frees the engine's pages via the abort path."""
    from dynamo_tpu.engine.async_engine import AsyncEngineRunner

    eng = JaxEngine(replace(tiny_cfg, overlap_decode=False))
    free_before = eng.allocator.num_free
    # pace the step loop with an injected delay so the deadline reliably
    # lapses mid-decode even with a warm compile cache (the stream would
    # otherwise race to its LENGTH cap first). 300ms: the 0.8s deadline
    # admits at most ~3 paced steps, well short of the ~5 this config
    # needs to reach its 28-token context cap — at 60ms the cap
    # occasionally won the race on a fast box and finished `length`.
    faults.install(seed=0).add_rule("engine.step", "delay", delay_ms=300.0)

    async def go():
        runner = AsyncEngineRunner(eng)
        runner.start()
        try:
            items = []
            async for item in runner.generate(
                Context(),
                _pre("exp", max_tokens=100_000,
                     deadline=time.time() + 0.8),
            ):
                items.append(item)
            assert items, "stream produced nothing at all"
            assert items[-1].get("finish_reason") == "error"
        finally:
            runner.stop()

    run(go())
    eng._refresh_metrics()  # folds the runner's expiry count
    assert eng.metrics.deadline_expired >= 1
    assert not eng.scheduler.running and not eng.scheduler.waiting
    assert eng.allocator.num_free == free_before


def test_deadline_guard_wrapper():
    """The worker-side guard for engines without runner enforcement
    (echo/mock/external): items flow until expiry, then the context is
    cancelled and one error finish closes the stream."""

    async def go():
        closed = asyncio.Event()

        async def stream():
            try:
                for i in range(1000):
                    await asyncio.sleep(0.03)
                    yield {"token_ids": [i], "finish_reason": None}
            finally:
                closed.set()

        ctx = Context()
        items = [
            item
            async for item in deadline_guard(
                ctx, time.time() + 0.25, stream()
            )
        ]
        assert items[-1] == {"token_ids": [], "finish_reason": "error"}
        assert 1 <= len(items) <= 30
        assert ctx.cancelled
        assert closed.is_set()

        # a stream that finishes inside its deadline is untouched
        async def quick():
            yield {"token_ids": [1], "finish_reason": "stop"}

        ctx2 = Context()
        items = [
            item
            async for item in deadline_guard(ctx2, time.time() + 60, quick())
        ]
        assert items == [{"token_ids": [1], "finish_reason": "stop"}]
        assert not ctx2.cancelled

    run(go())


def test_deadline_rides_the_wire():
    pre = _pre("w", deadline=1234.5)
    assert PreprocessedRequest.from_dict(pre.to_dict()).deadline == 1234.5
    # absent stays absent (older peers keep parsing the dict)
    d = _pre("w2").to_dict()
    assert "deadline" not in d
    assert PreprocessedRequest.from_dict(d).deadline is None


# -- pre-admission client disconnect (satellite 3) --------------------------


def test_disconnect_while_waiting_frees_the_slot(tiny_cfg):
    """A client that vanishes while its request still sits in the WAITING
    queue must not hold the slot: the queue empties, pages stay free and
    the running stream is untouched."""
    from dynamo_tpu.engine.async_engine import AsyncEngineRunner

    cfg = replace(tiny_cfg, max_seqs=1, overlap_decode=False)
    eng = JaxEngine(cfg)
    free_before = eng.allocator.num_free
    # keep "run" on the engine so "gone" is still pre-admission (WAITING)
    # when its client disconnects
    faults.install(seed=0).add_rule("engine.step", "delay", delay_ms=30.0)

    async def go():
        runner = AsyncEngineRunner(eng)
        runner.start()
        try:
            async def consume(rid, ctx, max_tokens):
                out = []
                async for item in runner.generate(
                    ctx, _pre(rid, max_tokens=max_tokens)
                ):
                    out.extend(item.get("token_ids", ()))
                return out

            t_run = asyncio.create_task(consume("run", Context(), 24))
            ctx_w = Context()
            t_wait = asyncio.create_task(consume("gone", ctx_w, 4))
            # let "run" admit and "gone" queue up behind it
            deadline = time.time() + 10
            while (
                not eng.scheduler.running
                or [r.request_id for r in eng.scheduler.waiting] != ["gone"]
            ) and time.time() < deadline:
                await asyncio.sleep(0.02)
            assert [r.request_id for r in eng.scheduler.running] == ["run"]
            assert [r.request_id for r in eng.scheduler.waiting] == ["gone"]

            ctx_w.cancel()  # the disconnect
            out_gone = await asyncio.wait_for(t_wait, 15)
            assert out_gone == []  # never admitted, never produced
            deadline = time.time() + 10
            while eng.scheduler.waiting and time.time() < deadline:
                await asyncio.sleep(0.02)
            assert not eng.scheduler.waiting
            assert len(await t_run) == 24  # survivor unaffected
        finally:
            runner.stop()

    run(go())
    assert eng.allocator.num_free == free_before


@pytest.mark.parametrize("how", ["closed", "collected"])
def test_a_stream_abandoned_mid_decode_is_aborted(tiny_cfg, how):
    """A client that hangs up MID-STREAM fails the frontend's next write,
    and the handler leaves its `async for`: the stream's generators are
    closed where they stand (explicitly, or when collected), never resumed
    to look at the context. The engine must stop decoding for nobody: the
    request is aborted, its pages come back, and far fewer tokens than it
    asked for were generated."""
    from dynamo_tpu.engine.async_engine import AsyncEngineRunner

    eng = JaxEngine(replace(tiny_cfg, num_pages=256, max_pages_per_seq=64))
    free_before = eng.allocator.num_free
    faults.install(seed=0).add_rule("engine.step", "delay", delay_ms=5.0)

    async def go():
        runner = AsyncEngineRunner(eng)
        runner.start()
        try:
            stream = runner.generate(Context(), _pre("left", max_tokens=200))
            seen = 0
            async for item in stream:
                seen += len(item.get("token_ids", ()))
                if seen >= 3:
                    break
            if how == "closed":
                await stream.aclose()
            else:
                del stream
            deadline = time.time() + 10
            while eng.scheduler.has_work and time.time() < deadline:
                await asyncio.sleep(0.02)
            assert not eng.scheduler.has_work
        finally:
            runner.stop()

    run(go())
    assert eng.metrics.generated_tokens < 100
    assert eng.allocator.num_free == free_before


# -- disagg dead-letter (satellite 2) ---------------------------------------


def test_prefill_queue_folds_broker_redeliveries():
    """A consumer that dies mid-prefill (nack/requeue by the broker) must
    advance the poison counter even though it never touched req.attempts."""
    from dynamo_tpu.disagg.prefill_queue import PrefillQueue
    from dynamo_tpu.disagg.protocol import RemotePrefillRequest
    from dynamo_tpu.runtime.fabric.local import LocalFabric

    async def go():
        fabric = LocalFabric()
        q = PrefillQueue(fabric, name="pq")
        req = RemotePrefillRequest(
            request_id="poison", token_ids=[1, 2], page_ids=[0],
            transfer_host="127.0.0.1", transfer_port=1, sampling={},
        )
        await q.push(req)
        for expected_attempts in (0, 1, 2):
            item_id, got = await q.pop(timeout=1)
            assert got.attempts == expected_attempts
            await q.nack(item_id)
        # dead-letter parks it on the side queue, visible in queue stats
        item_id, got = await q.pop(timeout=1)
        await q.dead_letter(got)
        await q.ack(item_id)
        assert await fabric.queue_len("pq.dead") == 1
        assert await fabric.queue_len("pq") == 0

    run(go())


def test_prefill_worker_dead_letters_and_error_finishes_decode(tiny_cfg):
    """At the redelivery cap the prefill worker parks the item AND tells
    the decode side, whose waiter raises RemotePrefillError immediately
    instead of burning out the transfer timeout."""
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.disagg.protocol import RemotePrefillRequest
    from dynamo_tpu.disagg.transfer import KvTransferServer, RemotePrefillError
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.fabric.local import LocalFabric

    async def go():
        fabric = LocalFabric()
        lease = await fabric.grant_lease(1e12)
        rt = DistributedRuntime(fabric, primary_lease=lease)
        server = KvTransferServer(write_fn=lambda *a, **k: None)
        await server.start()
        pw = PrefillWorker(rt, tiny_cfg, namespace="dl")
        await pw.start()
        try:
            waiter = server.expect("poison")
            req = RemotePrefillRequest(
                request_id="poison", token_ids=[1, 2, 3], page_ids=[1],
                transfer_host="127.0.0.1", transfer_port=server.port,
                sampling={}, attempts=PrefillWorker.MAX_ATTEMPTS,
            )
            await pw.queue.push(req)
            with pytest.raises(RemotePrefillError, match="dead-letter"):
                await asyncio.wait_for(waiter, 15)
            assert pw.dead_letters >= 1
            assert pw.prefills_done == 0
            assert await fabric.queue_len(f"{pw.queue.name}.dead") >= 1
        finally:
            await pw.stop()
            await server.stop()

    run(go())


def test_prefill_worker_drops_expired_item(tiny_cfg):
    """A queued remote prefill whose client deadline already passed is
    acked away without spending a single prefill flop — and the decode
    side is TOLD (its waiter raises instead of sitting out the whole
    transfer timeout holding pages + the client connection)."""
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.disagg.protocol import RemotePrefillRequest
    from dynamo_tpu.disagg.transfer import KvTransferServer, RemotePrefillError
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.fabric.local import LocalFabric

    async def go():
        fabric = LocalFabric()
        lease = await fabric.grant_lease(1e12)
        rt = DistributedRuntime(fabric, primary_lease=lease)
        server = KvTransferServer(write_fn=lambda *a, **k: None)
        await server.start()
        pw = PrefillWorker(rt, tiny_cfg, namespace="exp")
        await pw.start()
        try:
            waiter = server.expect("late")
            req = RemotePrefillRequest(
                request_id="late", token_ids=[1, 2, 3], page_ids=[1],
                transfer_host="127.0.0.1", transfer_port=server.port,
                sampling={}, deadline=time.time() - 2.0,
            )
            await pw.queue.push(req)
            with pytest.raises(RemotePrefillError, match="deadline expired"):
                await asyncio.wait_for(waiter, 15)
            assert pw.deadline_drops == 1
            assert pw.prefills_done == 0
            assert await fabric.queue_len(pw.queue.name) == 0
        finally:
            await pw.stop()
            await server.stop()

    run(go())


# -- push-router retry backoff (satellite 1) --------------------------------


def test_router_backoff_spreads_retries_and_lands_on_the_span():
    """Retries against an overloaded worker back off (capped exponential,
    jittered) instead of hammering back-to-back, the worker is NOT marked
    down (it is healthy, just full), and the dispatch span carries
    attempts + cumulative retry_backoff_ms."""
    from dynamo_tpu import telemetry
    from dynamo_tpu.runtime import DistributedRuntime, IngressServer, RouterMode
    from dynamo_tpu.runtime.fabric import FabricServer

    calls = {"n": 0, "t": []}

    async def full_then_free_handler(ctx, request):
        calls["n"] += 1
        calls["t"].append(time.perf_counter())
        if calls["n"] <= 2:
            raise OverloadedError("waiting queue full", retry_after_s=2.0)
        yield {"ok": True}

    async def main():
        server = FabricServer(port=0)
        await server.start()
        rt_w = await DistributedRuntime.create(server.address)
        rt_c = await DistributedRuntime.create(server.address)
        telemetry.configure(enabled=True, ring_size=16)
        try:
            ingress = IngressServer()
            ingress.add_handler("generate", full_then_free_handler)
            await ingress.start()
            ep_w = rt_w.namespace("t").component("w").endpoint("generate")
            await ep_w.register("127.0.0.1", ingress.port)

            ep = rt_c.namespace("t").component("w").endpoint("generate")
            router = await ep.router(mode=RouterMode.ROUND_ROBIN)
            # deterministic floor: full jitter draws in [0, delay) — force
            # the top of the range so elapsed time is assertable
            import dynamo_tpu.runtime.push_router as pr

            orig_random = pr.random.random
            pr.random.random = lambda: 0.999
            router.retry_backoff_base_ms = 40.0
            router.retry_backoff_max_ms = 80.0
            await router.source.wait_for_instances()
            t0 = time.perf_counter()
            try:
                out = [x async for x in router.generate({}, max_attempts=5)]
            finally:
                pr.random.random = orig_random
            elapsed = time.perf_counter() - t0
            assert out == [{"ok": True}]
            assert calls["n"] == 3
            # two backoffs: ~40ms then ~80ms (capped, x0.999 jitter draw)
            assert elapsed >= 0.10, elapsed
            gap = calls["t"][2] - calls["t"][1]
            assert gap >= 0.06, gap  # the second retry waited ~80ms
            # overloaded != broken: the instance is still in rotation
            assert len(router.source.list()) == 1

            spans = [
                s for t in telemetry.list_traces(16)
                for s in telemetry.get_trace(t["trace_id"]) or []
                if s.get("name") == "router.dispatch"
            ]
            assert spans, "router.dispatch span missing from the ring"
            attrs = spans[-1].get("attrs") or {}
            assert attrs.get("attempts") == 3
            assert attrs.get("retry_backoff_ms", 0) >= 100.0

            # exhausted attempts against a saturated fleet surface the
            # worker-supplied Retry-After hint to the frontend's 429
            calls["n"] = -10_000  # always overloaded from here on
            with pytest.raises(OverloadedError) as ei:
                async for _ in router.generate({}, max_attempts=2):
                    pass
            assert ei.value.retry_after_s == 2.0
            router.close()
        finally:
            telemetry.configure(enabled=False)
            await rt_c.close()
            await rt_w.close()
            await server.stop()

    run(main())


# -- graceful drain ---------------------------------------------------------


def test_drain_finishes_inflight_and_reroutes_new_work():
    """The `drain` ingress op: the worker acks immediately, finishes its
    in-flight stream, deregisters (new work lands on the survivor) and
    fires `drained` so the host process can exit 0."""
    from dynamo_tpu.engine.async_engine import EchoEngine
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime import DistributedRuntime, RouterMode
    from dynamo_tpu.runtime.fabric.local import LocalFabric
    from dynamo_tpu.runtime.push_router import PushRouter
    from dynamo_tpu.worker import Worker

    async def go():
        fabric = LocalFabric()

        async def rt():
            lease = await fabric.grant_lease(1e12)
            return DistributedRuntime(fabric, primary_lease=lease)

        card = ModelDeploymentCard(
            name="tiny", context_length=128, kv_page_size=4
        )
        w1 = Worker(await rt(), card, engine_kind="echo", drain_budget_s=20.0)
        w2 = Worker(await rt(), card, engine_kind="echo")
        await w1.start()
        await w2.start()
        w1.echo = EchoEngine(delay=0.05)

        crt = await rt()
        ep = crt.namespace("dynamo").component("backend").endpoint("generate")
        router = await ep.router(mode=RouterMode.ROUND_ROBIN)
        await router.source.wait_for_instances()
        drain_router = PushRouter(router.source, "drain", mode=RouterMode.DIRECT)

        def req(rid):
            return {
                "request_id": rid, "token_ids": list(range(1, 11)),
                "max_tokens": 10, "temperature": 0.0, "top_p": 1.0,
                "top_k": 0, "seed": None, "stop_token_ids": [],
                "stop_strings": [], "ignore_eos": False, "annotations": {},
            }

        async def consume(rid, instance_id=None):
            got = []
            async for item in router.generate(req(rid), instance_id=instance_id):
                got.extend(item.get("token_ids", ()))
            return got

        try:
            # a slow stream pinned to w1, then drain w1 mid-stream
            t_inflight = asyncio.create_task(
                consume("inflight", instance_id=w1.instance_id)
            )
            await asyncio.sleep(0.12)  # the stream is live on w1
            replies = [
                r async for r in drain_router.generate(
                    {}, instance_id=w1.instance_id, max_attempts=1
                )
            ]
            assert replies and replies[0]["draining"] is True
            assert w1.draining

            # the in-flight stream still completes in full
            assert await asyncio.wait_for(t_inflight, 20) == list(range(1, 11))
            await asyncio.wait_for(w1.drained.wait(), 20)

            # w1 deregistered: every new request lands on the survivor
            deadline = time.time() + 10
            while len(router.source.list()) != 1 and time.time() < deadline:
                await asyncio.sleep(0.05)
            assert [i.instance_id for i in router.source.list()] == [
                w2.instance_id
            ]
            for i in range(4):
                assert await consume(f"after-{i}") == list(range(1, 11))
        finally:
            drain_router.close()
            router.close()
            await w1.stop()
            await w2.stop()

    run(go())


def test_draining_worker_rejects_new_ingress_as_retryable():
    """A request that still reaches a draining worker (stale routing
    table) bounces with retryable=true so the router tries a survivor."""
    from dynamo_tpu.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime import DistributedRuntime, RouterMode
    from dynamo_tpu.runtime.fabric.local import LocalFabric
    from dynamo_tpu.worker import Worker

    async def go():
        fabric = LocalFabric()

        async def rt():
            lease = await fabric.grant_lease(1e12)
            return DistributedRuntime(fabric, primary_lease=lease)

        card = ModelDeploymentCard(
            name="tiny", context_length=128, kv_page_size=4
        )
        w1 = Worker(await rt(), card, engine_kind="echo")
        w2 = Worker(await rt(), card, engine_kind="echo")
        await w1.start()
        await w2.start()
        crt = await rt()
        ep = crt.namespace("dynamo").component("backend").endpoint("generate")
        router = await ep.router(mode=RouterMode.ROUND_ROBIN)
        await router.source.wait_for_instances()
        try:
            w1.draining = True  # flip WITHOUT deregistering: stale table
            for i in range(4):  # round robin must hit w1 at least once
                got = []
                async for item in router.generate({
                    "request_id": f"r{i}", "token_ids": [1, 2, 3],
                    "max_tokens": 3, "temperature": 0.0, "top_p": 1.0,
                    "top_k": 0, "seed": None, "stop_token_ids": [],
                    "stop_strings": [], "ignore_eos": False,
                    "annotations": {},
                }):
                    got.extend(item.get("token_ids", ()))
                assert got == [1, 2, 3]
        finally:
            router.close()
            w1.draining = False
            await w1.stop()
            await w2.stop()

    run(go())


def test_zero_request_timeout_means_no_deadline():
    """`x-request-timeout: 0` (or negative) reads as "no timeout", not a
    1ms deadline that would 504 every request silently."""
    import aiohttp

    from dynamo_tpu.engine.async_engine import EchoEngine
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.service import local_pipeline
    from dynamo_tpu.model_card import ModelDeploymentCard

    async def main():
        card = ModelDeploymentCard(
            name="echo-model", tokenizer={"kind": "byte"}, context_length=512
        )
        manager = ModelManager()
        manager.add("echo-model", local_pipeline(card, EchoEngine()))
        # a server default would normally impose a deadline; the
        # client's explicit 0 overrides it to "none"
        svc = HttpService(
            manager, host="127.0.0.1", port=0, request_timeout_s=30.0
        )
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        body = {
            "model": "echo-model",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 8,
        }
        try:
            async with aiohttp.ClientSession() as s:
                for raw in ("0", "-1", "bogus"):
                    async with s.post(
                        f"{base}/v1/chat/completions", json=body,
                        headers={"x-request-timeout": raw},
                    ) as r:
                        assert r.status == 200, (raw, r.status)
        finally:
            await svc.stop()

    run(main())


def test_admin_drain_endpoint_validation():
    """POST /v1/admin/drain input handling: missing instance_id is a
    400, an unknown model a 404, and an in-process pipeline (no
    distributed drain_fn) a 501 — the 200 path is exercised process-
    level in tests/test_chaos.py via SIGTERM and the drain ingress op."""
    import aiohttp

    from dynamo_tpu.engine.async_engine import EchoEngine
    from dynamo_tpu.frontend import HttpService, ModelManager
    from dynamo_tpu.frontend.service import local_pipeline
    from dynamo_tpu.model_card import ModelDeploymentCard

    async def main():
        card = ModelDeploymentCard(
            name="echo-model", tokenizer={"kind": "byte"}, context_length=512
        )
        manager = ModelManager()
        manager.add("echo-model", local_pipeline(card, EchoEngine()))
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(f"{base}/v1/admin/drain", json={}) as r:
                    assert r.status == 400
                async with s.post(
                    f"{base}/v1/admin/drain",
                    json={"instance_id": "w1", "model": "nope"},
                ) as r:
                    assert r.status == 404
                async with s.post(
                    f"{base}/v1/admin/drain", json={"instance_id": "w1"}
                ) as r:
                    assert r.status == 501
        finally:
            await svc.stop()

    run(main())


# -- the pin: everything off is bit-identical -------------------------------


def test_token_path_bit_identical_with_plane_off(tiny_cfg):
    """Default config (no caps, no deadlines) with an installed-but-empty
    injector produces exactly the tokens of a bare run: every hook site
    is a no-op and no admission/deadline branch perturbs scheduling."""
    prompt = [5, 17, 42, 99, 3, 8, 21, 60]
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)

    ref = JaxEngine(tiny_cfg)
    ref.add_request("r", prompt, sp)
    ref_tokens = ref.run_to_completion()["r"]
    assert len(ref_tokens) == 12

    faults.install(seed=9)  # installed, zero rules: hooks run, never fire
    try:
        eng = JaxEngine(tiny_cfg)
        eng.add_request("r", prompt, sp)
        assert eng.run_to_completion()["r"] == ref_tokens
        assert eng.metrics.overload_rejects == 0
        assert eng.metrics.deadline_expired == 0
    finally:
        faults.uninstall()
