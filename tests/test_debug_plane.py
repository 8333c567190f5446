"""Deep-profiling / debug plane (ISSUE 7): GET /v1/debug/programs on a
real compiled engine lists its programs and their first-call ms by kind;
/v1/debug/flight serves the ring over HTTP; POST /v1/debug/profile arms
a step-bounded jax.profiler capture (and 501s gracefully without an
engine); the metrics service serves the fleet's windows from frames."""

import asyncio
import time

import aiohttp
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.telemetry import debug as debug_mod


@pytest.fixture
def engine():
    eng = JaxEngine(EngineConfig.for_tests())
    for i in range(3):
        eng.add_request(
            f"r{i}", [1 + i, 2, 3, 4],
            SamplingParams(temperature=0.0, max_tokens=6),
        )
    eng.run_to_completion()
    return eng


def test_programs_report_has_cost_model_attainment(engine):
    """GET /v1/debug/programs is the compile table (the name predates PR
    47, which took the cost model out): every program a REAL engine
    loaded is listed once with its kind, key, first-call ms and that
    call's parts (tests/test_setup_timeline.py), the kinds table counts
    them, the wire is the kinds table, and `boot` is the constructor by
    phase."""
    rep = engine.programs_report()
    assert set(rep) == {"programs", "kinds", "boot"}
    assert set(rep["boot"]) == {"before_ms", "weights_ms", "pools_ms", "ms"}
    assert rep["programs"], "compiled programs must be recorded"
    for p in rep["programs"]:
        assert set(p) == {"kind", "key", "compile_ms", "trace_ms",
                          "lower_ms", "backend_ms", "run_ms", "cache"}, p
        assert p["compile_ms"] > 0
    keys = [p["key"] for p in rep["programs"]]
    assert sorted(keys) == sorted(str(k) for k in engine.programs)
    assert len(set(keys)) == len(keys)
    kinds = rep["kinds"]
    assert "prefill" in kinds
    assert "decode_multi" in kinds or "decode" in kinds
    for kind, k in kinds.items():
        mine = [p for p in rep["programs"] if p["kind"] == kind]
        assert set(k) == {"programs", "compiles", "compile_ms", "trace_ms",
                          "lower_ms", "backend_ms", "cache_hits"}, k
        assert k["programs"] == len(mine) >= 1
        assert k["compiles"] == engine.compiles_by_kind[kind] >= 1
        assert k["compile_ms"] == pytest.approx(
            sum(p["compile_ms"] for p in mine), abs=1e-2
        )
    assert sum(k["compiles"] for k in kinds.values()) == (
        engine.metrics.compiles
    )
    # the wire rollup is exactly the kinds table (rides metrics frames)
    assert engine.programs_wire() == kinds


def test_debug_payloads_list_the_engine(engine):
    body, status = debug_mod.programs_payload()
    assert status == 200
    assert engine.debug_name in body["engines"]
    assert "kinds" in body["engines"][engine.debug_name]

    body, status = debug_mod.flight_payload("2")
    assert status == 200
    mine = body["engines"][engine.debug_name]
    assert mine["enabled"] and len(mine["records"]) <= 2
    assert debug_mod.flight_payload("x")[1] == 400

    body, status = debug_mod.stalls_payload()
    assert status == 200
    assert "stalls_by_cause" in body and "diagnoses" in body


def test_debug_endpoints_over_frontend_http(engine):
    """The single-process topology serves its engines' debug plane on
    the OpenAI frontend port."""
    from dynamo_tpu.frontend import HttpService, ModelManager

    async def main():
        svc = HttpService(ModelManager(), host="127.0.0.1", port=0)
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/v1/debug/programs") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert engine.debug_name in doc["engines"]
                kinds = doc["engines"][engine.debug_name]["kinds"]
                assert kinds and all(
                    set(k) == {"programs", "compiles", "compile_ms",
                               "trace_ms", "lower_ms", "backend_ms",
                               "cache_hits"}
                    for k in kinds.values()
                )
                boot = doc["engines"][engine.debug_name]["boot"]
                assert boot["ms"] >= boot["weights_ms"] > 0
                async with s.get(f"{base}/v1/debug/flight?n=4") as r:
                    assert r.status == 200
                    doc = await r.json()
                recs = doc["engines"][engine.debug_name]["records"]
                assert recs and recs[-1]["kind"] in ("decode", "mixed")
                async with s.get(f"{base}/v1/debug/stalls") as r:
                    assert r.status == 200
        finally:
            await svc.stop()

    asyncio.run(main())


def test_profile_capture_brackets_k_steps(engine, monkeypatch):
    """request_profile arms; the engine thread starts the trace on the
    next step and stops after K dispatched steps (profiler faked so the
    test pins the choreography, not XLA's tracer)."""
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    out = engine.request_profile(2, outdir="artifacts/profile/test-cap")
    assert out == {"dir": "artifacts/profile/test-cap", "steps": 2}
    # double-arm refused while one is pending
    with pytest.raises(RuntimeError):
        engine.request_profile(1)
    with pytest.raises(ValueError):
        engine._profile = None
        engine.request_profile(0)
    engine.request_profile(2, outdir="artifacts/profile/test-cap")
    engine.add_request(
        "p0", [9, 8, 7], SamplingParams(temperature=0.0, max_tokens=6)
    )
    engine.run_to_completion()
    assert calls[0] == ("start", "artifacts/profile/test-cap")
    assert calls[-1] == ("stop",)
    assert engine._profile is None  # capture complete, re-armable


def test_profile_payload_501_without_engines(monkeypatch):
    debug_mod._clear_registry()
    body, status = debug_mod.profile_payload({"steps": 4})
    assert status == 501
    assert "no profilable engine" in body["error"]
    assert debug_mod.profile_payload({"steps": "x"})[1] == 400
    assert debug_mod.profile_payload({"steps": -1})[1] == 400


def test_profile_payload_confines_client_dirs(engine, monkeypatch):
    """HTTP-supplied 'dir' is confined under artifacts/profile — the
    unauthenticated endpoint must not become an arbitrary-path write
    primitive (absolute paths and .. escapes are 400s)."""
    import os

    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    for bad in ("/etc/cron.d/x", "../outside", "a/../../outside"):
        body, status = debug_mod.profile_payload({"steps": 1, "dir": bad})
        assert status == 400, (bad, body)
        assert "relative" in body["error"]
    body, status = debug_mod.profile_payload(
        {"steps": 1, "dir": "my-capture"}
    )
    assert status == 200, body
    armed = next(iter(body["armed"].values()))
    assert armed["dir"] == os.path.join("artifacts", "profile", "my-capture")
    engine._profile = None  # disarm for other tests


def test_metrics_service_serves_fleet_flight_and_programs():
    """The metrics service answers /v1/debug/{flight,programs} for the
    whole fleet from the windows shipped in metrics frames, and its
    /v1/debug/profile honestly 501s (no engine in that process)."""
    from dynamo_tpu.metrics_service import MetricsService
    from dynamo_tpu.runtime import DistributedRuntime
    from dynamo_tpu.runtime.fabric import FabricServer
    from dynamo_tpu.subjects import METRICS_SUBJECT

    async def main():
        server = FabricServer(port=0)
        await server.start()
        try:
            rt_m = await DistributedRuntime.create(server.address)
            rt_w = await DistributedRuntime.create(server.address)
            svc = MetricsService(rt_m.fabric, port=0)
            await svc.start()
            await asyncio.sleep(0.1)
            frame = {
                "instance_id": "w1",
                "kv_usage": 0.4,
                "stalls_total": 1,
                "stalls_by_cause": {"stalled_stream": 1},
                "flight": [
                    {"seq": 0, "kind": "prefill", "step_ms": 4.0},
                    {"seq": 1, "kind": "decode", "step_ms": 1.0},
                ],
                "programs_by_kind": {
                    "decode": {"programs": 2, "compiles": 3,
                               "compile_ms": 812.5},
                },
            }
            await rt_w.fabric.publish(
                f"{METRICS_SUBJECT}.backend.w1", frame
            )
            await asyncio.sleep(0.2)
            base = f"http://127.0.0.1:{svc.port}"
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/v1/debug/flight?n=1") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert doc["workers"]["w1"]["records"] == [
                    {"seq": 1, "kind": "decode", "step_ms": 1.0}
                ]
                async with s.get(f"{base}/v1/debug/programs") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert doc["workers"]["w1"]["kinds"] == {
                    "decode": {"programs": 2, "compiles": 3,
                               "compile_ms": 812.5},
                }
                # per-worker stall counter + cause split in the fleet
                snap = svc.fleet_snapshot()
                w = snap["workers"]["w1"]
                assert w["stalls_total"] == 1
                assert w["stalls_by_cause"] == {"stalled_stream": 1}
                text = svc.expose()
                assert (
                    'dynamo_tpu_worker_stalls_total{component="backend",'
                    'instance="w1"} 1' in text
                )
                from dynamo_tpu.telemetry import promlint

                assert promlint.lint(text) == [], promlint.lint(text)[:5]
                async with s.post(
                    f"{base}/v1/debug/profile", json={"steps": 2}
                ) as r:
                    assert r.status == 501
            await svc.stop()
            await rt_m.close()
            await rt_w.close()
        finally:
            await server.stop()

    asyncio.run(main())


# -- a step program's first call: trace, lower, compile, nothing else ---------

_LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@pytest.fixture(scope="module")
def first_calls():
    """Every first call of a tiny engine's programs, bracketed: kind ->
    [(lowerings, cost_analysis calls, as_text calls)], one entry a
    program. The traffic reaches every kind the parametrised test names:
    a short stream decodes ahead of its batch (`decode_multi`, `feed`), a
    two-chunk prompt joins it (`mixed`), another runs alone
    (`prefill_nosample`, `prefill`)."""
    import jax
    import jax.monitoring

    seen = {"lowered": 0, "cost_analysis": 0, "as_text": 0}

    def on_event(name, _secs, **_kw):
        if name == _LOWERED_EVENT:
            seen["lowered"] += 1

    def counting(name):
        real = getattr(jax.stages.Lowered, name)

        def method(self, *a, **kw):
            seen[name] += 1
            return real(self, *a, **kw)

        return method

    mp = pytest.MonkeyPatch()
    for name in ("cost_analysis", "as_text"):
        mp.setattr(jax.stages.Lowered, name, counting(name))
    jax.monitoring.register_event_duration_secs_listener(on_event)
    by_kind: dict[str, list] = {}
    try:
        eng = JaxEngine(EngineConfig.for_tests(max_pages_per_seq=16))
        install = eng._cache_jit

        def cache_jit(kind, cache_key, jitted):
            first_call = install(kind, cache_key, jitted)

            def bracketed(*args, **kwargs):
                before = dict(seen)
                out = first_call(*args, **kwargs)
                by_kind.setdefault(kind, []).append(
                    tuple(seen[k] - before[k]
                          for k in ("lowered", "cost_analysis", "as_text"))
                )
                return out

            eng._jit_cache[cache_key] = bracketed
            return bracketed

        eng._cache_jit = cache_jit
        eng.add_request(
            "a", [5, 6, 7], SamplingParams(max_tokens=56, ignore_eos=True)
        )
        for _ in range(3):
            eng.step()
        eng.add_request(
            "b", list(range(1, 25)),
            SamplingParams(max_tokens=4, ignore_eos=True),
        )
        eng.run_to_completion()
        eng.add_request(
            "c", list(range(30, 54)),
            SamplingParams(max_tokens=4, ignore_eos=True),
        )
        eng.run_to_completion()
        assert sum(len(v) for v in by_kind.values()) == len(eng.programs)
    finally:
        mp.undo()
        jax.monitoring.unregister_event_duration_listener(on_event)
    return by_kind


@pytest.mark.parametrize(
    "kind", ["prefill", "prefill_nosample", "decode", "mixed", "feed"]
)
def test_first_call_lowers_once_and_asks_for_no_cost_or_text(
    first_calls, kind
):
    """A program's first call emits exactly ONE lowering (jax shares the
    trace and the lowering between `jitted.lower()` and the call after
    it: what `chip_smoke.py`'s own Mosaic count leans on) and reaches
    neither `Lowered.cost_analysis` nor `Lowered.as_text` (the engine
    keeps no cost model: PR 47)."""
    calls = [
        c for k, cs in first_calls.items() for c in cs
        if k == kind or (kind == "decode" and k == "decode_multi")
    ]
    assert calls, (kind, sorted(first_calls))
    for lowered, cost_analysis, as_text in calls:
        assert lowered == 1
        assert cost_analysis == 0 and as_text == 0
