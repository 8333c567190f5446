"""Performance observability plane (ISSUE 19): live HBM accounting
(GET /v1/debug/memory + the dynamo_tpu_hbm_* families), mesh/sharding
introspection (GET /v1/debug/mesh), and the fleet-side wiring through
metrics frames. The CPU-fallback byte accounting is pinned against
hand-computed param + pool sums, and the plane's collection is pinned
bit-identical on the token path."""

import asyncio
import dataclasses

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


def test_memory_report_reconciles_with_engine_accounting(engine):
    """Acceptance: on the CPU path the per-device byte sums must
    reconcile with engine-side accounting within 1% — weights against
    the param tree, KV pool against the allocator's kv_pool_bytes, and
    the totals against the per-device rows."""
    import jax

    rep = engine.memory_report()
    # no memory_stats() on the CPU backend -> documented fallback
    assert rep["source"] == "accounted"
    assert rep["devices"], "at least one local device row"

    params_bytes = sum(
        x.nbytes for x in jax.tree.leaves(engine.params)
    )
    if engine.draft_params is not None:
        params_bytes += sum(
            x.nbytes for x in jax.tree.leaves(engine.draft_params)
        )
    total_w = sum(d["weights_bytes"] for d in rep["devices"].values())
    assert abs(total_w - params_bytes) <= 0.01 * params_bytes

    total_kv = sum(d["kv_pool_bytes"] for d in rep["devices"].values())
    expected_kv = engine.metrics.kv_pool_bytes
    assert abs(total_kv - expected_kv) <= max(1, 0.01 * expected_kv)

    # totals are exactly the column sums of the device rows
    assert set(rep["totals"]) == {
        "weights_bytes", "kv_pool_bytes", "state_pool_bytes",
        "live_bytes", "free_bytes", "peak_bytes",
    }
    for comp in ("weights", "kv_pool", "state_pool", "free", "peak",
                 "live"):
        key = f"{comp}_bytes"
        assert rep["totals"][key] == sum(
            d[key] for d in rep["devices"].values()
        )
    # accounted-fallback invariants: live = weights + pools, free =
    # limit - live
    for d in rep["devices"].values():
        assert d["live_bytes"] == (
            d["weights_bytes"] + d["kv_pool_bytes"] + d["state_pool_bytes"]
        )
        assert d["free_bytes"] == max(0, d["limit_bytes"] - d["live_bytes"])
        assert d["peak_bytes"] >= d["live_bytes"]

    # the EngineMetrics gauges fold the same totals
    engine.refresh_memory_metrics()
    m = engine.metrics
    assert m.hbm_weights_bytes == rep["totals"]["weights_bytes"]
    assert m.hbm_kv_pool_bytes == rep["totals"]["kv_pool_bytes"]
    assert m.hbm_free_bytes == rep["totals"]["free_bytes"]
    assert m.hbm_peak_bytes == rep["totals"]["peak_bytes"]
    assert m.dispatch_p95_ms > 0  # the fixture ran real dispatches


def test_memory_and_programs_agree_on_peaks(engine):
    """/v1/debug/memory takes its HBM limit from the platform's
    capacity table (the name predates PR 47: /v1/debug/programs carries
    no peaks any more; the chip's live in chipbench/peaks.json)."""
    from dynamo_tpu.platform import device_hbm_bytes

    rep = engine.memory_report()
    assert "peak_flops" not in engine.programs_report()
    for d in rep["devices"].values():
        assert d["limit_bytes"] == int(device_hbm_bytes())


def test_mesh_report_single_host_spmd(cpu_mesh_devices):
    """GET /v1/debug/mesh on a single-host SPMD engine: mesh shape +
    axis names, per-param-group sharding specs whose byte totals cover
    the weights, and process identity."""
    from dynamo_tpu.parallel import MeshConfig

    cfg = dataclasses.replace(EngineConfig.for_tests(), tp=2)
    eng = JaxEngine(cfg, mesh_config=MeshConfig(dp=1, tp=2, sp=1))
    eng.add_request(
        "m", [1, 2, 3, 4], SamplingParams(temperature=0.0, max_tokens=4)
    )
    eng.run_to_completion()

    rep = eng.mesh_report()
    assert rep["process_index"] == 0 and rep["process_count"] == 1
    assert rep["multiprocess"] is False
    mesh = rep["mesh"]
    assert mesh is not None
    assert "tp" in mesh["axis_names"]
    assert mesh["shape"]["tp"] == 2
    assert mesh["devices"] == 2
    groups = rep["param_groups"]
    assert groups, "param groups must be reported"
    import jax

    total = sum(g["bytes"] for g in groups.values())
    expect = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    assert abs(total - expect) <= 0.01 * expect
    # a tp=2 engine must actually shard something
    assert any(spec != "replicated" for spec in groups)
    assert "dispatch" in rep

    # the memory report splits shards per device: exactly the mesh's
    # two devices hold weight bytes (the other forced host devices are
    # honestly reported idle), and each holds less than the full tree
    mem = eng.memory_report()
    holders = {
        k: d["weights_bytes"]
        for k, d in mem["devices"].items()
        if d["weights_bytes"] > 0
    }
    assert len(holders) == 2
    for w in holders.values():
        assert w < expect
    assert sum(holders.values()) == pytest.approx(expect, rel=0.01)


def test_mesh_report_without_mesh(engine):
    """The classic single-device engine answers honestly: no mesh,
    everything replicated on one device."""
    rep = engine.mesh_report()
    assert rep["mesh"] is None
    assert rep["process_index"] == 0
    groups = rep["param_groups"]
    assert set(groups) == {"replicated"}


def test_token_path_bit_identical_with_collection_enabled():
    """Acceptance: the plane's collection (memory/mesh reports + gauge
    refresh between steps) must not perturb the token path — stochastic
    sampling with a fixed seed produces identical tokens either way."""
    prompt = [1, 2, 3, 4, 5]
    sp = SamplingParams(temperature=1.0, max_tokens=8, ignore_eos=True)

    def run(collect: bool):
        eng = JaxEngine(EngineConfig.for_tests(seed=7))
        eng.add_request("x", list(prompt), sp)
        toks = []
        while True:
            if collect:
                eng.refresh_memory_metrics()
                eng.memory_report()
                eng.mesh_report()
            outs = eng.step()
            done = False
            for o in outs:
                toks.extend(int(t) for t in o.new_token_ids)
                done = done or o.finish_reason is not None
            if done:
                return toks

    assert run(collect=True) == run(collect=False)


def test_hbm_lines_and_payloads(engine):
    """hbm_lines sums the registered engines' device tables into the
    dynamo_tpu_hbm_* families; the payloads mirror the reports; the
    frontend exposition carrying them lints clean."""
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.telemetry import promlint

    # engines from earlier tests may not have been collected yet — the
    # summed families need exactly one engine to assert against
    debug_mod._clear_registry()
    debug_mod.register_engine(engine, engine.debug_name)

    lines = debug_mod.hbm_lines()
    text = "\n".join(lines)
    for comp in debug_mod.HBM_COMPONENTS:
        assert f"# TYPE dynamo_tpu_hbm_{comp}_bytes gauge" in text
    rep = engine.memory_report()
    w0 = rep["devices"]["0"]["weights_bytes"]
    assert f'dynamo_tpu_hbm_weights_bytes{{device="0"}} {w0}' in text

    body, status = debug_mod.memory_payload()
    assert status == 200
    assert body["engines"][engine.debug_name]["source"] == "accounted"
    body, status = debug_mod.mesh_payload()
    assert status == 200
    assert body["engines"][engine.debug_name]["process_index"] == 0

    exposition = FrontendMetrics().expose()
    assert "dynamo_tpu_hbm_weights_bytes" in exposition
    assert promlint.lint(exposition) == [], promlint.lint(exposition)[:5]


def test_hbm_lines_zeroed_without_engines():
    """The families stay present (zeros) with no engines registered —
    the Grafana panel-vs-emitted-names gate depends on it."""
    debug_mod._clear_registry()
    text = "\n".join(debug_mod.hbm_lines())
    assert 'dynamo_tpu_hbm_weights_bytes{device="0"} 0' in text


def test_frontend_serves_memory_and_mesh(engine):
    from dynamo_tpu.frontend import HttpService, ModelManager

    async def main():
        svc = HttpService(ModelManager(), host="127.0.0.1", port=0)
        await svc.start()
        base = f"http://127.0.0.1:{svc.port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/v1/debug/memory") as r:
                    assert r.status == 200
                    doc = await r.json()
                mine = doc["engines"][engine.debug_name]
                dev = next(iter(mine["devices"].values()))
                assert dev["weights_bytes"] > 0
                async with s.get(f"{base}/v1/debug/mesh") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert (
                    doc["engines"][engine.debug_name]["param_groups"]
                )
        finally:
            await svc.stop()

    asyncio.run(main())


def test_metrics_service_fleet_memory_mesh_and_host_skew():
    """The metrics service serves the fleet's memory/mesh reports from
    frames, folds the hbm_* gauges into the worker families and the
    fleet snapshot, and derives the per-host dispatch-skew family."""
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
                "hbm_weights_bytes": 1000, "hbm_kv_pool_bytes": 500,
                "hbm_free_bytes": 4000,
                "hbm_peak_bytes": 1600, "host": 1,
                "dispatch_p95_ms": 12.5,
                "memory": {
                    "source": "accounted",
                    "devices": {"0": {"kind": "cpu", "weights_bytes": 1000}},
                    "totals": {"weights_bytes": 1000},
                },
                "mesh": {
                    "mesh": None, "process_index": 1,
                    "process_count": 2,
                    "param_groups": {"replicated": {"params": 4,
                                                    "bytes": 1000}},
                },
            }
            await rt_w.fabric.publish(
                f"{METRICS_SUBJECT}.backend.w1", frame
            )
            await asyncio.sleep(0.2)
            base = f"http://127.0.0.1:{svc.port}"
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/v1/debug/memory") as r:
                    assert r.status == 200
                    doc = await r.json()
                w = doc["workers"]["w1"]
                assert w["source"] == "accounted"
                assert w["devices"]["0"]["weights_bytes"] == 1000
                async with s.get(f"{base}/v1/debug/mesh") as r:
                    assert r.status == 200
                    doc = await r.json()
                assert doc["workers"]["w1"]["process_index"] == 1

            snap = svc.fleet_snapshot()
            w = snap["workers"]["w1"]
            assert w["hbm_weights_bytes"] == 1000
            assert w["host"] == 1 and w["dispatch_p95_ms"] == 12.5

            text = svc.expose()
            assert (
                'dynamo_tpu_worker_hbm_weights_bytes{component="backend",'
                'instance="w1"} 1000' in text
            )
            assert (
                'dynamo_tpu_fleet_host_dispatch_p95_ms{host="1"} 12.5'
                in text
            )
            from dynamo_tpu.telemetry import promlint

            assert promlint.lint(text) == [], promlint.lint(text)[:5]
            await svc.stop()
            await rt_m.close()
            await rt_w.close()
        finally:
            await server.stop()

    asyncio.run(main())
