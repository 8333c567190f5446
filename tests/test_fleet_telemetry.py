"""Fleet-wide live telemetry plane (ISSUE 6 acceptance).

- e2e: >=2 JaxEngine workers + HTTP frontend under generated traffic
  produce a /v1/fleet snapshot whose MERGED TTFT/ITL percentiles sit
  within 1% rank of the exact offline percentiles of the raw worker
  observations, with compile counters, page-pool gauges, and a
  tokens/s gauge present per worker; both Prometheus
  expositions (fleet + frontend SLO) pass the promlint gate.
- hardening: a worker vanishing between polls ages out of the snapshot
  (last_seen_s), malformed frames are logged-and-skipped, and the pump
  keeps serving later legitimate frames.
- scripts/fleet_top.py renders a recorded snapshot.
- --no-fleet-telemetry is bit-identical on the token path.
"""

import asyncio
import importlib.util
import json
import pathlib
import sys

import aiohttp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.frontend import HttpService, ModelManager
from dynamo_tpu.frontend.service import ModelWatcher
from dynamo_tpu.metrics_service import MetricsService
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.fabric import FabricServer
from dynamo_tpu.subjects import METRICS_SUBJECT
from dynamo_tpu.worker import Worker

REPO = pathlib.Path(__file__).resolve().parent.parent


def run(coro):
    return asyncio.run(coro)


def _card(name: str) -> ModelDeploymentCard:
    return ModelDeploymentCard(
        name=name, tokenizer={"kind": "byte"}, context_length=32,
        kv_page_size=4,
    )


def _rank_bracket(data, q: float, est: float, slack: float = 0.01):
    """`est` is within `slack` rank of the exact quantile iff it lies
    between the exact quantiles at q±slack (tiny float epsilon)."""
    lo = float(np.percentile(data, max(0.0, (q - slack)) * 100.0))
    hi = float(np.percentile(data, min(1.0, (q + slack)) * 100.0))
    eps = 1e-6 + 1e-3 * max(abs(lo), abs(hi))
    assert lo - eps <= est <= hi + eps, (
        f"q={q}: estimate {est} outside exact-rank bracket "
        f"[{lo}, {hi}] of n={len(data)}"
    )


def test_fleet_snapshot_e2e():
    async def main():
        from dynamo_tpu.telemetry import promlint

        server = FabricServer(port=0)
        await server.start()
        workers, runtimes = [], []
        recorded = {"ttft_ms": [], "itl_ms": [], "e2e_ms": []}
        try:
            for i in range(2):
                rt = await DistributedRuntime.create(server.address)
                runtimes.append(rt)
                w = Worker(
                    rt, _card("fleet-tiny"),
                    engine_config=EngineConfig.for_tests(),
                    engine_kind="jax", metrics_interval=0.15,
                )
                await w.start()
                workers.append(w)
                # spy on the worker-side SLO observations so the merged
                # fleet percentiles can be checked against the EXACT
                # offline percentiles of what the sketches ingested
                eng = w.runner.engine
                orig = eng.slo.observe

                def spy(metric, value_ms, _orig=orig):
                    recorded[metric].append(float(value_ms))
                    _orig(metric, value_ms)

                eng.slo.observe = spy

            rt_f = await DistributedRuntime.create(server.address)
            runtimes.append(rt_f)
            manager = ModelManager()
            watcher = ModelWatcher(rt_f, manager)
            await watcher.start()
            for _ in range(100):
                if manager.get("fleet-tiny"):
                    break
                await asyncio.sleep(0.05)
            assert manager.get("fleet-tiny") is not None
            svc = HttpService(manager, host="127.0.0.1", port=0)
            await svc.start()

            rt_m = await DistributedRuntime.create(server.address)
            runtimes.append(rt_m)
            metrics = MetricsService(rt_m.fabric, port=0)
            await metrics.start()

            base = f"http://127.0.0.1:{svc.port}"
            mbase = f"http://127.0.0.1:{metrics.port}"

            async def one(session, i):
                body = {
                    "model": "fleet-tiny",
                    "messages": [{"role": "user", "content": f"hi {i}"}],
                    "max_tokens": 6,
                    "temperature": 0.0,
                    "stream": True,
                }
                async with session.post(
                    f"{base}/v1/chat/completions", json=body
                ) as r:
                    assert r.status == 200
                    async for _ in r.content:
                        pass

            async with aiohttp.ClientSession() as s:
                for batch in range(10):
                    await asyncio.gather(
                        *[one(s, batch * 4 + j) for j in range(4)]
                    )

            n_ttft = len(recorded["ttft_ms"])
            assert n_ttft == 40
            assert len(recorded["itl_ms"]) >= 40

            # wait until both workers' published sketches carry every
            # observation (frames ship every 0.15 s)
            async with aiohttp.ClientSession() as s:
                snap = None
                for _ in range(100):
                    async with s.get(f"{mbase}/v1/fleet") as r:
                        assert r.status == 200
                        snap = await r.json()
                    fl = snap.get("fleet", {}).get("slo", {})
                    if (
                        len(snap.get("workers", {})) >= 2
                        and fl.get("ttft_ms", {}).get("n") == n_ttft
                        and fl.get("itl_ms", {}).get("n")
                        == len(recorded["itl_ms"])
                    ):
                        break
                    await asyncio.sleep(0.1)
                else:
                    raise AssertionError(
                        f"fleet snapshot never converged: {snap}"
                    )

                # merged percentiles within 1% rank of the exact offline
                # percentiles over the pooled raw observations
                for metric in ("ttft_ms", "itl_ms", "e2e_ms"):
                    data = np.asarray(recorded[metric])
                    pcts = snap["fleet"]["slo"][metric]
                    for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                        _rank_bracket(data, q, pcts[key])

                # per-worker engine internals
                assert len(snap["workers"]) == 2
                for iid, w in snap["workers"].items():
                    assert w["role"] == "decode"
                    assert w["compiles"] > 0, (iid, w)
                    assert sum(w["compiles_by_kind"].values()) == w["compiles"]
                    assert w["kv_free_pages"] >= 0
                    assert w["kv_pages_watermark"] > 0
                    assert w["kv_total_pages"] > 0
                    assert w["tokens_per_s"] > 0.0, (iid, w)
                    assert "mfu" not in w
                    assert w["last_seen_s"] < 5.0
                    assert "slo" in w and w["slo"]["requests_total"] > 0
                    # debug plane (ISSUE 7): healthy workers report the
                    # watchdog counter at zero
                    assert w["stalls_total"] == 0, (iid, w)

                # the workers' flight windows + compile tables
                # rode the frames into the metrics service
                async with s.get(f"{mbase}/v1/debug/flight?n=8") as r:
                    assert r.status == 200
                    fdoc = await r.json()
                assert len(fdoc["workers"]) == 2
                for iid, fw in fdoc["workers"].items():
                    assert fw["records"], iid
                    assert fw["records"][-1]["kind"] in (
                        "prefill", "decode", "mixed"
                    )
                async with s.get(f"{mbase}/v1/debug/programs") as r:
                    assert r.status == 200
                    pdoc = await r.json()
                for iid, pw in pdoc["workers"].items():
                    assert pw["kinds"] and all(
                        set(k) == {"programs", "compiles", "compile_ms",
                                   "trace_ms", "lower_ms", "backend_ms",
                                   "cache_hits"}
                        for k in pw["kinds"].values()
                    ), (iid, pw)
                role = snap["roles"]["decode"]
                assert role["workers"] == 2
                assert role["slo"]["requests_total"] == 40

                # both Prometheus surfaces pass the lint gate and carry
                # the new families
                async with s.get(f"{mbase}/metrics") as r:
                    fleet_text = await r.text()
                async with s.get(f"{base}/metrics") as r:
                    front_text = await r.text()
            assert promlint.lint(fleet_text) == [], promlint.lint(fleet_text)[:5]
            assert promlint.lint(front_text) == [], promlint.lint(front_text)[:5]
            assert 'dynamo_tpu_fleet_workers{role="decode"} 2' in fleet_text
            assert "dynamo_tpu_fleet_ttft_ms{" in fleet_text
            assert "dynamo_tpu_fleet_goodput_tokens_total{" in fleet_text
            assert "dynamo_tpu_fleet_burn_rate{" in fleet_text
            assert "dynamo_tpu_fleet_compile_total{" in fleet_text
            assert "dynamo_tpu_worker_tokens_per_s{" in fleet_text
            assert "_mfu" not in fleet_text
            assert "dynamo_tpu_worker_compiles_total{" in fleet_text
            assert "dynamo_tpu_worker_kv_pages_watermark{" in fleet_text
            assert 'dynamo_tpu_slo_ttft_ms{endpoint="chat"' in front_text
            assert 'dynamo_tpu_slo_attainment{endpoint="chat"' in front_text

            await metrics.stop()
            await svc.stop()
            await watcher.stop()
        finally:
            for w in workers:
                await w.stop(drain_timeout=0)
            for rt in runtimes:
                await rt.close()
            await server.stop()

    run(main())


def test_worker_vanishes_and_malformed_frames_never_kill_the_pump():
    """Regression (satellite 1): a worker that stops publishing between
    polls ages out of the fleet snapshot; malformed frames (non-dict
    header, garbage slo wire, string-valued gauges) are skipped; the
    pump keeps serving frames that arrive after the garbage."""

    async def main():
        server = FabricServer(port=0)
        await server.start()
        try:
            rt_m = await DistributedRuntime.create(server.address)
            rt_w = await DistributedRuntime.create(server.address)
            svc = MetricsService(rt_m.fabric, component="backend", port=0)
            for agg in svc.aggregators:
                agg.stale_after = 0.6
            await svc.start()
            await asyncio.sleep(0.1)

            async def publish(iid, **extra):
                await rt_w.fabric.publish(
                    f"{METRICS_SUBJECT}.backend.{iid}",
                    {
                        "instance_id": iid,
                        "kv_usage": 0.5,
                        "requests_received": 3,
                        "generated_tokens": 12,
                        **extra,
                    },
                )

            await publish("w-stable")
            await publish(
                "w-vanishes", preemptions=5,
                compiles_by_kind={"prefill": 2},
            )
            # malformed traffic: non-dict header, garbage slo, junk gauge
            await rt_w.fabric.publish(
                f"{METRICS_SUBJECT}.backend.junk", ["not", "a", "dict"]
            )
            await publish(
                "w-garbage", slo="not-a-wire", tokens_per_s="NaN-ish"
            )
            await asyncio.sleep(0.2)

            snap = svc.fleet_snapshot()
            assert set(snap["workers"]) == {
                "w-stable", "w-vanishes", "w-garbage"
            }
            assert "slo" not in snap["workers"]["w-garbage"]
            assert "tokens_per_s" not in snap["workers"]["w-garbage"]
            assert snap["workers"]["w-stable"]["last_seen_s"] < 0.6

            def fleet_counter(text, name):
                for line in text.splitlines():
                    if line.startswith(f"dynamo_tpu_fleet_{name}"):
                        return float(line.rsplit(" ", 1)[1])
                return None

            before = svc.expose()
            assert fleet_counter(before, "preemptions_total") == 5.0
            assert 'compile_total{role="decode",kind="prefill"} 2' in before

            # w-vanishes dies between polls: only w-stable keeps
            # publishing; the stale entry ages out
            for _ in range(4):
                await asyncio.sleep(0.25)
                await publish("w-stable")
            snap = svc.fleet_snapshot()
            assert "w-vanishes" not in snap["workers"]
            assert "w-stable" in snap["workers"]

            # fleet counter families must stay monotonic across the
            # departure (Prometheus rate() would read a drop as a
            # counter reset and manufacture a spike), and the departed
            # worker's rate baseline must be pruned
            after = svc.expose()
            assert fleet_counter(after, "preemptions_total") == 5.0
            assert 'compile_total{role="decode",kind="prefill"} 2' in after
            assert "w-vanishes" not in svc._rate_state

            # the pump survived all of it: a brand-new worker lands
            await publish("w-late")
            await asyncio.sleep(0.2)
            snap = svc.fleet_snapshot()
            assert "w-late" in snap["workers"]

            # /metrics never corrupts
            from dynamo_tpu.telemetry import promlint

            text = svc.expose()
            assert promlint.lint(text) == []

            await svc.stop()
            await rt_m.close()
            await rt_w.close()
        finally:
            await server.stop()

    run(main())


def test_pump_survives_header_less_message():
    """Regression: a message object with NO .header attribute must be
    logged-and-skipped by the aggregator pump — the guard used to
    re-read msg.header inside its own except block, re-raising the very
    AttributeError it had just caught and killing the pump."""
    from dynamo_tpu.kv_router.metrics_aggregator import MetricsAggregator

    class _HeaderlessMsg:
        pass

    class _GoodMsg:
        header = {"instance_id": "w-after", "kv_usage": 0.1}

    class _FakeSub:
        def __init__(self):
            self._msgs = [_HeaderlessMsg(), _GoodMsg(), None]

        async def next(self):
            return self._msgs.pop(0)

    agg = MetricsAggregator.__new__(MetricsAggregator)
    agg._latest = {}
    agg._sub = _FakeSub()
    run(agg._pump())  # must NOT raise
    assert "w-after" in agg._latest


def test_transient_missing_slo_wire_does_not_double_count():
    """Regression: one frame with a transiently missing slo wire (the
    worker drops the key when to_wire() throws) used to read as a
    counter regression — the fold+restore cycle then permanently
    double-counted the monotonic dynamo_tpu_fleet_* families."""
    from dynamo_tpu.telemetry.slo import SloTracker

    async def main():
        server = FabricServer(port=0)
        await server.start()
        try:
            rt_m = await DistributedRuntime.create(server.address)
            rt_w = await DistributedRuntime.create(server.address)
            svc = MetricsService(rt_m.fabric, component="backend", port=0)
            await svc.start()
            await asyncio.sleep(0.1)

            tracker = SloTracker()
            tracker.observe("ttft_ms", 50.0)
            tracker.finish_request(ttft_ms=50.0, tokens=100)
            tracker.observe("ttft_ms", 60.0)
            tracker.finish_request(ttft_ms=60.0, tokens=100)

            async def publish(**extra):
                await rt_w.fabric.publish(
                    f"{METRICS_SUBJECT}.backend.w-flaky",
                    {
                        "instance_id": "w-flaky",
                        "preemptions": 3,
                        "compiles_by_kind": {"prefill": 2},
                        **extra,
                    },
                )

            def fleet_counter(name):
                for line in svc.expose().splitlines():
                    if line.startswith(f"dynamo_tpu_fleet_{name}"):
                        return float(line.rsplit(" ", 1)[1])
                return None

            # good -> degraded (slo + compiles_by_kind keys dropped,
            # exactly what worker.py does on a to_wire() failure) ->
            # good again; each expose() runs a fold pass
            await publish(slo=tracker.to_wire())
            await asyncio.sleep(0.2)
            assert fleet_counter("requests_total") == 2.0

            await publish()
            await asyncio.sleep(0.2)
            svc.expose()

            await publish(slo=tracker.to_wire())
            await asyncio.sleep(0.2)
            assert fleet_counter("requests_total") == 2.0
            assert fleet_counter("preemptions_total") == 3.0
            assert (
                'compile_total{role="decode",kind="prefill"} 2'
                in svc.expose()
            )

            await svc.stop()
            await rt_m.close()
            await rt_w.close()
        finally:
            await server.stop()

    run(main())


RECORDED_SNAPSHOT = {
    "workers": {
        "worker-decode-1": {
            "role": "decode", "component": "backend", "model": "llama3-1b",
            "last_seen_s": 0.4, "req_s": 12.5, "tok_s": 812.0,
            "kv_usage": 0.42, "kv_free_pages": 1187,
            "kv_pages_watermark": 1622, "preemptions": 3,
            "stalls_total": 2,
            "stalls_by_cause": {"stalled_stream": 1, "queue_wait": 1},
            "num_running": 9, "num_waiting": 1, "compiles": 14,
            "compiles_by_kind": {"prefill": 6, "decode_multi": 8},
            "tokens_per_s": 812.0,
            "kvbm_host_blocks": 12, "kvbm_disk_blocks": 3,
            "kvbm_demotions_total": 15, "kvbm_promotions_total": 6,
            "kvbm_host_hits_total": 5, "kvbm_disk_hits_total": 1,
            "hbm_weights_bytes": 2147483648, "hbm_kv_pool_bytes": 3435973836,
            "hbm_free_bytes": 25769803776, "hbm_peak_bytes": 6000000000,
            "host": 0, "dispatch_p95_ms": 7.2,
            "slo": {
                "requests_total": 400, "within_sla_total": 392,
                "tokens_total": 25600, "goodput_tokens_total": 25100,
                "attainment": 0.98,
                "ttft_ms": {"p50": 130.1, "p95": 410.2, "p99": 601.3,
                            "n": 400},
                "itl_ms": {"p50": 13.2, "p95": 21.8, "p99": 30.0,
                           "n": 25000},
                "windows": {"60": {"requests": 80, "attainment": 0.975,
                                   "burn_rate": 2.5}},
            },
        },
        "worker-prefill-1": {
            "role": "prefill", "component": "prefill", "model": "llama3-1b",
            "last_seen_s": 1.1, "req_s": 4.0, "tok_s": 4100.0,
            "kv_usage": 0.11, "compiles": 4,
        },
    },
    "roles": {
        "decode": {"workers": 1, "kv_usage": 0.42,
                   "tokens_per_s": 812.0, "preemptions": 3,
                   "compiles_by_kind": {"prefill": 6, "decode_multi": 8}},
        "prefill": {"workers": 1, "kv_usage": 0.11,
                    "tokens_per_s": 4100.0, "preemptions": 0,
                    "compiles_by_kind": {}},
    },
    "fleet": {
        "workers": 2,
        "slo": {
            "requests_total": 400, "within_sla_total": 392,
            "tokens_total": 25600, "goodput_tokens_total": 25100,
            "attainment": 0.98,
            "ttft_ms": {"p50": 130.1, "p95": 410.2, "p99": 601.3, "n": 400},
            "itl_ms": {"p50": 13.2, "p95": 21.8, "p99": 30.0, "n": 25000},
            "windows": {"60": {"requests": 80, "attainment": 0.975,
                               "burn_rate": 2.5},
                        "600": {"requests": 400, "attainment": 0.98,
                                "burn_rate": 2.0}},
        },
    },
}


def _load_fleet_top():
    spec = importlib.util.spec_from_file_location(
        "fleet_top", REPO / "scripts" / "fleet_top.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: recorded kept-trace summaries (metrics service GET /v1/traces) for
#: the WORST-TRACE column
RECORDED_TRACES = [
    {"trace_id": "aa11" * 8, "duration_ms": 4200.5,
     "workers": ["worker-decode-1"], "kept_reasons": ["slow_e2e"],
     "breakdown": {"total_ms": 4200.5, "dominant": "queue_wait",
                   "phases": {"queue_wait": 3000.0, "decode": 1200.5}}},
    {"trace_id": "bb22" * 8, "duration_ms": 900.0,
     "workers": ["worker-decode-1", "worker-prefill-1"],
     "kept_reasons": ["healthy_sample"],
     "breakdown": {"total_ms": 900.0, "dominant": "decode",
                   "phases": {"decode": 900.0}}},
]


def test_fleet_top_renders_events_timeline():
    ft = _load_fleet_top()
    events = [
        {"id": 1, "ts": 1754300000.0, "type": "role_flip",
         "severity": "info", "source": "worker-1", "count": 1,
         "attrs": {"src": "prefill", "dst": "decode"}},
        {"id": 2, "ts": 1754300011.0, "type": "shed",
         "severity": "warning", "source": "frontend:burn", "count": 37,
         "attrs": {"reason": "burn"}},
        {"id": 3, "ts": 1754300012.5, "type": "worker_lost",
         "severity": "critical", "source": "worker-9", "count": 1,
         "attrs": {"role": "decode"}},
    ]
    text = ft.render_events(events, color=True)
    lines = text.splitlines()
    assert len(lines) == 3
    assert "role_flip" in lines[0] and "dst=decode" in lines[0]
    assert "x37" in lines[1] and "\x1b[33m" in lines[1]  # warning color
    assert "\x1b[31m" in lines[2]  # critical color
    plain = ft.render_events(events, color=False)
    assert "\x1b[" not in plain
    assert "(no fleet events)" in ft.render_events([])


def test_fleet_top_hbm_column(tmp_path):
    """ISSUE 19 satellite: the HBM w/kv/free column renders the frame's
    hbm_* gauges compactly; workers without the plane degrade to a
    dash, not a crash."""
    ft = _load_fleet_top()
    assert ft._bshort(2147483648) == "2.0G"
    assert ft._bshort(3435973836) == "3.2G"
    assert ft._bshort(25769803776) == "24G"
    assert ft._bshort(427264) == "417K"  # binary units
    assert ft._bshort(0) == "0"
    assert ft._bshort(None) == "-"

    text = ft.render(RECORDED_SNAPSHOT)
    assert "HBM w/kv/free" in text
    decode_row = next(
        l for l in text.splitlines() if l.startswith("worker-decode-1")
    )
    assert "2.0G/3.2G/24G" in decode_row
    # prefill worker predates the plane: no hbm_* fields -> dash
    prefill_row = next(
        l for l in text.splitlines() if l.startswith("worker-prefill-1")
    )
    cols = prefill_row.split()
    assert "-" in cols


def test_fleet_top_renders_recorded_snapshot(tmp_path):
    ft = _load_fleet_top()
    text = ft.render(RECORDED_SNAPSHOT, traces=RECORDED_TRACES)
    # WORST-TRACE column: slowest kept trace touching each worker
    assert "WORST-TRACE" in text
    decode_row0 = next(
        l for l in text.splitlines() if l.startswith("worker-decode-1")
    )
    assert "aa11aa11 4200ms" in decode_row0
    prefill_row0 = next(
        l for l in text.splitlines() if l.startswith("worker-prefill-1")
    )
    assert "bb22bb22 900ms" in prefill_row0
    # without trace data the column degrades to dashes, not a crash
    text = ft.render(RECORDED_SNAPSHOT)
    assert "worker-decode-1" in text
    assert "decode" in text and "prefill" in text
    assert "MFU" not in text and "mfu" not in text
    assert "tok/s 812.0" in text  # the role's throughput in the footer
    assert "130.1" in text or "130/" in text  # ttft p50 in fleet footer
    assert "burn rate 2.50x" in text
    assert "goodput 25100/25600 tokens" in text
    # KV-economy TIER/HIT column: lower-tier residency + which tier
    # served the hits ("12h3d 5/1"); workers without KVBM show "-"
    assert "TIER/HIT" in text
    assert "12h3d 5/1" in decode_row0
    assert "12h3d" not in prefill_row0
    # stall-count + burn-rate columns (sourced from the watchdog's
    # stalls_total and the worker SLO windows)
    assert "STALLS" in text and "BURN" in text
    decode_row = next(
        l for l in text.splitlines() if l.startswith("worker-decode-1")
    )
    assert " 2 " in decode_row  # stalls_total
    assert "2.5x" in decode_row  # 60s-window burn rate
    prefill_row = next(
        l for l in text.splitlines() if l.startswith("worker-prefill-1")
    )
    assert " - " in prefill_row  # no stall/burn data: dashes, not zeros
    # the CLI one-shot path over a recorded file
    snap_file = tmp_path / "fleet.json"
    snap_file.write_text(json.dumps(RECORDED_SNAPSHOT))
    import subprocess

    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "fleet_top.py"),
         "--snapshot", str(snap_file)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "worker-prefill-1" in out.stdout


def test_no_fleet_telemetry_is_bit_identical():
    """--no-fleet-telemetry: same config/seed/prompts => identical token
    streams, no SLO tracker, zero throughput-window bookkeeping."""
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    import dataclasses

    outs = {}
    for on in (True, False):
        cfg = dataclasses.replace(
            EngineConfig.for_tests(), fleet_telemetry=on
        )
        eng = JaxEngine(cfg)
        for i in range(3):
            eng.add_request(
                f"r{i}", [1 + i, 2, 3, 4],
                SamplingParams(temperature=0.8, top_p=0.9, max_tokens=6),
            )
        outs[on] = eng.run_to_completion()
        if on:
            assert eng.slo is not None
            assert eng.metrics.tokens_per_s >= 0.0
        else:
            assert eng.slo is None
            assert len(eng._thru_window) == 0
            assert eng.metrics.tokens_per_s == 0.0
    assert outs[True] == outs[False]


def test_metrics_service_promlint_gate_with_fleet_families():
    """CI gate (satellite 5): a fully-populated exposition — worker
    frames with SLO wires + fleet families + phase histograms — lints
    clean, so future fleet metrics can't regress the format."""

    async def main():
        from dynamo_tpu.engine.engine import EngineMetrics
        from dynamo_tpu.telemetry import phases, promlint
        from dynamo_tpu.telemetry.slo import SloTracker

        server = FabricServer(port=0)
        await server.start()
        try:
            rt_m = await DistributedRuntime.create(server.address)
            rt_w = await DistributedRuntime.create(server.address)
            svc = MetricsService(rt_m.fabric, port=0)
            await svc.start()
            await asyncio.sleep(0.1)
            tr = SloTracker()
            tr.observe("ttft_ms", 100.0)
            tr.observe("itl_ms", 10.0)
            tr.observe("e2e_ms", 500.0)
            tr.finish_request(ttft_ms=100.0, itl_ms=10.0, e2e_ms=500.0,
                              tokens=64)
            frame = EngineMetrics().to_dict()
            frame.update(
                instance_id="w1", model="tiny", component="backend",
                role="decode", slo=tr.to_wire(),
                compiles_by_kind={"prefill": 2, "decode": 1},
                kv_transfer_shm_total=1, remote_prefills_total=1,
                ext_ready=1, ext_restarts_total=0,
            )
            await rt_w.fabric.publish(
                f"{METRICS_SUBJECT}.backend.w1", frame
            )
            prefill_frame = dict(frame)
            prefill_frame.update(
                instance_id="p1", component="prefill", role="prefill"
            )
            await rt_w.fabric.publish(
                f"{METRICS_SUBJECT}.prefill.p1", prefill_frame
            )
            await asyncio.sleep(0.2)
            for phase in phases.PHASES:
                phases.observe(phase, 1.5)
            text = svc.expose()
            assert promlint.lint(text) == [], promlint.lint(text)[:8]
            assert 'dynamo_tpu_fleet_workers{role="prefill"} 1' in text
            assert (
                'dynamo_tpu_fleet_sla_requests_total{role="decode"} 1'
                in text
            )
            assert "dynamo_tpu_phase_compile_ms_bucket" in text
            await svc.stop()
            await rt_m.close()
            await rt_w.close()
        finally:
            await server.stop()

    run(main())
