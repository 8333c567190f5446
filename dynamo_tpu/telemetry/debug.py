"""In-process debug surface: the `/v1/debug/*` payload layer.

Engines register themselves here (weakly — a GC'd engine drops out) so
whatever HTTP surface the process happens to have (the OpenAI frontend
in single-process serving, the metrics service for its own process) can
serve:

  GET  /v1/debug/flight[?n=]   the flight-recorder window per engine
  GET  /v1/debug/programs      the compile table: every loaded program
                               (kind, key, first-call ms) and a rollup
                               by kind
  GET  /v1/debug/memory        per-device HBM byte breakdown (weights /
                               kv_pool / live / free / peak —
                               engine.memory_report, docs/
                               observability.md "Reading the perf
                               plane")
  GET  /v1/debug/mesh          mesh shape + axis names, per-param-group
                               sharding specs, process seat, dispatch
                               window (engine.mesh_report)
  GET  /v1/debug/stalls        watchdog counters + recent diagnoses
  POST /v1/debug/profile       {"steps": K[, "dir": path]} — arm a
                               jax.profiler capture for K engine steps
                               (501 when no engine/profiler is here)

Framework-free like telemetry/http_api.py: handlers pass raw strings /
parsed bodies in and get (json-able body, status) back, so the two
aiohttp mounts can't drift apart. Remote workers' windows are served by
the metrics service from their metrics frames instead (docs/
observability.md "Debugging a slow or stuck worker").
"""

from __future__ import annotations

import itertools
import os
import weakref
from typing import Optional

#: the only place HTTP-supplied profile captures may land — a debug
#: endpoint must not become an arbitrary-path write primitive
PROFILE_BASE = os.path.join("artifacts", "profile")

#: name -> engine (weak: an engine that fell out of scope must not be
#: resurrected by its debug surface)
_engines: "weakref.WeakValueDictionary[str, object]" = (
    weakref.WeakValueDictionary()
)
_counter = itertools.count()


def register_engine(engine, name: Optional[str] = None) -> str:
    """Called by JaxEngine at construction. Returns the registry key."""
    if name is None:
        model = getattr(getattr(engine, "config", None), "model", "engine")
        name = f"{model}-{next(_counter)}"
    _engines[name] = engine
    return name


def registered_engines() -> dict:
    return dict(_engines)


def _clear_registry() -> None:
    """Test hook: isolate registry state between tests."""
    _engines.clear()


#: fabric clients (RemoteFabric) living in this process — weak, like the
#: engine registry: whatever Prometheus surface the process has gauges
#: the control-plane connection state off them (docs/operations.md
#: "Control-plane HA")
_fabric_clients: "weakref.WeakSet" = weakref.WeakSet()


def register_fabric_client(client) -> None:
    """Called by RemoteFabric at construction."""
    _fabric_clients.add(client)


def control_plane_lines(prefix: str = "dynamo_tpu") -> list[str]:
    """Process-global control-plane health: the degraded gauge (1 = no
    broker has answered past the budget; this process is serving from
    cached discovery / buffering publishes), outage counters, and
    client-observed broker failovers. Included by BOTH Prometheus
    surfaces; always emitted (zeros — including for LocalFabric
    processes, which are their own broker) so the dashboard
    panel-vs-emitted gate sees the families."""
    degraded = 0
    disconnected_s = 0.0
    entries = 0
    seconds = 0.0
    failovers = 0
    for c in list(_fabric_clients):
        if getattr(c, "degraded", False):
            degraded = 1
        disconnected_s = max(
            disconnected_s, float(getattr(c, "disconnected_s", 0.0) or 0.0)
        )
        entries += int(getattr(c, "degraded_total", 0) or 0)
        seconds += float(getattr(c, "degraded_seconds_total", 0.0) or 0.0)
        failovers += int(getattr(c, "failovers_total", 0) or 0)
    return [
        f"# TYPE {prefix}_control_plane_degraded gauge",
        f"{prefix}_control_plane_degraded {degraded}",
        f"# TYPE {prefix}_control_plane_disconnected_seconds gauge",
        f"{prefix}_control_plane_disconnected_seconds "
        f"{round(disconnected_s, 3)}",
        # "_entries_total", not "_total": the OpenMetrics rendering
        # strips counter _total suffixes into family names, and
        # "control_plane_degraded" is already the gauge's family
        f"# TYPE {prefix}_control_plane_degraded_entries_total counter",
        f"{prefix}_control_plane_degraded_entries_total {entries}",
        f"# TYPE {prefix}_control_plane_degraded_seconds_total counter",
        f"{prefix}_control_plane_degraded_seconds_total "
        f"{round(seconds, 3)}",
        f"# TYPE {prefix}_fabric_client_failovers_total counter",
        f"{prefix}_fabric_client_failovers_total {failovers}",
    ]


def spec_lines(prefix: str = "dynamo_tpu") -> list[str]:
    """Process-global speculative-decoding exposition, summed over the
    registered in-process engines: `{prefix}_spec_*_total` counters plus
    the live acceptance-rate gauge. Included by BOTH Prometheus surfaces
    (FrontendMetrics for in-process serving, MetricsService for its own
    process) — the per-WORKER fleet view rides the metrics frames as
    `{prefix}_worker_spec_*` instead. Always emitted (zeros when no
    engine speculates) so dashboards and the panel-name gate see the
    families."""
    drafted = accepted = skip_inel = skip_cool = 0
    rate_num = rate_den = 0.0
    for eng in registered_engines().values():
        m = getattr(eng, "metrics", None)
        if m is None:
            continue
        drafted += getattr(m, "spec_drafted", 0)
        accepted += getattr(m, "spec_accepted", 0)
        skip_inel += getattr(m, "spec_skipped_ineligible", 0)
        skip_cool += getattr(m, "spec_skipped_cooldown", 0)
        # weight each engine's windowed rate by its windowed drafts:
        # an ACTIVELY-FAILING draft (rate 0, window drafted > 0) must
        # pull the aggregate down, while idle engines (window drained)
        # must not — gating on the rate's truthiness would conflate them
        wd = getattr(m, "spec_window_drafted", 0) or 0
        r = getattr(m, "spec_accept_rate", None)
        if wd > 0 and isinstance(r, (int, float)):
            rate_num += float(r) * wd
            rate_den += wd
    rate = rate_num / rate_den if rate_den else 0.0
    return [
        f"# TYPE {prefix}_spec_drafted_total counter",
        f"{prefix}_spec_drafted_total {drafted}",
        f"# TYPE {prefix}_spec_accepted_total counter",
        f"{prefix}_spec_accepted_total {accepted}",
        f"# TYPE {prefix}_spec_skipped_ineligible_total counter",
        f"{prefix}_spec_skipped_ineligible_total {skip_inel}",
        f"# TYPE {prefix}_spec_skipped_cooldown_total counter",
        f"{prefix}_spec_skipped_cooldown_total {skip_cool}",
        f"# TYPE {prefix}_spec_accept_rate gauge",
        f"{prefix}_spec_accept_rate {round(rate, 4)}",
    ]


def integrity_lines(prefix: str = "dynamo_tpu") -> list[str]:
    """Process-global data-integrity counters: KV bytes whose checksum
    failed verification and were REJECTED — disk-tier blocks at rest
    (kvbm/tiers.py xxh3 trailer) and transfer-plane frames on the wire
    (runtime/codec.py framing). Always emitted (zeros included) so the
    dashboard-name gate sees the families; a nonzero rate is bit-rot or
    a failing link, never served tokens."""
    from dynamo_tpu.disagg import transfer as _transfer
    from dynamo_tpu.kvbm import tiers as _tiers

    return [
        f"# TYPE {prefix}_kvbm_disk_corrupt_total counter",
        f"{prefix}_kvbm_disk_corrupt_total {_tiers.disk_corrupt_total}",
        f"# TYPE {prefix}_transfer_corrupt_total counter",
        f"{prefix}_transfer_corrupt_total {_transfer.transfer_corrupt_total}",
    ]


def kv_index_lines(prefix: str = "dynamo_tpu") -> list[str]:
    """Process-global KV index health (kv_router/indexer.py counters):
    sequence gaps detected, targeted resyncs run (and failed), drift
    blocks corrected, and the live stale-subtree gauge. Included by BOTH
    Prometheus surfaces — the process hosting a KV-aware router (the
    frontend in single-process serving) is where the index lives; the
    metrics service additionally folds router-published kv_index.status
    frames for multi-process fleets. Always emitted (zeros) so the
    dashboard panel-vs-emitted gate sees the families."""
    from dynamo_tpu.kv_router.indexer import (
        index_counters,
        process_stale_workers,
    )

    c = index_counters
    return [
        f"# TYPE {prefix}_kv_index_gaps_total counter",
        f"{prefix}_kv_index_gaps_total {c.gaps}",
        f"# TYPE {prefix}_kv_index_resyncs_total counter",
        f"{prefix}_kv_index_resyncs_total {c.resyncs}",
        f"# TYPE {prefix}_kv_index_resync_failures_total counter",
        f"{prefix}_kv_index_resync_failures_total {c.resync_failures}",
        f"# TYPE {prefix}_kv_index_drift_blocks_total counter",
        f"{prefix}_kv_index_drift_blocks_total {c.drift_blocks}",
        f"# TYPE {prefix}_kv_index_digest_mismatches_total counter",
        f"{prefix}_kv_index_digest_mismatches_total {c.digest_mismatches}",
        f"# TYPE {prefix}_kv_index_stale_workers gauge",
        f"{prefix}_kv_index_stale_workers {process_stale_workers()}",
    ]


#: the hbm_* family names in exposition order — one list shared by the
#: emitter below, the memory-report totals, and the tests that pin them
HBM_COMPONENTS = ("weights", "kv_pool", "free", "peak")


def hbm_lines(prefix: str = "dynamo_tpu") -> list[str]:
    """Process-global HBM accounting exposition, per DEVICE, from the
    registered in-process engines' memory_report (docs/observability.md
    "Reading the perf plane"): `{prefix}_hbm_{weights,kv_pool,free,
    peak}_bytes{device=...}`. Included by BOTH Prometheus surfaces
    like spec_lines; the per-WORKER fleet rollup rides the metrics
    frames as `{prefix}_worker_hbm_*` instead. Always emitted (a zeroed
    device="0" series when no engine lives here) so dashboards and the
    panel-vs-emitted-names gate see the families."""
    per_dev: dict[str, dict[str, int]] = {}
    for eng in registered_engines().values():
        report = getattr(eng, "memory_report", None)
        if not callable(report):
            continue
        try:
            devices = report()["devices"]
        except Exception:
            continue
        for dev, row in devices.items():
            acc = per_dev.setdefault(dev, dict.fromkeys(HBM_COMPONENTS, 0))
            for comp in HBM_COMPONENTS:
                acc[comp] += int(row.get(f"{comp}_bytes", 0) or 0)
    if not per_dev:
        per_dev = {"0": dict.fromkeys(HBM_COMPONENTS, 0)}
    lines: list[str] = []
    for comp in HBM_COMPONENTS:
        lines.append(f"# TYPE {prefix}_hbm_{comp}_bytes gauge")
        for dev in sorted(per_dev):
            lines.append(
                f'{prefix}_hbm_{comp}_bytes{{device="{dev}"}} '
                f"{per_dev[dev][comp]}"
            )
    return lines


# -- payloads -------------------------------------------------------------


def parse_window(n_str: Optional[str]):
    """The `?n=` parse shared by the frontend AND metrics-service mounts
    (one copy, so the two can't drift): -> (n, error_body_or_None)."""
    if n_str is None:
        return None, None
    try:
        return int(n_str), None
    except ValueError:
        return None, {"error": "n must be int"}


def flight_payload(n_str: Optional[str]) -> tuple[dict, int]:
    """GET /v1/debug/flight?n=N -> (body, status)."""
    n, err = parse_window(n_str)
    if err is not None:
        return err, 400
    engines = {}
    for name, eng in sorted(registered_engines().items()):
        fl = getattr(eng, "flight", None)
        engines[name] = {
            "enabled": fl is not None,
            "records": fl.snapshot(n) if fl is not None else [],
        }
    return {"engines": engines}, 200


def programs_payload() -> tuple[dict, int]:
    """GET /v1/debug/programs -> per-engine compile tables."""
    engines = {}
    for name, eng in sorted(registered_engines().items()):
        report = getattr(eng, "programs_report", None)
        engines[name] = report() if callable(report) else {}
    return {"engines": engines}, 200


def memory_payload() -> tuple[dict, int]:
    """GET /v1/debug/memory -> per-engine HBM accounting tables."""
    engines = {}
    for name, eng in sorted(registered_engines().items()):
        report = getattr(eng, "memory_report", None)
        engines[name] = report() if callable(report) else {}
    return {"engines": engines}, 200


def mesh_payload() -> tuple[dict, int]:
    """GET /v1/debug/mesh -> per-engine mesh/sharding introspection."""
    engines = {}
    for name, eng in sorted(registered_engines().items()):
        report = getattr(eng, "mesh_report", None)
        engines[name] = report() if callable(report) else {}
    return {"engines": engines}, 200


def stalls_payload() -> tuple[dict, int]:
    """GET /v1/debug/stalls -> process stall counters + diagnoses."""
    from dynamo_tpu.telemetry.watchdog import stall_counters

    diagnoses = []
    for eng in registered_engines().values():
        wd = getattr(eng, "_watchdog_ref", None)
        wd = wd() if callable(wd) else wd
        if wd is not None:
            diagnoses.extend(wd.diagnoses[-8:])
    return {
        "stalls_by_cause": stall_counters.snapshot(),
        "stalls_total": stall_counters.total,
        "diagnoses": diagnoses,
    }, 200


def profile_payload(body: Optional[dict]) -> tuple[dict, int]:
    """POST /v1/debug/profile -> arm a capture on every registered
    engine that supports it. Graceful 501 when jax.profiler is missing
    or no engine lives in this process (e.g. the metrics service)."""
    body = body or {}
    try:
        steps = int(body.get("steps", 8))
        if steps < 1:
            raise ValueError
    except (TypeError, ValueError):
        return {"error": "steps must be a positive int"}, 400
    outdir = body.get("dir")
    if outdir is not None:
        if not isinstance(outdir, str):
            return {"error": "dir must be a string path"}, 400
        # confine client-supplied dirs under PROFILE_BASE: this endpoint
        # is unauthenticated and os.makedirs at an attacker-chosen
        # absolute path is a write primitive (in-process callers of
        # engine.request_profile keep full path freedom)
        norm = os.path.normpath(outdir)
        if os.path.isabs(norm) or norm.split(os.sep, 1)[0] == "..":
            return {
                "error": "dir must be a relative path "
                         f"(captures land under {PROFILE_BASE}/)"
            }, 400
        outdir = os.path.join(PROFILE_BASE, norm)
    try:
        from jax import profiler as _profiler  # noqa: F401

        if not hasattr(_profiler, "start_trace"):
            raise ImportError("jax.profiler.start_trace unavailable")
    except Exception as e:
        return {"error": f"jax profiler unavailable: {e}"}, 501
    armed = {}
    for name, eng in sorted(registered_engines().items()):
        req = getattr(eng, "request_profile", None)
        if callable(req):
            try:
                armed[name] = req(steps, outdir)
            except Exception as e:  # an un-armable engine must not 500
                armed[name] = {"error": str(e)}
    if not armed:
        return {"error": "no profilable engine in this process"}, 501
    return {"armed": armed, "steps": steps}, 200
