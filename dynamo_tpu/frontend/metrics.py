"""Prometheus text-format metrics for the HTTP service (hand-rolled
exposition; no client library in the image).

Metric names mirror the reference's HTTP service plane
(http/service/metrics.rs:104-111): requests_total, inflight_requests,
request_duration, input/output_sequence_tokens, time_to_first_token,
inter_token_latency. Latency histograms use a seconds ladder (≤30 s);
sequence-token histograms use their own power-of-two ladder (8…32768) —
a p99 prompt length must land in a real bucket, not +Inf.

The exposition is linted in tests by telemetry/promlint.py — new
metrics must keep unique TYPE lines, escaped labels, `_total` counter
names, and monotonic histogram buckets.
"""

from __future__ import annotations

import threading
from collections import defaultdict
from typing import Optional

PREFIX = "dynamo_tpu_http_service"

_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

#: token-count ladder for input/output_sequence_tokens (power of two up
#: to a 32k context)
_TOKEN_BUCKETS = (
    8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0, 1024.0, 2048.0, 4096.0,
    8192.0, 16384.0, 32768.0,
)


class Histogram:
    def __init__(self, buckets: tuple = _BUCKETS):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.total = 0.0
        self.n = 0

    def observe(self, v: float) -> None:
        self.total += v
        self.n += 1
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def expose(self, name: str, labels: str) -> list[str]:
        out = []
        cum = 0
        for i, b in enumerate(self.buckets):
            cum += self.counts[i]
            out.append(f'{name}_bucket{{{labels},le="{b}"}} {cum}')
        cum += self.counts[-1]
        out.append(f'{name}_bucket{{{labels},le="+Inf"}} {cum}')
        out.append(f"{name}_sum{{{labels}}} {self.total}")
        out.append(f"{name}_count{{{labels}}} {self.n}")
        return out


def _token_histogram() -> Histogram:
    return Histogram(buckets=_TOKEN_BUCKETS)


class FrontendMetrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.requests_total = defaultdict(int)  # (model, endpoint, status)
        self.inflight = defaultdict(int)  # model
        #: per-request sequence-length distributions (token ladder); the
        #: _sum series still carries total tokens for rate() dashboards
        self.input_tokens = defaultdict(_token_histogram)
        self.output_tokens = defaultdict(_token_histogram)
        self.duration = defaultdict(Histogram)  # model
        self.ttft = defaultdict(Histogram)
        self.itl = defaultdict(Histogram)
        #: streaming SLO accounting per endpoint (telemetry/slo.py):
        #: TTFT/ITL/e2e quantile sketches + SLA-attainment, goodput and
        #: multi-window burn-rate gauges, exposed as dynamo_tpu_slo_*
        from dynamo_tpu.telemetry.slo import SloTracker

        self.slo: dict[str, SloTracker] = {}
        self._slo_factory = SloTracker
        #: load shedding (docs/operations.md "Overload & draining"):
        #: requests rejected with 429, by reason — exposed as
        #: dynamo_tpu_shed_total{reason}. Reasons: frontend_inflight
        #: (--max-inflight gate), burn (SLO burn-rate shedder),
        #: worker_queue_full (every worker's bounded admission refused)
        self.shed_total: dict[str, int] = defaultdict(int)

    def request_done(
        self, model: str, endpoint: str, status: str, duration_s: float,
        input_tokens: int = 0, output_tokens: int = 0,
        ttft_s: Optional[float] = None, itl_s: Optional[list[float]] = None,
    ) -> None:
        with self._lock:
            self.requests_total[(model, endpoint, status)] += 1
            # error paths (400/404/500) report no token counts; a zero
            # there is absence of data, not a zero-length sequence — it
            # must not drag the length distribution into the first bucket
            if input_tokens:
                self.input_tokens[model].observe(input_tokens)
            if output_tokens:
                self.output_tokens[model].observe(output_tokens)
            self.duration[model].observe(duration_s)
            if ttft_s is not None:
                self.ttft[model].observe(ttft_s)
            for v in itl_s or ():
                self.itl[model].observe(v)
            if status == "200":
                tr = self.slo.get(endpoint)
                if tr is None:
                    tr = self.slo[endpoint] = self._slo_factory()
                ttft_ms = ttft_s * 1000.0 if ttft_s is not None else None
                if ttft_ms is not None:
                    tr.observe("ttft_ms", ttft_ms)
                itl_ms = None
                if itl_s:
                    for v in itl_s:
                        tr.observe("itl_ms", v * 1000.0)
                    itl_ms = sum(itl_s) / len(itl_s) * 1000.0
                e2e_ms = duration_s * 1000.0
                tr.observe("e2e_ms", e2e_ms)
                tr.finish_request(
                    ttft_ms=ttft_ms, itl_ms=itl_ms, e2e_ms=e2e_ms,
                    tokens=output_tokens,
                )

    def shed(self, reason: str) -> None:
        """Count one load-shed 429 (the request_done 429 row is separate:
        shed_total answers "why", requests_total answers "how many").
        Also marks the fleet event timeline: per-request 429s coalesce
        into one shed EPISODE event per ~5 s burst (GET /v1/fleet/events
        + the Grafana annotation layer)."""
        with self._lock:
            self.shed_total[reason] += 1
        from dynamo_tpu.telemetry import events

        events.record(
            "shed", severity="warning", source=f"frontend:{reason}",
            coalesce_s=5.0, reason=reason,
        )

    def total_inflight(self) -> int:
        with self._lock:
            return sum(self.inflight.values())

    def retry_after_s(self, endpoint: str) -> float:
        """Retry-After hint for a frontend-side shed, priced from the
        endpoint's live SLO sketches (runtime/overload.py)."""
        from dynamo_tpu.runtime.overload import estimate_retry_after_s

        with self._lock:
            tracker = self.slo.get(endpoint)
        return estimate_retry_after_s(tracker)

    def inflight_guard(self, model: str) -> "InflightGuard":
        return InflightGuard(self, model)

    def expose(self, openmetrics: bool = False) -> str:
        """Classic Prometheus text by default; `openmetrics=True` is the
        negotiated rendering — OpenMetrics counter-family naming, the
        `# EOF` terminator, and phase-histogram EXEMPLARS (which the
        classic parser would reject, failing the whole scrape)."""
        lines = []
        with self._lock:
            lines.append(f"# TYPE {PREFIX}_requests_total counter")
            for (model, ep, status), n in sorted(self.requests_total.items()):
                lines.append(
                    f'{PREFIX}_requests_total{{model="{model}",endpoint="{ep}",status="{status}"}} {n}'
                )
            lines.append(f"# TYPE {PREFIX}_inflight_requests gauge")
            for model, n in sorted(self.inflight.items()):
                lines.append(f'{PREFIX}_inflight_requests{{model="{model}"}} {n}')
            if self.shed_total:
                lines.append("# TYPE dynamo_tpu_shed_total counter")
                for reason, n in sorted(self.shed_total.items()):
                    lines.append(
                        f'dynamo_tpu_shed_total{{reason="{reason}"}} {n}'
                    )
            for name, table in (
                ("input_sequence_tokens", self.input_tokens),
                ("output_sequence_tokens", self.output_tokens),
                ("request_duration_seconds", self.duration),
                ("time_to_first_token_seconds", self.ttft),
                ("inter_token_latency_seconds", self.itl),
            ):
                lines.append(f"# TYPE {PREFIX}_{name} histogram")
                for model, h in sorted(table.items()):
                    lines.extend(h.expose(f"{PREFIX}_{name}", f'model="{model}"'))
            if self.slo:
                from dynamo_tpu.telemetry import slo as slo_mod

                lines.extend(
                    slo_mod.expose_lines(
                        "dynamo_tpu_slo",
                        [
                            (f'endpoint="{ep}"', tr)
                            for ep, tr in sorted(self.slo.items())
                        ],
                    )
                )
        # per-phase latency histograms live process-global (telemetry
        # layer); whichever process hosts a phase shows it here
        from dynamo_tpu.telemetry import phases

        lines.extend(phases.expose_lines(exemplars=openmetrics))
        # stall-watchdog counters (telemetry/watchdog.py): also
        # process-global — the single-process topology hosts the engine
        # (and therefore its stalls) right here
        from dynamo_tpu.telemetry.watchdog import stall_counters

        lines.extend(stall_counters.expose_lines())
        # speculative-decoding counters + live acceptance-rate gauge:
        # process-global over in-process engines (single-process serving
        # exposes them here; the metrics service mirrors the families
        # for its own process — "both Prometheus surfaces")
        from dynamo_tpu.telemetry import debug as _debug

        lines.extend(_debug.spec_lines())  # fixed dynamo_tpu_spec_* name
        # data-integrity rejections (disk-tier checksum misses, corrupt
        # transfer frames): process-global like the phase histograms
        lines.extend(_debug.integrity_lines())
        # control-plane HA: degraded gauge + outage/failover counters
        # for this process's fabric connection (zeros for local
        # pipelines, which have no broker to lose)
        lines.extend(_debug.control_plane_lines())
        # KV index health (gaps / resyncs / drift / stale subtrees): the
        # KV-aware router lives in this process in single-process
        # serving — docs/operations.md "KV index consistency"
        lines.extend(_debug.kv_index_lines())
        # HBM accounting plane (docs/observability.md "Reading the perf
        # plane"): per-device weights/kv_pool/free/peak bytes of
        # the in-process engines
        lines.extend(_debug.hbm_lines())
        text = "\n".join(lines) + "\n"
        if openmetrics:
            from dynamo_tpu.telemetry.openmetrics import to_openmetrics

            return to_openmetrics(text)
        return text


class InflightGuard:
    """RAII inflight counter (reference: metrics.rs InflightGuard :41)."""

    def __init__(self, metrics: FrontendMetrics, model: str):
        self.metrics = metrics
        self.model = model

    def __enter__(self):
        with self.metrics._lock:
            self.metrics.inflight[self.model] += 1
        return self

    def __exit__(self, *exc):
        with self.metrics._lock:
            self.metrics.inflight[self.model] -= 1
        return False
