#!/usr/bin/env python3
"""fleet_top: terminal view of the metrics service's /v1/fleet snapshot.

One-shot by default; `--watch N` redraws every N seconds. For operators
who want the fleet at a glance without Grafana:

    python scripts/fleet_top.py --url http://127.0.0.1:9091
    python scripts/fleet_top.py --watch 2
    python scripts/fleet_top.py --snapshot artifacts/fleet.json  # offline
    python scripts/fleet_top.py --events            # fleet event timeline
    python scripts/fleet_top.py --events --watch 2  # tail it

Per worker: role, model, req/s, tok/s, TTFT/ITL p50/p95, KV-pool %,
jit compiles, stall count (dynamo_tpu_stalls_total, via the
worker frames' stalls_total), KVBM tier residency + hit split
(TIER/HIT — docs/operations.md "The KV economy"), HBM byte breakdown
(HBM w/kv/free — the worker frames' hbm_*_bytes gauges, summed over
its local devices; docs/observability.md "Reading the perf plane"),
SLO burn rate
(shortest attainment window), the worst KEPT trace touching the worker (fleet trace plane,
GET /v1/traces — its id pastes straight into /v1/traces/{id}),
last_seen age. Fleet footer: merged percentiles, SLA attainment + burn
rates, goodput. `--events` tails GET /v1/fleet/events instead — one
severity-colored line per control-plane event (flips, handovers, shed
episodes, replays, resyncs, planner decisions). Dependency-free
(urllib only); `render()` / `render_events()` are pure functions
smoke-tested against recorded snapshots in tests/test_fleet_telemetry.py.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request


def _fmt(v, nd: int = 1, suffix: str = "") -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}{suffix}"
    return f"{v}{suffix}"


def _pct(slo: dict, metric: str, q: str):
    return (slo or {}).get(metric, {}).get(q)


def _bshort(v) -> str:
    """Compact byte count for fixed-width columns: 427K, 3.2G, 24G."""
    if v is None:
        return "-"
    v = float(v)
    for div, s in ((2**40, "T"), (2**30, "G"), (2**20, "M"), (2**10, "K")):
        if v >= div:
            x = v / div
            return f"{x:.1f}{s}" if x < 10 else f"{x:.0f}{s}"
    return f"{int(v)}"


def _worker_burn(slo: dict):
    """Per-worker burn rate from its SHORTEST attainment window (the
    fast-paging one of the multi-window pair)."""
    windows = (slo or {}).get("windows") or {}
    if not windows:
        return None
    shortest = min(windows, key=lambda x: int(x))
    return (windows[shortest] or {}).get("burn_rate")


def _worst_traces_by_worker(traces) -> dict:
    """worker id -> (trace_id, duration_ms) of the slowest kept trace
    that touched it (fleet trace plane summaries)."""
    worst: dict = {}
    for t in traces or ():
        if not isinstance(t, dict):
            continue
        dur = t.get("duration_ms")
        if dur is None:
            continue
        for w in t.get("workers") or ():
            cur = worst.get(w)
            if cur is None or dur > cur[1]:
                worst[w] = (str(t.get("trace_id") or ""), float(dur))
    return worst


def render(snap: dict, traces=None) -> str:
    """Pure snapshot -> text table (no I/O; unit-testable). `traces`
    is the metrics service's kept-trace summary list (GET /v1/traces);
    the WORST-TRACE column shows the slowest kept trace touching each
    worker as `<id prefix> <ms>`."""
    cols = (
        ("WORKER", 22), ("ROLE", 8), ("MODEL", 12), ("REQ/S", 7),
        ("TOK/S", 8), ("TTFT p50/p95", 14), ("ITL p50/p95", 12),
        ("KV%", 6), ("WM", 6), ("COMP", 5), ("PREEMPT", 7),
        ("SPEC%", 6), ("TIER/HIT", 12), ("HBM w/kv/free", 15),
        ("STALLS", 6), ("BURN", 6),
        ("WORST-TRACE", 16), ("AGE s", 6),
    )
    worst = _worst_traces_by_worker(traces)
    out = [" ".join(f"{h:<{w}}" for h, w in cols)]
    for iid, w in sorted((snap.get("workers") or {}).items()):
        slo = w.get("slo") or {}
        kv = w.get("kv_usage")
        burn = _worker_burn(slo)
        wt = worst.get(iid)
        row = (
            iid[:22], w.get("role", "?"), str(w.get("model", "?"))[:12],
            _fmt(w.get("req_s")), _fmt(w.get("tok_s")),
            f"{_fmt(_pct(slo, 'ttft_ms', 'p50'), 0)}/"
            f"{_fmt(_pct(slo, 'ttft_ms', 'p95'), 0)}",
            f"{_fmt(_pct(slo, 'itl_ms', 'p50'), 0)}/"
            f"{_fmt(_pct(slo, 'itl_ms', 'p95'), 0)}",
            _fmt(kv * 100.0 if kv is not None else None, 0),
            _fmt(w.get("kv_pages_watermark"), 0),
            _fmt(w.get("compiles"), 0),
            _fmt(w.get("preemptions"), 0),
            # live draft-acceptance rate (speculative decoding), keyed
            # on the windowed draft count so the three states read
            # apart: a rate (incl. "0" = actively-failing draft) while
            # the window has drafts, "idle" when speculation ran before
            # but the window drained, "-" when it never ran
            (
                _fmt((w.get("spec_accept_rate") or 0.0) * 100.0, 0)
                if w.get("spec_window_drafted")
                else ("idle" if w.get("spec_drafted") else "-")
            ),
            # KV economy tier view: lower-tier block residency
            # (host/disk, KVBM write-back demotion) and which tier
            # served prefix-hit continuations — "12h3d 5/1" reads
            # "12 host + 3 disk blocks resident, 5 host / 1 disk hits".
            # Workers without KVBM tiers show "-", never zeros.
            (
                f"{int(w.get('kvbm_host_blocks') or 0)}h"
                f"{int(w.get('kvbm_disk_blocks') or 0)}d "
                f"{int(w.get('kvbm_host_hits_total') or 0)}/"
                f"{int(w.get('kvbm_disk_hits_total') or 0)}"
                if any(
                    w.get(f) is not None for f in (
                        "kvbm_host_blocks", "kvbm_disk_blocks",
                        "kvbm_demotions_total",
                    )
                )
                else "-"
            ),
            # HBM accounting view: weights-resident / KV-pool / free
            # bytes summed over the worker's local devices ("3.2G/1.1G/
            # 11G"). Workers predating the perf plane show "-" — absence
            # of accounting, not an empty device.
            (
                f"{_bshort(w.get('hbm_weights_bytes'))}/"
                f"{_bshort(w.get('hbm_kv_pool_bytes'))}/"
                f"{_bshort(w.get('hbm_free_bytes'))}"
                if any(
                    w.get(f) is not None for f in (
                        "hbm_weights_bytes", "hbm_kv_pool_bytes",
                        "hbm_free_bytes",
                    )
                )
                else "-"
            ),
            _fmt(w.get("stalls_total"), 0),
            _fmt(burn, 1, "x") if burn is not None else "-",
            f"{wt[0][:8]} {wt[1]:.0f}ms" if wt else "-",
            _fmt(w.get("last_seen_s")),
        )
        out.append(
            " ".join(f"{str(v):<{wd}}" for v, (_, wd) in zip(row, cols))
        )
    fleet = snap.get("fleet") or {}
    out.append("")
    out.append(f"fleet: {fleet.get('workers', 0)} workers")
    slo = fleet.get("slo")
    if slo:
        for m, label in (
            ("ttft_ms", "ttft"), ("itl_ms", "itl"), ("e2e_ms", "e2e"),
        ):
            q = slo.get(m)
            if q:
                out.append(
                    f"  {label:<5} p50 {_fmt(q.get('p50'))} ms   "
                    f"p95 {_fmt(q.get('p95'))} ms   "
                    f"p99 {_fmt(q.get('p99'))} ms   (n={q.get('n')})"
                )
        out.append(
            f"  sla   attainment {_fmt(slo.get('attainment'), 4)}   "
            f"goodput {slo.get('goodput_tokens_total', 0)}/"
            f"{slo.get('tokens_total', 0)} tokens"
        )
        for w_s, wd in sorted(
            (slo.get("windows") or {}).items(), key=lambda x: int(x[0])
        ):
            out.append(
                f"    {w_s:>4}s window: attainment "
                f"{_fmt(wd.get('attainment'), 4)}  burn rate "
                f"{_fmt(wd.get('burn_rate'), 2)}x  "
                f"({wd.get('requests', 0)} req)"
            )
    for role, r in sorted((snap.get("roles") or {}).items()):
        out.append(
            f"  {role:<6} {r.get('workers', 0)} workers  "
            f"tok/s {_fmt(r.get('tokens_per_s'))}  "
            f"kv {_fmt((r.get('kv_usage') or 0) * 100, 0)}%  "
            f"compiles {sum((r.get('compiles_by_kind') or {}).values())}"
        )
    return "\n".join(out)


#: severity -> ANSI color for the --events timeline
_SEV_COLORS = {"info": "\x1b[36m", "warning": "\x1b[33m",
               "critical": "\x1b[31m"}
_RESET = "\x1b[0m"


def render_events(events, color: bool = True) -> str:
    """Pure event list (GET /v1/fleet/events order: newest last) ->
    one line per event, severity-colored: time, type, source, count,
    compact attrs."""
    lines = []
    for e in events or ():
        if not isinstance(e, dict):
            continue
        sev = str(e.get("severity") or "info")
        ts = time.strftime(
            "%H:%M:%S", time.localtime(float(e.get("ts") or 0.0))
        )
        count = int(e.get("count") or 1)
        attrs = " ".join(
            f"{k}={v}" for k, v in sorted((e.get("attrs") or {}).items())
        )
        head = f"{e.get('type', '?'):<16}"
        if color:
            head = f"{_SEV_COLORS.get(sev, '')}{head}{_RESET}"
        lines.append(
            f"{ts} {sev[:4]:<4} {head} "
            f"{str(e.get('source') or '-'):<22}"
            + (f" x{count}" if count > 1 else "")
            + (f"  {attrs}" if attrs else "")
        )
    if not lines:
        lines = ["(no fleet events)"]
    return "\n".join(lines)


def fetch(url: str, path: str = "/v1/fleet") -> dict:
    with urllib.request.urlopen(f"{url}{path}", timeout=5) as resp:
        return json.loads(resp.read().decode())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--url", default="http://127.0.0.1:9091",
        help="metrics service base URL",
    )
    ap.add_argument(
        "--watch", type=float, default=0.0, metavar="SECONDS",
        help="redraw every N seconds (0 = one shot)",
    )
    ap.add_argument(
        "--snapshot", default=None,
        help="render a recorded snapshot JSON file instead of fetching",
    )
    ap.add_argument(
        "--events", action="store_true",
        help="render the fleet event timeline (GET /v1/fleet/events) "
             "instead of the worker table",
    )
    ap.add_argument(
        "--no-color", action="store_true",
        help="disable ANSI severity colors in --events output",
    )
    args = ap.parse_args(argv)
    while True:
        if args.events:
            try:
                doc = fetch(args.url, "/v1/fleet/events")
            except Exception as e:
                print(
                    f"fetch {args.url}/v1/fleet/events failed: {e}",
                    file=sys.stderr,
                )
                if not args.watch:
                    return 1
                time.sleep(args.watch)
                continue
            text = render_events(
                doc.get("events"), color=not args.no_color
            )
        else:
            if args.snapshot:
                with open(args.snapshot) as f:
                    snap = json.load(f)
                traces = None
            else:
                try:
                    snap = fetch(args.url)
                except Exception as e:
                    print(
                        f"fetch {args.url}/v1/fleet failed: {e}",
                        file=sys.stderr,
                    )
                    if not args.watch:
                        return 1
                    time.sleep(args.watch)
                    continue
                try:
                    # kept-trace summaries feed the WORST-TRACE column;
                    # an older metrics service without the trace plane
                    # just loses the column, never the table
                    traces = fetch(
                        args.url, "/v1/traces?sort=duration&limit=64"
                    ).get("traces")
                except Exception:
                    traces = None
            text = render(snap, traces=traces)
        if args.watch:
            print("\x1b[2J\x1b[H" + text, flush=True)
            time.sleep(args.watch)
        else:
            print(text)
            return 0


if __name__ == "__main__":
    sys.exit(main())
