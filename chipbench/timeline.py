"""The dispatch timeline of the program's flight recorder, read over the
part of the window the profiler never touched.

Since PR 38 the engine keeps a dry clock (dynamo_tpu/telemetry/flight.py
`DryClock`): at every boundary of its loop phases the engine thread asks
the output of its newest launch whether it is ready, and so knows, with
no profiler, when the device had nothing queued. A flight record then
carries `disp` (one entry per program the step launched: `seq`, `kind`,
`rows`, `n_rows`, `k`, `ahead`, `t_launch`, for a chunk `t`, `b_pre`,
`chunk_tokens`, and on a launch made with the device empty
`dry_before_ms`, `dry_phase`, `slack_ms`), `ready` (one per dispatch the step read:
`seq`, `kind`, `t_ready`, `blocked_ms`, and `dev_ms` where both ends of
the dispatch on the device are known) and the per-step deltas of the
clock's counters (`dry_ms`, `dry_<phase>_ms`, `launches`, ...).

`run.py` hands every reader the window's records (`ctx["flight"]`, cut
by their wall-clock `ts`) and the traced slice's wall-clock ends
(`ctx["trace_info"]`). The slice opens in the middle of the window; the
profiler's Python tracer slows the host loop it watches, and stopping it
stalls the loop for seconds, so:

- BEFORE the slice: records whose `ts` is earlier than `wall_start` less
  `MARGIN_S`: what the loop does untraced;
- INSIDE: `wall_start <= ts <= wall_stop`: what the trace's own metrics
  (`device_idle_share`, `mixed_step_device_ms`) describe;
- AFTER: `ts > wall_stop`: the stall of `stop_trace` and what follows
  (reported in the `timeline` note, read by no metric).

Rates are taken over the seconds between the first and the last launch
of a part (`t_launch`, the host's monotonic clock), and the first
launch's own dry time lies before them, so a part's books close: dry
time + the dispatches' time on the device = its seconds. Records of a
program without the clock (the parent's) have none of the fields: every
function gives None there, never an error.
"""

from __future__ import annotations

import bisect
import json
import statistics

from chipbench import stats

#: kept clear of the slice's start: `start_trace` itself takes a moment
MARGIN_S = 0.5
PARTS = ("before", "inside", "after")


def part(ctx: dict, where: str) -> list | None:
    """The window's flight records of one part, oldest first. None for
    a run without a traced slice."""
    info = ctx.get("trace_info") or {}
    if "wall_start" not in info or "wall_stop" not in info:
        return None
    lo, hi = info["wall_start"], info["wall_stop"]
    keep = {
        "before": lambda ts: ts < lo - MARGIN_S,
        "inside": lambda ts: lo <= ts <= hi,
        "after": lambda ts: ts > hi,
    }[where]
    return [r for r in ctx.get("flight") or () if keep(r["ts"])]


def entries(records) -> list:
    """Every dispatch the records launched, in launch order."""
    return [e for r in records or () for e in r.get("disp", ())]


def readies(records) -> list:
    return [e for r in records or () for e in r.get("ready", ())]


def seconds(es: list) -> float | None:
    """First launch to last launch; None under two launches."""
    if len(es) < 2:
        return None
    dt = es[-1]["t_launch"] - es[0]["t_launch"]
    return dt if dt > 0 else None


def late_launch_share(records) -> float | None:
    es = entries(records)
    if not es:
        return None
    return 100.0 * sum("dry_before_ms" in e for e in es) / len(es)


def dry_share(records) -> float | None:
    """Dry ms before each launch after the first, over the seconds."""
    es = entries(records)
    s = seconds(es)
    if s is None:
        return None
    return 0.1 * sum(e.get("dry_before_ms", 0.0) for e in es[1:]) / s


def delta_share(records, field: str) -> float | None:
    """A dry counter's per-step deltas after the first record, over the
    seconds between the first record and the last (%). None where no
    record holds a timeline (a counter that stayed 0 writes no delta,
    so the field's absence alone says nothing)."""
    if not entries(records) or len(records) < 2:
        return None
    s = records[-1]["ts"] - records[0]["ts"]
    if s <= 0:
        return None
    return 0.1 * sum(r.get(field, 0.0) for r in records[1:]) / s


def host_turns_ms(records) -> list:
    """Per dispatch: from the return of the readback before it to its
    `t_launch`: the turn a dispatch on the device has to cover."""
    ends = sorted(e["t_ready"] for e in readies(records))
    out = []
    for e in entries(records):
        i = bisect.bisect_right(ends, e["t_launch"])
        if i:
            out.append(1e3 * (e["t_launch"] - ends[i - 1]))
    return out


def host_turn_ms(records, q: float) -> float | None:
    turns = host_turns_ms(records)
    return stats.percentile(turns, q) if turns else None


def of_kind(es: list, kind: str) -> list:
    return [e for e in es if e.get("kind") == kind]


def mixed_steps_per_s(records) -> float | None:
    es = entries(records)
    s = seconds(es)
    if s is None:
        return None
    return len(of_kind(es[1:], "mixed")) / s


def mixed_pad_share(records) -> float | None:
    """1 - real prompt tokens / (piece rows x T bucket) over the mixed
    dispatches (%)."""
    es = [e for e in of_kind(entries(records), "mixed")
          if e.get("b_pre") and e.get("t")]
    if not es:
        return None
    room = sum(e["b_pre"] * e["t"] for e in es)
    return 100.0 * (1.0 - sum(e.get("chunk_tokens", 0) for e in es) / room)


def dev_ms(records, kind: str | None = None) -> list:
    return [e["dev_ms"] for e in readies(records)
            if "dev_ms" in e and kind in (None, e.get("kind"))]


def mixed_step_ms_p50(records) -> float | None:
    ms = dev_ms(records, "mixed")
    return statistics.median(ms) if ms else None


def mixed_busy_share(records) -> float | None:
    """Mixed dispatches' share of the device time of every dispatch
    whose time on the device is known (%)."""
    every = dev_ms(records)
    if not every or not entries(records):
        return None
    return 100.0 * sum(dev_ms(records, "mixed")) / sum(every)


def summary(records) -> dict | None:
    """One part's books, for the `timeline` note."""
    es = entries(records)
    if not es:
        return None
    s = seconds(es)
    kinds = sorted({e.get("kind") for e in es}, key=str)
    by_kind = {}
    for k in kinds:
        ms, launched = dev_ms(records, k), of_kind(es, k)
        by_kind[k] = {
            "launches": len(launched),
            "late": sum("dry_before_ms" in e for e in launched),
            "ahead": sum(e.get("ahead", 0) for e in launched),
            "dev_ms_n": len(ms),
            "dev_ms_p50": round(statistics.median(ms), 3) if ms else None,
            "dev_ms_mean": round(statistics.fmean(ms), 3) if ms else None,
        }
    late = [e for e in es if "dry_phase" in e]
    begun, slack = {}, {}
    for e in late:
        for total, field in ((begun, "dry_before_ms"), (slack, "slack_ms")):
            total[e["dry_phase"]] = round(
                total.get(e["dry_phase"], 0.0) + e.get(field, 0.0), 3)
    late.sort(key=lambda e: e.get("slack_ms", 0.0), reverse=True)
    counters = {
        f: round(sum(r.get(f, 0.0) for r in records), 3)
        for f in ("dry_ms", "dry_slack_ms", "dry_wait_ms", "dry_intake_ms",
                  "dry_schedule_ms", "dry_stage_ms", "dry_launch_ms",
                  "dry_readback_ms", "dry_postprocess_ms", "dry_emit_ms",
                  "dry_launches", "launches", "tokens")
    }
    wall = records[-1]["ts"] - records[0]["ts"]
    turns = host_turns_ms(records)
    gaps = sorted((b["ts"] - a["ts"] for a, b in zip(records, records[1:])),
                  reverse=True)
    return {
        "records": len(records), "wall_s": round(wall, 3),
        "launch_span_s": s and round(s, 3),
        "tokens_per_s": round(
            sum(r.get("tokens", 0) for r in records[1:]) / wall, 1)
        if wall > 0 else None,
        "device_dry_share": dry_share(records),
        "late_launch_share": late_launch_share(records),
        "host_turn_ms": turns and {
            "n": len(turns),
            "p50": round(stats.percentile(turns, 50), 3),
            "p95": round(stats.percentile(turns, 95), 3)},
        "mixed_steps_per_s": mixed_steps_per_s(records),
        "mixed_pad_share": mixed_pad_share(records),
        "mixed_busy_share": mixed_busy_share(records),
        "by_kind": by_kind,
        "dry_ms_by_phase_it_began_under": begun,
        "slack_ms_by_phase_it_began_under": slack,
        "largest_slacks": [
            {k: e.get(k) for k in ("seq", "kind", "dry_phase", "slack_ms",
                                   "dry_before_ms")} for e in late[:3]],
        "counters": counters,
        "longest_record_gaps_s": [round(g, 3) for g in gaps[:3]],
    }


_NOTED: list = []


def of_part(ctx: dict, where: str) -> list | None:
    """`part`, and once a run the free-form `timeline` note with the
    three parts' books side by side."""
    flight = ctx.get("flight")
    if flight and not (_NOTED and _NOTED[0] is flight):
        _NOTED[:] = [flight]  # held, so that its identity stays its own
        print(json.dumps({
            "note": "timeline",
            "trace_info": ctx.get("trace_info"),
            **{w: summary(part(ctx, w)) for w in PARTS},
        }, default=str), flush=True)
    return part(ctx, where)
