"""From the profiler's trace to numbers. `jax.profiler.ProfileData`
reads the `.xplane.pb`; everything after that is plain arithmetic on
(name, start, duration) triples, checked on the small recorded trace in
chipbench/testdata/."""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_s, duration_s)]}} of
    the device planes."""
    from jax.profiler import ProfileData

    if path.endswith(".txt"):  # a recorded trace kept as text proto
        with open(path) as f:
            data = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    else:
        data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines[line.name] = [
                (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in line.events
            ]
    return out


def describe(planes: dict) -> dict:
    """{plane: {line: number of events}} — what the trace calls things."""
    return {p: {ln: len(ev) for ln, ev in lines.items()}
            for p, lines in planes.items()}


def union_s(intervals) -> tuple[float, list]:
    """Total covered length of (start, duration) intervals, and the
    uncovered gaps between them as (start, length)."""
    busy, gaps, end = 0.0, [], None
    for s, d in sorted(intervals):
        e = s + d
        if end is None:
            busy, end = d, e
        elif s > end:
            gaps.append((end, s - end))
            busy += d
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy, gaps


def module_name(name: str) -> str:
    """`jit_multi_fn(1234567)` -> `jit_multi_fn`."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """The trace names an operation by its whole HLO line
    (`%while.50 = (s32[], ...) while(...)`): keep what precedes ` = `."""
    return name.split(" = ", 1)[0][:80]


def reduce(planes: dict) -> dict | None:
    """Busy seconds (averaged over the device planes), the window, time
    per module, the ten operations with most time and the five longest
    idle gaps, each named by the modules on either side of it. The window
    is the trace's own: first to last device event, so busy time and the
    window it is a share of are on one clock (the profiler goes on
    recording while `stop_trace` runs, which no host clock brackets).
    Operations nest (a `while` covers its body's operations), so the top
    of the list is the loop itself. None when the trace has no device
    plane (a CPU rehearsal)."""
    if not planes:
        return None
    busy_all, span_all = [], []
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    gaps_named: list = []
    for lines in planes.values():
        timeline = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not timeline:
            continue
        busy, _ = union_s((s, d) for _n, s, d in timeline)
        busy_all.append(busy)
        first = min(s for _n, s, _d in timeline)
        last = max(s + d for _n, s, d in timeline)
        span_all.append(last - first)
        for n, _s, d in lines.get(OPS_LINE, ()):
            n = op_name(n)
            ops[n] = ops.get(n, 0.0) + d
        mods = sorted(lines.get(MODULES_LINE, ()), key=lambda e: e[1])
        for n, _s, d in mods:
            m = modules.setdefault(module_name(n), [0, 0.0])
            m[0] += 1
            m[1] += d
        _b, gaps = union_s((s, d) for _n, s, d in mods)
        for gs, gl in gaps:
            before = [n for n, s, d in mods if s + d <= gs + 1e-9]
            after = [n for n, s, _d in mods if s >= gs + gl - 1e-9]
            gaps_named.append((
                f"{module_name(before[-1]) if before else 'start'}"
                f"->{module_name(after[0]) if after else 'end'}", gl,
            ))
    if not busy_all:
        return None
    n = len(busy_all)
    span = sum(span_all) / n
    return {
        "busy_s": sum(busy_all) / n,
        "window_s": span,
        "modules": {k: {"count": c, "seconds": s}
                    for k, (c, s) in modules.items()},
        "device_ops": [
            [k, v / n] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [
            [k, v] for k, v in sorted(gaps_named, key=lambda kv: -kv[1])[:5]
        ],
        "idle_gap_total_s": sum(g for _k, g in gaps_named) / n,
    }
