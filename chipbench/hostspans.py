"""The engine loop and the device on one clock. The program writes its
loop phases into the profiler's own trace (`engine.*` spans on the
engine thread: engine/engine.py `phase`) and names the parts of a step
inside its programs (`jax.named_scope` in models/llama.py, the kernels'
`name`), so one `.xplane.pb` says under which host phase the chip sat
idle and on which part of the model it spent its time. This reads that:

- `idle_by_phase`: every device idle gap, split over the engine-thread
  spans that cover it (the innermost span wins; what no phase covers is
  `unattributed`). The shares sum to `device_idle_share`: same timeline
  ("XLA Ops"), same window (first to last device event), same union.
- `scope_self_s`: device *self* time per named scope inside the events
  of one module (`jit_multi_fn`): an operation's duration less what its
  children cover, since a `while` covers its body's operations.
- `fused_steps`: the decode steps each `jit_multi_fn` dispatch fused,
  exact, from the `k` of the `engine.launch` span that sent it.

`chipbench/trace.py` loads device planes as (name, start, duration) and
stays as it is; this module reads, beside it, the host planes, the
spans' args and the stats of the operations' metadata. A trace without
`engine.*` spans (the parent commit's, or a program that names nothing)
gives None, never an error.
"""

from __future__ import annotations

import bisect
import functools
import json
import statistics

from chipbench import manifest, trace

#: the phases of the loop that hold the chip up, in loop order; the
#: idle under `engine.wait`, under `engine.step` outside any phase, or
#: under no span at all is `unattributed`
PHASES = ("intake", "schedule", "stage", "launch", "readback",
          "postprocess", "emit")
#: spans that count for the span around them (a first call's compile is
#: part of its launch; a rollback has no length)
TRANSPARENT = ("engine.compile", "engine.rollback")
#: the parts of a step the programs name (the first of them in an
#: operation's path is its scope), and the parts of `attn` named inside it
SCOPES = ("embed", "attn", "mlp", "final_norm", "lm_head", "sample",
          "feedback")
SUBSCOPES = ("qkv", "kv_update", "paged", "flash", "out")
#: the stat of a device operation's *metadata* that carries its scope
#: path (`jit(multi_fn)/while/body/closed_call/attn/qkv/dot_general:`);
#: seen by hand in a v5e trace of PR 24, jax 0.9
SCOPE_STAT = "tf_op"
#: gaps shorter than this are summed but not listed by name: between two
#: operations of one program the chip pauses for nanoseconds
MIN_NAMED_GAP_S = 50e-6
#: device module of a launch kind (engine.launch's `kind`)
MODULE_OF_KIND = {
    "decode_multi": "jit_multi_fn", "mixed": "jit_mixed_fn",
    "decode": "jit_decode_fn", "prefill": "jit_prefill_fn",
    "decode_kstep": "jit_kstep_fn",
}


def newest_xplane() -> str | None:
    """What run.py wrote for this run: the newest trace under
    RUN_DIR/trace (`ctx` does not carry the path)."""
    found = sorted(
        (manifest.RUN_DIR / "trace").glob("*/plugins/profile/*/*.xplane.pb"),
        key=lambda p: p.stat().st_mtime)
    return str(found[-1]) if found else None


def _xspace_class():
    """The profiler's XSpace message (tsl/profiler/protobuf/xplane.proto),
    declared here field by field: `jax.profiler.ProfileData` shows an
    event's own stats but not its metadata's, and the scope path of a
    device operation (`tf_op`) is a stat of the metadata. Maps are
    declared as what they are on the wire, repeated (key, value)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    kinds = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
             "bytes": F.TYPE_BYTES}
    schema = {
        "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
                  ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
                  ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
                  ("ref_value", 7, "uint64")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64"), ("stats", 4, "*XStat")],
        "XLine": [("id", 1, "int64"), ("name", 2, "string"),
                  ("timestamp_ns", 3, "int64"), ("events", 4, "*XEvent")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "*XStat")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        "EventMetadataEntry": [("key", 1, "int64"),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("id", 1, "int64"), ("name", 2, "string"),
                   ("lines", 3, "*XLine"),
                   ("event_metadata", 4, "*EventMetadataEntry"),
                   ("stat_metadata", 5, "*StatMetadataEntry")],
        "XSpace": [("planes", 1, "*XPlane")],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        if msg == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number)
            if name.endswith("_value"):
                f.oneof_index = 0
            repeated = kind.startswith("*")
            kind = kind.lstrip("*")
            f.label = F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL
            if kind in kinds:
                f.type = kinds[kind]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".chipbench.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def read_xspace(path: str):
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_value(stat, stat_names: dict):
    which = stat.WhichOneof("value")
    if which == "ref_value":  # a string kept once, among the stat names
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, which) if which else None


def _stats(stats, stat_names: dict) -> dict:
    return {stat_names.get(s.metadata_id, ""): _stat_value(s, stat_names)
            for s in stats}


def scope_of(path: str) -> str:
    """`jit(multi_fn)/while/body/closed_call/attn/qkv/dot_general:` ->
    `attn/qkv`; `unscoped` where no part of the model is named."""
    parts = path.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part in SCOPES:
            sub = parts[i + 1] if i + 1 < len(parts) else ""
            return f"{part}/{sub}" if sub in SUBSCOPES else part
    return "unscoped"


def in_scope(scope: str, *tops: str) -> bool:
    """`attn/qkv` and `attn` are in `attn`."""
    return scope.split("/", 1)[0] in tops


def _seconds(line, event, base_ns: int) -> tuple[float, float]:
    """(start_s, end_s) after `base_ns`: a line's timestamp counts from
    the epoch, where a float of seconds resolves a quarter microsecond."""
    start = (line.timestamp_ns - base_ns) * 1e-9 + event.offset_ps * 1e-12
    return start, start + event.duration_ps * 1e-12


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"spans": [(name, start_s, end_s, args)] of the engine thread, in
    start order, outer before inner; "devices": {plane: {"modules":
    [(name, start_s, end_s)], "ops": [(name, start_s, end_s, scope)]}}}.
    Seconds count from the trace's earliest line. The engine thread is
    the host line that holds `engine.*` events: it is found by what it
    wrote, not by a thread id."""
    spans: list = []
    devices: dict = {}
    space = read_xspace(path)
    base_ns = min((line.timestamp_ns for plane in space.planes
                   for line in plane.lines), default=0)
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name.startswith(trace.DEVICE_PLANE):
            names, scopes = {}, {}
            for entry in plane.event_metadata:
                md = entry.value
                names[entry.key] = md.name
                scopes[entry.key] = scope_of(str(
                    _stats(md.stats, stat_names).get(SCOPE_STAT) or ""))
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                if line.name == trace.MODULES_LINE:
                    dev["modules"] = sorted(
                        ((trace.module_name(names[e.metadata_id]),
                          *_seconds(line, e, base_ns)) for e in line.events),
                        key=lambda m: m[1])
                elif line.name == trace.OPS_LINE:
                    dev["ops"] = sorted(
                        ((trace.op_name(names[e.metadata_id]),
                          *_seconds(line, e, base_ns), scopes[e.metadata_id])
                         for e in line.events),
                        key=lambda o: (o[1], -o[2]))
            continue
        ours = {e.key: e.value.name for e in plane.event_metadata
                if e.value.name.startswith("engine.")}
        if not ours:
            continue
        for line in plane.lines:
            spans += [
                (ours[e.metadata_id], *_seconds(line, e, base_ns),
                 _stats(e.stats, stat_names))
                for e in line.events if e.metadata_id in ours
            ]
    spans.sort(key=lambda s: (s[1], -s[2]))
    return {"spans": spans, "devices": devices}


def flatten(spans) -> list:
    """Nested spans of one thread -> disjoint (start_s, end_s, name)
    segments, each named by the innermost span that covers it."""
    out: list = []
    stack: list = []  # (name, end)
    t = None

    def emit(until):
        nonlocal t
        if stack and until - t > 1e-12:  # no slivers of float rounding
            out.append((t, until, stack[-1][0]))
        t = max(t, until)

    for name, start, end, _args in spans:
        if name in TRANSPARENT:
            continue
        # a nanosecond of slack: two spans that meet are siblings, however
        # the float sums that place them round
        while stack and stack[-1][1] <= start + 1e-9:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(start)
        t = start
        stack.append((name, min(end, stack[-1][1]) if stack else end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _overlaps(segments, ends, lo: float, hi: float) -> dict:
    """{name: seconds} of the (disjoint, ordered) segments inside
    [lo, hi]; `ends` are their ends, to start the walk by bisection."""
    got: dict = {}
    for i in range(bisect.bisect_right(ends, lo), len(segments)):
        s, e, name = segments[i]
        if s >= hi:
            break
        got[name] = got.get(name, 0.0) + min(e, hi) - max(s, lo)
    return got


def phase_of(span_name: str) -> str:
    short = span_name.removeprefix("engine.")
    return short if short in PHASES else "unattributed"


def device_gaps(dev: dict) -> tuple[list, float]:
    """The idle gaps (start_s, length_s) of one device plane and its
    window, as trace.reduce takes them: the "XLA Ops" line, or the
    modules where a trace has no operations."""
    timeline = dev["ops"] or dev["modules"]
    if not timeline:
        return [], 0.0
    _busy, gaps = trace.union_s((e[1], e[2] - e[1]) for e in timeline)
    window = max(e[2] for e in timeline) - min(e[1] for e in timeline)
    return gaps, window


def idle_by_phase(loaded: dict) -> dict | None:
    """{"shares": {phase: % of the traced slice}, "gaps": the ten
    longest gaps, each with the host spans under it and the launch that
    ended it}. None without `engine.*` spans or without a device."""
    if not loaded["spans"] or not loaded["devices"]:
        return None
    segments = flatten(loaded["spans"])
    ends = [e for _s, e, _name in segments]
    launches = [s for s in loaded["spans"] if s[0] == "engine.launch"]
    idle = dict.fromkeys((*PHASES, "unattributed"), 0.0)
    windows, longest = [], []
    for plane, dev in loaded["devices"].items():
        gaps, window = device_gaps(dev)
        if not window:
            continue
        windows.append(window)
        for g0, length in gaps:
            under = _overlaps(segments, ends, g0, g0 + length)
            covered = 0.0
            for name, secs in under.items():
                idle[phase_of(name)] += secs
                covered += secs
            idle["unattributed"] += length - covered
            longest.append((length, g0, plane, under, covered))
    if not windows:
        return None
    n, window = len(windows), sum(windows) / len(windows)
    named = []
    for length, g0, plane, under, covered in sorted(
            (g for g in longest if g[0] >= MIN_NAMED_GAP_S),
            key=lambda g: -g[0])[:10]:
        end = g0 + length
        # the launch nearest the gap's end (the device's clock runs about
        # a millisecond ahead of the host's, so "the last one before it"
        # would miss), and the step program after the gap (the tiny
        # programs of the on-device token feedback run in front of it)
        ended_by = min(launches, key=lambda s: abs(s[1] - end), default=None)
        after = [m for m in loaded["devices"][plane]["modules"]
                 if m[1] >= end - 1e-5 and m[2] - m[1] >= 1e-4]
        named.append({
            "ms": round(length * 1e3, 3),
            "host_ms": {
                **{k.removeprefix("engine."): round(v * 1e3, 3)
                   for k, v in sorted(under.items(), key=lambda kv: -kv[1])},
                "none": round((length - covered) * 1e3, 3)},
            "ended_by": ended_by and ended_by[3],
            "next_module": after[0][0] if after else None,
        })
    return {
        "shares": {k: 100.0 * v / n / window for k, v in idle.items()},
        "window_s": window,
        "gaps": named,
    }


def scope_self_s(loaded: dict, module: str) -> dict | None:
    """{scope: device self seconds} inside the events of `module`, mean
    over the device planes, with "_count" (its events) and "_seconds"
    (their summed length). None where the trace has no such module or
    none of its operations carries a named scope."""
    totals: dict = {}
    count = seconds = planes = 0
    for dev in loaded["devices"].values():
        mods = [m for m in dev["modules"] if m[0] == module]
        if not mods:
            continue
        planes += 1
        count += len(mods)
        seconds += sum(e - s for _n, s, e in mods)
        mi = 0
        stack: list = []  # [end, scope, self]

        def close(until):
            while stack and stack[-1][0] <= until:
                _end, scope, self_s = stack.pop()
                totals[scope] = totals.get(scope, 0.0) + self_s

        for _name, s, e, scope in dev["ops"]:
            while mi < len(mods) and mods[mi][2] <= s:
                mi += 1
            if mi == len(mods):
                break
            if s < mods[mi][1] - 1e-9:
                continue  # an operation of another module
            close(s)
            if stack:  # a child: its time is not its parent's own
                stack[-1][2] -= min(e, stack[-1][0]) - s
            stack.append([e, scope, e - s])
        close(float("inf"))
    if not planes or not any(in_scope(k, *SCOPES) for k in totals):
        return None
    out = {k: v / planes for k, v in totals.items()}
    out["_count"] = count / planes
    out["_seconds"] = seconds / planes
    return out


def fused_steps(loaded: dict, module: str = "jit_multi_fn") -> list | None:
    """The `k` of each `module` dispatch on the device, from the
    `engine.launch` span that sent it: the latest launch of that kind
    not yet matched that began before the device did. A dispatch
    launched before the capture takes the commonest `k` seen. None
    without such launches."""
    kinds = [k for k, m in MODULE_OF_KIND.items() if m == module]
    launches = [(s[1], int(s[3].get("k", 1))) for s in loaded["spans"]
                if s[0] == "engine.launch" and s[3].get("kind") in kinds]
    if not launches:
        return None
    usual = statistics.mode(k for _t, k in launches)
    out = []
    for dev in loaded["devices"].values():
        j = -1
        for name, start, _end in dev["modules"]:
            if name != module:
                continue
            last = j
            while last + 1 < len(launches) and launches[last + 1][0] <= start:
                last += 1
            if last > j:
                j = last
                out.append(launches[j][1])
            else:
                out.append(usual)
        break  # the planes of one program run the same dispatches
    return out or None


def ms_per_step(loaded: dict, scopes: tuple,
                module: str = "jit_multi_fn") -> float | None:
    """Device self time under `scopes` inside `module`, per fused decode
    step (ms): over dispatches x k."""
    per_scope = scope_self_s(loaded, module)
    ks = fused_steps(loaded, module)
    if not per_scope or not ks:
        return None
    return 1e3 * sum(v for k, v in per_scope.items()
                     if in_scope(k, *scopes)) / sum(ks)


#: counter (EngineMetrics, cumulative ms) of each phase's span
COUNTER_OF_PHASE = {
    "intake": "time_intake_ms", "schedule": "time_schedule_ms",
    "stage": "time_stage_ms", "readback": "time_decode_sync_ms",
    "postprocess": "time_decode_host_ms", "emit": "time_emit_ms",
}


def loop_ms_per_dispatch(engine: dict) -> dict | None:
    """The loop's phases on the host's clock: window deltas of the
    phases' counters over the window's dispatches (ms). The window is
    mostly untraced (the profiler runs for a slice of it), so this is
    what the phases cost without the profiler's Python tracer; `launch`
    is dispatch less stage. None for a program without the counters."""
    n = sum(engine.get(k, 0) for k in (
        "decode_dispatches", "mixed_dispatches", "prefill_dispatches"))
    if not n or "time_stage_ms" not in engine:
        return None
    out = {ph: engine.get(c, 0.0) / n for ph, c in COUNTER_OF_PHASE.items()}
    out["launch"] = (engine.get("time_decode_dispatch_ms", 0.0)
                     - engine["time_stage_ms"]) / n
    return {k: round(v, 3) for k, v in out.items()}


_THIS_RUN: dict = {}


def of_this_run(ctx: dict) -> dict | None:
    """The newest trace of this run, loaded and reduced once; prints the
    free-form `note` line with the ten longest gaps. None when the run
    wrote no trace."""
    path = newest_xplane()
    if path is None:
        return None
    if path not in _THIS_RUN:
        loaded = load(path)
        idle = idle_by_phase(loaded)
        multi = scope_self_s(loaded, "jit_multi_fn")
        ks = fused_steps(loaded)
        print(json.dumps({
            "note": "hostspans", "path": path,
            "engine_spans": len(loaded["spans"]),
            "idle_shares": idle and idle["shares"],
            "longest_gaps": idle["gaps"] if idle else [],
            "jit_multi_fn": multi and {
                k: round(v, 6) for k, v in multi.items()},
            "fused_steps": ks and {
                "dispatches": len(ks), "steps": sum(ks),
                "k": sorted(set(ks))},
            "window_host_ms_per_dispatch": loop_ms_per_dispatch(
                ctx.get("engine", {})),
        }, default=str), flush=True)
        _THIS_RUN.clear()
        _THIS_RUN[path] = {"loaded": loaded, "idle": idle}
    return _THIS_RUN[path]


def idle_share(ctx: dict, phase_: str) -> float | None:
    """Reader body of the `idle_in_<phase>_share` metrics."""
    run = of_this_run(ctx)
    if not run or not run["idle"]:
        return None
    return run["idle"]["shares"][phase_]


def step_ms(ctx: dict, *scopes: str) -> float | None:
    """Reader body of the `decode_<part>_ms_per_step` metrics."""
    run = of_this_run(ctx)
    return ms_per_step(run["loaded"], scopes) if run else None
