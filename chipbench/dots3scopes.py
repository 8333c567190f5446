"""Device time of dots3-note-prev's step programs (models/dots3.py) by the
scopes its per-layer metrics read, over WHOLE dispatches only.

chipbench/hostspans.py names an operation by the first part of its path in
SCOPES and, under `attn`, by SUBSCOPES; this module names the operations
of the same trace by the deeper paths below (as chipbench/subscopes.py and
chipbench/indexscopes.py do for theirs) and hands them to hostspans' own
interval arithmetic.

What is new here is the denominator. `hostspans.fused_steps` counts every
`jit_multi_fn` event of the trace as a whole dispatch of `k` steps, the
first and the last too, which the capture may have cut: their operations
are partly outside the trace, so seconds a step read low and a share of a
roofline reads high (PERF.md 7 m: 108.97 % in `keye-longctx`). Here a
step's length is taken from the events of a module that start AND end
inside the capture with a neighbour on both sides: the first and the last
event of each device plane are left out, with their operations and their
`k`. No share read through this module can pass 100 % by that count.

A trace whose operations carry none of these names (the parent commit's,
any other configuration's) gives None, never an error.
"""

from __future__ import annotations

import functools

from chipbench import hostspans, trace

#: full scope paths read here, longest first
DEEP = ("mlp/moe/experts", "mlp/moe/route", "mlp/moe/shared", "attn/absorb",
        "attn/index", "attn/select", "attn/window", "attn/gate")


def deep_scope_of(path: str) -> str:
    parts = path.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part in hostspans.SCOPES:
            for deep in DEEP:
                want = deep.split("/")
                if parts[i:i + len(want)] == want:
                    return deep
            break
    return hostspans.scope_of(path)


@functools.lru_cache(maxsize=2)
def load_deep(path: str) -> dict:
    """hostspans.load's dict with each device operation under its deep
    scope (the spans and modules are the same objects)."""
    loaded = hostspans.load(path)
    space = hostspans.read_xspace(path)
    base_ns = min((line.timestamp_ns for plane in space.planes
                   for line in plane.lines), default=0)
    devices = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names, scopes = {}, {}
        for entry in plane.event_metadata:
            md = entry.value
            names[entry.key] = md.name
            scopes[entry.key] = deep_scope_of(str(hostspans._stats(
                md.stats, stat_names).get(hostspans.SCOPE_STAT) or ""))
        ops = []
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops = sorted(
                    ((trace.op_name(names[e.metadata_id]),
                      *hostspans._seconds(line, e, base_ns),
                      scopes[e.metadata_id]) for e in line.events),
                    key=lambda o: (o[1], -o[2]))
        devices[plane.name] = {
            "modules": loaded["devices"][plane.name]["modules"], "ops": ops}
    return {"spans": loaded["spans"], "devices": devices}


#: the device modules a decode step runs in: a fused dispatch of `k`
#: steps, or the decode rows' part of a mixed step (one step)
MODULES = ("jit_multi_fn", "jit_mixed_fn")


def whole(ctx: dict, module: str):
    """(this run's trace with only the WHOLE events of `module` in each
    device plane, the `k` of each of them), or None where the trace has
    fewer than three events of it or names no window layer's scope (no
    program of this family ran)."""
    path = hostspans.newest_xplane()
    if not hostspans.of_this_run(ctx) or path is None:
        return None
    loaded = load_deep(path)
    ks = hostspans.fused_steps(loaded, module)
    devices = {}
    for name, dev in loaded["devices"].items():
        mods = [m for m in dev["modules"] if m[0] == module]
        if len(mods) < 3 or not any(o[3] == "attn/window"
                                    for o in dev["ops"]):
            return None
        devices[name] = {"modules": mods[1:-1], "ops": dev["ops"]}
    if not devices or not ks or len(ks) < 3:
        return None
    return {"spans": loaded["spans"], "devices": devices}, ks[1:-1]


def step_seconds(ctx: dict, *scopes: str) -> float | None:
    """Device self seconds under the deep `scopes` a MIXED step: over the
    whole `jit_mixed_fn` dispatches alone. This family's cell spends 85-89
    % of its time in mixed steps and a 4 s slice holds 0 to 5 fused
    dispatches (PERF.md 6, PR 48), whose steps are a tenth as long under
    these scopes: counted in, a reading moved 2x with how many the slice
    caught. The decode rows' work and the prompt chunk's stand under the
    same scope."""
    found = whole(ctx, "jit_mixed_fn")
    if found is None:
        return None
    loaded, ks = found
    per_scope = hostspans.scope_self_s(loaded, "jit_mixed_fn") or {}
    seconds = sum(per_scope.get(s, 0.0) for s in scopes)
    return seconds / sum(ks) if seconds and ks else None


def kernel_seconds(ctx: dict, kernel: str, module: str, scope: str):
    """(seconds of the OWN events of the operations named `kernel` (and
    its numbered twins, not a longer name) under the deep `scope` inside
    the whole events of `module`, mean over the device planes; the count
    of those events of `module`; the steps they fused), or None. The scope
    tells a kernel's uses apart: the window layers walk their rings with
    the full layers' two kernels."""
    found = whole(ctx, module)
    if found is None:
        return None
    loaded, ks = found
    total = planes = count = 0.0
    for dev in loaded["devices"].values():
        mods = dev["modules"]
        planes += 1
        count += len(mods)
        mi = 0
        for name, s, e, at in dev["ops"]:
            while mi < len(mods) and mods[mi][2] <= s:
                mi += 1
            if mi == len(mods):
                break
            op = name.lstrip("%_")
            if (s >= mods[mi][1] - 1e-9 and at == scope
                    and (op == kernel or op.startswith(kernel + "."))):
                total += e - s
    if not total:
        return None
    return total / planes, count / planes, sum(ks)


def decode_kernel_step_seconds(ctx: dict, kernel: str,
                               scope: str) -> float | None:
    """Seconds a STEP of the decode rows' kernel `kernel` under `scope`:
    its own events inside the whole dispatches of the `MODULES` that ran
    it (a mixed step runs it once for its decode rows) over the steps
    those dispatches hold."""
    seconds = steps = 0.0
    for module in MODULES:
        found = kernel_seconds(ctx, kernel, module, scope)
        if found is not None:
            seconds += found[0]
            steps += found[2]
    return seconds / steps if steps else None


def slice_records(ctx: dict, kind: str | None = None) -> list:
    """The flight records inside the traced slice (of `kind`)."""
    info = ctx.get("trace_info") or {}
    if "wall_start" not in info:
        return []
    return [r for r in ctx["flight"]
            if info["wall_start"] <= r["ts"] <= info["wall_stop"]
            and (kind is None or r.get("kind") == kind)]


def experts_touched(ctx: dict) -> float | None:
    """The held experts the rows of a MIXED step chose, summed over its
    expert layers, as the step programs count them on the device
    (`moe_experts_touched` in the flight records): the mean over the traced
    slice's mixed dispatches; one that also read back a rolled-back
    dispatch's count is left out. None where the program counts none."""
    counts = [r["moe_experts_touched"]
              for r in slice_records(ctx, "mixed")
              if r.get("moe_experts_touched")
              and not r.get("overlap_rollbacks")]
    return sum(counts) / len(counts) if counts else None


def decode_steps(ctx: dict) -> dict | None:
    """What a decode step of the traced slice holds, the mean over the
    steps of its fused AND mixed dispatches (flight records): `rows` (the
    decode rows), `live` (the tokens they hold, counted on the device a
    full layer each: `walk_pages_live` over the full layers), `chunk` (a
    mixed step's prompt tokens, 0 in a fused one)."""
    from chipbench import flight

    if "layer_types" not in ctx["hf"]:
        return None
    full = sum(k == "full_attention" for k in ctx["hf"]["layer_types"][
        :ctx["hf"]["num_hidden_layers"]])
    steps = rows = live = chunk = 0.0
    for r in slice_records(ctx):
        if r.get("kind") not in ("decode_multi", "mixed") or not r.get(
                "n_decode"):
            continue
        k = flight.fused_steps(r) if r["kind"] == "decode_multi" else 1.0
        steps += k
        rows += k * r["n_decode"]
        live += r.get("walk_pages_live", 0) / max(full, 1)
        chunk += r.get("prefill_tokens", 0) if r["kind"] == "mixed" else 0
    if not steps:
        return None
    return {"rows": rows / steps, "live": live / steps,
            "chunk": chunk / steps}
