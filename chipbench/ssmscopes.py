"""Device self time under the state-space layers' scopes. A Mamba-2
mixer is its layer's sequence mixer and is named UNDER `attn`
(models/nemotron_h.py: `attn/ssm/{in_proj,conv,scan,gate_norm,out}`), so
chipbench/hostspans.py reads all of it as `attn`
(`decode_attn_ms_per_step` counts it) and chipbench/subscopes.py does
not name it. This module names the same operations of the same trace by
these deeper paths and hands them to hostspans' own interval arithmetic
(`scope_self_s`, `fused_steps`), as subscopes.py does for `mlp/moe/*`.

A trace whose operations carry none of these names (the parent commit's,
any other configuration's) gives None, never an error.
"""

from __future__ import annotations

import functools

from chipbench import hostspans, trace

#: full scope paths read here
DEEP = ("attn/ssm/in_proj", "attn/ssm/conv", "attn/ssm/scan",
        "attn/ssm/gate_norm", "attn/ssm/out")
SSM = DEEP
STATE = ("attn/ssm/conv", "attn/ssm/scan")


def deep_scope_of(path: str) -> str:
    """`jit(multi_fn)/while/body/attn/ssm/scan/mul:` -> `attn/ssm/scan`;
    what hostspans.scope_of says elsewhere."""
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


def module_seconds(ctx: dict, scopes, module: str):
    """(device self seconds under the deep `scopes` inside `module` over
    this run's trace, how many times `module` ran there), or None where
    the trace names none of them."""
    run = hostspans.of_this_run(ctx)
    path = hostspans.newest_xplane()
    if not run or path is None:
        return None
    per_scope = hostspans.scope_self_s(load_deep(path), module)
    if not per_scope:
        return None
    found = [per_scope[s] for s in scopes if per_scope.get(s)]
    return (sum(found), per_scope["_count"]) if found else None


def step_seconds(ctx: dict, *scopes: str) -> float | None:
    """Device self seconds under the deep `scopes` per fused decode step
    (`jit_multi_fn`, over dispatches x k), or None."""
    got = module_seconds(ctx, scopes, "jit_multi_fn")
    path = hostspans.newest_xplane()
    if got is None or path is None:
        return None
    ks = hostspans.fused_steps(load_deep(path), "jit_multi_fn")
    return got[0] / sum(ks) if ks else None


def fused_records(ctx: dict) -> list:
    """The flight records of fused decode dispatches inside the traced
    slice."""
    from chipbench import flight

    info = ctx.get("trace_info") or {}
    if "wall_start" not in info:
        return []
    return [
        r for r in ctx["flight"]
        if info["wall_start"] <= r["ts"] <= info["wall_stop"]
        and flight.fused_steps(r) >= 1.5
    ]
