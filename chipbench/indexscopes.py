"""Device time of a learned indexer's token selection (models/keye_vl.py):
the index projections and scores are named `attn/index` and the top-k
`attn/select`, both of which chipbench/hostspans.py reads as `attn`
(neither is among its `SUBSCOPES`); the decode attention is `attn/paged`,
inside which the kernel `paged_decode_attention` has events of its own,
and a prompt chunk's `attn/flash` holds the kernel
`token_chunk_attention`. This module names the operations of the same
trace by those paths and hands them to hostspans' interval arithmetic, as
chipbench/sparsescopes.py does for `attn/select` alone.

A trace whose operations carry none of these names (the parent commit's,
any other configuration's) gives None, never an error.
"""

from __future__ import annotations

import functools

from chipbench import hostspans, trace

DEEP = ("attn/index", "attn/select")


def deep_scope_of(path: str) -> str:
    parts = path.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part in hostspans.SCOPES:
            for deep in DEEP:
                if parts[i:i + 2] == deep.split("/"):
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


def _loaded(ctx: dict):
    path = hostspans.newest_xplane()
    if not hostspans.of_this_run(ctx) or path is None:
        return None
    return load_deep(path)


def step_seconds(ctx: dict, scope: str) -> float | None:
    """Device self seconds under `scope` per fused decode step
    (`jit_multi_fn`, over dispatches x k), or None."""
    loaded = _loaded(ctx)
    if loaded is None:
        return None
    per_scope = hostspans.scope_self_s(loaded, "jit_multi_fn")
    ks = hostspans.fused_steps(loaded, "jit_multi_fn")
    if not per_scope or not ks or not per_scope.get(scope):
        return None
    return per_scope[scope] / sum(ks)


def kernel_seconds(ctx: dict, kernel: str, module: str) -> float | None:
    """Seconds of the OWN events of the operations named `kernel*` inside
    `module`, summed over its calls, mean over the device planes; None
    where the trace names no `attn/index` operation (no indexer ran)."""
    loaded = _loaded(ctx)
    if loaded is None or step_seconds(ctx, "attn/index") is None:
        return None
    total = planes = 0.0
    for dev in loaded["devices"].values():
        mods = [m for m in dev["modules"] if m[0] == module]
        if not mods:
            continue
        planes += 1
        mi = 0
        for name, s, e, _scope in dev["ops"]:
            while mi < len(mods) and mods[mi][2] <= s:
                mi += 1
            if mi == len(mods):
                break
            if (s >= mods[mi][1] - 1e-9
                    and name.lstrip("%_").startswith(kernel)):
                total += e - s
    return total / planes if planes and total else None


def slice_records(ctx: dict) -> list:
    """The flight records inside the traced slice."""
    info = ctx.get("trace_info") or {}
    if "wall_start" not in info:
        return []
    return [r for r in ctx["flight"]
            if info["wall_start"] <= r["ts"] <= info["wall_stop"]]
