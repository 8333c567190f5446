"""Device time of the selecting page walk (models/minicpm_sala.py): the
selection is named `attn/select`, which chipbench/hostspans.py reads as
`attn` (`select` is not among its `SUBSCOPES`), and the walk `attn/paged`,
inside which the kernel `paged_decode_attention` has events of its own.
This module names the operations of the same trace by those paths and
hands them to hostspans' interval arithmetic, as chipbench/ssmscopes.py
does for `attn/ssm/*`.

A trace whose operations carry none of these names (the parent commit's,
any other configuration's) gives None, never an error.
"""

from __future__ import annotations

import functools

from chipbench import hostspans, ssmscopes, trace

SELECT = "attn/select"
WALK_KERNEL = "paged_decode_attention"


def deep_scope_of(path: str) -> str:
    """`jit(multi_fn)/while/body/attn/select/sort:` -> `attn/select`;
    what hostspans.scope_of says elsewhere."""
    parts = path.rstrip(":").split("/")
    for i, part in enumerate(parts):
        if part in hostspans.SCOPES:
            if parts[i:i + 2] == SELECT.split("/"):
                return SELECT
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


def select_step_seconds(ctx: dict) -> float | None:
    """Device self seconds under `attn/select` per fused decode step
    (`jit_multi_fn`, over dispatches x k), or None."""
    loaded = _loaded(ctx)
    if loaded is None:
        return None
    per_scope = hostspans.scope_self_s(loaded, "jit_multi_fn")
    ks = hostspans.fused_steps(loaded, "jit_multi_fn")
    if not per_scope or not ks or not per_scope.get(SELECT):
        return None
    return per_scope[SELECT] / sum(ks)


def walk_kernel_step_seconds(ctx: dict) -> float | None:
    """Seconds of the walk kernel's OWN events (operations named
    `paged_decode_attention*`) inside `jit_multi_fn` per fused decode
    step, mean over the device planes, or None where a selecting walk
    never ran (no `attn/select` operation in the trace)."""
    loaded = _loaded(ctx)
    if loaded is None or select_step_seconds(ctx) is None:
        return None
    ks = hostspans.fused_steps(loaded, "jit_multi_fn")
    total = planes = 0.0
    for dev in loaded["devices"].values():
        mods = [m for m in dev["modules"] if m[0] == "jit_multi_fn"]
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
                    and name.lstrip("%_").startswith(WALK_KERNEL)):
                total += e - s
    if not planes or not ks or not total:
        return None
    return total / planes / sum(ks)


fused_records = ssmscopes.fused_records
