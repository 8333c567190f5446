"""Device self time under scopes that chipbench/hostspans.py folds into
their parent: it names an operation by the first part of its path in
SCOPES and, under `attn`, by SUBSCOPES; `mlp/moe/experts` reads `mlp`
there and `attn/absorb` reads `attn`. The per-layer metrics of a sparse
latent decoder (models/mla.py) read deeper names, so this module names
the same operations of the same trace by their DEEP scopes and hands
them to hostspans' own interval arithmetic (`scope_self_s`,
`fused_steps`): one definition of self time, one of a fused step.

A trace whose operations carry none of these names (the parent commit's,
a dense decoder's) gives None, never an error.
"""

from __future__ import annotations

import functools

from chipbench import hostspans, trace

#: full scope paths read here, longest first
DEEP = ("mlp/moe/experts", "mlp/moe/route", "mlp/moe/shared", "attn/absorb")


def deep_scope_of(path: str) -> str:
    """`jit(multi_fn)/while/body/mlp/moe/experts/ragged_dot:` ->
    `mlp/moe/experts`; what hostspans.scope_of says elsewhere."""
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


def step_seconds(ctx: dict, *scopes: str,
                 module: str = "jit_multi_fn") -> float | None:
    """Device self seconds under the deep `scopes` per fused decode step
    of this run's trace, or None where it names none of them."""
    run = hostspans.of_this_run(ctx)
    path = hostspans.newest_xplane()
    if not run or path is None:
        return None
    loaded = load_deep(path)
    per_scope = hostspans.scope_self_s(loaded, module)
    ks = hostspans.fused_steps(loaded, module)
    if not per_scope or not ks:
        return None
    found = [per_scope[s] for s in scopes if per_scope.get(s)]
    return sum(found) / sum(ks) if found else None
