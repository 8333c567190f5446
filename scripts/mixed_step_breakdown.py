"""Where a mixed step's device time goes, from the newest trace a
`--trace 1` benchmark run left under this checkout's run directory
(`.chipbench_run/trace/`): device self time per named scope inside
`jit_mixed_fn` and `jit_multi_fn`, ms a dispatch, each operation under
the DEEPEST scope one of the benchmark's readers names (`mlp/moe/*` and
`attn/absorb` of chipbench/subscopes.py, `attn/ssm/*` of ssmscopes.py,
`attn/select` of sparsescopes.py, `attn/index` of indexscopes.py,
`attn/window` and `attn/gate` of dots3scopes.py; `attn` alone is then what
none of them names), the
operations under `attn/flash` by name, and
`latent_flash_ms_per_mixed_step` (ISSUE 39's reading: the prefill chunk's
attention, scope `attn/flash` inside `jit_mixed_fn`, ms a mixed step; for
a latent model the kernel `latent_prefill_attention`, floor 1.25 ms at
`docgen`'s mean history; for MiniCPM-SALA the kernel
`sparse_chunk_attention` and the tile lists it is handed; for Keye-VL the
kernel `token_chunk_attention` and the masks it is handed; for
dots3-note-prev `latent_plain_attention` and the masks it is handed in the
full layers (`latent_prefill_attention` there before PR 53), beside
`attn/window`, the sliding layers' ring attention).
Reads files only (run it after the benchmark's process has gone;
`JAX_PLATFORMS=cpu` keeps it off the chip).

    JAX_PLATFORMS=cpu python scripts/mixed_step_breakdown.py [TRACE.xplane.pb]
        [--ops attn/out,attn]

`--ops` lists the operations of further scopes inside `jit_mixed_fn` by
name, as `attn/flash`'s are listed.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def flash_ms_per_mixed_step(loaded: dict) -> float | None:
    """Device self time under scope `attn/flash` inside `jit_mixed_fn`
    over its dispatches, ms; None where the trace has no mixed step or
    names no such scope."""
    from chipbench import hostspans

    per_scope = hostspans.scope_self_s(loaded, "jit_mixed_fn")
    if not per_scope or not per_scope.get("attn/flash"):
        return None
    return 1e3 * per_scope["attn/flash"] / per_scope["_count"]


def load_deepest(path: str) -> dict:
    """`hostspans.load`'s dict with each device operation under the
    longest scope any of the deep readers gives it (they sort one
    trace's operations alike, so their lists run in step)."""
    from chipbench import (dots3scopes, indexscopes, sparsescopes, ssmscopes,
                           subscopes)

    loads = [m.load_deep(path) for m in (
        subscopes, ssmscopes, sparsescopes, indexscopes, dots3scopes)]
    devices = {
        plane: {"modules": dev["modules"], "ops": [
            max(same, key=lambda op: len(op[3])) for same in zip(
                *(one["devices"][plane]["ops"] for one in loads))]}
        for plane, dev in loads[0]["devices"].items()}
    return {"spans": loads[0]["spans"], "devices": devices}


def main(argv=None) -> int:
    from chipbench import hostspans

    argv = list(sys.argv[1:] if argv is None else argv)
    scopes = ["attn/flash"]
    if "--ops" in argv:
        at = argv.index("--ops")
        scopes += argv[at + 1].split(",")
        del argv[at:at + 2]
    path = argv[0] if argv else hostspans.newest_xplane()
    if path is None:
        print("mixed_step_breakdown: no trace under the run directory",
              file=sys.stderr)
        return 2
    loaded = load_deepest(path)
    for module in ("jit_mixed_fn", "jit_multi_fn"):
        per_scope = hostspans.scope_self_s(loaded, module) or {}
        n = per_scope.get("_count") or 1
        print(json.dumps({
            "module": module, "dispatches": per_scope.get("_count"),
            "ms_per_dispatch": round(
                1e3 * per_scope.get("_seconds", 0.0) / n, 3),
            "self_ms_by_scope": {
                k: round(1e3 * v / n, 4)
                for k, v in sorted(per_scope.items())
                if not k.startswith("_")},
        }))
    for dev in loaded["devices"].values():  # one chip
        mixed = [m for m in dev["modules"] if m[0] == "jit_mixed_fn"]
        for listed in scopes:
            by_name: dict = {}
            for name, start, end, scope in dev["ops"]:
                if scope == listed and any(
                        s <= start and end <= e for _n, s, e in mixed):
                    key = name.split(".")[0]
                    by_name[key] = by_name.get(key, 0.0) + end - start
            print(json.dumps({f"{listed} inside jit_mixed_fn, ms a dispatch "
                              "by operation (whole length, not self time)": {
                k: round(1e3 * v / (len(mixed) or 1), 4)
                for k, v in sorted(
                    by_name.items(), key=lambda kv: -kv[1])[:12]
            }}))
        break
    print(json.dumps(
        {"latent_flash_ms_per_mixed_step": flash_ms_per_mixed_step(loaded)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
