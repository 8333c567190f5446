"""Engine loop (engine/engine.py): host wall time of a pure decode
dispatch over the steps it fused, median (ms) — flight recorder."""
import statistics

from chipbench import flight


def read(ctx):
    per = [
        r["step_ms"] / flight.fused_steps(r)
        for r in ctx["flight"] if r["kind"] == "decode" and r.get("tokens")
    ]
    return statistics.median(per) if per else None
