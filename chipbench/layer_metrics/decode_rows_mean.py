"""Scheduler: decode rows per decode-carrying step (flight recorder)."""


def read(ctx):
    rows = [r["n_decode"] for r in ctx["flight"] if r.get("n_decode")]
    return sum(rows) / len(rows) if rows else None
