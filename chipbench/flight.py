"""Reading the program's flight-recorder steps (telemetry/flight.py)."""


def fused_steps(rec: dict) -> float:
    """Decode steps a record's dispatch fused: tokens emitted per decode
    row (rows that finish mid-dispatch drop their overshoot, so this
    reads a little under the fused count)."""
    n = rec.get("n_decode") or 0
    if not n:
        return 0.0
    tokens = rec.get("tokens", 0)
    if rec["kind"] == "mixed":
        tokens -= rec.get("n_prefill", 0)  # at most one first token each
    return max(1.0, tokens / n)
