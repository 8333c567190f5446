"""What the per-layer metrics of the cell `cmdaplus-longctx` read of the
flight records, beside chipbench/dots3scopes.py's readers of the device
trace (which this family's scopes satisfy: it names `attn/window` too).

The step programs of models/cohere2_moe.py count on the device, a SLIDING
layer each: `walk_pages_named` the keys its decode rows attended under the
window (`min(context, sliding_window)` a row) and `walk_pages_live` the
tokens those rows held; `chunk_pages_read` the (query, key) pairs inside
the band of its prompt pieces; and a FULL layer each: `chunk_pages_named`
the pairs under the causal mask. A program without these counts, or a
configuration of another family, gives None, never an error."""

from __future__ import annotations

from chipbench import dots3scopes


def layers(ctx: dict):
    """(sliding, full) layers of the configuration, or None."""
    hf = ctx["hf"]
    if "layer_types" not in hf or "sliding_window" not in hf:
        return None
    kinds = hf["layer_types"][:hf["num_hidden_layers"]]
    full = sum(k == "full_attention" for k in kinds)
    return len(kinds) - full, full


def decode_steps(ctx: dict) -> dict | None:
    """What a decode step of the traced slice holds, the mean over the
    steps of its fused AND mixed dispatches: `rows`, `live` (the tokens the
    rows hold) and `in_reach` (the keys their windows hold)."""
    from chipbench import flight

    kinds = layers(ctx)
    if kinds is None or not kinds[0]:
        return None
    steps = rows = live = reach = 0.0
    for r in dots3scopes.slice_records(ctx):
        if r.get("kind") not in ("decode_multi", "mixed") or not r.get(
                "n_decode") or not r.get("walk_pages_live"):
            continue
        k = flight.fused_steps(r) if r["kind"] == "decode_multi" else 1.0
        steps += k
        rows += k * r["n_decode"]
        live += r["walk_pages_live"] / kinds[0]
        reach += r.get("walk_pages_named", 0) / kinds[0]
    if not steps:
        return None
    return {"rows": rows / steps, "live": live / steps,
            "in_reach": reach / steps}


def chunk_pairs(ctx: dict, counter: str, per: int) -> float | None:
    """The mean over the traced slice's mixed dispatches of `counter`, a
    layer (`per` layers count into it). A dispatch that also read back a
    rolled-back dispatch's count is left out."""
    mixed = [r[counter] for r in dots3scopes.slice_records(ctx, "mixed")
             if r.get(counter) and not r.get("overlap_rollbacks")]
    return sum(mixed) / len(mixed) / per if mixed and per else None
