"""Model step: the share of the decode rows' cached tokens that their
selections named in dots3-note-prev's full layers (%), over the window:
the flight records' deltas of the engine's counters `walk_pages_named` /
`walk_pages_live`, in TOKENS a full layer for this family
(`models/keye_vl.tokens_attended`, counted ON THE DEVICE from what each
row's selection was given). 100 means the selection never fired; 2,048 of
8.2k-17.9k read 11-25. Also prints the note `attended_rows`: the shortest
sequence among the rows that decoded inside the window. None for a program
without the counters or a configuration without `index_topk`."""
import json


def read(ctx):
    if "index_topk" not in ctx["hf"]:
        return None
    named = sum(r.get("walk_pages_named", 0) for r in ctx["flight"])
    live = sum(r.get("walk_pages_live", 0) for r in ctx["flight"])
    if not live:
        return None
    shortest = [r["ctx_min"] for r in ctx["flight"] if "ctx_min" in r]
    if shortest:
        print(json.dumps({
            "note": "attended_rows", "steps_with_decode_rows": len(shortest),
            "shortest_decode_row_tokens": min(shortest),
            "topk": ctx["hf"]["index_topk"]}), flush=True)
    return 100.0 * named / live
