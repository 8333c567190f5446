"""Model step: the share of the decode rows' pages that the lists handed
to their walks named (%), over the window: the flight records' deltas of
the engine's counters `walk_pages_named` / `walk_pages_live` (pages a KV
head and a sparse layer), which the step programs COUNT ON THE DEVICE
from the lists the walk kernel is given and the engine reads back beside
each dispatch's ids (`models/minicpm_sala.pages_walked`). 100 means every
list named every page its row holds: the switch to the sparse rule never
fired; 64 of 128-280 pages a row read 23-50. Also prints the note
`walk_rows`: the shortest sequence among the rows that decoded inside the
window (`ctx_min` of the flight records), which says whether every one of
them stood past `dense_len`. None for a program without the counters
(every other configuration, the parent commit)."""
import json


def read(ctx):
    named = sum(r.get("walk_pages_named", 0) for r in ctx["flight"])
    live = sum(r.get("walk_pages_live", 0) for r in ctx["flight"])
    if not live:
        return None
    shortest = [r["ctx_min"] for r in ctx["flight"] if "ctx_min" in r]
    if shortest:
        print(json.dumps({
            "note": "walk_rows", "steps_with_decode_rows": len(shortest),
            "shortest_decode_row_tokens": min(shortest),
            "shortest_at_the_windows_first_step": shortest[0],
            "dense_len": ctx["hf"]["sparse_config"]["dense_len"]}),
            flush=True)
    return 100.0 * named / live
