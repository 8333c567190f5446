"""Entry layer (frontend/http.py, preprocessor/): first chunk at the
client minus first token at the engine seam, median (ms)."""
import statistics


def read(ctx):
    d = [
        (r.chunks[0][0] - ctx["seam"][r.rid]["t_first"]) * 1000.0
        for r in ctx["results"]
        if r.measured and r.chunks and r.rid in ctx["seam"]
        and ctx["seam"][r.rid]["t_first"] is not None
    ]
    return statistics.median(d) if d else None
