"""DeepSeek-V2-Lite's expert FFN alone, on the chip: device time of one
expert layer's `_deepseek_moe_ffn` (models/mla.py: gate, sort, three
grouped matmuls, un-sort, shared experts) from a profiler trace at the
rows a decode step (64) and a prefill chunk (512, 2048) give it, beside
the time the chip needs to read the weights the rows touch.

    python scripts/mla_moe_bench.py [--rows 64,512,2048] [--rehearse]

One JSON line per row count on stdout; refuses a backend that is not a
TPU unless `--rehearse` (mla-tiny-moe, never a number).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PEAKS = json.loads((ROOT / "chipbench" / "peaks.json").read_text())


def hf_of(cfg) -> dict:
    return {"n_routed_experts": cfg.n_routed_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "hidden_size": cfg.hidden_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_hidden_layers": 2, "first_k_dense_replace": 1}


def scope_seconds(trace_dir: str) -> dict:
    """Device self seconds per deep scope inside the traced `jit_fn`
    calls (hostspans' own arithmetic), with "_count" and "_seconds"."""
    from chipbench import hostspans, subscopes, trace

    loaded = subscopes.load_deep(trace.find_xplane(trace_dir))
    return hostspans.scope_self_s(loaded, "jit_fn") or {}


def measure(rows: int, rehearse: bool, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import costs_deepseek_v2_lite as costs
    from dynamo_tpu.models import mla

    cfg = (mla.MlaConfig.tiny_moe() if rehearse
           else mla.MlaConfig.deepseek_v2_lite(2))  # bf16, 1 expert layer
    params = mla.init_params(jax.random.key(seed), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    del params
    x = jax.random.normal(jax.random.key(seed + 1),
                          (1, rows, cfg.hidden_size), cfg.dtype)

    @jax.jit
    def fn(x, lp):
        with jax.named_scope("mlp"):
            return mla._deepseek_moe_ffn(x, lp, cfg)

    out = jax.block_until_ready(fn(x, lp))
    topw, topi = mla._gate(x[0], lp, cfg)
    load = jnp.zeros((cfg.n_routed_experts,), jnp.int32).at[
        topi.reshape(-1)].add(1)
    doc = {"rows": rows, "device": jax.devices()[0].device_kind,
           "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
           "experts_touched": int((load > 0).sum()),
           "load_max_over_mean": float(load.max() / load.mean())}
    if rehearse:
        return doc
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(5):
            jax.block_until_ready(fn(x, lp))
        jax.profiler.stop_trace()
        sec = scope_seconds(tmp)
        calls = sec.pop("_count")
        sec = {k: v / calls for k, v in sec.items()}
    hf = hf_of(cfg)
    expert_bytes = costs.routed_expert_bytes(
        {**hf, "n_routed_experts": cfg.n_routed_experts}, rows) * (
        doc["experts_touched"] / costs.experts_touched(hf, rows))
    peak = PEAKS[doc["device"]]
    flops = 2 * 3 * rows * cfg.num_experts_per_tok * cfg.hidden_size \
        * cfg.moe_intermediate_size
    floor = max(expert_bytes / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops_per_s"])
    experts_s = sec.get("mlp/moe/experts", 0.0)
    doc.update(
        layer_us=sec["_seconds"] * 1e6,
        scopes_us={k: round(v * 1e6, 1) for k, v in sec.items()
                   if not k.startswith("_")},
        expert_bytes=expert_bytes, expert_flops=flops,
        experts_floor_us=floor * 1e6,
        bound="memory" if expert_bytes / peak["hbm_bytes_per_s"] >= flops
        / peak["bf16_flops_per_s"] else "compute",
        experts_roofline_share=100.0 * floor / experts_s if experts_s
        else None)
    return doc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="64,512,2048")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args()
    import jax

    if jax.default_backend() != "tpu" and not ns.rehearse:
        print("mla_moe_bench: no TPU; --rehearse for the CPU",
              file=sys.stderr)
        return 2
    for rows in (int(r) for r in ns.rows.split(",")):
        print(json.dumps(measure(rows, ns.rehearse, ns.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
