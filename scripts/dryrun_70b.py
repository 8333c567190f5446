"""70B-readiness dryrun (VERDICT r2 item 6; BASELINE config 4).

Two halves:

1. HBM accounting for REAL llama3-70b shapes on a v5e-16 mesh (4 hosts x
   4 chips, tp=8 x dp=2): per-leaf sharded bytes from eval_shape + the
   parallel/shardings specs — no weights materialize anywhere. Asserts
   int8 weights + bf16 KV page pool + workspace fit 16GB/chip and
   records the full bytes/chip table.

2. Execution proof on a 16-virtual-device CPU mesh: a 70B-ARCHITECTURE
   config (80 layers, 64 q / 8 kv heads, GQA ratio 8 — dims scaled down)
   runs one serving step (prefill + decode + sample) under the exact
   same sharding specs, proving the tp=8 x dp=2 layout compiles and
   executes end to end.

Writes artifacts/dryrun_70b.json. Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
      python scripts/dryrun_70b.py

A third, chip-free mode (ISSUE 20):
  python scripts/dryrun_70b.py --check-rules
dry-resolves EVERY registry preset's logical axis names through the
one rule table under both the 1-host layout and the tp=8 x dp=2 pod
layout — no weights, no mesh, no devices. A model declaring a logical
axis the table doesn't know fails here (UnknownLogicalAxisError) as a
fast tier-1 test instead of an on-chip surprise.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

V5E_HBM = 16 * 1024**3  # bytes/chip
TP, DP = 8, 2  # llama3-70b has 8 kv heads -> tp=8 keeps GQA head-sharded


def _sharded_bytes(shape, dtype_size, spec, axis_sizes) -> int:
    """Bytes per device for one leaf under a PartitionSpec."""
    n = dtype_size
    for dim, name in zip(shape, tuple(spec) + (None,) * len(shape)):
        if name is not None:
            dim = -(-dim // axis_sizes[name])
        n *= dim
    return n


def accounting() -> dict:
    import jax

    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.models.registry import get_model
    from dynamo_tpu.parallel.shardings import kv_cache_spec

    cfg = LlamaConfig.llama3_70b()
    adapter = get_model("llama3-70b", dtype="bfloat16")
    shapes = jax.eval_shape(
        lambda k: adapter.init_params(k), jax.random.key(0)
    )
    specs = adapter.param_specs(quantized=False)
    axis = {"tp": TP, "dp": DP}

    rows = []
    bf16_total = 0
    int8_total = 0
    from jax.sharding import PartitionSpec

    flat_shapes = jax.tree_util.tree_leaves_with_path(shapes)
    flat_specs = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    for (path, s), spec in zip(flat_shapes, flat_specs):
        name = jax.tree_util.keystr(path)
        b16 = _sharded_bytes(s.shape, 2, spec, axis)
        # int8 weight-only halves every quantized dense leaf; norms/embeds
        # stay bf16. Scales are ~1/in_dim of the weight — counted at 1%.
        quantizable = any(
            k in name
            for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
        )
        b8 = (b16 // 2 + b16 // 100) if quantizable else b16
        bf16_total += b16
        int8_total += b8
        rows.append(
            {
                "leaf": name,
                "global_shape": list(s.shape),
                "spec": str(spec),
                "bf16_bytes_per_chip": b16,
                "int8_bytes_per_chip": b8,
            }
        )

    # KV pool: [L, P, S, Hkv, D] bf16, kv-heads sharded over tp. Pages
    # budget = whatever fits after weights + workspace.
    kv_spec = kv_cache_spec()
    page_shape = (cfg.num_layers, 1, 64, cfg.num_kv_heads, cfg.head_dim)
    per_page = 2 * _sharded_bytes(page_shape, 2, kv_spec, axis)  # k + v
    workspace = 2 * 1024**3  # activations + XLA scratch headroom
    budget = V5E_HBM - int8_total - workspace
    pages = budget // per_page
    ctx_tokens = pages * 64 // DP  # dp halves the batch, not the ctx

    return {
        "mesh": {"tp": TP, "dp": DP, "chips": TP * DP, "hosts": 4},
        "weights_bf16_bytes_per_chip": bf16_total,
        "weights_int8_bytes_per_chip": int8_total,
        "kv_bytes_per_page_per_chip": per_page,
        "workspace_reserve_bytes": workspace,
        "kv_pages_possible_int8": int(pages),
        "kv_tokens_possible_int8": int(pages * 64),
        "fits_bf16": bool(
            bf16_total + workspace + 64 * per_page < V5E_HBM
        ),
        "fits_int8": bool(
            int8_total + workspace + 64 * per_page < V5E_HBM
        ),
        "leaves": rows,
        "note": (
            "bf16 70B weights alone are "
            f"{bf16_total / 2**30:.1f}GB/chip on v5e-16 — int8 "
            "weight-only is the serving configuration (BASELINE.md's "
            "reference config serves 70B FP8 for the same reason)"
        ),
        "ctx_tokens_note": int(ctx_tokens),
    }


def execution_proof() -> dict:
    import time

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams
    from dynamo_tpu.models.llama import LlamaConfig
    from dynamo_tpu.parallel.mesh import MeshConfig

    assert len(jax.devices()) >= 16, "needs 16 virtual devices"
    # 70B architecture (layer count, head layout, GQA=8), hidden dims
    # scaled so 80 layers compile quickly on CPU
    cfg = LlamaConfig(
        vocab_size=512,
        hidden_size=512,
        intermediate_size=1024,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=8,
        dtype=jnp.float32,
        tie_word_embeddings=False,
    )
    t0 = time.time()
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.models.registry import _LLAMA_PRESETS

    _LLAMA_PRESETS["dryrun-70b-arch"] = lambda: cfg
    eng = JaxEngine(
        EngineConfig(
            model="dryrun-70b-arch",
            tp=TP,
            dp=DP,
            num_pages=64,
            page_size=16,
            max_pages_per_seq=8,
            decode_buckets=(2, 4),
            prefill_chunk=32,
            max_seqs=8,
            dtype="float32",
        ),
        mesh_config=MeshConfig(dp=DP, tp=TP),
    )
    rng = np.random.default_rng(3)
    for i in range(4):
        eng.add_request(
            f"r{i}",
            [int(x) for x in rng.integers(1, 500, 20 + 7 * i)],
            SamplingParams(temperature=0.0, max_tokens=4),
        )
    done = eng.run_to_completion()
    assert all(len(v) == 4 for v in done.values()), done
    return {
        "mesh": f"tp={TP} x dp={DP} over 16 virtual devices",
        "layers": 80,
        "heads": "64q/8kv (GQA 8)",
        "requests_served": len(done),
        "wall_s": round(time.time() - t0, 1),
    }


#: axis sizes of the two layouts --check-rules validates against
CHECK_RULES_LAYOUTS = {
    "1-host": {"dp": 1, "sp": 1, "ep": 1, "tp": 1},
    "tp=8,dp=2": {"dp": 2, "sp": 1, "ep": 1, "tp": 8},
}


def check_rules() -> dict:
    """Dry-resolve every registry preset x {fp, quantized} through the
    logical-axis rule table; validate every resolved PartitionSpec only
    references mesh axes the layouts actually have. Raises on any
    unknown logical axis name. Pure metadata — no arrays, no devices."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.models.registry import get_model, list_presets
    from dynamo_tpu.parallel.logical import default_rules
    from dynamo_tpu.parallel.shardings import kv_logical_axes

    rules = default_rules()
    mesh_axes = {a for _, a in rules.rules if a is not None}
    for layout, sizes in CHECK_RULES_LAYOUTS.items():
        missing = mesh_axes - set(sizes)
        assert not missing, f"{layout} lacks mesh axes {missing}"

    presets = list_presets()
    report = {}
    for name in presets:
        adapter = get_model(name, dtype="bfloat16")
        row = {"leaves": 0, "sharded": {}, "quantized_leaves": 0}
        for quantized in (False, True):
            tree = adapter.logical_axes(quantized=quantized)
            specs = rules.tree_specs(tree)  # raises on unknown names
            leaves = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)
            )
            for spec in leaves:
                for axis in spec:
                    if axis is None:
                        continue
                    assert axis in mesh_axes, (
                        f"{name}: resolved spec {spec} references "
                        f"unknown mesh axis {axis!r}"
                    )
                    if not quantized:
                        row["sharded"][axis] = (
                            row["sharded"].get(axis, 0) + 1
                        )
            key = "quantized_leaves" if quantized else "leaves"
            row[key] = len(leaves)
        assert row["sharded"].get("tp"), (
            f"{name}: no dim resolves to 'tp' — the rule table left the "
            "whole model replicated under tensor parallelism"
        )
        report[name] = row
    # the KV page pool rides the same table
    kv_spec = rules.spec(kv_logical_axes())
    return {
        "presets_checked": len(presets),
        "layouts": CHECK_RULES_LAYOUTS,
        "rules": rules.doc(),
        "kv_pool_spec": str(kv_spec),
        "per_preset": report,
    }


def main() -> None:
    if "--check-rules" in sys.argv:
        print(json.dumps(check_rules(), indent=2))
        return
    out = {"accounting": accounting(), "execution": execution_proof()}
    path = Path(__file__).resolve().parent.parent / "artifacts"
    path.mkdir(parents=True, exist_ok=True)
    (path / "dryrun_70b.json").write_text(json.dumps(out, indent=2))
    acc = out["accounting"]
    print(
        json.dumps(
            {
                "fits_int8": acc["fits_int8"],
                "fits_bf16": acc["fits_bf16"],
                "weights_int8_gb_per_chip": round(
                    acc["weights_int8_bytes_per_chip"] / 2**30, 2
                ),
                "kv_pages_possible": acc["kv_pages_possible_int8"],
                "execution": out["execution"],
            }
        )
    )


if __name__ == "__main__":
    main()
