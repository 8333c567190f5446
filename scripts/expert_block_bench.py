"""python scripts/expert_block_bench.py [--rehearse] [--tree DIR]
    [--only dots3,keye,nano3] [--set NAME=VALUE ...] [--profile]

One expert layer's block (norm, gate, route, the held experts, the shared
expert, the residual add) of the three families whose chip holds a SHARE of
a layer's experts, alone on the chip at their cells' shapes: each family's
own `moe_ffn` over one group of rows, inside a loop whose carry it touches
(the block's output is the next turn's input, and the turns walk the eight
layers of an expert stack as large as a cell's, so XLA can hoist nothing,
no turn finds its matrices where the last one left them, and the compiler
cannot keep the stack in VMEM: with two layers it prefetched all of it, 0.12
ms a turn of keye's block, PR 51).

- dots3 (`dots3-longctx`): 5,120 wide, 8 of 256 held, top 8, + shared;
  544 rows (a 512-token piece beside 32 decode rows) and 32 (a decode step)
- keye (`keye-longctx`): 2,048 wide, 16 of 128 held, top 8; 543 and 32 rows
- nano3 (`nano3-chat-churn`): 2,688 wide, 16 of 128 held, top 6, relu2,
  + shared; 576 rows (a 512-token bucket beside 64 decode rows) and 64

`block_ms` is wall time of the jitted loop over its turns, the median of
`--calls` after a warm-up. `rows_a_pass` is `mla.share_rows` at that shape
where the tree has one, `extra_passes` the passes beyond the first over the
loop's turns (0 where the bound held in every turn), `held_mean` / `_max`
the share's assignments a turn. With `--tree DIR` the block of ANOTHER
checkout (`git archive` the parent into `.archive_parent/`) on the same
inputs. `--set _SHARE_ROOM=4` sets an attribute of models/mla.py before
the trace. `--profile` also prints the block's longest device operations
from a profiler trace of three calls, ms a turn. `--rehearse`
(JAX_PLATFORMS=cpu) walks it at the tiny presets: never a number.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument(
        "--tree", default=str(Path(__file__).resolve().parent.parent))
    ap.add_argument("--only", default="dots3,keye,nano3")
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--turns", type=int, default=16)
    ap.add_argument("--profile", action="store_true")
    ns = ap.parse_args(argv)
    sys.path.insert(0, ns.tree)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from dynamo_tpu.models import dots3, keye_vl, mla, nemotron_h
    from dynamo_tpu.models.llama import rms_norm

    for item in ns.set:
        name, value = item.split("=", 1)
        setattr(mla, name, type(getattr(mla, name))(value))
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not ns.rehearse:
        raise SystemExit("no TPU: say --rehearse (JAX_PLATFORMS=cpu)")

    # family -> (module, configuration, the layer's shapes, its norm's
    # name, (mixed rows, decode rows))
    families = {
        "dots3": (
            dots3, dots3.Dots3Config.dots3_1chip() if on_chip
            else dots3.Dots3Config.tiny(),
            lambda c: dots3._stack_shapes(c)["moe"], "mlp_norm", (544, 32)),
        "keye": (
            keye_vl, keye_vl.KeyeVLConfig.keye_vl2_1chip() if on_chip
            else keye_vl.KeyeVLConfig.tiny(),
            lambda c: {n: s for n, s in keye_vl._layer_shapes(c).items()
                       if n.startswith(("we_", "w_router", "mlp_norm"))},
            "mlp_norm", (543, 32)),
        "nano3": (
            nemotron_h,
            nemotron_h.NemotronHConfig.nemotron3_nano_1chip() if on_chip
            else nemotron_h.NemotronHConfig.tiny(),
            lambda c: nemotron_h._shapes(c)["moe"], "norm", (576, 64)),
    }
    layers, key = 8, jax.random.key(0)

    def timed(f, *args):
        jax.block_until_ready(f(*args))
        out = []
        for _ in range(1 if ns.rehearse else ns.calls):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            out.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(out)

    for fam in ns.only.split(","):
        mod, cfg, shapes, norm, row_counts = families[fam]
        h = cfg.hidden_size
        lp = {}
        for i, (name, shape) in enumerate(shapes(cfg).items()):
            k = jax.random.fold_in(key, i)
            if name.endswith("norm"):
                w = jnp.ones((layers, *shape), cfg.dtype)
            elif name == "router_bias":
                w = 0.01 * jax.random.normal(k, (layers, *shape), jnp.float32)
            elif name == "w_router":  # logits of standard deviation 2
                w = 2.0 / h ** 0.5 * jax.random.normal(
                    k, (layers, *shape), jnp.float32)
            else:
                w = (jax.random.normal(k, (layers, *shape), jnp.float32)
                     / shape[-2] ** 0.5).astype(cfg.dtype)
            lp[name] = w
        held = cfg.experts_held or (0, cfg.n_routed_experts)

        def block(lp, x, count, cfg=cfg, mod=mod, norm=norm, held=held):
            """The loop; with `count` (never timed) a second gate a turn
            says how many assignments the share drew."""
            experts = {n: w for n, w in lp.items() if n.startswith("we_")}

            def turn(i, carry):
                x, extra, most, total = carry
                li = i % layers
                mine = {n: lax.dynamic_index_in_dim(w, li, 0, keepdims=False)
                        for n, w in lp.items() if n not in experts}
                xn = rms_norm(x, mine[norm], cfg.rms_norm_eps)
                args = ((None, (experts, li)) if mod is nemotron_h
                        else ((experts, li),))
                out = mod.moe_ffn(xn, mine, cfg, *args)
                if isinstance(out, tuple):  # (out, the tree's counts)
                    out, counts = out
                    if hasattr(mla, "share_rows"):
                        extra = extra + jnp.asarray(
                            counts, jnp.int32).reshape(-1)[-1]
                if count:
                    _, topi = mla._gate(
                        xn.reshape(-1, x.shape[-1]), mine,
                        getattr(cfg, "full_geo", cfg),
                        precision=lax.Precision.HIGHEST)
                    n = jnp.sum((topi >= held[0])
                                & (topi < held[0] + held[1]))
                    most, total = jnp.maximum(most, n), total + n
                return x + out.astype(x.dtype), extra, most, total

            zero = jnp.int32(0)
            return lax.fori_loop(0, ns.turns, turn, (x, zero, zero, zero))

        for rows in row_counts if on_chip else (24, 4):
            x = jax.random.normal(
                jax.random.fold_in(key, rows), (1, rows, h), jnp.float32
            ).astype(cfg.dtype)
            f = jax.jit(lambda lp, x: block(lp, x, False))
            ms = timed(f, lp, x)
            _, extra, most, total = jax.jit(
                lambda lp, x: block(lp, x, True))(lp, x)
            k = cfg.num_experts_per_tok
            line = {
                "tree": ns.tree, "family": fam, "rows": rows,
                "held": list(held), "of": cfg.n_routed_experts, "top": k,
                "platform": jax.devices()[0].platform, "set": ns.set,
                "block_ms": round(ms / ns.turns, 4),
                "held_mean": round(int(total) / ns.turns, 1),
                "held_max": int(most),
            }
            if hasattr(mla, "share_rows"):
                line["rows_a_pass"] = mla.share_rows(
                    rows, k, held[1], cfg.n_routed_experts)
                line["extra_passes"] = int(extra)
            print(json.dumps(line), flush=True)
            if ns.profile:
                for ms, calls, name in device_ops(f, lp, x):
                    print(f"  {ms / ns.turns:8.4f} ms a turn  x{calls:3d}  "
                          f"{name[:100]}", flush=True)
    return 0


def device_ops(f, *args, top: int = 24):
    """[(device ms a call, events a call, operation)] of a jitted call, the
    longest first, from a profiler trace of three calls."""
    import collections
    import glob
    import tempfile

    import jax

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            out = f(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        data = jax.profiler.ProfileData.from_file(
            glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0])
    total, count = collections.Counter(), collections.Counter()
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    total[e.name] += e.duration_ns
                    count[e.name] += 1
    return [(ns_ / 3e6, count[name] // 3, name)
            for name, ns_ in total.most_common(top)]


if __name__ == "__main__":
    raise SystemExit(main())
