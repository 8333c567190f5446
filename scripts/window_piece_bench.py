"""python scripts/window_piece_bench.py [--rehearse] [--tree DIR]
    [--shapes 1x512@8192,..]

A sliding layer's attention block of dots3-note-prev for a prompt PIECE
alone on the chip, at the cell's shapes (64 heads, c 1,024, a window of 513
in a ring of 1,088 rows, 36 slots, bf16): `dots3.window_attention` on one
group of B pieces of T tokens from position `first`, the six window layers
chained as a step chains them (each layer's output added to the next one's
input, so nothing is shared between them).

- `block_ms`: the whole block a layer (projections, attention, `wo`) in the
  form the tree picks for the shape; with `--tree DIR` the block of ANOTHER
  checkout (the parent's absorbed form: `git archive` it into
  `.archive_parent/`) on the same inputs;
- `plain_ms` / `absorbed_ms`: the same block with the form FORCED
  (`dots3.plain_piece` replaced for the trace): where the two cross is
  what that rule rests on;
- `distance`: the block's output against the path without kernels in
  float32 on the same bf16 weights (`attention_impl` "xla": the rows
  written first, the whole ring attended absorbed), as a share of its norm.

Wall time of a jitted call, the median of `--calls` after a warm-up,
`block_until_ready` around each. `--rehearse` (JAX_PLATFORMS=cpu) walks it
at the tiny preset with the kernels interpreted: never a number.
"""

from __future__ import annotations

import argparse
import dataclasses
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
    ap.add_argument(
        "--shapes",
        default="1x512@8192,2x512@4096,1x256@8192,1x128@8192,1x64@8192,"
                "1x32@8192,32x32@0")
    ap.add_argument("--calls", type=int, default=20)
    ns = ap.parse_args(argv)
    sys.path.insert(0, ns.tree)

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import dots3
    from dynamo_tpu.models.llama import StepGroup

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not ns.rehearse:
        raise SystemExit("no TPU: say --rehearse (JAX_PLATFORMS=cpu)")
    if on_chip:
        base, slots = dots3.Dots3Config.dots3_1chip(), 36
        shapes = [tuple(int(x) for x in s.replace("@", "x").split("x"))
                  for s in ns.shapes.split(",")]
    else:
        base, slots, shapes = dots3.Dots3Config.tiny(), 2, [(2, 16, 64)]
    cfg = dataclasses.replace(base, attention_impl="pallas")
    geo, layers = cfg.swa_geo, cfg.state_layers
    key = jax.random.key(0)
    lp = {name: (jnp.ones(shape, cfg.dtype) if name.endswith("norm") else (
        jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        / shape[0] ** 0.5).astype(cfg.dtype))
        for i, (name, shape) in enumerate(
            dots3._stack_shapes(cfg)["swa"].items())}
    rings = tuple(
        jax.random.normal(jax.random.fold_in(key, 100 + i),
                          (layers, slots + 1, cfg.ring_tokens, w), cfg.dtype)
        for i, w in enumerate((geo.kv_lora_rank, geo.kv_rope_dim)))

    def timed(f, *args):
        jax.block_until_ready(f(*args))
        out = []
        for _ in range(1 if ns.rehearse else ns.calls):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            out.append(1e3 * (time.perf_counter() - t0))
        return statistics.median(out)

    def group(b, t, first):
        pos = first + jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        slot = 1 + jnp.arange(b, dtype=jnp.int32)
        return StepGroup(jnp.zeros((b, t), jnp.int32), pos,
                         jnp.ones((b, t), bool), jnp.zeros((b, 1), jnp.int32),
                         state_rows=jnp.stack([slot, slot], axis=1))

    def block(cfg, lp, rings, x, g):
        """The six window layers' attention blocks, chained."""
        for li in range(layers):
            out, rings = dots3.window_attention(
                x, lp, cfg, rings, jnp.int32(li), [g])
            x = x + out
        return x, rings

    for b, t, first in shapes:
        g = group(b, t, first)
        x = jax.random.normal(
            jax.random.fold_in(key, t), (b, t, cfg.hidden_size), jnp.float32
        ).astype(cfg.dtype)
        line = {"tree": ns.tree, "b": b, "t": t, "first": first,
                "platform": jax.devices()[0].platform}
        f = lambda c: jax.jit(  # noqa: E731
            lambda lp, rings, x, g=g: block(c, lp, rings, x, g)[0])
        line["block_ms"] = round(timed(f(cfg), lp, rings, x) / layers, 4)
        if hasattr(dots3, "window_piece"):
            rule = dots3.plain_piece
            for name, forced in (("plain_ms", True), ("absorbed_ms", False)):
                dots3.plain_piece = lambda t, cfg, forced=forced: forced
                line[name] = round(timed(f(cfg), lp, rings, x) / layers, 4)
            dots3.plain_piece = rule
            plain = dataclasses.replace(cfg, attention_impl="xla",
                                        dtype=jnp.float32)
            up = lambda tree: jax.tree.map(  # noqa: E731
                lambda a: a.astype(jnp.float32), tree)
            wide = tuple(r[..., :w] for r, w in zip(
                up(rings), (geo.kv_lora_rank, geo.qk_rope_head_dim)))
            one = lambda c, *a: dots3.window_attention(  # noqa: E731
                a[2], a[0], c, a[1], jnp.int32(1), [g])[0]
            got = jax.jit(lambda *a: one(cfg, *a))(lp, rings, x)
            want = jax.jit(lambda *a: one(plain, *a))(up(lp), wide, up(x))
            line["distance"] = round(float(
                jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want)), 6)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
