"""python scripts/full_piece_bench.py [--rehearse] [--tree DIR]
    [--shapes 1x512@8192,..] [--distance-at 1x512@12288] [--set NAME=VALUE]
    [--forms plain,absorbed]

A FULL layer's attention block of dots3-note-prev for a prompt PIECE alone
on the chip, at the cell's shapes (128 heads of 128 | 64 | 128 over a
512-wide latent, pages of 64 in a table of 288, bf16), in the manner of
scripts/window_piece_bench.py: `dots3.full_attention` on one group of B
pieces of T tokens from position `first` (so a history of `first` cached
tokens each), the three full layers chained as a step chains them (each
layer's output added to the next one's input). The SELECTION IS GIVEN: the
indexer's choice (`models/keye_vl.chosen_keys`) is replaced by a mask made
once outside the program, 2,048 keys a query drawn evenly from the keys up
to its own, so the block is the projections, the attention and `wo`.

- `plain_ms` / `absorbed_ms`: the whole block a layer with the form FORCED
  (`dots3.plain_full` replaced for the trace), both inside the same block,
  in one call;
- `block_ms`: with `--tree DIR`, the block of ANOTHER checkout in the form
  it picks (the parent's absorbed form: `git archive` it into
  `.archive_parent/`) on the same inputs;
- `distance_plain` / `distance_absorbed` (the shapes of `--distance-at`):
  each form's output of ONE layer against the plain form in float32 on the
  same bf16 weights and pools in plain XLA, 16 heads at a time, as a share
  of its norm.

Wall time of a jitted call, the median of `--calls` after a warm-up,
`block_until_ready` around each; weights, pools and mask are the call's
arguments (a closed-over stack is a constant the compiler folds for
minutes). `--set PLAIN_HEADS=16` sets a constant of ops/flash_prefill.py
(the plain kernel's blocking) first; `--forms plain` times one form alone.
`--rehearse` (JAX_PLATFORMS=cpu) walks it at the tiny preset with the
kernels interpreted: never a number.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
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
        default="1x512@0,1x512@8192,1x512@16384,2x512@0,2x512@8192,"
                "2x512@16384,4x512@0,4x512@8192,4x512@16384,1x32@16384,"
                "32x32@0")
    ap.add_argument("--distance-at", default="1x512@12288")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--forms", default="plain,absorbed")
    ns = ap.parse_args(argv)
    sys.path.insert(0, ns.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import dots3, mla
    from dynamo_tpu.models.llama import StepGroup
    from dynamo_tpu.ops import flash_prefill
    from dynamo_tpu.ops import token_select as ts

    for item in ns.set:
        name, value = item.split("=")
        setattr(flash_prefill, name, int(value))
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not ns.rehearse:
        raise SystemExit("no TPU: say --rehearse (JAX_PLATFORMS=cpu)")
    parse = lambda text: [  # noqa: E731
        tuple(int(x) for x in s.replace("@", "x").split("x"))
        for s in text.split(",") if s]
    if on_chip:
        base, page, mp, pages = dots3.Dots3Config.dots3_1chip(), 64, 288, 2400
        shapes, judged = parse(ns.shapes), parse(ns.distance_at)
    else:
        base, page, mp, pages = dots3.Dots3Config.tiny(), 4, 16, 80
        shapes = judged = [(2, 16, 24)]
    cfg = dataclasses.replace(base, attention_impl="pallas")
    geo, layers = cfg.full_geo, cfg.full_layers
    a_q, a_kv = cfg.rescale(geo)
    key = jax.random.key(0)
    # a trained block's scales, as `dots3.init_params` draws them
    spread = {"wq_b": 1 / a_q, "wi_q": 1 / a_q, "wkv_b": 1 / a_kv}
    lp = {name: (jnp.ones(shape, cfg.dtype) if name.endswith("norm") else (
        jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        * spread.get(name, 1.0) / shape[0] ** 0.5).astype(cfg.dtype))
        for i, (name, shape) in enumerate(
            dots3._stack_shapes(cfg)["full"].items())}
    def pool(i, width, scale=1.0):
        return (scale * jax.random.normal(
            jax.random.fold_in(key, 100 + i),
            (layers, pages, page, 1, width), jnp.float32)).astype(cfg.dtype)

    kv = (pool(0, geo.kv_lora_rank, a_kv), mla._pad_last(
        pool(1, geo.qk_rope_head_dim), geo.kv_rope_dim))
    ki_pool = jnp.zeros((layers, pages, page, cfg.index_head_dim), cfg.dtype)

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
        used = -(-(first + t) // page)
        tables = np.zeros((b, mp), np.int32)
        tables[:, :used] = 1 + np.arange(b * used).reshape(b, used)
        return StepGroup(jnp.zeros((b, t), jnp.int32), pos,
                         jnp.ones((b, t), bool), jnp.asarray(tables))

    def given(g, seed):
        """bool [B, T, N]: `index_topk` keys a query, drawn evenly from
        the keys up to its own (all of them where they are fewer)."""
        b, t = g.positions.shape
        scores = jax.random.uniform(
            jax.random.fold_in(key, seed), (b * t, mp * page), jnp.float32)
        return jax.jit(ts.select_tokens, static_argnums=2)(
            scores, (g.positions + 1).reshape(-1), cfg.index_topk
        ).reshape(b, t, -1)

    @contextlib.contextmanager
    def traced_with(chosen, form):
        """The selection given and, where `form` is not None, the form a
        group attends in forced, while a program is traced."""
        rules = dots3.keye.chosen_keys, getattr(dots3, "plain_full", None)
        dots3.keye.chosen_keys = lambda *a: chosen
        if form is not None:
            dots3.plain_full = lambda t, cfg: form
        try:
            yield
        finally:
            dots3.keye.chosen_keys = rules[0]
            if form is not None:
                dots3.plain_full = rules[1]

    def block(form, depth, g, lp, kv, ki_pool, x, chosen):
        """(`depth` full layers' attention blocks chained, the last one's
        output alone), the selection given."""
        with traced_with(chosen, form):
            for li in range(depth):
                out, kv, ki_pool, _, _ = dots3.full_attention(
                    x, lp, cfg, kv, ki_pool, jnp.int32(li), [g], [None])
                x = x + out
        return x, out

    def reference(g, lp, kv, x, chosen):
        """One layer's block in the plain form, float32, plain XLA."""
        f32 = jnp.float32
        wide = dataclasses.replace(cfg, attention_impl="xla", dtype=f32)
        geo = wide.full_geo
        n, hn = geo.qk_nope_head_dim, geo.num_heads
        lp, kv, x = jax.tree.map(lambda a: a.astype(f32), (lp, kv, x))
        q, c_kv, kv_a, _ = mla.latent_projections(
            x, lp, geo, wide.rescale(geo))
        qp = mla._interleaved_rope(q[..., n:], g.positions, geo)
        kp = mla._interleaved_rope(
            kv_a[..., geo.kv_lora_rank:], g.positions, geo)
        b, t = g.positions.shape
        first = g.positions[:, 0]
        put = jax.vmap(
            lambda rows, own, at: jax.lax.dynamic_update_slice_in_dim(
                jnp.pad(rows, ((0, t), (0, 0))), own, at, 0)[:mp * page])
        lat = put(kv[0][0][g.page_tables].reshape(b, mp * page, -1), c_kv,
                  first)
        rope = put(kv[1][0][g.page_tables].reshape(b, mp * page, -1)[
            ..., :geo.qk_rope_head_dim], kp, first)
        wkv_b = lp["wkv_b"].reshape(geo.kv_lora_rank, hn, -1)
        some = math.gcd(hn, 16)

        def heads(args):  # [B, T, some, .] queries, [c, some, n + v]
            qn, qr, w = args
            k = jnp.einsum("bkc,chd->bkhd", lat, w[..., :n])
            v = jnp.einsum("bkc,chd->bkhd", lat, w[..., n:])
            sc = (jnp.einsum("bthd,bkhd->bhtk", qn, k) + jnp.einsum(
                "bthr,bkr->bhtk", qr, rope)) * geo.softmax_scale
            p = jax.nn.softmax(jnp.where(chosen[:, None], sc, -jnp.inf), -1)
            return jnp.einsum("bhtk,bkhd->bthd", p, v)

        by = lambda a, axis: jnp.moveaxis(a.reshape(  # noqa: E731
            *a.shape[:axis], hn // some, some, *a.shape[axis + 1:]), axis, 0)
        o = jax.lax.map(heads, (by(q[..., :n], 2), by(qp, 2), by(wkv_b, 1)))
        o = jnp.moveaxis(o, 0, 2).reshape(b, t, hn, -1)
        return mla.heads_output(o, lp, geo, dots3.head_gate(x, lp, wide))

    for b, t, first in dict.fromkeys(shapes + judged):
        g = group(b, t, first)
        x = jax.random.normal(
            jax.random.fold_in(key, t), (b, t, cfg.hidden_size), jnp.float32
        ).astype(cfg.dtype)
        chosen = given(g, 1000 + b * t + first)
        line = {"tree": ns.tree, "b": b, "t": t, "first": first,
                "platform": jax.devices()[0].platform, "set": ns.set}
        args = (lp, kv, ki_pool, x, chosen)
        run = lambda form, depth: jax.jit(  # noqa: E731
            lambda *a, g=g: block(form, depth, g, *a))
        forms = {name: form for name, form in (
            ("plain", True), ("absorbed", False))
            if name in ns.forms.split(",")} if hasattr(
            dots3, "plain_full") else {"block": None}
        if (b, t, first) in shapes:
            for name, form in forms.items():
                line[f"{name}_ms"] = round(
                    timed(run(form, layers), *args) / layers, 4)
        if (b, t, first) in judged:
            want = jax.jit(lambda *a, g=g: reference(g, *a))(
                lp, kv, x, chosen)
            for name, form in forms.items():
                got = run(form, 1)(*args)[1].astype(jnp.float32)
                line[f"distance_{name}"] = round(float(
                    jnp.linalg.norm(got - want) / jnp.linalg.norm(want)), 6)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
