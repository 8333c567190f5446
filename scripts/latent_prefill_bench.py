"""A latent-cache prefill chunk's attention alone, on the chip: device
time of `models/mla.py`'s `_latent_prefill_attention` (scope `attn/flash`
of a latent model: the chunk over its paged history and over itself) in
all 8 layers of `deepseek-v2-lite-8l`'s pool, from a profiler trace,
beside the time the MXU needs for its multiplications, and its error
against dense float32 attention over the same bf16 operands.

    python scripts/latent_prefill_bench.py [--case NAME ...]
                     [--sweep NAME=V1,V2 ...] [--seed N] [--rehearse]

`--sweep` re-times the kernel under each value of a module
constant of `ops/flash_prefill.py` (the blocking). One JSON line a case
and setting on stdout; refuses a backend that is not a TPU unless
`--rehearse` (mla-tiny-moe's widths, interpreted, never a number).
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PEAKS = json.loads((ROOT / "chipbench" / "peaks.json").read_text())

#: prompts side by side, chunk rows (the T bucket), valid rows of each,
#: history of each (tokens, page-aligned as the engine's chunks are)
CASES = {
    # `docgen`'s mean: a 512-token chunk over ~1,400 tokens of history
    "chunk-mid": dict(t=512, cur=[512], hist=[1408]),
    "chunk-first": dict(t=512, cur=[512], hist=[0], first=True),
    "chunk-late": dict(t=512, cur=[512], hist=[3584]),
    "two-prompts": dict(t=512, cur=[512, 512], hist=[512, 2560]),
    "four-prompts": dict(t=512, cur=[512, 300, 512, 0],
                         hist=[1024, 3072, 0, 0]),
    "tail-32": dict(t=32, cur=[17, 32], hist=[2048, 3584]),
}
REHEARSAL = {
    "rehearsal": dict(t=16, cur=[16, 9, 0], hist=[36, 8, 0]),
    "rehearsal-first": dict(t=8, cur=[8], hist=[0], first=True),
}


def make_case(cfg, case: dict, seed: int, page: int, layers: int,
              pages: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, t = len(case["cur"]), case["t"]
    hn, c, r = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    keys = jax.random.split(jax.random.key(seed), 6)
    mp = max(-(-(max(case["hist"]) + t) // page), 2)
    rng = np.random.default_rng(seed)
    pt = np.zeros((b, mp), np.int32)
    for i, h in enumerate(case["hist"]):
        used = -(-h // page)
        pt[i, :used] = rng.choice(np.arange(1, pages), used, replace=False)
    hist = np.asarray(case["hist"], np.int32)
    cur = np.asarray(case["cur"], np.int32)
    pos = hist[:, None] + np.arange(t, dtype=np.int32)[None, :]
    valid = np.arange(t)[None, :] < cur[:, None]
    k_pe = jax.random.normal(keys[3], (b, t, r), cfg.dtype)
    return dict(
        q_lat=jax.random.normal(keys[0], (b, t, hn, c), jnp.float32),
        q_pe=jax.random.normal(keys[1], (b, t, hn, r), cfg.dtype),
        c_kv=jax.random.normal(keys[2], (b, t, c), cfg.dtype),
        k_pe=k_pe,
        k=jax.random.normal(keys[4], (layers, pages, page, 1, c), cfg.dtype),
        v=jnp.pad(
            jax.random.normal(keys[5], (layers, pages, page, 1, r),
                              cfg.dtype),
            ((0, 0),) * 4 + ((0, cfg.kv_rope_dim - r),)),
        pt=jnp.asarray(pt), pos=jnp.asarray(pos), valid=jnp.asarray(valid),
        hist=hist, cur=cur,
    )


def program(mla, cfg, first_chunk: bool):
    """Every layer of the pool in one program, as a step's layer scan."""
    import jax
    import jax.numpy as jnp

    def fn(q_lat, q_pe, c_kv, k_pe, k, v, pt, pos, valid):
        pe = mla._pad_last(k_pe, cfg.kv_rope_dim)  # the rope key as cached

        def layer(_, li):
            return None, mla._latent_prefill_attention(
                q_lat, q_pe, c_kv, pe, k, v, li, pt, pos, valid, cfg,
                first_chunk)

        _, out = jax.lax.scan(
            layer, None, jnp.arange(k.shape[0], dtype=jnp.int32))
        return out

    return jax.jit(fn)


def reference(cfg, case: dict, layer: int):
    """Dense float32 attention of the valid rows over what the program's
    operands are once rounded to the model dtype: [B, T, H, c]."""
    import jax
    import jax.numpy as jnp

    r = cfg.qk_rope_head_dim

    @jax.jit
    def ref(q_lat, q_pe, c_kv, k_pe, k, v, pt, pos, valid):
        f32, dt = jnp.float32, cfg.dtype
        b = pt.shape[0]
        ql = (q_lat * cfg.softmax_scale).astype(dt).astype(f32)
        qp = (q_pe.astype(f32) * cfg.softmax_scale).astype(dt).astype(f32)
        lat = jnp.concatenate(
            [k[layer][pt].reshape(b, -1, k.shape[-1]), c_kv], 1).astype(f32)
        rope = jnp.concatenate(
            [v[layer][pt].reshape(b, -1, v.shape[-1])[..., :r], k_pe],
            1).astype(f32)
        n_hist = lat.shape[1] - c_kv.shape[1]
        key_pos = jnp.concatenate([
            jnp.broadcast_to(jnp.arange(n_hist), (b, n_hist)),
            jnp.where(valid, pos, 1 << 30)], 1)
        start = jnp.where(valid[:, 0], pos[:, 0], 0)
        seen = jnp.where(
            jnp.arange(lat.shape[1])[None] < n_hist,
            key_pos < start[:, None], True)
        s = jnp.einsum("bthc,bkc->bhtk", ql, lat, precision="highest") + (
            jnp.einsum("bthr,bkr->bhtk", qp, rope, precision="highest"))
        mask = seen[:, None, None, :] & (
            key_pos[:, None, None, :] <= pos[:, None, :, None])
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return jnp.einsum("bhtk,bkc->bthc", p, lat, precision="highest")

    return ref(*(case[n] for n in (
        "q_lat", "q_pe", "c_kv", "k_pe", "k", "v", "pt", "pos", "valid")))


def multiply_flops(cfg, case: dict) -> float:
    """2 x (C + r) for a score and 2 x C for its weighted latent, for
    every head, valid query row and key it sees, one layer."""
    seen = sum(
        int(h) * int(n) + int(n) * (int(n) + 1) // 2
        for h, n in zip(case["hist"], case["cur"]))
    return 2.0 * cfg.num_heads * seen * (
        2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def program_seconds(trace_dir: str) -> tuple[float, int, float]:
    """Summed device time and count of `jit_fn`'s events in a trace, and
    the part of it inside the kernel's own events."""
    from chipbench import hostspans, trace

    loaded = hostspans.load(trace.find_xplane(trace_dir))
    for dev in loaded["devices"].values():
        mods = [e - s for n, s, e in dev["modules"] if n == "jit_fn"]
        kernel = sum(e - s for n, s, e, _scope in dev["ops"]
                     if n.startswith("%latent_prefill_attention"))
        return sum(mods), len(mods), kernel  # one chip
    return 0.0, 0, 0.0


def measure(mla, name: str, case: dict, seed: int, rehearse: bool) -> dict:
    import jax
    import numpy as np

    cfg = (mla.MlaConfig.tiny_moe() if rehearse
           else mla.MlaConfig.deepseek_v2_lite(8))
    from dataclasses import replace

    cfg = replace(cfg, attention_impl="pallas")
    # the cell's pool
    layers, pages, page = (2, 40, 4) if rehearse else (8, 5000, 64)
    data = make_case(cfg, case, seed, page, layers, pages)
    fn = program(mla, cfg, bool(case.get("first")))
    args = tuple(data[n] for n in (
        "q_lat", "q_pe", "c_kv", "k_pe", "k", "v", "pt", "pos", "valid"))
    got = np.asarray(jax.block_until_ready(fn(*args))[0], np.float32)
    want = np.asarray(reference(cfg, data, 0))
    live = np.asarray(data["valid"])
    err = np.abs(got - want)[live]
    flops = multiply_flops(cfg, case)
    out = {
        "case": name, "prompts": len(case["cur"]), "t": case["t"],
        "hist": case["hist"], "cur": case["cur"], "layers": layers,
        "max_abs_err": float(err.max()) if err.size else 0.0,
        "mean_abs_err": float(err.mean()) if err.size else 0.0,
        "finite": bool(np.isfinite(got).all()),
        "gflop_per_layer": flops / 1e9,
        "device": jax.devices()[0].device_kind,
    }
    if rehearse:
        return out
    peak = PEAKS[out["device"]]["bf16_flops_per_s"]
    with tempfile.TemporaryDirectory() as tmp:
        jax.block_until_ready(fn(*args))
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        seconds, calls, kernel = program_seconds(tmp)
    per_layer = seconds / max(calls, 1) / layers
    out.update(
        calls=calls, us_per_layer=per_layer * 1e6,
        kernel_us_per_layer=kernel / max(calls, 1) / layers * 1e6,
        mxu_floor_us=flops / peak * 1e6,
        mxu_share=100.0 * flops / peak / per_layer if per_layer else None,
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", action="append", choices=sorted(CASES))
    ap.add_argument("--sweep", action="append", default=[],
                    metavar="NAME=V1,V2", help="ops/flash_prefill constant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    import jax

    if jax.default_backend() != "tpu" and not ns.rehearse:
        print("latent_prefill_bench: no TPU; --rehearse for the CPU",
              file=sys.stderr)
        return 2
    from dynamo_tpu.models import mla
    from dynamo_tpu.ops import flash_prefill

    names, values = [], []
    for item in ns.sweep:
        key, vals = item.split("=", 1)
        if not hasattr(flash_prefill, key):
            raise SystemExit(f"ops/flash_prefill.py has no {key}")
        names.append(key)
        values.append([int(x) for x in vals.split(",")])
    cases = REHEARSAL if ns.rehearse else {
        n: CASES[n] for n in (ns.case or CASES)}
    failed = 0
    for setting in itertools.product(*values):
        for key, value in zip(names, setting):
            setattr(flash_prefill, key, value)
        for name, case in cases.items():
            try:
                doc = measure(mla, name, case, ns.seed, ns.rehearse)
            except Exception as e:  # noqa: BLE001 — the others still run
                doc = {"case": name,
                       "error": f"{type(e).__name__}: {e}"[:2000]}
                failed += 1
            doc["set"] = dict(zip(names, setting))
            print(json.dumps(doc), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
