"""The decode page walk alone, on the chip: device time of
`paged_decode_attention` from a profiler trace (not the host's clock) at
the shapes the presets give it, beside its HBM floor, and its error
against a dense f32 reference.

    python scripts/paged_decode_bench.py [--shape NAME ...] [--impl FILE]
                     [--set NAME=VALUE ...] [--rows N] [--hist LO,HI]
                     [--budget-mib N] [--dma-only | --arith-only]

`--impl` times another file's `paged_decode_attention` (a copy of the
parent commit's `ops/paged_attention.py`, an experiment) under the same
inputs; `--set` assigns a module constant of it before tracing (block
rule experiments). `--dma-only` and `--arith-only` split a turn of the
walk through the kernel's `_PROBE`: its DMAs issued and waited with no
arithmetic, and its arithmetic on a resident slot with no DMA (times only,
nothing to compare). One JSON line per shape on stdout; refuses a backend
that is not a TPU unless `--rehearse` (tiny sizes, interpreted, never a
number).
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import math
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PAGE = 64
#: rows, query heads, kv heads, (padded) head dim, history range, layers
#: walked per call, KV pool dtype
SHAPES = {
    # qwen2-7b-int8 as `qwen2-longgen` serves it
    "qwen2-7b": dict(b=64, hq=28, hkv=4, d=128, hist=(256, 1400), layers=8),
    "qwen2-7b-kv8": dict(b=64, hq=28, hkv=4, d=128, hist=(256, 1400),
                         layers=8, kv="int8"),
    # phi3-mini: MHA, heads of 96 padded to 128
    "phi3-mini": dict(b=16, hq=32, hkv=32, d=128, scale_dim=96,
                      hist=(300, 700), layers=8),
    # llama3-8b: many blocks a row
    "llama3-8b": dict(b=32, hq=32, hkv=8, d=128, hist=(1000, 4000),
                      layers=4),
    # deepseek-v2-lite as `dsv2lite-docgen` serves it: a latent cache
    # (one 512-wide row a token that is key and value, and a rope key of
    # 64 cached as 128 lanes), 16 heads, softmax scale 1/sqrt(128 + 64)
    "deepseek-v2-lite": dict(b=64, hq=16, hkv=1, d=512, rope=128,
                             scale_dim=192, hist=(2100, 5700), layers=8),
    # command-a-plus-4l-16e as `cmdaplus-longctx` serves it, 128 / 8 heads:
    # the full layer's pages, and a sliding layer's ring as pages (65 in
    # reach of a decode row, the window's 4,095 keys named by a bit a row)
    "command-a-plus-full": dict(b=32, hq=128, hkv=8, d=128,
                                hist=(8200, 18000), layers=1),
    "command-a-plus-ring": dict(b=32, hq=128, hkv=8, d=128,
                                hist=(4160, 4160), layers=3, bits=4095),
}
REHEARSAL = dict(b=3, hq=8, hkv=2, d=128, hist=(1, 40), layers=2)
REHEARSAL_LATENT = dict(b=3, hq=8, hkv=1, d=128, rope=128, scale_dim=24,
                        hist=(1, 40), layers=2)
REHEARSAL_BITS = dict(b=3, hq=16, hkv=2, d=128, hist=(40, 40), layers=2,
                      bits=29)
PEAKS = json.loads((ROOT / "chipbench" / "peaks.json").read_text())


def load_impl(path: str | None):
    if path is None:
        from dynamo_tpu.ops import paged_attention

        return paged_attention
    spec = importlib.util.spec_from_file_location("paged_decode_impl", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_case(shape: dict, seed: int, page: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, hq, hkv, d = shape["b"], shape["hq"], shape["hkv"], shape["d"]
    layers = shape["layers"]
    rng = np.random.default_rng(seed)
    hist = rng.integers(shape["hist"][0], shape["hist"][1] + 1, b)
    mp = -(-int(shape["hist"][1]) // page)
    num_pages = 1 + b * mp
    # every row's pages scattered over the pool, none shared
    ids = rng.permutation(np.arange(1, num_pages)).reshape(b, mp)
    keys = jax.random.split(jax.random.key(seed), 5)
    pool = (layers, num_pages, page, hkv, d)
    kv = shape.get("kv")
    if kv == "int8":
        k = jax.random.randint(keys[0], pool, -127, 128, jnp.int8)
        v = jax.random.randint(keys[1], pool, -127, 128, jnp.int8)
        lanes = -(-page // 128) * 128
        plane = (layers, num_pages, hkv, lanes)
        scales = tuple(
            jax.random.uniform(kk, plane, jnp.float32, 0.5, 1.5) / 127.0
            for kk in keys[2:4]
        )
    else:
        dtype = jnp.dtype(shape.get("dtype", "bfloat16"))
        k = jax.random.normal(keys[0], pool, dtype)
        v = jax.random.normal(keys[1], pool, dtype)
        scales = (None, None)
    rope = shape.get("rope", 0)
    if rope:  # latent: `v` holds the rope key, q its part past `d`
        v = jax.random.normal(keys[1], (*pool[:4], rope), dtype)
    q = jax.random.normal(
        keys[4], (b, hq, d + rope), shape.get("qdtype", jnp.bfloat16))
    bits = None
    if shape.get("bits"):  # a run of that many cached tokens a row
        at = np.arange(mp * page)[None]
        first = rng.integers(0, hist - shape["bits"] + 1)[:, None]
        bits = jnp.asarray((at >= first) & (at < first + shape["bits"]))
    return dict(
        q=q, k=k, v=v, k_scale=scales[0], v_scale=scales[1], bits=bits,
        pt=jnp.asarray(ids, jnp.int32), hist=jnp.asarray(hist, jnp.int32),
    )


def walk(impl, scale_dim: int, interpret: bool, latent: bool = False,
         budget: int | None = None):
    """All layers of the pool in one program, as a step program's layer
    scan does: (acc, m, l) of every layer. `budget`: the VMEM the caller
    lets the block rule plan for."""
    import jax
    import jax.numpy as jnp

    def fn(q, k, v, pt, hist, k_scale, v_scale, bits):
        # the parent commit's work list also took the page size
        extra = (k.shape[2],) if "page_size" in inspect.signature(
            impl.decode_work_list).parameters else ()
        work = impl.decode_work_list(pt, hist, *extra)

        def layer(_, li):
            return None, impl.paged_decode_attention(
                q, k, v, li, pt, hist, scale_dim=scale_dim,
                work_list=work, k_scale=k_scale, v_scale=v_scale,
                interpret=interpret, **({"latent": True} if latent else {}),
                **({} if bits is None else {"token_bits": bits}),
                **({"vmem_budget": budget} if budget else {}),
            )

        _, out = jax.lax.scan(
            layer, None, jnp.arange(k.shape[0], dtype=jnp.int32)
        )
        return out

    return jax.jit(fn)


def reference(case: dict, layer: int, scale_dim: int):
    """Dense f32 attention over layer `layer`'s history: (out [B, Hq, D],
    m [B, Hq] the largest score, l [B, Hq] the softmax denominator)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ref(q, k, v, pt, hist, k_scale, v_scale, bits):
        b, hq, _d = q.shape
        hkv, page = k.shape[3], k.shape[2]

        def rows(pool, plane):
            x = pool[layer][pt].astype(jnp.float32)  # [B, MP, S, Hkv, D]
            if plane is not None:
                sc = plane[layer][pt][..., :page]  # [B, MP, Hkv, S]
                x = x * jnp.swapaxes(sc, 2, 3)[..., None]
            return x.reshape(b, -1, hkv, x.shape[-1])

        kk, vv = rows(k, k_scale), rows(v, v_scale)
        if q.shape[-1] != k.shape[-1]:  # latent: [latent | rope key], latent
            kk, vv = jnp.concatenate([kk, vv], axis=-1), kk
        # a kv head's rows once for its whole group of query heads
        qg = q.astype(jnp.float32).reshape(b, hkv, hq // hkv, -1)
        s = jnp.einsum(
            "bngd,bknd->bngk", qg, kk, precision="highest",
        ) / math.sqrt(scale_dim)
        mask = jnp.arange(kk.shape[1])[None, None, None, :] < hist[
            :, None, None, None]
        if bits is not None:
            mask &= bits[:, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        out = jnp.einsum("bngk,bknd->bngd", p, vv, precision="highest")
        out = out / l[..., None]
        return (out.reshape(b, hq, -1), m.reshape(b, hq), l.reshape(b, hq))

    return ref(case["q"], case["k"], case["v"], case["pt"], case["hist"],
               case["k_scale"], case["v_scale"], case["bits"])


def kernel_seconds(trace_dir: str) -> tuple[float, int]:
    """Summed device time and count of the kernel's events in a trace."""
    from chipbench import trace

    planes = trace.load(trace.find_xplane(trace_dir))
    total, count = 0.0, 0
    for lines in planes.values():
        for name, _start, dur in lines.get(trace.OPS_LINE, ()):
            if trace.op_name(name).startswith("%paged_decode_attention"):
                total += dur
                count += 1
        break  # one chip
    return total, count


def measure(impl, name: str, shape: dict, seed: int, rehearse: bool,
            budget: int | None = None) -> dict:
    import jax
    import numpy as np

    page = 4 if rehearse else PAGE
    case = make_case(shape, seed, page)
    scale_dim = shape.get("scale_dim", shape["d"])
    rope = shape.get("rope", 0)
    fn = walk(impl, scale_dim, interpret=rehearse, latent=bool(rope),
              budget=budget)
    args = (case["q"], case["k"], case["v"], case["pt"], case["hist"],
            case["k_scale"], case["v_scale"], case["bits"])
    acc, m, l = (np.asarray(x[0]) for x in jax.block_until_ready(fn(*args)))
    err = err_m = err_l = None  # half a turn computes nothing to compare
    if getattr(impl, "_PROBE", None) is None:
        want, want_m, want_l = (
            np.asarray(x) for x in reference(case, 0, scale_dim))
        err = float(np.max(np.abs(
            acc / np.maximum(l, 1e-30)[..., None] - want)))
        # the caller merges the current token by m and l themselves
        err_m = float(np.max(np.abs(m - want_m)))
        err_l = float(np.max(np.abs(l * np.exp(m - want_m) / want_l - 1.0)))
    itemsize = case["k"].dtype.itemsize
    live = int(np.asarray(case["hist"]).sum())
    kv_bytes = live * shape["hkv"] * itemsize * (
        shape["d"] + rope if rope else 2 * shape["d"])  # as cached
    out = {
        "shape": name, "rows": shape["b"], "heads": [shape["hq"], shape["hkv"]],
        "head_dim": shape["d"], "kv_dtype": str(case["k"].dtype),
        "live_tokens": live, "kv_bytes_per_layer": kv_bytes,
        "max_abs_err": err, "max_abs_err_m": err_m, "max_rel_err_l": err_l,
        "device": jax.devices()[0].device_kind,
    }
    if rehearse:
        return out
    peak = PEAKS[out["device"]]["hbm_bytes_per_s"]
    with tempfile.TemporaryDirectory() as tmp:
        jax.block_until_ready(fn(*args))
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        seconds, calls = kernel_seconds(tmp)
    per_call = seconds / max(calls, 1)
    out.update(
        kernel_calls=calls, kernel_us=per_call * 1e6,
        hbm_floor_us=kv_bytes / peak * 1e6,
        hbm_share=100.0 * kv_bytes / peak / per_call if per_call else None,
        gb_per_s=kv_bytes / per_call / 1e9 if per_call else None,
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--impl", help="another ops/paged_attention.py to time")
    ap.add_argument("--set", action="append", default=[],
                    metavar="NAME=VALUE", help="module constant of the impl")
    ap.add_argument("--rows", type=int, help="another batch size")
    ap.add_argument("--hist", help="LO,HI: another range of histories")
    ap.add_argument("--budget-mib", type=int, default=12,
                    help="VMEM the block rule plans under, the models' own "
                         "by default (models/llama.py); 0: none")
    half = ap.add_mutually_exclusive_group()
    half.add_argument("--dma-only", dest="probe", action="store_const",
                      const="copies", help="a turn's DMAs, no arithmetic")
    half.add_argument("--arith-only", dest="probe", action="store_const",
                      const="body", help="its arithmetic on a resident slot")
    ap.add_argument("--label", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ns = ap.parse_args()
    import jax

    if jax.default_backend() != "tpu" and not ns.rehearse:
        print("paged_decode_bench: no TPU; --rehearse for the CPU",
              file=sys.stderr)
        return 2
    impl = load_impl(ns.impl)
    for item in ns.set:
        key, value = item.split("=", 1)
        if not hasattr(impl, key):
            raise SystemExit(f"{ns.impl or 'paged_attention'} has no {key}")
        setattr(impl, key, int(value))
    if ns.probe:
        if not hasattr(impl, "_PROBE"):
            raise SystemExit(f"{ns.impl} has no _PROBE to split a turn by")
        impl._PROBE = ns.probe
    shapes = {"rehearsal": REHEARSAL, "rehearsal-latent": REHEARSAL_LATENT,
              "rehearsal-bits": REHEARSAL_BITS} if ns.rehearse else {
        n: SHAPES[n] for n in (ns.shape or SHAPES)}
    failed = 0
    for name, shape in shapes.items():
        if ns.rows:
            shape = {**shape, "b": ns.rows}
        if ns.hist:
            shape = {**shape, "hist": tuple(map(int, ns.hist.split(",")))}
        try:
            doc = measure(impl, name, shape, ns.seed, ns.rehearse,
                          ns.budget_mib << 20)
        except Exception as e:  # noqa: BLE001 — the other shapes still run
            doc = {"shape": name, "error": f"{type(e).__name__}: {e}"[:2000]}
            failed += 1
        doc.update(impl=ns.label or ns.impl or "tree", set=ns.set,
                   probe=ns.probe, budget_mib=ns.budget_mib)
        print(json.dumps(doc), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
