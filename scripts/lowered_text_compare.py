"""python lower_cmp.py <repo root> <out.json>: sha256 of lowered texts.

(1) whole model passes of tiny presets (CPU lowering, xla and pallas
    interpreted): prefill chunk, decode step, mixed step.
(2) the shared kernels at the accepted cells' OWN shapes, lowered for the
    TPU platform (Mosaic module inside the text; nothing compiles or runs).
"""
import hashlib
import json
import sys

root, out = sys.argv[1], sys.argv[2]
sys.path.insert(0, root)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dynamo_tpu.models.registry import get_model  # noqa: E402

res = {}


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


S = jax.ShapeDtypeStruct


def abstract(tree):
    return jax.tree.map(lambda a: S(a.shape, a.dtype), tree)


def model_passes(preset, impl):
    ad = get_model(preset, attention_impl=impl)
    params = jax.eval_shape(ad.init_params, jax.random.key(0))
    state = ad.state_layers
    kv = jax.eval_shape(
        lambda: ad.init_kv(64, 16, **({"state_slots": 4} if state else {})))
    i32 = jnp.int32

    def group(b, t):
        pt = S((b, 8), i32)
        if state:
            pt = (pt, S((b, 2), i32))
        return (S((b, t), i32), S((b, t), i32), S((b, t), jnp.bool_), pt)

    for name, (b, t) in {"prefill": (1, 32), "decode": (4, 1)}.items():
        tok, pos, val, pt = group(b, t)
        text = jax.jit(ad.forward_hidden).lower(
            params, tok, pos, val, kv, pt).as_text()
        res[f"{preset}/{impl}/{name}"] = sha(text)
    text = jax.jit(ad.forward_hidden_mixed).lower(
        params, group(1, 32), group(4, 1), kv).as_text()
    res[f"{preset}/{impl}/mixed"] = sha(text)


for preset in ("tiny", "mla-tiny", "mla-tiny-moe", "keye-vl2-tiny",
               "nemotron-h-tiny", "falcon-h1-tiny", "dots3-tiny",
               "command-a-plus-tiny"):
    for impl in ("xla", "pallas"):
        try:
            model_passes(preset, impl)
        except Exception as e:  # noqa: BLE001
            res[f"{preset}/{impl}"] = f"ERR {type(e).__name__}: {e}"[:300]

# -- the kernels at the cells' shapes, for the TPU ---------------------------
from dynamo_tpu.ops import flash_prefill, index_scores  # noqa: E402
from dynamo_tpu.ops import paged_attention as pa  # noqa: E402

bf, i32 = jnp.bfloat16, jnp.int32


import re  # noqa: E402

from jax._src.pallas.mosaic import lowering as _mosaic_lowering  # noqa: E402
from jax._src.pallas.mosaic import pallas_call_registration as _reg  # noqa: E402

_ASMS = []
_lower = _mosaic_lowering.lower_jaxpr_to_module


def _recording(*a, **k):
    module = _lower(*a, **k)
    _ASMS.append(module.operation.get_asm(enable_debug_info=False))
    return module


_reg.lowering.lower_jaxpr_to_module = _recording


def tpu_text(fn, *args, **kw):
    """The outer StableHLO (the kernels' serialized payloads, which embed
    file paths and line numbers, cut out) + each kernel's Mosaic module
    printed without locations."""
    del _ASMS[:]
    text = jax.jit(lambda *a: fn(*a, interpret=False, **kw)).trace(
        *args).lower(lowering_platforms=("tpu",)).as_text()
    text = re.sub(r'backend_config = "[^"]*"', "backend_config = <cut>", text)
    assert _ASMS, "no kernel lowered"
    return text + "\n".join(_ASMS)


def cell_kernels():
    # dsv2lite-docgen: deepseek-v2-lite-8l, 16 heads, latent 512 + rope 64
    # cached in 128 lanes, page 64, chunk 512, 64 rows of <= 4096 tokens
    L, P, s, c, r, h = 8, 4096, 64, 512, 128, 16
    mp = 64
    for t in (32, 512):
        res[f"dsv2lite/latent_prefill/T{t}"] = sha(tpu_text(
            flash_prefill.latent_prefill_attention,
            S((1, t, h, c), bf), S((1, t, h, r), bf), S((1, t, c), bf),
            S((1, t, r), bf), S((L, P, s, 1, c), bf), S((L, P, s, 1, r), bf),
            S((), i32), S((1, mp), i32), S((1,), i32), S((1,), i32)))
    # its decode walk: 64 rows, latent
    b = 64
    res["dsv2lite/latent_walk"] = sha(tpu_text(
        pa.paged_decode_attention,
        S((b, h, c + r), bf), S((L, P, s, 1, c), bf), S((L, P, s, 1, r), bf),
        S((), i32), S((b, mp), i32), S((b,), i32), latent=True))
    # keye-longctx: 16 index heads of 64, pair rows of 128, 32 rows of
    # <= 18,432 tokens (288 pages), GQA 32/4 of 128
    Lk, Pk, nj, di = 8, 9000, 16, 64
    mpk, bk = 288, 32
    pool = S((Lk // 2, Pk, s, 2 * di), bf)
    res["keye/index_scores/decode"] = sha(tpu_text(
        index_scores.paged_index_scores,
        S((bk, 1, nj, di), bf), S((bk, 1, nj), jnp.float32), pool,
        S((), i32), S((bk, mpk), i32), S((bk,), i32)))
    res["keye/index_scores/chunk"] = sha(tpu_text(
        index_scores.paged_index_scores,
        S((1, 512, nj, di), bf), S((1, 512, nj), jnp.float32), pool,
        S((), i32), S((1, mpk), i32), S((1,), i32), S((1, 512, di), bf)))
    hq, hkv, d = 32, 4, 128
    res["keye/walk_bits"] = sha(tpu_text(
        lambda q, k, v, layer, pt, hl, bits, interpret: (
            pa.paged_decode_attention(q, k, v, layer, pt, hl,
                                      token_bits=bits, interpret=interpret)),
        S((bk, hq, d), bf), S((Lk, Pk, s, hkv, d), bf),
        S((Lk, Pk, s, hkv, d), bf), S((), i32), S((bk, mpk), i32),
        S((bk,), i32), S((bk, mpk * s), jnp.bool_)))
    # the decode walk at every other cell's shape, under models/llama.py's
    # budget: (rows, Hq, Hkv, pool dtype); the latent ones a chip's heads
    budget = 12 << 20
    for name, (rows, hq_, hkv_, dt) in {
        "qwen2-longgen": (64, 28, 4, jnp.int8),
        "phi3-chat-closed": (16, 32, 32, bf),
        "nano3-chat-churn": (64, 32, 2, bf),
        "falconh1-longdoc": (32, 20, 4, bf),
        "keye-longctx/dense": (32, 32, 4, bf),
        "command-a-plus": (32, 128, 8, bf),
    }.items():
        pool = S((4, 2000, s, hkv_, d), dt)
        planes = [S((4, 2000, hkv_, 128), jnp.float32)] * 2 * (dt != bf)
        res[f"walk/{name}"] = sha(tpu_text(
            lambda q, k, v, layer, pt, hl, *sc, interpret: (
                pa.paged_decode_attention(
                    q, k, v, layer, pt, hl, interpret=interpret,
                    vmem_budget=budget,
                    **dict(zip(("k_scale", "v_scale"), sc)))),
            S((rows, hq_, d), bf), pool, pool, S((), i32),
            S((rows, 64), i32), S((rows,), i32), *planes))
    for name, heads in {"dots3-longctx": 128, "dots3-longctx/64": 64}.items():
        res[f"walk/{name}"] = sha(tpu_text(
            lambda q, k, v, layer, pt, hl, bits, interpret: (
                pa.paged_decode_attention(
                    q, k, v, layer, pt, hl, interpret=interpret, latent=True,
                    token_bits=bits, vmem_budget=48 << 20)),
            S((bk, heads, c + r), bf), S((3, Pk, s, 1, c), bf),
            S((3, Pk, s, 1, r), bf), S((), i32), S((bk, mpk), i32),
            S((bk,), i32), S((bk, mpk * s), jnp.bool_)))
    # qwen2-longgen / phi3-chat-closed / falconh1-longdoc: the GQA chunk
    # kernels (qwen2-7b: 28 / 4 heads of 128, a 512-token chunk)
    hq, hkv = 28, 4
    qkv = (S((1, 512, hq, d), bf), S((1, 512, hkv, d), bf),
           S((1, 512, hkv, d), bf))
    res["qwen2/flash_prefill"] = sha(tpu_text(
        flash_prefill.flash_prefill_attention, *qkv, S((1,), i32)))
    res["qwen2/paged_prefill"] = sha(tpu_text(
        flash_prefill.paged_prefill_attention, *qkv,
        S((28, 1700, s, hkv, d), bf), S((28, 1700, s, hkv, d), bf),
        S((), i32), S((1, 128), i32), S((1,), i32), S((1,), i32)))


try:
    cell_kernels()
except Exception as e:  # noqa: BLE001
    import traceback
    traceback.print_exc()
    res["cell_kernels"] = f"ERR {type(e).__name__}: {e}"[:400]

json.dump(res, open(out, "w"), indent=1)
print(json.dumps(res, indent=1))
