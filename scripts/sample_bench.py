"""The sampler alone, on the chip: device time of one jitted
`engine.sampling.sample` from a profiler trace (not the host's clock) at
the rows x vocabulary of the five cells, and whether it draws the tokens
a vocabulary-wide `lax.top_k` draws.

    python scripts/sample_bench.py [--shape NAME ...] [--impl FILE]
                     [--set NAME=VALUE ...]

`--impl` times another file's `sample` (a copy of the parent commit's
`engine/sampling.py`) on the same inputs; `--set` assigns a module
constant of it before tracing (`CAND_BLOCKS=(256,8)`). One JSON line per
shape on stdout; refuses a backend that is not a TPU unless `--rehearse`
(tiny sizes, never a number).
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

#: sampled rows x vocabulary of a decode step, per cell
SHAPES = {
    "qwen2-longgen": (64, 152_064),
    "falconh1-longdoc": (32, 261_120),
    "nano3-chat-churn": (64, 131_072),
    "dsv2lite-docgen": (64, 102_400),
    "phi3-chat-closed": (16, 32_064),
    # a mixed step samples the decode rows and the pieces' tails
    "qwen2-longgen.mixed": (128, 152_064),
}
REHEARSAL = (4, 9_000)


def load_impl(path: str | None):
    if path is None:
        from dynamo_tpu.engine import sampling

        return sampling
    spec = importlib.util.spec_from_file_location("sampling_impl", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_case(rows: int, vocab: int, seed: int):
    """Logits as a bf16 head gives them (so equal values occur), sampled at
    0.7 / 0.9 as every cell's traffic is, one greedy row among them."""
    import jax.numpy as jnp
    import numpy as np

    r = np.random.default_rng([seed, rows, vocab])
    logits = jnp.asarray(
        r.standard_normal((rows, vocab), np.float32) * 3.0, jnp.bfloat16
    ).astype(jnp.float32)
    temps = np.full((rows,), 0.7, np.float32)
    temps[rows // 2] = 0.0
    return (
        logits, jnp.asarray(temps), jnp.full((rows,), 0.9, jnp.float32),
        jnp.zeros((rows,), jnp.int32),
        jnp.asarray(r.integers(0, 2**32, rows, dtype=np.uint32)),
        jnp.asarray(r.integers(0, 1000, rows, dtype=np.int32)),
    )


def wide_reference(args):
    """Tokens by a vocabulary-wide top-k: `sample` of the tree with its
    candidates taken by `lax.top_k` itself."""
    import jax

    from dynamo_tpu.engine import sampling

    wide = lambda scaled, k: jax.lax.top_k(scaled, k)  # noqa: E731
    narrow, sampling.top_candidates = sampling.top_candidates, wide
    try:
        return jax.jit(sampling.sample)(*args)
    finally:
        sampling.top_candidates = narrow


def module_seconds(trace_dir: str) -> tuple[float, int]:
    """Summed device time and count of `jit_sample` in a trace."""
    from chipbench import trace

    planes = trace.load(trace.find_xplane(trace_dir))
    total, count = 0.0, 0
    for lines in planes.values():
        for name, _start, dur in lines.get(trace.MODULES_LINE, ()):
            if trace.module_name(name) == "jit_sample":
                total += dur
                count += 1
        break  # one chip
    return total, count


def measure(impl, name: str, shape, seed: int, rehearse: bool) -> dict:
    import jax
    import numpy as np

    rows, vocab = shape
    args = make_case(rows, vocab, seed)
    fn = jax.jit(impl.sample)
    got = np.asarray(jax.block_until_ready(fn(*args)))
    out = {
        "shape": name, "rows": rows, "vocab": vocab,
        "same_tokens": bool((got == np.asarray(wide_reference(args))).all()),
        "device": jax.devices()[0].device_kind,
    }
    if rehearse:
        return out
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(20):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        seconds, calls = module_seconds(tmp)
    out.update(calls=calls, sample_us=seconds / max(calls, 1) * 1e6,
               ns_per_element=seconds / max(calls, 1) / (rows * vocab) * 1e9)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shape", action="append", choices=sorted(SHAPES))
    p.add_argument("--impl", help="another engine/sampling.py to time")
    p.add_argument("--set", action="append", default=[], metavar="NAME=VALUE")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()

    from dynamo_tpu import platform

    if platform.require_platform() != "tpu" and not args.rehearse:
        raise SystemExit("a device time needs the chip (or --rehearse)")
    impl = load_impl(args.impl)
    for item in args.set:
        key, value = item.split("=", 1)
        if not hasattr(impl, key):
            raise SystemExit(f"{impl.__name__} has no constant {key}")
        setattr(impl, key, ast.literal_eval(value))
    shapes = {"rehearsal": REHEARSAL} if args.rehearse else {
        n: SHAPES[n] for n in (args.shape or SHAPES)}
    for name, shape in shapes.items():
        line = measure(impl, name, shape, args.seed, args.rehearse)
        line.update(impl=args.impl or "tree", set=args.set)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
