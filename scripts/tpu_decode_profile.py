"""Dissect the decode-step time on the real chip.

bench_1b measures ~13 ms/token at batch 128 for llama3-1b, vs a ~4 ms
memory roofline (2.5 GB bf16 weights + ~0.7 GB KV reads per fused step at
819 GB/s v5e HBM). This script times the SAME jitted fused-decode program
the engine serves with, under both attention impls, plus a dense-only
floor, to locate the gap:

  full_pallas       — engine's decode_multi program, attention_impl=pallas
  full_xla          — same, attention_impl=xla
  full_pallas_kvq   — pallas with kv_quantize=int8 (halved KV traffic)
  dense_floor       — model forward with attention replaced by identity
                      (weight-streaming floor for the dense stack)

For each impl the SAME program is also timed WITHOUT the host loop
(`pure_*`): fixed device inputs, one block per dispatch. That DIRECT
split — pure program ms/dispatch vs serve ms/dispatch, difference =
host-loop overhead — is what the 13 ms → 3.7 ms roofline argument rests
on (VERDICT r06 item #9; previously inferred from the 3.1× serve ratio).
A computed `roofline` block (weight + actual-dtype KV bytes / HBM BW)
rides in the artifact so program time and its floor sit side by side.

Times are per-token (per fused inner step), steady state, K=16 fused
steps per dispatch. Writes artifacts/tpu/decode_profile.json.

Usage (on the chip, one process): python scripts/tpu_decode_profile.py
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

BATCHES = (16, 128)  # small-batch latency vs large-batch throughput regime
K_STEPS = 16
ISL = 128  # resident context per sequence when decode is measured
MODEL = os.environ.get("PROFILE_MODEL", "llama3-1b")
#: v5e HBM bandwidth for the computed roofline (override per generation)
HBM_GB_S = float(os.environ.get("PROFILE_HBM_GB_S", "819"))


def build_engine(attention_impl: str, batch: int, kv_quantize=None):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine

    cfg = EngineConfig(
        model=MODEL,
        num_pages=batch * 4 + 64,
        page_size=64,
        max_pages_per_seq=8,
        decode_buckets=(batch,),
        prefill_chunk=128,
        prefill_token_budget=batch * 128,
        decode_steps=K_STEPS,
        max_seqs=batch,
        dtype="bfloat16",
        enable_prefix_caching=False,
        attention_impl=attention_impl,
        kv_quantize=kv_quantize,
    )
    return JaxEngine(cfg)


def roofline(eng, batch: int) -> dict:
    """Computed per-fused-step HBM floor for THIS engine's dtypes: the
    whole weight stack streams once per fused step; each step reads every
    resident sequence's KV history once (the flash walk's contract) and
    writes one token row per layer. Quantized pools count narrow pages +
    their f32 scale planes — the measured program time should close
    toward this number, and the fp-vs-int8 delta IS the KV-traffic
    saving."""
    import jax

    weight_bytes = sum(
        int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(eng.params)
    )
    kv = eng.kv
    s = eng.config.page_size
    pages_per_seq = -(-ISL // s)
    # bytes of one (layer, page) k+v slice incl. scale planes
    per_page = sum(
        int(x.shape[2] * (x.shape[3] if x.ndim > 3 else 1)
            * (x.shape[4] if x.ndim > 4 else 1))
        * x.dtype.itemsize
        for x in (kv.k, kv.v, kv.k_scale, kv.v_scale)
        if x is not None
    )
    n_layers = kv.k.shape[0]
    kv_read = batch * pages_per_seq * per_page * n_layers
    kv_write = kv_read // (pages_per_seq * s)  # one row/seq/layer
    total = weight_bytes + kv_read + kv_write
    return {
        "weight_bytes": weight_bytes,
        "kv_read_bytes_per_step": int(kv_read),
        "hbm_floor_ms_per_step": round(1000 * total / (HBM_GB_S * 1e9), 3),
    }


def time_full(eng, batch: int) -> dict:
    """Steady-state per-token time of the engine's own fused decode. Run
    the real serving loop with max_tokens large enough that the timed
    region is pure decode_multi dispatches."""
    import numpy as np

    from dynamo_tpu.engine.request import SamplingParams

    rng = np.random.default_rng(0)
    vocab = int(getattr(eng.adapter.config, "vocab_size", 32000))
    hi = min(32000, vocab - 1)
    prompts = [
        [int(x) for x in rng.integers(1, hi, ISL)] for _ in range(batch)
    ]
    for i, p in enumerate(prompts):
        eng.add_request(
            f"w{i}", p, SamplingParams(temperature=0.0, max_tokens=K_STEPS * 5)
        )
    # prefill + first fused decode dispatch (compiles) — untimed
    while eng.has_work:
        outs = eng.step()
        if outs and not outs[0].is_first:
            break
    t0 = time.perf_counter()
    tokens = 0
    dispatches = 0
    while eng.has_work:
        outs = eng.step()
        tokens += sum(len(o.new_token_ids) for o in outs)
        dispatches += 1
    dt = time.perf_counter() - t0
    return {
        "tokens": tokens,
        "dispatches": dispatches,
        "wall_s": round(dt, 3),
        "ms_per_token_row": round(1000 * dt / max(1, tokens / batch), 3),
        "tok_s": round(tokens / dt, 1),
    }


def time_pure_program(eng, batch: int) -> dict:
    """The same fused decode_multi program timed WITHOUT the engine's host
    loop: fixed device-resident inputs, kv threaded through (it may be
    donated), one block per call mirroring the per-dispatch sync. The gap
    serve_ms_per_dispatch - pure_ms_per_dispatch is the host overhead
    (scheduling, input staging, detokenize feedback) — the number that
    says whether further host-loop work (input packing) pays."""
    import jax
    import numpy as np

    fn = eng._get_step_fn(
        "decode_multi", batch, K_STEPS, greedy=True, lp=-1, pen=0,
        bias=False,
    )
    mp = eng.config.max_pages_per_seq
    tokens = np.ones((batch, 1), np.int32)
    positions = np.full((batch, 1), ISL - 1, np.int32)
    valid = np.ones((batch, 1), bool)
    pt = np.zeros((batch, mp), np.int32)
    for i in range(batch):
        pt[i, :4] = 1 + 4 * i + np.arange(4)
    samp, _ = eng._sampling_arrays([], pad_to=batch)
    dev = eng._dev_tree({"base": (tokens, positions, valid, pt),
                         "samp": samp})
    d_tokens, d_positions, d_valid, d_pt = dev["base"]
    kv = eng.kv
    ids, kv = fn(eng.params, d_tokens, d_positions, d_valid, kv, d_pt,
                 *dev["samp"])
    jax.block_until_ready(ids)
    n = 5
    t0 = time.perf_counter()
    for _ in range(n):
        ids, kv = fn(eng.params, d_tokens, d_positions, d_valid, kv, d_pt,
                     *dev["samp"])
        jax.block_until_ready(ids)
    dt = (time.perf_counter() - t0) / n
    eng.kv = kv
    return {
        "ms_per_dispatch": round(1000 * dt, 3),
        "ms_per_token_row": round(1000 * dt / K_STEPS, 3),
    }


def time_dense_floor(batch: int) -> dict:
    """Weight-streaming floor: the same parameter stack driven as pure
    dense matmuls (one token per sequence, attention output zeroed via a
    no-op context of length 1 is still paged — instead we time the lm
    head + mlp/qkv matmuls directly)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.registry import get_model

    adapter = get_model(MODEL, dtype="bfloat16", attention_impl="xla")
    params = adapter.init_params(jax.random.key(0))

    leaves = [x for x in jax.tree.leaves(params) if x.ndim >= 2]
    x0 = jnp.ones((batch, max(l.shape[0] for l in leaves)), jnp.bfloat16)

    @jax.jit
    def stream_all(x, ws):
        # touch every >=2D parameter with a matmul shaped [B, in] @ [in, out]
        # (ws passed as an ARGUMENT — closing over the params bakes 2.5GB
        # of constants into the lowered program)
        acc = jnp.zeros((batch,), jnp.float32)
        for leaf in ws:
            w = leaf.reshape(leaf.shape[0], -1)
            y = jax.lax.dot_general(
                x[:, : w.shape[0]], w,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + y.sum(axis=-1)
        return acc

    stream_all(x0, leaves).block_until_ready()
    n = 10
    t0 = time.perf_counter()
    for _ in range(n):
        stream_all(x0, leaves).block_until_ready()
    dt = (time.perf_counter() - t0) / n
    total_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    return {
        "ms": round(1000 * dt, 3),
        "weight_bytes": int(total_bytes),
        "implied_gb_s": round(total_bytes / dt / 1e9, 1),
    }


def main() -> None:
    import jax

    out = {
        "platform": jax.devices()[0].platform,
        "k_steps": K_STEPS,
        "model": MODEL,
        "batches": {},
    }
    for batch in BATCHES:
        row = {"dense_floor": time_dense_floor(batch)}
        for tag, impl, kvq in (
            ("pallas", "pallas", None),
            ("xla", "xla", None),
            ("pallas_kvq", "pallas", "int8"),
        ):
            eng = build_engine(impl, batch, kv_quantize=kvq)
            row[f"full_{tag}"] = time_full(eng, batch)
            row[f"pure_{tag}"] = time_pure_program(eng, batch)
            row[f"roofline_{tag}"] = roofline(eng, batch)
            full = row[f"full_{tag}"]
            if full["dispatches"]:
                # the DIRECT program-vs-host split: serve ms/dispatch −
                # pure program ms/dispatch = host-loop overhead
                serve_ms = 1000 * full["wall_s"] / full["dispatches"]
                row[f"serve_ms_per_dispatch_{tag}"] = round(serve_ms, 3)
                row[f"host_overhead_ms_{tag}"] = round(
                    serve_ms - row[f"pure_{tag}"]["ms_per_dispatch"], 3
                )
            del eng
        out["batches"][str(batch)] = row
    path = Path(__file__).resolve().parent.parent / "artifacts" / "tpu"
    path.mkdir(parents=True, exist_ok=True)
    (path / "decode_profile.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    # perf-regression ledger row (scripts/perf_diff.py): headline
    # tok_s / ms_per_dispatch of this profile, best-effort — a ledger
    # problem never fails the profile run
    try:
        from dynamo_tpu.telemetry import perf_ledger

        row = perf_ledger.row_from_decode_profile(
            out, os.environ.get("DYNTPU_ROUND", "adhoc")
        )
        ledger = os.environ.get("DYNTPU_PERF_LEDGER")
        if ledger != "":
            perf_ledger.append_row(
                row,
                ledger
                or str(
                    Path(__file__).resolve().parent.parent
                    / perf_ledger.DEFAULT_LEDGER
                ),
            )
    except Exception as e:
        print(f"decode_profile: perf_ledger append failed: {e}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
