"""python -m chipbench.control --config <name> [--seeds a,b,c]

The control of the comparison that decides `correct`: the plain
reference put in the program's place and computed in the nearest
precision below the one the configuration states — int8 weights
(per-output-channel, w = q * scale) where the configuration serves
bfloat16, int4 where it serves int8. It decodes the greedy streams the
benchmark asks the served model for (chipbench/run.py `greedy_streams`:
48-token prompts, 64 tokens out, the chosen token's log-prob), and
those streams go through the same `compare` against the float32
reference on the weights as the configuration states them. The
control has to come out as not correct under the configuration's
`reference_tolerance`; it prints each number beside its limit.

No engine, no window: one process builds the weights as the engine does
(`get_model(preset).init_params(jax.random.key(0))`, quantised where the
serve flags say so) and reads every seed. `--seeds` draws the prompts;
the benchmark's own runs use the fixed draw 1234. Without a TPU it runs
the configuration's `rehearsal` preset (tests). It covers configurations
compared with the default reference; a `reference_module` of another
architecture brings its own control.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import manifest, reference, traffic

PROMPT_LEN, OUT_LEN, STREAMS = 48, 64, 2  # as run.greedy_streams


def lower(lp: dict, names) -> dict:
    """The layer's dense matrices one precision down: bf16/f32 -> int8,
    int8 -> int4, symmetric per output channel, kept as the float32
    values the lower precision can hold."""
    import jax.numpy as jnp

    out = dict(lp)
    for n in names:
        w = reference._dense(lp, n)
        top = 7.0 if lp[n].dtype == jnp.int8 else 127.0
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True) / top,
                            1e-8)
        out[n] = jnp.round(w / scale) * scale
        out.pop(n + "_scale", None)
    return out


DENSE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def control_streams(params: dict, hf: dict, seed: int) -> list[dict]:
    """Greedy streams decoded by the reference on lowered weights. Every
    pass runs the whole padded sequence: under the causal mask a position
    sees nothing after it, so one shape serves all 64 steps."""
    import jax
    import jax.numpy as jnp

    n_layers = params["layers"]["wq"].shape[0]
    total = PROMPT_LEN + OUT_LEN
    pos = jnp.arange(total, dtype=jnp.int32)
    w_head = params["lm_head"] if "lm_head" in params else params["embed"].T

    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp: reference.block(
            x, lower(lp, DENSE), hf, pos))

        @jax.jit
        def head(x, at, norm, w):  # arguments: a closed-over head is a
            h = reference._rms(x[at], norm, hf["rms_norm_eps"])  # constant
            return jax.nn.log_softmax(h @ w.astype(jnp.float32))

        def next_log_probs(ids, at):
            x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(
                jnp.float32)
            for i in range(n_layers):
                x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
            return np.asarray(head(x, at, params["final_norm"], w_head))

        rng = np.random.default_rng(seed)
        streams = []
        for _ in range(STREAMS):
            prompt = [int(v) for v in rng.integers(
                traffic.FIRST_ID, hf["vocab_size"], PROMPT_LEN)]
            ids = prompt + [0] * OUT_LEN
            out, lps = [], []
            for t in range(PROMPT_LEN - 1, total - 1):
                lp = next_log_probs(ids, t)
                ids[t + 1] = int(lp.argmax())
                out.append(ids[t + 1])
                lps.append(float(lp.max()))
            streams.append({"prompt": prompt, "out": out, "logprobs": lps})
    return streams


def build_params(serve: dict):
    import jax

    from dynamo_tpu.models.registry import get_model

    flags = serve["serve_flags"]
    dtype = flags[flags.index("--dtype") + 1] if "--dtype" in flags else None
    adapter = get_model(serve["preset"], dtype=dtype)
    if "--quantize" in flags and adapter.init_params_quantized is not None:
        return adapter.init_params_quantized(jax.random.key(0))
    return adapter.init_params(jax.random.key(0))


def read(conf: dict, seeds, on_chip: bool) -> list[dict]:
    from chipbench.run import check_reference

    serve = conf if on_chip else conf["rehearsal"]
    hf = conf if on_chip else serve["hf"]
    params = build_params(serve)
    rows = []
    for seed in seeds:
        res = check_reference(params, hf, control_streams(params, hf, seed),
                              conf["reference_tolerance"])
        rows.append({"seed": seed, **res})
        print(json.dumps({"note": "control", **rows[-1]}), flush=True)
    return rows


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="1234,1,2")
    ns = ap.parse_args(argv)
    man = manifest.load()
    conf = manifest.config_of(man, {"config": ns.config})
    if "reference_module" in conf:
        raise SystemExit("chipbench.control covers the default reference; "
                         f"{ns.config} brings its own")
    rows = read(conf, [int(s) for s in ns.seeds.split(",")],
                jax.devices()[0].platform == "tpu")
    fooled = [r["seed"] for r in rows if r["passed"]]
    print(json.dumps({"control_comes_out_not_correct": not fooled,
                      "passed_on_seeds": fooled}), flush=True)
    return 1 if fooled else 0


if __name__ == "__main__":
    sys.exit(main())
