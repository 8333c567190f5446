"""The plain float32 reference against models/llama.py at a tiny size,
for the features the two configurations pull apart: grouped-query
heads, qkv bias, a head dim the kernels pad to 128 lanes, int8 weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from dynamo_tpu.models import llama

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


def served_log_probs(params, cfg, ids):
    t, page = len(ids), 4
    kv = llama.init_kv_pages(cfg, num_pages=1 + t // page + 1, page_size=page)
    pt = jnp.arange(1, 1 + t // page + 1, dtype=jnp.int32)[None]
    logits, _kv = llama.forward(
        params, cfg, jnp.asarray([ids], jnp.int32),
        jnp.arange(t, dtype=jnp.int32)[None], jnp.ones((1, t), bool), kv, pt)
    return np.asarray(jax.nn.log_softmax(logits[0].astype(jnp.float32)))


@pytest.mark.parametrize("case", ["mha", "gqa_bias", "padded_head_dim",
                                  "int8"])
def test_reference_agrees_with_the_served_model(case):
    hf = dict(HF)
    kw = {}
    if case == "mha":
        hf["num_key_value_heads"] = 4
    if case == "padded_head_dim":
        kw["attention_impl"] = "pallas"  # head dim 16 cached as 128 lanes
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=hf["num_key_value_heads"], head_dim=16,
        rope_theta=10000.0, dtype=jnp.float32,
        attention_bias=case in ("gqa_bias", "int8"), **kw)
    key = jax.random.key(3)
    if case == "int8":
        params = llama.init_params_int8(key, cfg)
    else:
        params = llama.init_params(key, cfg)
    if cfg.attention_bias:  # the program zero-initialises biases
        for i, n in enumerate(("bq", "bk", "bv")):
            b = params["layers"][n]
            params["layers"][n] = 0.3 * jax.random.normal(
                jax.random.key(10 + i), b.shape, b.dtype)
    ids = [int(x) for x in np.random.default_rng(0).integers(1, 256, 24)]
    got = reference.log_probs(params, hf, ids, np.arange(len(ids)))
    want = served_log_probs(params, cfg, ids)
    # float32 on both sides: only the order of accumulation differs
    assert np.abs(got - want).max() < 2e-4
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_a_dropped_bias_or_rope_lands_outside_the_tolerance():
    """The tolerance is tight enough to catch a missing piece."""
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
        dtype=jnp.float32, attention_bias=True)
    params = llama.init_params(jax.random.key(3), cfg)
    for i, n in enumerate(("bq", "bk", "bv")):
        b = params["layers"][n]
        params["layers"][n] = 0.5 * jax.random.normal(
            jax.random.key(10 + i), b.shape, b.dtype)
    ids = [int(x) for x in np.random.default_rng(0).integers(1, 256, 24)]
    at = np.arange(len(ids))
    want = served_log_probs(params, cfg, ids)
    no_bias = {**params, "layers": {k: v for k, v in params["layers"].items()
                                    if k not in ("bq", "bk", "bv")}}
    assert np.abs(reference.log_probs(no_bias, HF, ids, at) - want).max() > 0.01
    no_rope = {**HF, "rope_theta": 1e30}
    assert np.abs(reference.log_probs(params, no_rope, ids, at) - want).max() \
        > 0.01


def test_compare_reports_agreement_drift_and_gap():
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10000.0,
        dtype=jnp.float32)
    params = llama.init_params(jax.random.key(1), cfg)
    prompt = [5, 6, 7, 8]
    seq = list(prompt)
    out, lps = [], []
    for _ in range(6):  # greedy by the served model
        lp = served_log_probs(params, cfg, seq)[-1]
        out.append(int(lp.argmax()))
        lps.append(float(lp.max()))
        seq.append(out[-1])
    res = reference.compare(params, HF, [
        {"prompt": prompt, "out": out, "logprobs": lps}])
    assert res["tokens"] == 6 and res["argmax_agreement"] == 1.0
    assert res["max_logprob_drift"] < 1e-4
    assert res["max_gap_to_reference_best"] < 1e-6
    wrong = [{"prompt": prompt, "out": [(t + 1) % 256 for t in out],
              "logprobs": lps}]
    assert reference.compare(params, HF, wrong)["argmax_agreement"] < 1.0


def test_a_configuration_may_also_limit_the_mean_drift(monkeypatch):
    """`max_mean_logprob_drift` in a `reference_tolerance` is a further
    gate; a file without it (qwen2-7b-int8.json) is judged as before."""
    from chipbench import control, run

    params = llama.init_params(jax.random.key(1), llama.LlamaConfig.tiny())
    monkeypatch.setattr(control, "DENSE", ())  # the reference's own greedy
    streams = control.control_streams(params, HF, 3)
    for s in streams:
        s["logprobs"] = [v + 0.02 for v in s["logprobs"]]  # all 0.02 off
    tol = {"min_argmax_agreement": 0.9, "max_logprob_drift": 0.1}
    res = run.check_reference(params, HF, streams, tol)
    assert res["mean_logprob_drift"] == pytest.approx(0.02, abs=1e-4)
    assert res["max_logprob_drift"] == pytest.approx(0.02, abs=1e-4)
    assert res["passed"] is True
    strict = {**tol, "max_mean_logprob_drift": 0.01}
    assert run.check_reference(params, HF, streams, strict)["passed"] is False
    loose = {**tol, "max_mean_logprob_drift": 0.03}
    assert run.check_reference(params, HF, streams, loose)["passed"] is True
