"""chipbench/control.py at a size a test run can hold: the reference in
the program's place, one precision down, is told apart from the program
by the comparison that decides `correct`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, reference
from dynamo_tpu.models import llama

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


def tiny_params(quantized=False):
    cfg = llama.LlamaConfig.tiny()
    init = llama.init_params_int8 if quantized else llama.init_params
    return init(jax.random.key(0), cfg)


def exact_streams(params, seed):
    """What a sound program gives: greedy by the float32 reference."""
    rng = np.random.default_rng(seed)
    streams = []
    for _ in range(control.STREAMS):
        prompt = [int(v) for v in rng.integers(10, 256, control.PROMPT_LEN)]
        seq, out, lps = list(prompt), [], []
        for _ in range(8):
            lp = reference.log_probs(params, HF, seq, [len(seq) - 1])[0]
            out.append(int(lp.argmax()))
            lps.append(float(lp.max()))
            seq.append(out[-1])
        streams.append({"prompt": prompt, "out": out, "logprobs": lps})
    return streams


@pytest.mark.parametrize("quantized,levels", [(False, 255), (True, 15)])
def test_lower_is_the_next_precision_down(quantized, levels):
    lp = jax.tree.map(lambda a: a[0], tiny_params(quantized)["layers"])
    low = control.lower(lp, control.DENSE)
    for n in control.DENSE:
        w = np.asarray(low[n])
        assert w.dtype == np.float32 and n + "_scale" not in low
        # every output channel holds at most the grid's values
        assert max(len(np.unique(w[:, j])) for j in range(w.shape[1])) \
            <= levels
        full = np.asarray(reference._dense(lp, n))
        assert 0 < np.abs(w - full).max() < np.abs(full).max() / (levels // 4)
    assert np.array_equal(np.asarray(low["attn_norm"]),
                          np.asarray(lp["attn_norm"]))


@pytest.mark.parametrize("seed", [1234, 1, 2])
def test_the_control_reads_far_above_a_sound_program(seed):
    params = tiny_params()
    sound = reference.compare(params, HF, exact_streams(params, seed))
    assert sound["max_logprob_drift"] < 1e-5
    streams = control.control_streams(params, HF, seed)
    assert all(len(s["out"]) == len(s["logprobs"]) == control.OUT_LEN
               and len(s["prompt"]) == control.PROMPT_LEN for s in streams)
    read = reference.compare(params, HF, streams)
    assert read["tokens"] == control.STREAMS * control.OUT_LEN
    assert read["max_logprob_drift"] > 1000 * sound["max_logprob_drift"]
    assert read["max_logprob_drift"] > 0.01


def test_padded_decoding_is_the_plain_greedy_decode():
    """One padded shape for all 64 steps changes nothing: with the
    weights left as they are (`lower` of no matrix) the control's stream
    is the float32 reference's own greedy stream."""
    params = tiny_params()
    want = exact_streams(params, 7)
    orig, control.DENSE = control.DENSE, ()
    try:
        got = control.control_streams(params, HF, 7)
    finally:
        control.DENSE = orig
    for g, w in zip(got, want):
        assert g["prompt"] == w["prompt"] and g["out"][:8] == w["out"]
        assert np.allclose(g["logprobs"][:8], w["logprobs"], atol=1e-5)


def test_main_fails_when_the_control_passes_the_tolerance(monkeypatch,
                                                          capsys):
    # at the rehearsal's tiny size int8 weights stay inside the chip
    # tolerance of phi3-mini-4k: main says so and exits 1; int4 on the
    # int8 configuration does not pass: 0
    assert control.main(["--config", "qwen2-7b-int8", "--seeds", "3"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert '"control_comes_out_not_correct": true' in out[-1]
    assert '"passed": false' in out[0]
