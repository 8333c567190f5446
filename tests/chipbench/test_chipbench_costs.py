"""chipbench.costs against the program's own arithmetic and trees."""
import json

import jax
import pytest

from chipbench import costs, manifest
from dynamo_tpu.models import llama

TINY_HF = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256}


#: the published sizes of Phi-3-mini-4k-instruct: a head of 96 that the
#: kernels pad to 128 lanes, the case no admitted configuration has yet
PHI3_HF = {"hidden_size": 3072, "intermediate_size": 8192,
           "num_hidden_layers": 32, "num_attention_heads": 32,
           "num_key_value_heads": 32, "head_dim": 96, "vocab_size": 32064}


def published(name):
    if name == "phi3-mini-4k":
        return PHI3_HF
    with open(manifest.HERE / "configs" / f"{name}.json") as f:
        return json.load(f)


def preset_of(hf, **kw):
    return llama.LlamaConfig(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        attention_bias=bool(hf.get("attention_bias")), **kw)


@pytest.mark.parametrize("name", ["phi3-mini-4k", "qwen2-7b-int8"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_kv_bytes_per_token_is_the_programs_page_cost(name, impl):
    hf = published(name)
    cfg = preset_of(hf, attention_impl=impl)
    assert costs.kv_bytes_per_token(hf, 2, kernels=impl == "pallas") * 64 \
        == llama.kv_page_bytes(cfg, 64)


def test_phi3_page_is_33_5_mb_and_qwen2_page_3_67_mb():
    assert costs.kv_bytes_per_token(published("phi3-mini-4k")) * 64 \
        == 33_554_432
    assert costs.kv_bytes_per_token(published("qwen2-7b-int8")) * 64 \
        == 3_670_016


@pytest.mark.parametrize("quantized,bias", [(False, False), (False, True),
                                            (True, True)])
def test_weight_bytes_is_the_parameter_tree(quantized, bias):
    hf = {**TINY_HF, "attention_bias": bias}
    cfg = preset_of(hf, dtype=jax.numpy.float32)
    init = llama.init_params_int8 if quantized else llama.init_params
    tree = init(jax.random.key(0), cfg)
    want = sum(x.nbytes for x in jax.tree.leaves(tree))
    got = costs.weight_bytes(hf, 1 if quantized else 4, 4, with_embed=True)
    assert got == want
    # a decode step gathers rows of the embedding, it does not stream it
    assert costs.weight_bytes(hf, 1 if quantized else 4, 4) == \
        want - tree["embed"].nbytes


def test_decode_step_bytes_at_the_issues_operating_points():
    phi3 = published("phi3-mini-4k")
    # 40 live sequences of 350 tokens: ~7.6 GB of weights, ~7.3 GB of cache
    b = costs.decode_step_bytes(phi3, 40 * 350)
    w = costs.weight_bytes(phi3)
    assert 7.3e9 < w < 7.7e9
    assert 7.2e9 < b - w < 7.5e9
    qwen = published("qwen2-7b-int8")
    wq = costs.weight_bytes(qwen, dense_itemsize=1)
    assert 7.5e9 < wq < 8.0e9  # int8 dense + bf16 head, no embedding
