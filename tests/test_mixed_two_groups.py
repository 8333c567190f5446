"""The fused mixed step's model pass (`ModelAdapter.forward_hidden_mixed`):
one layer scan over a prompt chunk and the decode rows together. The
two-group layer body of models/llama.py and models/mla.py must give what
two `forward_hidden` calls give, prompt first — hidden states and the
written KV pages — and the lowered `mixed` program must hold ONE pass over
the stacked weights, so that a later refactor cannot quietly bring the
second pass back."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig
from dynamo_tpu.engine.engine import JaxEngine
from dynamo_tpu.engine.request import SamplingParams
from dynamo_tpu.models.registry import (
    _llama_adapter, _two_pass_mixed, get_model,
)

PAGE, PAGES, MP = 4, 48, 8  # 32-token contexts
T = 8  # the prompt chunk's T bucket
HIST = 8  # tokens a "chunk over history" row holds already (page-aligned)


def _model(family: str, impl: str):
    """(adapter, params) of one family at test size, seeded."""
    key = jax.random.key(7)
    if family == "llama-tiny-bf16-mha":
        gqa = get_model("tiny", dtype="bfloat16", attention_impl=impl)
        cfg = dataclasses.replace(
            gqa.config, num_kv_heads=gqa.config.num_heads
        )
        adapter = _llama_adapter("tiny-mha", cfg)
        return adapter, adapter.init_params(key)
    if family == "llama-tiny-int8-gqa":
        adapter = get_model("tiny", dtype="float32", attention_impl=impl)
        return adapter, adapter.init_params_quantized(key)
    adapter = get_model("mla-tiny-moe", dtype="float32", attention_impl=impl)
    return adapter, adapter.init_params(key)


def _rows(rng, lengths, starts, t, first_page):
    """(tokens, positions, valid, page_tables) of len(lengths) rows of `t`
    slots; a length of 0 is a padding row (nothing valid, the null page)."""
    b = len(lengths)
    tokens = np.zeros((b, t), np.int32)
    positions = np.zeros((b, t), np.int32)
    valid = np.zeros((b, t), bool)
    pt = np.zeros((b, MP), np.int32)
    for i, (n, s) in enumerate(zip(lengths, starts)):
        if not n:
            continue
        tokens[i, :n] = rng.integers(1, 200, n)
        positions[i] = np.arange(t) + s
        valid[i, :n] = True
        pt[i] = first_page + i * MP + np.arange(MP)
    return tuple(jnp.asarray(a) for a in (tokens, positions, valid, pt))


@pytest.mark.parametrize("chunk", ["first-chunk", "chunk-over-history"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("family", [
    "llama-tiny-bf16-mha", "llama-tiny-int8-gqa", "mla-tiny-moe",
])
def test_two_group_pass_equals_two_passes(family, impl, chunk):
    """Hidden states of every real token and every written page: one scan
    over both groups against a prompt pass then a decode pass. Both
    groups carry a padding row; the decode rows' histories differ."""
    adapter, params = _model(family, impl)
    rng = np.random.default_rng(11)
    first = chunk == "first-chunk"
    kv = adapter.init_kv(PAGES, PAGE)
    fwd = jax.jit(adapter.forward_hidden, static_argnames=("first_chunk",))

    # what the step finds in the cache: the decode rows' histories (5, 9
    # and 3 tokens) and, over history, the prompt row's first HIST tokens
    d_hist = (5, 9, 3)
    hist = _rows(rng, d_hist, (0, 0, 0), 12, first_page=1 + 2 * MP)
    _, kv = fwd(params, *hist[:3], kv, hist[3], first_chunk=True)
    if not first:
        pre = _rows(rng, (HIST, 0), (0, 0), T, first_page=1)
        _, kv = fwd(params, *pre[:3], kv, pre[3], first_chunk=True)

    prompt = _rows(rng, (6, 0), (0 if first else HIST, 0), T, first_page=1)
    decode = _rows(rng, (1, 1, 1, 0), (*d_hist, 0), 1, first_page=1 + 2 * MP)

    one = jax.jit(
        adapter.forward_hidden_mixed, static_argnames=("first_chunk",)
    )
    h_p, h_d, kv_one = one(params, prompt, decode, kv, first_chunk=first)
    ref_p, kv_two = fwd(
        params, *prompt[:3], kv, prompt[3], first_chunk=first
    )
    ref_d, kv_two = fwd(params, *decode[:3], kv_two, decode[3])

    tol = 3e-2 if adapter.config.dtype == jnp.bfloat16 else 2e-5
    for got, ref, rows in ((h_p, ref_p, prompt), (h_d, ref_d, decode)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        live = np.asarray(rows[2])
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[live],
            np.asarray(ref, np.float32)[live], atol=tol, rtol=tol,
        )
    # every page but the null page, where padding lanes land
    for got, ref in zip((kv_one.k, kv_one.v), (kv_two.k, kv_two.v)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32)[:, 1:],
            np.asarray(ref, np.float32)[:, 1:], atol=tol, rtol=tol,
        )
    # the step wrote: the prompt row's chunk is in its pages now
    page = 1 + (0 if first else HIST // PAGE)
    assert np.abs(np.asarray(kv_one.k, np.float32)[:, page]).sum() > 0


@pytest.mark.parametrize("model,stacks", [
    ("tiny", ("w_gate",)),
    ("mla-tiny-moe", ("w_gate", "we_gate")),
])
def test_mixed_program_reads_each_weight_stack_once(model, stacks):
    """The point of the fused mixed step: ONE layer `while` a layer group
    (llama: one; mla: the dense prefix and the expert suffix), not one a
    group of rows, and each stacked weight sliced by one scan only."""
    adapter = get_model(model, dtype="float32")
    params = jax.eval_shape(lambda: adapter.init_params(jax.random.key(0)))
    kv = jax.eval_shape(lambda: adapter.init_kv(PAGES, PAGE))

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    prompt = (s((1, 32)), s((1, 32)), s((1, 32), jnp.bool_), s((1, MP)))
    decode = (s((2, 1)), s((2, 1)), s((2, 1), jnp.bool_), s((2, MP)))
    text = jax.jit(adapter.forward_hidden_mixed).lower(
        params, prompt, decode, kv
    ).as_text()
    layer_groups = 2 if model == "mla-tiny-moe" else 1
    assert len(re.findall(r"stablehlo\.while", text)) == layer_groups
    # the two-pass composition (models/moe.py keeps it) is what this PR
    # left behind: twice the loops
    text_two = jax.jit(_two_pass_mixed(adapter.forward_hidden)).lower(
        params, prompt, decode, kv
    ).as_text()
    assert len(re.findall(r"stablehlo\.while", text_two)) == 2 * layer_groups
    # a stacked weight enters a scan as a loop operand sliced per layer:
    # its [L, ...] type is an operand of exactly one while
    flat = jax.tree_util.tree_leaves_with_path(params)
    heads = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    for name in stacks:
        shapes = {
            "x".join(map(str, leaf.shape))
            for path, leaf in flat
            if getattr(path[-1], "key", None) == name and leaf.ndim >= 3
        }
        assert shapes, name
        for shape in shapes:
            assert sum(f"tensor<{shape}xf32>" in h for h in heads) == 1, (
                name, shape,
            )


def test_engine_mixed_program_holds_one_layer_scan():
    """`mixed_fn` itself, as `_launch_mixed` calls it on `llama-tiny`: the
    model is traced once a compiled variant, the lowered program holds
    ONE `while` (the layer scan; two before the groups shared it), and
    `mixed_shared_rows` counts the decode rows that rode a chunk's pass."""
    eng = JaxEngine(EngineConfig.for_tests(decode_steps=1))
    traced, texts = [], []
    real_pass = eng.adapter.forward_hidden_mixed
    real_cache_jit = eng._cache_jit

    def counting(*a, **kw):
        traced.append(1)
        return real_pass(*a, **kw)

    def spy(kind, cache_key, jitted):
        first_call = real_cache_jit(kind, cache_key, jitted)
        if getattr(jitted, "__name__", "") != "mixed_fn":
            return first_call

        def lowered_first(*args, **kwargs):
            n0 = len(traced)
            texts.append(jitted.lower(*args, **kwargs).as_text())
            assert len(traced) == n0 + 1  # one model pass a program
            out = first_call(*args, **kwargs)
            assert len(traced) == n0 + 1  # the call shares the lowering
            return out

        eng._jit_cache[cache_key] = lowered_first
        return lowered_first

    object.__setattr__(eng.adapter, "forward_hidden_mixed", counting)
    eng._cache_jit = spy
    eng.add_request(
        "a", [5, 6, 7], SamplingParams(max_tokens=24, ignore_eos=True)
    )
    for _ in range(3):
        eng.step()
    eng.add_request(
        "b", list(range(1, 25)), SamplingParams(max_tokens=4, ignore_eos=True)
    )
    while eng.has_work:
        eng.step()
    m = eng.metrics
    assert m.mixed_dispatches > 0 and texts
    for text in texts:
        assert len(re.findall(r"stablehlo\.while", text)) == 1
    # one decode row beside each of "b"'s chunks that ran fused (a mixed
    # step whose decode half was launched ahead runs split, shares nothing)
    assert 0 < m.mixed_shared_rows <= m.mixed_dispatches
