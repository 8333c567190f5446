"""`top_candidates` is a stable top-k bit for bit (values, ids, order, ties):
`lax.top_k` as documented and as the CPU runs it, and `sample` draws what
the vocabulary-wide top-k drew.

The same file runs ON THE CHIP (`DYNTPU_TEST_ON_TPU=1 python -m pytest
tests/test_sampling.py` through the chip tool; conftest then leaves the
platform alone). There the reference is numpy's stable sort alone: the
TPU's own `lax.top_k` keeps the values but not the order of equal ones
(PERF.md 6, PR 37), so `_reference_sample` is compared on the CPU only
where a row's draw can reach a tie.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.sampling import (
    CAND_BLOCKS,
    DEFAULT_K_CAP,
    _NEG_INF,
    apply_logit_bias,
    sample,
    top_candidates,
)

K = DEFAULT_K_CAP
W = CAND_BLOCKS[0]
#: the five cells' vocabularies (phi3, deepseek-v2-lite, nano3, qwen2, falcon-h1)
CELL_VOCABS = [32_064, 102_400, 131_072, 152_064, 261_120]
NINF = -np.inf


def _stable_top_k(x: np.ndarray, k: int):
    """numpy: the k largest by the total order of floats, equal ones by id."""
    bits = x.view(np.int32).astype(np.int64)
    keys = np.where(bits < 0, -(bits & 0x7FFFFFFF) - 1, bits)
    ids = np.argsort(-keys, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(x, ids, axis=-1), ids


def _check(rows: np.ndarray, k_cap: int = K):
    x = np.ascontiguousarray(rows, np.float32)
    k = min(k_cap, x.shape[1])
    got_v, got_i = jax.jit(lambda a: top_candidates(a, k))(jnp.asarray(x))
    wants = [_stable_top_k(x, k)]
    if jax.default_backend() == "cpu":  # the TPU's top-k is not stable
        wants.append(jax.jit(lambda a: jax.lax.top_k(a, k))(jnp.asarray(x)))
    for want_v, want_i in wants:
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
        np.testing.assert_array_equal(
            np.asarray(got_v).view(np.int32), np.asarray(want_v).view(np.int32)
        )


def _rng(*key):
    return np.random.default_rng([*key])


# ---- (a) random rows at the cells' vocabularies ---------------------------


@pytest.mark.parametrize("vocab", CELL_VOCABS)
def test_random_rows_at_a_cells_vocabulary(vocab):
    _check(_rng(1, vocab).standard_normal((2, vocab)) * 4.0)


# ---- (b) ties -------------------------------------------------------------


def _tie_rows(case: str) -> np.ndarray:
    v = 100 * W  # 100 blocks
    r = _rng(2)
    low = r.standard_normal((1, v)).astype(np.float32) - 50.0  # all below 0
    if case == "signed_zeros":
        # the CPU's top-k sorts by the total order: 0.0 before -0.0
        low[0, 40 * W : 42 * W] = np.where(np.arange(2 * W) % 3, -0.0, 0.0)
        return low
    if case == "eight_levels":
        return np.round(r.standard_normal((3, 32_064)) * 2.0).clip(-4, 3)
    if case == "all_equal":
        return np.full((2, 32_064), 0.25)
    if case == "inside_one_block":
        # 60 larger entries spread out, then 10 equal entries in block 7:
        # places 61-64 are the block's four lowest ids
        low[0, W * np.arange(10, 70)] = 5.0 + np.arange(60)
        low[0, 7 * W + 3 * np.arange(10)] = 1.0
        return low
    if case == "across_a_block_edge":
        # the tie runs over the last ids of block 7 and the first of block 8
        low[0, W * np.arange(10, 72) + 1] = 5.0 + np.arange(62)
        low[0, 8 * W - 1 : 8 * W + 6] = 1.0
        return low
    if case == "across_the_64th_block_maximum":
        # 70 blocks share one maximum, 40 above it elsewhere: the top-k over
        # maxima has to take the LOWEST-numbered 24 of the 70
        low[0, W * np.arange(0, 40) + 5] = 5.0 + np.arange(40)
        low[0, W * np.arange(20, 90) + 77] = 1.0
        return low
    if case == "more_than_64_blocks_of_one_value":
        low[0, W * np.arange(3, 99) + 127] = 2.0
        low[0, W * np.arange(3, 99, 2)] = 2.0
        return low
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "eight_levels", "all_equal", "inside_one_block", "across_a_block_edge",
    "across_the_64th_block_maximum", "more_than_64_blocks_of_one_value",
    "signed_zeros",
])
def test_ties_break_by_lowest_id(case):
    _check(_tie_rows(case))


# ---- (c) -inf -------------------------------------------------------------


def _inf_rows(case: str) -> np.ndarray:
    v = 32_064
    r = _rng(3)
    x = r.standard_normal((2, v)).astype(np.float32)
    if case == "scattered_bans":
        x[:, r.choice(v, v // 2, replace=False)] = NINF
    elif case == "whole_blocks":
        x[:, : 40 * W] = NINF
        x[1, 90 * W : 200 * W] = NINF
    elif case == "fewer_than_k_finite":
        keep = r.choice(v, 30, replace=False)
        y = np.full_like(x, NINF)
        y[:, keep] = x[:, keep]
        x = y
    elif case == "all_banned":
        x[:] = NINF
    elif case == "finite_only_in_the_padded_block":
        x[:] = NINF
        x[:, 250 * W + 5 :] = 1.0  # 32,064 = 250 blocks + 64 ids
    else:
        raise AssertionError(case)
    return x


@pytest.mark.parametrize("case", [
    "scattered_bans", "whole_blocks", "fewer_than_k_finite", "all_banned",
    "finite_only_in_the_padded_block",
])
def test_minus_infinity_is_an_ordinary_value(case):
    _check(_inf_rows(case))


# ---- (d) shapes -----------------------------------------------------------


@pytest.mark.parametrize("vocab,k_cap", [
    (1_000, K),  # 8 blocks: no more than k_cap, the direct call
    (K * W, K),  # exactly k_cap blocks
    (K * W + 1, K),  # one id into a 65th block
    (8_321, K),  # 66 blocks, the last one id wide
    (32_064, K),  # not a multiple of the block
    (40, K),  # V < k_cap
    (8_321, 5),
    (32_064, 200),
])
def test_vocabulary_shapes(vocab, k_cap):
    r = _rng(4, vocab)
    x = np.round(r.standard_normal((3, vocab)) * 3.0) / 2.0  # ties too
    _check(x, k_cap)


def test_the_wide_path_sorts_nothing_as_wide_as_the_vocabulary():
    """The static shape condition is the only chooser: above k_cap blocks
    nothing as wide as [B, V] is sorted, and `lax.top_k` is not called."""
    jaxpr = jax.make_jaxpr(lambda a: top_candidates(a, K))(
        jnp.zeros((2, 32_064), jnp.float32)
    )

    def widths(jp):
        for e in jp.eqns:
            assert e.primitive.name != "top_k"
            if e.primitive.name == "sort":
                yield e.invars[0].aval.shape[-1]
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from widths(sub)

    sub = CAND_BLOCKS[1]
    want = [K, K, 251, K * sub, K * W // sub]
    assert sorted(widths(jaxpr.jaxpr)) == sorted(want)


# ---- `sample` against the parent's body -----------------------------------


def _reference_sample(logits, temperature, top_p, top_k, seeds, counters,
                      k_cap=K):
    """`sample` as it stood before `top_candidates` (PR 34's tree)."""
    k_cap = min(k_cap, logits.shape[1])
    greedy = temperature <= 0.0
    scaled = logits / jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))[:, None]
    cand_logits, cand_idx = jax.lax.top_k(scaled, k_cap)
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(cand_logits - lse)
    keep = ((jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]) & (
        jnp.arange(k_cap)[None, :]
        < jnp.where(top_k > 0, jnp.minimum(top_k, k_cap), k_cap)[:, None]
    )
    gumbel = jax.vmap(lambda s, c: jax.random.gumbel(
        jax.random.fold_in(jax.random.key(s), c), (k_cap,), jnp.float32
    ))(seeds, counters)
    rank = jnp.argmax(jnp.where(keep, cand_logits, _NEG_INF) + gumbel, axis=-1)
    sampled = jnp.take_along_axis(cand_idx, rank[:, None], axis=-1)[:, 0]
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)


@pytest.mark.parametrize("top_k", [0, 5, 64, 500])
@pytest.mark.parametrize("top_p", [1.0, 0.9, 0.3])
@pytest.mark.parametrize("seed", [0, 7, 2_900_000_011])
def test_sample_draws_the_parents_tokens(seed, top_p, top_k):
    b, v = 8, 32_064
    r = _rng(5, seed)
    logits = (r.standard_normal((b, v)) * 2.0).astype(np.float32)
    if jax.default_backend() == "cpu":
        # rounded so that ties reach the draw; not on the TPU, whose own
        # top-k (the reference's) is not stable
        logits = np.round(logits, 1)
    # a peaked head over a flat tail
    logits[:, r.choice(v, 200, replace=False)] += 6.0
    temps = np.array([0.7, 0.0, 1.0, 0.2, 1.5, 0.7, 0.0, 3.0], np.float32)
    args = (
        jnp.asarray(logits), jnp.asarray(temps),
        jnp.full((b,), top_p, jnp.float32), jnp.full((b,), top_k, jnp.int32),
        jnp.asarray(np.arange(b) + seed, jnp.uint32),
        jnp.asarray(np.arange(b) * 3, jnp.int32),
    )
    got = np.asarray(jax.jit(sample)(*args))
    want = np.asarray(jax.jit(_reference_sample)(*args))
    np.testing.assert_array_equal(got, want)
    assert (got[temps <= 0] == logits[temps <= 0].argmax(-1)).all()


@pytest.mark.parametrize("counter,banned", [(0, True), (3, False)])
def test_sample_under_a_gated_min_tokens_ban(counter, banned):
    """A min_tokens ban is a gated -inf on the eos id: while it holds the
    row's best token is out of reach, in `sample` as in the parent."""
    b, v, eos = 4, 8_321, 8_320  # eos in the one-id last block
    logits = _rng(6).standard_normal((b, v)).astype(np.float32)
    logits[:, eos] = 30.0
    counters = jnp.full((b,), counter, jnp.int32)
    eff = apply_logit_bias(
        jnp.asarray(logits),
        jnp.full((b, 1), eos, jnp.int32), jnp.full((b, 1), NINF, jnp.float32),
        jnp.ones((b, 1), bool), counters, jnp.full((b,), 2, jnp.int32),
    )
    args = (
        eff, jnp.full((b,), 0.7, jnp.float32), jnp.full((b,), 0.9, jnp.float32),
        jnp.zeros((b,), jnp.int32), jnp.arange(b, dtype=jnp.uint32), counters,
    )
    got = np.asarray(jax.jit(sample)(*args))
    np.testing.assert_array_equal(got, np.asarray(jax.jit(_reference_sample)(*args)))
    assert (got != eos).all() if banned else (got == eos).all()
