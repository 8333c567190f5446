"""A chip that holds a share of a layer's experts (models/mla.py
`_routed_experts` with `held`): only the share's own assignments move,
`share_rows` at a time, and none is dropped at any count.

(a) the share against a plain float32 loop over the assignments, at held
    counts on both sides of the bound, a swiglu and a relu2 expert;
(b) the bound from what the code sees;
(c) the count of passes beyond the first: through a layer, the engine's
    metrics and the flight record;
(d) the program WITHOUT a share keeps its lowered text."""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import mla, nemotron_h as nh

HIDDEN, WIDE = 64, 32


def _share(kind):
    """(cfg, lp of the 4 held experts, held, rows): 4 of a router's 32, so
    that even routing sends the share an eighth of the assignments and a
    pass (`share_rows`) holds 256 of the step's 640; 160 rows under top 4
    (64 rows a slot) add a pass's rows by the product, 640 under top 1 by
    the gather (`mla._ONE_HOT_ROWS` a slot)."""
    rng = np.random.default_rng(7)
    mat = lambda *s: jnp.asarray(  # noqa: E731
        rng.normal(size=s) / s[-2] ** 0.5, jnp.float32)
    if kind == "swiglu":  # dots3's and keye's expert, at toy widths
        cfg = mla.MlaConfig(
            hidden_size=HIDDEN, moe_intermediate_size=WIDE,
            n_routed_experts=32, num_experts_per_tok=4, dtype=jnp.float32)
        lp = {"we_gate": mat(4, HIDDEN, WIDE), "we_up": mat(4, HIDDEN, WIDE),
              "we_down": mat(4, WIDE, HIDDEN)}
        return cfg, lp, (8, 4), 160
    # models/nemotron_h.py's tiny preset and its share, behind a wider router
    cfg = dataclasses.replace(
        nh.NemotronHConfig.tiny(), n_routed_experts=32,
        num_experts_per_tok=1, dtype=jnp.float32)
    assert cfg.experts_held == (2, 4) and cfg.expert_mlp == "relu2"
    h, w = cfg.hidden_size, WIDE
    return cfg, {"we_up": mat(4, h, w), "we_down": mat(4, w, h)}, (2, 4), 640


def _plain(xf, topw, topi, lp, held):
    """Every assignment in turn, float32: its expert's FFN of its token's
    row, times its gate weight, added to the token's row."""
    first, count = held
    out = np.zeros(xf.shape, np.float32)
    lp = {n: np.asarray(w, np.float32) for n, w in lp.items()}
    for t, (ws, es) in enumerate(zip(topw, topi)):
        for w, e in zip(ws, es):
            e = int(e) - first
            if not 0 <= e < count:
                continue  # held elsewhere
            up = xf[t] @ lp["we_up"][e]
            if "we_gate" in lp:
                g = xf[t] @ lp["we_gate"][e]
                mid = g / (1.0 + np.exp(-g)) * up
            else:
                mid = np.maximum(up, 0.0) ** 2
            out[t] += w * (mid @ lp["we_down"][e])
    return out


def _routing(n_held, held, rows, k, one_expert=False, seed=0):
    """[rows, k] expert ids with exactly `n_held` assignments on the share,
    at random places, the others on experts held elsewhere; `one_expert`:
    every held assignment on the share's second expert."""
    rng = np.random.default_rng(seed)
    first, count = held
    elsewhere = np.setdiff1d(np.arange(32), first + np.arange(count))
    flat = rng.choice(elsewhere, size=rows * k)
    at = rng.permutation(rows * k)[:n_held]
    flat[at] = first + (1 if one_expert else rng.integers(0, count, n_held))
    return flat.reshape(rows, k)


# -- (a) exact at every count ----------------------------------------------------


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("count", [
    "none", "one", "the-mean", "exactly-a-pass", "a-pass-and-one",
    "several-passes", "all-on-one-held-expert"])
def test_a_share_moves_its_own_assignments_and_drops_none(kind, count):
    cfg, lp, held, rows = _share(kind)
    k = cfg.num_experts_per_tok
    c = mla.share_rows(rows, k, held[1], cfg.n_routed_experts)
    assert c == 256 and rows * k == 640  # a bound that can be passed
    assert (c <= mla._ONE_HOT_ROWS * k) == (kind == "swiglu")  # both forms
    n_held = {"none": 0, "one": 1, "the-mean": rows * k // 8,
              "exactly-a-pass": c, "a-pass-and-one": c + 1,
              "several-passes": 2 * c + 37,
              "all-on-one-held-expert": rows * k}[count]
    topi = _routing(n_held, held, rows, k, count == "all-on-one-held-expert")
    rng = np.random.default_rng(n_held)
    xf = rng.normal(size=(rows, cfg.hidden_size)).astype(np.float32)
    topw = rng.uniform(0.1, 1.0, size=(rows, k)).astype(np.float32)
    got, extra = jax.jit(
        lambda *a: mla._routed_experts(*a, cfg, held=held))(
            jnp.asarray(xf), jnp.asarray(topw), jnp.asarray(topi), lp)
    want = _plain(xf, topw, topi, lp, held)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=1e-5)
    if n_held:
        assert np.abs(want).max() > 1e-2  # there is something to drop
    else:
        assert not np.asarray(got).any()
    assert int(extra) == max(-(-n_held // c) - 1, 0)


# -- (b) the bound ----------------------------------------------------------------


@pytest.mark.parametrize("shape,want", [
    # a mixed and a decode step of the three cells (PERF.md 5)
    pytest.param((544, 8, 8, 256), 384, id="dots3-mixed"),
    pytest.param((32, 8, 8, 256), 128, id="dots3-decode"),
    pytest.param((543, 8, 16, 128), 1152, id="keye-mixed"),
    pytest.param((32, 8, 16, 128), 128, id="keye-decode"),
    pytest.param((576, 6, 16, 128), 896, id="nano3-mixed"),
    pytest.param((64, 6, 16, 128), 128, id="nano3-decode"),
    # a share that is everything, a handful of rows: one pass of them all
    pytest.param((40, 2, 8, 8), 80, id="the-whole-layer"),
    pytest.param((3, 8, 8, 256), 24, id="three-rows"),
])
def test_a_pass_holds_a_multiple_of_the_even_share_in_whole_tiles(
        shape, want):
    assert mla.share_rows(*shape) == want


# -- (c) the passes beyond the first, counted -------------------------------------


def test_a_layer_counts_the_passes_beyond_its_first(monkeypatch):
    """models/nemotron_h.py's tiny share, (2, 4) of 8 under top 2: the
    rule gives it all its rows' assignments in one pass, so the count is 0;
    with a pass of 8 rows it is what the router's choices make it."""
    cfg = nh.NemotronHConfig.tiny()
    params = nh.init_params(jax.random.key(1), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe"])
    x = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, 40, cfg.hidden_size)), jnp.float32)
    out, extra = nh.moe_ffn(x, lp, cfg)
    assert int(extra) == 0
    monkeypatch.setattr(mla, "share_rows", lambda *a: 8)
    forced, extra = nh.moe_ffn(x, lp, cfg)
    _, topi = mla._gate(x[0], lp, cfg, precision=jax.lax.Precision.HIGHEST)
    mine = int(np.sum((np.asarray(topi) >= 2) & (np.asarray(topi) < 6)))
    assert mine > 16 and int(extra) == -(-mine // 8) - 1
    np.testing.assert_allclose(forced, out, atol=1e-5)


@pytest.mark.parametrize("model,forced", [
    ("dots3-tiny", False), ("dots3-tiny", True),
    ("nemotron-h-tiny", False), ("nemotron-h-tiny", True),
    ("keye-vl2-tiny", False),  # (its tiny preset holds every expert)
])
def test_the_count_reaches_the_metrics_and_the_flight_record(
        model, forced, monkeypatch):
    """A prompt in two pieces and a few decode steps through the engine:
    0 under the rule at these sizes, and with a pass of 8 rows whatever
    the device counted, in `EngineMetrics` and, step by step, in the
    flight records."""
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.engine.engine import JaxEngine
    from dynamo_tpu.engine.request import SamplingParams

    if forced:
        monkeypatch.setattr(mla, "share_rows", lambda *a: 8)
    eng = JaxEngine(EngineConfig.for_tests(
        model=model, num_pages=64, max_pages_per_seq=16, prefill_chunk=32,
        max_seqs=2, decode_buckets=(1, 2)))
    rng = np.random.default_rng(3)
    eng.add_request("a", [int(t) for t in rng.integers(3, 250, 40)],
                    SamplingParams(max_tokens=6, temperature=0.0))
    while eng.has_work:
        eng.step()
    records = eng.flight.snapshot()
    in_flight = sum(r.get("moe_extra_passes", 0) for r in records)
    assert in_flight == eng.metrics.moe_extra_passes
    assert (eng.metrics.moe_extra_passes > 0) == forced


# -- (d) no share: the parent's program -------------------------------------------

#: sha256[:16] of the lowered text of `mla-tiny-moe`'s three passes at
#: commit c2f2a81 (scripts/lowered_text_compare.py's routine and shapes):
#: `dsv2lite-docgen` runs this branch of `_routed_experts`
NO_SHARE = {
    "prefill": "d83847ae72e8f465", "decode": "6047c1fd5beba61c",
    "mixed": "69a32ac2d8420cc5",
}


def test_the_program_without_a_share_keeps_its_lowered_text():
    from dynamo_tpu.models.registry import get_model

    S = jax.ShapeDtypeStruct
    ad = get_model("mla-tiny-moe", attention_impl="xla")
    params = jax.eval_shape(ad.init_params, jax.random.key(0))
    kv = jax.eval_shape(lambda: ad.init_kv(64, 16))
    i32 = jnp.int32

    def group(b, t):
        return (S((b, t), i32), S((b, t), i32), S((b, t), jnp.bool_),
                S((b, 8), i32))

    texts = {
        name: jax.jit(ad.forward_hidden).lower(
            params, *group(b, t)[:3], kv, group(b, t)[3]).as_text()
        for name, (b, t) in {"prefill": (1, 32), "decode": (4, 1)}.items()}
    texts["mixed"] = jax.jit(ad.forward_hidden_mixed).lower(
        params, group(1, 32), group(4, 1), kv).as_text()
    assert {n: hashlib.sha256(t.encode()).hexdigest()[:16]
            for n, t in texts.items()} == NO_SHARE


# -- (e) the loop's operations keep their scopes -----------------------------------


@pytest.mark.parametrize("model", ["dots3-tiny", "nemotron-h-tiny"])
def test_the_readers_find_the_loop_under_the_expert_layers_scopes(model):
    """An operation in a loop's body is named `<the loop's own
    path>/while/body/<its scopes>`, and the benchmark's readers take the
    FIRST scope of a path they know: a share's loop bound under `mlp/moe`
    would read as `mlp` and leave `mlp/moe/experts` empty. The grouped
    matmuls of a compiled mixed step lie under `mlp/moe/experts` as
    chipbench/dots3scopes.py reads a path, the gather of a pass's rows and
    the product that adds them to their tokens under `mlp/moe/route`."""
    import re

    from chipbench import dots3scopes
    from dynamo_tpu.models.registry import get_model

    S = jax.ShapeDtypeStruct
    ad = get_model(model, attention_impl="xla")
    assert ad.config.experts_held is not None
    params = jax.eval_shape(ad.init_params, jax.random.key(0))
    kv = jax.eval_shape(lambda: ad.init_kv(64, 16, state_slots=4))
    i32 = jnp.int32

    def group(b, t):
        return (S((b, t), i32), S((b, t), i32), S((b, t), jnp.bool_),
                (S((b, 8), i32), S((b, 2), i32)))

    text = jax.jit(ad.forward_hidden_mixed).lower(
        params, group(1, 32), group(4, 1), kv).compile().as_text()
    scopes = {}
    for path in set(re.findall(r'op_name="([^"]*)"', text)):
        scopes.setdefault(dots3scopes.deep_scope_of(path), []).append(path)
    assert any("dot_general" in p for p in scopes["mlp/moe/experts"])
    route = [p for p in scopes["mlp/moe/route"] if "while/body/mlp" in p]
    assert any(p.endswith("/gather") for p in route)  # a pass's rows
    assert any(p.endswith("/dot_general") for p in route)  # and their sum
    assert not any("mlp/moe/while" in p or "mlp/while" in p
                   for ps in scopes.values() for p in ps)
