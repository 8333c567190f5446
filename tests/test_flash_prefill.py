"""Flash prefill kernel == the XLA causal-attention fallback, bit-close.

Runs the real Pallas kernel in interpret mode on CPU (same lowering
semantics as TPU), mirroring tests/test_ops_paged_attention.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.flash_prefill import flash_prefill_attention


def _ref_causal(q, k, v, valid_len, scale_dim):
    """Dense fp32 causal attention with a validity mask (the fallback's
    semantics, models/llama.py:paged_attention with key_pos masking)."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(np.float32).reshape(b, t, hkv, g, d)
    kf = k.astype(np.float32)
    vf = v.astype(np.float32)
    scores = np.einsum("btkgd,bskd->bkgts", qf, kf) / np.sqrt(scale_dim)
    pos = np.arange(t)
    mask = (pos[None, :] <= pos[:, None])[None, None, None]  # causal
    kmask = (pos[None, :] < np.asarray(valid_len)[:, None])[
        :, None, None, None, :
    ]
    scores = np.where(mask & kmask, scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("bkgts,bskd->btkgd", p, vf)
    return out.reshape(b, t, hq, d)


@pytest.mark.parametrize(
    "b,t,hq,hkv,d,valid",
    [
        (2, 128, 4, 2, 128, (128, 100)),   # one block, padding tail
        (1, 384, 8, 2, 128, (384,)),       # multi-block, GQA g=4
        (2, 256, 2, 2, 128, (256, 17)),    # g=1, short valid prefix
        (1, 130, 4, 4, 128, (130,)),       # ragged T (pads to 256)
    ],
)
def test_matches_dense_causal(b, t, hq, hkv, d, valid):
    rng = np.random.default_rng(hash((b, t, hq, hkv)) % 2**31)
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    k = rng.standard_normal((b, t, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, t, hkv, d)).astype(np.float32)
    valid_len = np.asarray(valid, np.int32)

    got = np.asarray(
        flash_prefill_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(valid_len), scale_dim=d, interpret=True,
        )
    )
    ref = _ref_causal(q, k, v, valid_len, d)
    for bi in range(b):
        n = valid_len[bi]
        np.testing.assert_allclose(
            got[bi, :n], ref[bi, :n], rtol=2e-5, atol=2e-5
        )


def test_scale_dim_override():
    """Lane-padded D: logits scale by the REAL head dim, padding zeros
    contribute nothing."""
    rng = np.random.default_rng(0)
    b, t, h, d_real, d_pad = 1, 128, 2, 64, 128
    q = np.zeros((b, t, h, d_pad), np.float32)
    k = np.zeros((b, t, h, d_pad), np.float32)
    v = np.zeros((b, t, h, d_pad), np.float32)
    q[..., :d_real] = rng.standard_normal((b, t, h, d_real))
    k[..., :d_real] = rng.standard_normal((b, t, h, d_real))
    v[..., :d_real] = rng.standard_normal((b, t, h, d_real))
    got = np.asarray(
        flash_prefill_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.full((b,), t, jnp.int32), scale_dim=d_real, interpret=True,
        )
    )
    ref = _ref_causal(q, k, v, np.full((b,), t), d_real)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def _ref_hist(q, kc, vc, k_cache, v_cache, layer, pt, hist, cur, scale_dim):
    """Dense reference: gather history pages + concat current chunk (the
    old XLA path's semantics)."""
    b, t, hq, d = q.shape
    s = k_cache.shape[2]
    outs = []
    for bi in range(b):
        if cur[bi] == 0:  # dead (padded) row: output unspecified
            outs.append(np.zeros((t, hq, d), np.float32))
            continue
        kh = k_cache[layer, pt[bi]].reshape(-1, k_cache.shape[3], d)[: hist[bi]]
        vh = v_cache[layer, pt[bi]].reshape(-1, k_cache.shape[3], d)[: hist[bi]]
        keys = np.concatenate([kh, kc[bi, : cur[bi]]], axis=0)
        vals = np.concatenate([vh, vc[bi, : cur[bi]]], axis=0)
        n = keys.shape[0]
        hkv = keys.shape[1]
        g = hq // hkv
        qf = q[bi].astype(np.float32).reshape(t, hkv, g, d)
        scores = np.einsum("tkgd,skd->kgts", qf, keys.astype(np.float32))
        scores /= np.sqrt(scale_dim)
        key_pos = np.arange(n)
        row_pos = hist[bi] + np.arange(t)
        mask = key_pos[None, None, None, :] <= row_pos[None, None, :, None]
        scores = np.where(mask, scores, -1e30)
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("kgts,skd->tkgd", p, vals.astype(np.float32))
        outs.append(o.reshape(t, hq, d))
    return np.stack(outs)


@pytest.mark.parametrize(
    "b,t,hq,hkv,hist,cur",
    [
        (2, 128, 4, 2, (128, 65), (128, 90)),   # full + ragged chunk
        (1, 256, 8, 2, (192,), (256,)),         # GQA g=4, multi-page hist
        (2, 128, 2, 2, (64, 0), (128, 0)),      # one padded (dead) row
    ],
)
def test_paged_history_matches_dense(b, t, hq, hkv, hist, cur):
    from dynamo_tpu.ops.flash_prefill import paged_prefill_attention

    d, s, num_pages, mp = 128, 64, 16, 8
    layers = 1
    rng = np.random.default_rng(hash((b, t, hq, hist)) % 2**31)
    q = rng.standard_normal((b, t, hq, d)).astype(np.float32)
    kc = rng.standard_normal((b, t, hkv, d)).astype(np.float32)
    vc = rng.standard_normal((b, t, hkv, d)).astype(np.float32)
    k_cache = rng.standard_normal((layers, num_pages, s, hkv, d)).astype(
        np.float32
    )
    v_cache = rng.standard_normal((layers, num_pages, s, hkv, d)).astype(
        np.float32
    )
    # distinct pages per sequence
    pt = np.stack(
        [np.arange(1 + bi * mp, 1 + bi * mp + mp) % num_pages for bi in range(b)]
    ).astype(np.int32)
    hist = np.asarray(hist, np.int32)
    cur = np.asarray(cur, np.int32)

    got = np.asarray(
        paged_prefill_attention(
            jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
            jnp.asarray(k_cache), jnp.asarray(v_cache),
            jnp.int32(0), jnp.asarray(pt), jnp.asarray(hist),
            jnp.asarray(cur), scale_dim=d, interpret=True,
        )
    )
    ref = _ref_hist(q, kc, vc, k_cache, v_cache, 0, pt, hist, cur, d)
    for bi in range(b):
        n = cur[bi]
        if n == 0:
            continue
        np.testing.assert_allclose(
            got[bi, :n], ref[bi, :n], rtol=2e-5, atol=2e-5
        )


def test_paged_history_tp_shard_and_layer(cpu_mesh_devices):
    """paged_prefill_attention under a tp mesh == unsharded, reading a
    NONZERO layer of the stacked cache."""
    from dynamo_tpu.ops.flash_prefill import paged_prefill_attention
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    b, t, hq, hkv, d, s, num_pages, mp, layers = 1, 128, 4, 2, 128, 64, 8, 4, 3
    layer = 2
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((b, t, hq, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, t, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, t, hkv, d)), jnp.float32)
    k_cache = jnp.asarray(
        rng.standard_normal((layers, num_pages, s, hkv, d)), jnp.float32
    )
    v_cache = jnp.asarray(
        rng.standard_normal((layers, num_pages, s, hkv, d)), jnp.float32
    )
    pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    hist = jnp.asarray([130], jnp.int32)  # partial third page
    cur = jnp.asarray([t], jnp.int32)

    args = (q, kc, vc, k_cache, v_cache, jnp.int32(layer), pt, hist, cur)
    ref = np.asarray(
        paged_prefill_attention(*args, scale_dim=d, interpret=True)
    )
    # cross-check layer indexing against the dense reference too
    dense = _ref_hist(
        np.asarray(q), np.asarray(kc), np.asarray(vc),
        np.asarray(k_cache), np.asarray(v_cache), layer,
        np.asarray(pt), np.asarray(hist), np.asarray(cur), d,
    )
    np.testing.assert_allclose(ref, dense, rtol=2e-5, atol=2e-5)

    mesh = make_mesh(MeshConfig(dp=1, tp=2, sp=1))
    got = np.asarray(
        paged_prefill_attention(
            *args, scale_dim=d, interpret=True, mesh=mesh
        )
    )
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_tp_shard_map(cpu_mesh_devices):
    """Head-sharded kernel under a tp mesh == unsharded."""
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    rng = np.random.default_rng(3)
    b, t, hq, hkv, d = 1, 128, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, t, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, t, hkv, d)), jnp.float32)
    vl = jnp.full((b,), t, jnp.int32)

    ref = np.asarray(
        flash_prefill_attention(q, k, v, vl, scale_dim=d, interpret=True)
    )
    mesh = make_mesh(MeshConfig(dp=1, tp=2, sp=1))
    got = np.asarray(
        flash_prefill_attention(
            q, k, v, vl, scale_dim=d, interpret=True, mesh=mesh
        )
    )
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# -- a band by position (models/dots3.py: a window layer's prompt piece) ------


def _band_layout(first, t, held, shift=0):
    """Positions as a window layer's piece lays its keys out: `held` ring
    columns that hold `base + i` (a key where that is not negative and
    before the piece's first position), then the piece's own `t` rows."""
    base = first - held + shift
    cached = base + np.arange(held)
    q_pos = first + np.arange(t)
    k_pos = np.concatenate(
        [np.where((cached >= 0) & (cached < first), cached, -1), q_pos])
    return q_pos[None].astype(np.int32), k_pos[None].astype(np.int32)


@pytest.mark.parametrize("window,first,t,held,shift", [
    pytest.param(9, 70, 40, 16, 5, id="window-9-one-tile"),
    pytest.param(129, 1000, 512, 192, 64, id="window-129-two-query-tiles"),
    pytest.param(513, 8192, 512, 576, 64, id="published-513-of-1088-keys"),
    pytest.param(513, 0, 512, 576, 64, id="published-a-piece-from-position-0"),
    pytest.param(513, 300, 32, 576, 20, id="published-a-short-piece-early"),
])
def test_a_band_query_attends_exactly_its_window(window, first, t, held,
                                                 shift):
    """With zero queries the softmax is uniform over the kept keys, and
    with value row k the k-th unit vector the output NAMES them: a query
    at position p attends the keys at `max(0, p - (window - 1)) .. p`,
    `window` of them once it has them, its own among them, and not the
    key `window` before it; a ring column at or past the piece's first
    position (a stale row) is no key."""
    from dynamo_tpu.ops.flash_prefill import window_prefill_attention

    q_pos, k_pos = _band_layout(first, t, held, shift)
    kk = k_pos.shape[1]
    got = np.asarray(window_prefill_attention(
        jnp.zeros((1, t, 1, 128), jnp.float32),
        jnp.ones((1, kk, 1, 128), jnp.float32),
        jnp.eye(kk, dtype=jnp.float32)[None, :, None, :],
        jnp.asarray(q_pos), jnp.asarray(k_pos), window=window,
        interpret=True))[0, :, 0]  # [T, K]
    for j in (0, 1, t // 2, t - 1):
        p = int(q_pos[0, j])
        kept = np.flatnonzero(got[j] > 0)
        want = list(range(max(0, p - (window - 1)), p + 1))
        assert sorted(k_pos[0, kept]) == want, (j, p)
        assert len(kept) == min(window, p + 1)
        np.testing.assert_allclose(got[j, kept], 1.0 / len(kept), rtol=1e-5)
        assert p in k_pos[0, kept] and (p - window) not in k_pos[0, kept]
        # its own token is the piece's own row, not a ring column
        assert held + j in kept


@pytest.mark.parametrize("b,t,hn,d,dv,kk,window", [
    pytest.param(2, 40, 3, 32, 16, 100, 9, id="three-heads-narrow-widths"),
    pytest.param(1, 512, 8, 160, 128, 704, 129,
                 id="two-query-tiles-two-head-groups"),
    pytest.param(2, 16, 2, 256, 128, 48, 9, id="no-ring-row-yet"),
])
def test_a_band_matches_dense_attention_under_the_same_rule(
        b, t, hn, d, dv, kk, window):
    """Rows of a batch at different positions, widths that fill no lane
    tile, queries and keys past the valid ones (a padding query comes out
    as zeros): against dense float32 attention under the rule written
    out."""
    from dynamo_tpu.ops.flash_prefill import window_prefill_attention

    rng = np.random.default_rng(kk)
    q = rng.standard_normal((b, t, hn, d)).astype(np.float32) * 0.3
    k = rng.standard_normal((b, kk, hn, d)).astype(np.float32)
    v = rng.standard_normal((b, kk, hn, dv)).astype(np.float32)
    first = (0 if kk == 48 else 50) + 7 * np.arange(b)
    q_pos, k_pos = (np.concatenate(x) for x in zip(*(
        _band_layout(int(f), t, kk - t, 3) for f in first)))
    q_pos[:, -3:] = -1  # the piece's tail is padding: no key in its band
    k_pos[:, -3:] = -1
    k[:, -3:] = np.nan  # and may hold anything as a KEY;
    v[:, -3:] = 0  # as a value zeros (a zero weight silences no NaN)
    got = np.asarray(window_prefill_attention(
        *(jnp.asarray(x) for x in (q, k, v, q_pos, k_pos)), window=window,
        interpret=True))
    s = np.einsum("bthd,bkhd->bhtk", q, k)
    at, key = q_pos[:, :, None], k_pos[:, None, :]
    keep = (key >= 0) & (key <= at) & (key >= at - (window - 1))
    s = np.where(keep[:, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhtk,bkhd->bthd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(got[:, :-3], want[:, :-3], atol=2e-5)
    assert (got[:, -3:] == 0).all()
