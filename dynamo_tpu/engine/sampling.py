"""On-device batched sampling: temperature / top-k / top-p / greedy.

One jitted function handles a heterogeneous batch (per-row params) so decode
stays a single XLA program: greedy rows take argmax, sampling rows take a
Gumbel draw over the top-k/top-p-masked, temperature-scaled distribution.

TPU note: a full-vocab argsort is a bitonic network over 128k lanes and
costs tens of milliseconds — it would dominate the whole decode step. The
sampler instead takes the top `k_cap` candidates with lax.top_k (already
sorted) and computes their *true* probabilities under the full distribution
via one logsumexp over the vocab. Sampling is thus truncated to the k_cap
most likely tokens (requested top_k values above k_cap are clamped); top-p
mass is exact w.r.t. the full softmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

#: static candidate-set bound; per-request top_k is clamped to this
DEFAULT_K_CAP = 64


def build_output_counts(
    out_tokens: jax.Array,  # [B, O] i32 output-token history (padded)
    out_valid: jax.Array,  # [B, O] bool
    vocab: int,
) -> jax.Array:  # [B, V] f32 per-token output frequency
    """Scatter the output-token history into a per-vocab count table (the
    state the OpenAI frequency/presence penalties are defined over; output
    tokens only, matching the common engine interpretation)."""
    b = out_tokens.shape[0]
    counts = jnp.zeros((b, vocab), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], out_tokens.shape)
    return counts.at[rows, out_tokens].add(out_valid.astype(jnp.float32))


def apply_penalties(
    logits: jax.Array,  # [B, V] f32
    counts: jax.Array,  # [B, V] f32 output-token frequency
    freq_pen: jax.Array,  # [B] f32
    pres_pen: jax.Array,  # [B] f32
    rep_pen: jax.Array | None = None,  # [B] f32 (1 = off)
) -> jax.Array:
    """OpenAI penalty rule: logit -= freq_pen * count + pres_pen * (count>0),
    applied to the raw logits before temperature scaling. `rep_pen` is a
    multiplicative repetition penalty (the reference exposes one via
    nvext — protocols/openai/nvext.rs repetition_penalty): seen tokens'
    logits divide by r when positive and multiply when negative, applied
    before the additive penalties. Like frequency/presence here, "seen"
    means GENERATED tokens only — prompt tokens are not penalized (HF's
    generate also walks the prompt; penalizing it would grow the history
    bucket to the full context length for every penalized step)."""
    if rep_pen is not None:
        seen = counts > 0
        r = rep_pen[:, None]
        adjusted = jnp.where(logits > 0, logits / r, logits * r)
        logits = jnp.where(seen, adjusted, logits)
    return (
        logits
        - freq_pen[:, None] * counts
        - pres_pen[:, None] * (counts > 0).astype(logits.dtype)
    )


#: static per-row sparse logit-bias slots (OpenAI logit_bias entries +
#: min_tokens eos/stop bans share them); requests needing more are
#: rejected at the API boundary
BIAS_SLOTS = 16


def apply_logit_bias(
    logits: jax.Array,  # [B, V] f32
    bias_ids: jax.Array,  # [B, K] i32 token ids (0-padded)
    bias_vals: jax.Array,  # [B, K] f32 additive biases (0 = no-op)
    bias_gated: jax.Array,  # [B, K] bool — active only before min_tokens
    counters: jax.Array,  # [B] i32 output-token counter
    min_toks: jax.Array,  # [B] i32 min_tokens per request
) -> jax.Array:
    """Sparse additive logit bias (OpenAI `logit_bias`), with slots that
    can be GATED on the output count — min_tokens is implemented as
    gated -inf entries on the eos/stop ids, lifted once `counters`
    reaches the request's minimum. Zero-valued padding slots scatter-add
    nothing, so bias-free rows are exact no-ops."""
    active = (~bias_gated) | (counters < min_toks)[:, None]
    vals = jnp.where(active, bias_vals, 0.0)
    rows = jnp.arange(logits.shape[0])[:, None]
    return logits.at[rows, bias_ids].add(vals)


def sample(
    logits: jax.Array,  # [B, V] f32
    temperature: jax.Array,  # [B] f32 (<=0 => greedy)
    top_p: jax.Array,  # [B] f32 in (0, 1]
    top_k: jax.Array,  # [B] i32 (0 => disabled)
    seeds: jax.Array,  # [B] u32 per-request seed
    counters: jax.Array,  # [B] i32 per-request draw counter (token position)
    k_cap: int = DEFAULT_K_CAP,
) -> jax.Array:  # [B] i32 sampled token ids
    """Per-row PRNG: each request draws from key(seed) folded with its own
    token counter, so a (prompt, seed) pair reproduces exactly regardless of
    what else shares the batch or how steps interleave."""
    b, v = logits.shape
    k_cap = min(k_cap, v)
    greedy = temperature <= 0.0
    safe_t = jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))
    scaled = logits / safe_t[:, None]

    # Top-k_cap candidates, descending — the only vocab-wide work besides
    # one reduction for the softmax denominator.
    cand_logits, cand_idx = jax.lax.top_k(scaled, k_cap)  # [B, K]
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(cand_logits - lse)  # true full-softmax mass of candidates
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(k_cap)[None, :]
    # top-p: keep tokens whose preceding mass is < p (first always kept)
    keep_p = (cum - probs) < top_p[:, None]
    # top-k: keep the first k ranks (k == 0 disables => k_cap)
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k_cap), k_cap)
    keep = keep_p & (ranks < eff_k[:, None])
    masked = jnp.where(keep, cand_logits, _NEG_INF)

    def row_gumbel(seed, counter):
        key = jax.random.fold_in(jax.random.key(seed), counter)
        return jax.random.gumbel(key, (k_cap,), jnp.float32)

    gumbel = jax.vmap(row_gumbel)(seeds, counters)  # [B, K]
    sampled_rank = jnp.argmax(masked + gumbel, axis=-1)  # [B]
    sampled = jnp.take_along_axis(cand_idx, sampled_rank[:, None], axis=-1)[:, 0]
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)


def spec_accept_step(
    logits: jax.Array,  # [B, V] f32 raw (penalty/bias-adjusted) target logits
    draft: jax.Array,  # [B] i32 proposed token (ignored when has_draft=False)
    has_draft: bool,  # static: False for the bonus position (fresh draw)
    temperature: jax.Array,  # [B] f32 (<=0 => greedy row)
    top_p: jax.Array,  # [B] f32
    top_k: jax.Array,  # [B] i32
    seeds: jax.Array,  # [B] u32
    counters: jax.Array,  # [B] i32 draw counter for THIS position
    k_cap: int = DEFAULT_K_CAP,
) -> tuple[jax.Array, jax.Array]:  # (chosen [B] i32, accept [B] bool)
    """One position of speculative rejection sampling (Leviathan et al.
    2023 / Chen et al. 2023), specialized to a DETERMINISTIC draft: the
    proposal q is a point mass at the draft token, so accept it with
    probability p_eff(draft) and otherwise resample from the residual —
    p_eff with the draft zeroed, renormalized. The marginal of the
    emitted token is exactly p_eff for every position: p_eff(draft) from
    acceptance plus (1-p_eff(draft)) * p_eff(y)/(1-p_eff(draft))
    elsewhere.

    p_eff here is the PRECISE distribution `sample()` draws from —
    temperature-scaled logits truncated to the top-k_cap candidates,
    top-p/top-k masked, softmax over the surviving candidates — so
    spec-on sampling is distributionally identical to spec-off sampling
    (pinned by tests/test_spec_draft.py). Greedy rows (temperature<=0)
    take the argmax and accept iff it equals the draft — the bit-exact
    greedy path. The bonus position (has_draft=False) draws with the
    SAME fold_in(key(seed), counter) gumbel stream as `sample()`, so a
    bonus token is bit-identical to what the plain sampler would have
    drawn at that counter.
    """
    b, v = logits.shape
    k_cap = min(k_cap, v)
    greedy = temperature <= 0.0
    safe_t = jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))
    scaled = logits / safe_t[:, None]
    cand_logits, cand_idx = jax.lax.top_k(scaled, k_cap)  # [B, K]
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(cand_logits - lse)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.arange(k_cap)[None, :]
    keep_p = (cum - probs) < top_p[:, None]
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k_cap), k_cap)
    keep = keep_p & (ranks < eff_k[:, None])
    masked = jnp.where(keep, cand_logits, _NEG_INF)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def row_gumbel(seed, counter):
        key = jax.random.fold_in(jax.random.key(seed), counter)
        return jax.random.gumbel(key, (k_cap,), jnp.float32)

    gumbel = jax.vmap(row_gumbel)(seeds, counters)  # [B, K]

    if not has_draft:
        rank = jnp.argmax(masked + gumbel, axis=-1)
        samp_tok = jnp.take_along_axis(cand_idx, rank[:, None], axis=-1)[:, 0]
        chosen = jnp.where(greedy, greedy_tok, samp_tok).astype(jnp.int32)
        return chosen, jnp.ones((b,), bool)

    # p_eff(draft): the draft's true mass under the kept-candidate softmax
    kept_lse = jax.scipy.special.logsumexp(masked, axis=-1, keepdims=True)
    is_draft = cand_idx == draft[:, None]
    p_draft = jnp.sum(
        jnp.where(is_draft & keep, jnp.exp(masked - kept_lse), 0.0), axis=-1
    )

    def row_u(seed, counter):
        # accept-uniform: an extra fold keeps it independent of the
        # gumbel stream that shares (seed, counter)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), counter), 0x5BEC
        )
        return jax.random.uniform(key, ())

    u = jax.vmap(row_u)(seeds, counters)
    # residual resample: p_eff restricted to kept candidates minus the
    # draft (gumbel-argmax over masked logits == softmax-renormalized)
    masked_excl = jnp.where(is_draft, _NEG_INF, masked)
    has_alt = jnp.any(keep & ~is_draft, axis=-1)
    rank = jnp.argmax(masked_excl + gumbel, axis=-1)
    resampled = jnp.take_along_axis(cand_idx, rank[:, None], axis=-1)[:, 0]
    accept_s = (u < p_draft) | ~has_alt
    chosen_s = jnp.where(accept_s, draft, resampled)
    chosen = jnp.where(greedy, greedy_tok, chosen_s).astype(jnp.int32)
    accept = jnp.where(greedy, greedy_tok == draft, accept_s)
    return chosen, accept


def sample_greedy(logits: jax.Array) -> jax.Array:
    """Argmax-only fast path: when every request in the batch is greedy the
    engine compiles this instead of the sampling pipeline."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def token_logprobs(
    logits: jax.Array,  # [B, V] f32
    ids: jax.Array,  # [B] i32 chosen token per row
    k: int,  # top-k alternatives to report (0 => chosen only)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Log-probabilities under the UNSCALED distribution (OpenAI semantics:
    logprobs describe the model, not the sampling temperature).

    Returns (chosen_lp [B], top_ids [B, max(k,1)], top_lps [B, max(k,1)]);
    with k == 0 the top arrays are computed for 1 candidate and ignored by
    the caller (keeps one jaxpr shape per k)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B]
    chosen = jnp.take_along_axis(logits, ids[:, None].astype(jnp.int32), axis=-1)[
        :, 0
    ]
    kk = max(k, 1)
    top_vals, top_idx = jax.lax.top_k(logits, kk)  # [B, kk]
    return chosen - lse, top_idx.astype(jnp.int32), top_vals - lse[:, None]
