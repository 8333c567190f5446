"""On-device batched sampling: temperature / top-k / top-p / greedy.

One jitted function handles a heterogeneous batch (per-row params) so decode
stays a single XLA program: greedy rows take argmax, sampling rows take a
Gumbel draw over the top-k/top-p-masked, temperature-scaled distribution:
the `k_cap` most likely tokens (requested top_k values above k_cap are
clamped) under their *true* probabilities (one logsumexp over the vocab).

TPU note: nothing as wide as the vocabulary is sorted. `top_candidates`
keeps k_cap blocks of 128 ids by their maxima, then k_cap sub-blocks of 16,
and stable-sorts 1,188-2,040 maxima, 512 maxima and 1,024 values: exact
including ties. `lax.top_k(scaled, 64)` there cost 1.40-1.98 ms a step at
64 x 102k-152k and 32 x 261k logits (this: 0.22-0.30) and left equal values
as its kernel happened on them (v5e, `scripts/sample_bench.py`; PERF.md 6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

#: static candidate-set bound; per-request top_k is clamped to this
DEFAULT_K_CAP = 64

#: ids a block of `top_candidates` holds: the TPU's lane width over the
#: vocabulary, then sub-blocks of the chosen blocks (constants: PERF.md 6)
CAND_BLOCKS = (128, 16)


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


def _total_order(bits: jax.Array) -> jax.Array:
    """i32 bits of floats <-> keys in floats' total order; its own inverse."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _top_keys(keys, k, widths):
    """One level: a row's k largest i32 keys and their positions, [B, k]."""
    b, n = keys.shape
    w = widths[0] if widths else n
    nb = -(-n // w)
    if nb <= k:  # every block would be chosen: one stable sort
        place = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        top, pos = jax.lax.sort((~keys, place), num_keys=1, is_stable=True)
        return ~top[:, :k], pos[:, :k]
    pad = ((0, 0), (0, nb * w - n))  # past V: a key below every float's
    blocks = jnp.pad(keys, pad, constant_values=-(2**31)).reshape(b, nb, w)
    _, chosen = _top_keys(jnp.max(blocks, axis=-1), k, ())
    chosen = jnp.sort(chosen, axis=-1)  # ascending: positions keep id order
    inside = jnp.take_along_axis(blocks, chosen[:, :, None], axis=1)
    top, pos = _top_keys(inside.reshape(b, k * w), k, widths[1:])
    picked = (pos // w)[:, :, None] == jnp.arange(k)  # [B, K, K] one-hot
    block = jnp.sum(jnp.where(picked, chosen[:, None, :], 0), axis=-1)
    return top, block * w + pos % w  # (a gather of k scalars a row is slower)


def top_candidates(scaled: jax.Array, k_cap: int) -> tuple[jax.Array, jax.Array]:
    """(values [B, K] descending, ids [B, K]): the k_cap <= V largest of
    each row of `scaled` [B, V] f32, of equal values the lower id first (-0
    below +0): what `lax.top_k(scaled, k_cap)` is documented to return and
    returns on the CPU, bit for bit, here on every backend.

    A row is blocks of `CAND_BLOCKS[0]` consecutive ids. Block maxima,
    stably sorted, choose k_cap blocks; their numbers are put in ascending
    order and their values gathered in that order; the same step over
    sub-blocks of `CAND_BLOCKS[1]` leaves k_cap x 16 values, and a stable
    sort ends it. With no more blocks than k_cap (tiny vocabularies) that
    static shape alone goes straight to the sort.

    Exact at each level: let t be the k_cap-th largest value. A block with
    an entry > t has a maximum > t; there are fewer than k_cap such blocks
    and the sort over maxima takes them all. A stable top-k takes the
    lowest-numbered t-entries; those in blocks whose maximum is exactly t
    are a prefix, by id, of all t-entries in such blocks, so lie in a prefix,
    by number, of those blocks, and the stable sort takes just the lowest-
    numbered blocks among equal maxima. Gathered in ascending block order,
    positions keep id order, so the next level breaks ties as a stable top-k
    would. -inf (min_tokens bans, whole blocks) is an ordinary value."""
    bits = jax.lax.bitcast_convert_type(scaled, jnp.int32)
    top, ids = _top_keys(_total_order(bits), k_cap, CAND_BLOCKS)
    return jax.lax.bitcast_convert_type(_total_order(top), jnp.float32), ids


def _masked_candidates(logits, temperature, top_p, top_k, seeds, counters, k_cap):
    """What `sample` and `spec_accept_step` draw from: (greedy [B], cand_idx,
    keep, masked scaled logits, gumbel noise at the row's counter: [B, K])."""
    k_cap = min(k_cap, logits.shape[1])
    greedy = temperature <= 0.0
    safe_t = jnp.where(greedy, 1.0, jnp.maximum(temperature, 1e-6))
    scaled = logits / safe_t[:, None]

    cand_logits, cand_idx = top_candidates(scaled, k_cap)  # [B, K] descending
    lse = jax.scipy.special.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(cand_logits - lse)  # true full-softmax mass of candidates
    cum = jnp.cumsum(probs, axis=-1)
    # top-p: keep tokens whose preceding mass is < p (first always kept)
    keep_p = (cum - probs) < top_p[:, None]
    # top-k: keep the first k ranks (k == 0 disables => k_cap)
    eff_k = jnp.where(top_k > 0, jnp.minimum(top_k, k_cap), k_cap)
    keep = keep_p & (jnp.arange(k_cap)[None, :] < eff_k[:, None])
    masked = jnp.where(keep, cand_logits, _NEG_INF)

    def row_gumbel(seed, counter):
        key = jax.random.fold_in(jax.random.key(seed), counter)
        return jax.random.gumbel(key, (k_cap,), jnp.float32)

    gumbel = jax.vmap(row_gumbel)(seeds, counters)  # [B, K]
    return greedy, cand_idx, keep, masked, gumbel


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
    greedy, cand_idx, _, masked, gumbel = _masked_candidates(
        logits, temperature, top_p, top_k, seeds, counters, k_cap
    )
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
    greedy path. The bonus position (has_draft=False) IS `sample()` at
    that counter.
    """
    if not has_draft:
        chosen = sample(logits, temperature, top_p, top_k, seeds, counters, k_cap)
        return chosen, jnp.ones(chosen.shape, bool)

    greedy, cand_idx, keep, masked, gumbel = _masked_candidates(
        logits, temperature, top_p, top_k, seeds, counters, k_cap
    )
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
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
