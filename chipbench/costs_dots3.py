"""What one step of dots3-note-prev's language model (latent attention
under a learned indexer in the full layers, latent attention of another
geometry under a 513-token window in the sliding ones, 256 sigmoid-routed
experts of which a chip holds a share) has to move through HBM and the
MXU, from shapes alone: the `costs_module` of `dots3-note-prev-1chip` (see
`manifest.module_of`), kept with the benchmark so that no later PR can
change the yardstick.

A decode step streams every weight but the embedding table once (it is
gathered from) and of the experts those its rows touch. Per live row it
READS, in each FULL layer, the row's index keys (128 wide, one a cached
token: the indexer scores every one) and the latent and the rope key of
EVERY cached token (the decode attention is the page walk under a bit a
token: it fetches every page of the row and masks the tokens not chosen,
so what it reads is the context, whatever it attends; the rope key is
cached in a whole 128-lane tile, models/mla.py `kv_rope_dim`), and in each
SLIDING layer the `sliding_window_size` ring rows its window holds. The
walk also MULTIPLIES every cached token: at 128 heads of a 576-wide key
and a 512-wide value it stands at the chip's ridge, so `walk_flops` is
kept beside `kv_read_bytes` and the reader takes the larger time.
"""

from __future__ import annotations

FULL = "full_attention"
#: the lanes a cached rope key takes under the kernels (models/mla.py)
ROPE_LANES = 128


def kinds(hf: dict) -> list:
    return list(hf["layer_types"][:hf["num_hidden_layers"]])


def full_layers(hf: dict) -> int:
    return sum(k == FULL for k in kinds(hf))


def sliding_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - full_layers(hf)


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - hf["first_k_dense_replace"]


def latent_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """The latent and the rope key as cached of one token, a full layer."""
    rope = -(-hf["qk_rope_head_dim"] // ROPE_LANES) * ROPE_LANES
    return (hf["kv_lora_rank"] + rope) * itemsize


def ring_row_bytes(hf: dict, itemsize: int = 2) -> int:
    """One ring row of a sliding layer: its latent and its rope key."""
    return (hf["swa_kv_lora_rank"] + hf["swa_qk_rope_head_dim"]) * itemsize


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes the full layers' decode walks of one step read
    (`paged_attn_hbm_share`, `latent_walk_roofline_share.dots3`): every
    live token's latent and rope key, a full layer each."""
    return live_tokens * full_layers(hf) * latent_bytes_per_token(
        hf, weights.get("itemsize", 2))


def walk_flops(hf: dict, live_tokens: float) -> float:
    """Floating-point operations the full layers' decode walks of one step
    execute: every live token scored by every head over the latent and the
    rope key as cached, and summed as a value over the latent."""
    c = hf["kv_lora_rank"]
    rope = -(-hf["qk_rope_head_dim"] // ROPE_LANES) * ROPE_LANES
    return (2.0 * live_tokens * full_layers(hf) * hf["num_attention_heads"]
            * (2 * c + rope))


def index_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """The index keys one decode step's scores read
    (`index_keys_hbm_share.dots3`): one a live token and full layer."""
    return (live_tokens * full_layers(hf) * hf["index_head_dim"]
            * weights.get("itemsize", 2))


def window_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """The ring rows one decode step's window attention must read
    (`window_attn_hbm_share.dots3`): `sliding_window_size` a row and
    sliding layer (the least: the program walks the whole ring, with the
    rope key in its lane tile)."""
    return (rows * sliding_layers(hf) * hf["sliding_window_size"]
            * ring_row_bytes(hf, weights.get("itemsize", 2)))


def experts_touched(hf: dict, rows: float) -> float:
    """Expected number of the experts HELD that `rows` rows touch a layer
    under even routing over all the router's experts."""
    e, k = hf["n_routed_experts_published"], hf["num_experts_per_tok"]
    return hf["n_routed_experts"] * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of one routed expert."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"] * itemsize


def moe_experts_read_bytes(hf, weights, live_tokens, rows, kernels=True,
                           touched=None):
    """Least bytes the grouped matmuls of one step read
    (`moe_experts_hbm_share.dots3`): three matrices of every held expert
    its rows touch. `touched` is the step's count over its expert layers
    as the program counts it on the device (`moe_experts_touched`); without
    it, the expectation under even routing at `rows` rows
    (`step_read_bytes`, whose accepted reader hands no count)."""
    if touched is None:
        touched = expert_layers(hf) * experts_touched(hf, rows)
    return touched * expert_bytes(hf, weights.get("itemsize", 2))


def attention_params(hf: dict, kind: str) -> int:
    """Parameters of one attention block (its norms among them)."""
    p = "" if kind == FULL else "swa_"
    h, n = hf["hidden_size"], hf[p + "num_attention_heads"]
    rq, c = hf[p + "q_lora_rank"], hf[p + "kv_lora_rank"]
    dn, dr, dv = (hf[p + "qk_nope_head_dim"], hf[p + "qk_rope_head_dim"],
                  hf[p + "v_head_dim"])
    out = (h * rq + rq * n * (dn + dr) + h * (c + dr) + c * n * (dn + dv)
           + n * dv * h + h * n + h + rq + c)
    if kind == FULL:
        j, di = hf["index_n_heads"], hf["index_head_dim"]
        out += rq * j * di + h * di + h * j + 2 * di
    return out


def dense_weight_bytes(hf: dict, itemsize: int = 2) -> float:
    """Every streamed weight outside the routed experts (no embedding
    table: it is gathered from); the router and its biases are float32."""
    h = hf["hidden_size"]
    attn = sum(attention_params(hf, k) for k in kinds(hf))
    dense = hf["first_k_dense_replace"] * (3 * h * hf["intermediate_size"]
                                           + h)
    shared = expert_layers(hf) * (
        3 * h * hf["moe_intermediate_size"] * hf["n_shared_experts"] + h)
    router = expert_layers(hf) * (h + 1) * hf["n_routed_experts_published"]
    return ((attn + dense + shared + h + h * hf["vocab_size"]) * itemsize
            + router * 4)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes one decode step reads (`decode_hbm_share`): the weights, the
    touched experts, the index keys and the latent rows of every live
    token, the windows' ring rows."""
    item = weights.get("itemsize", 2)
    return (dense_weight_bytes(hf, item)
            + moe_experts_read_bytes(hf, weights, live_tokens, rows)
            + index_read_bytes(hf, weights, live_tokens, rows)
            + kv_read_bytes(hf, weights, live_tokens, rows)
            + window_read_bytes(hf, weights, live_tokens, rows))


def chunk_flops(hf: dict, pairs: float) -> float:
    """Floating-point operations of `pairs` (query, key) pairs a full layer
    in the chunk kernel (`sparse_chunk_flops_share.dots3`): the score over
    the latent and the rope key as cached, the sum over the latent, every
    head (absorbed form)."""
    c = hf["kv_lora_rank"]
    rope = -(-hf["qk_rope_head_dim"] // ROPE_LANES) * ROPE_LANES
    return 2.0 * pairs * hf["num_attention_heads"] * (2 * c + rope)
