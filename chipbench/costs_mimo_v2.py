"""What one step of MiMo-V2.5's language model (a 128-token window under a
sink on 8 KV heads in five layers of six, full layers on pages with 4 KV
heads, keys 192 wide beside values 128 wide, a leading dense layer, 256
sigmoid-routed experts of which a chip holds a share, an untied head) has to
move through HBM and the MXU, from shapes alone: the `costs_module` of
`mimo-v2.5-1chip` (see `manifest.module_of`), kept with the benchmark so
that no later PR can change the yardstick.

A cached token is `head_dim + v_head_dim` = 320 columns a KV head, 640 B in
bf16, whatever the program pads its pools to. A decode step streams every
weight once and of the routed experts those its rows touch. Per live row
it READS, in each WINDOW layer, K and V of the ring rows IN REACH of its
window (`min(context, sliding_window)` rows of 8 KV heads: 5,120 B a row;
the walk reads whole pages, two or three) and in each FULL layer K and V
of every cached token (4 KV heads: 2,560 B). A prompt piece MULTIPLIES, a
(query, key) pair inside the band or under the causal mask and query head,
`head_dim` for the score and `v_head_dim` for the value sum, twice each.
"""

from __future__ import annotations

FULL = "full_attention"


def kinds(hf: dict) -> list:
    """The HELD layers' kinds (`layer_types` of the file: the published
    `hybrid_layer_pattern` at `layer_ids`, spelled out)."""
    return list(hf["layer_types"][:hf["num_hidden_layers"]])


def full_layers(hf: dict) -> int:
    return sum(k == FULL for k in kinds(hf))


def window_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - full_layers(hf)


def expert_layers(hf: dict) -> int:
    ids = hf.get("layer_ids") or range(hf["num_hidden_layers"])
    return sum(bool(hf["moe_layer_freq"][li]) for li in ids)


def kv_row_bytes(hf: dict, kv_heads: int, itemsize: int = 2) -> int:
    """K and V of one cached token, a layer: KV heads x (192 + 128)."""
    return kv_heads * (hf["head_dim"] + hf["v_head_dim"]) * itemsize


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes the FULL layers' decode walks of one step read
    (`full_attn_hbm_share.mimo25`): K and V of every live token."""
    return live_tokens * full_layers(hf) * kv_row_bytes(
        hf, hf["num_key_value_heads"], weights.get("itemsize", 2))


def window_read_bytes(hf, weights, in_reach, rows, kernels=True):
    """Bytes the WINDOW layers' decode walks of one step must read
    (`window_attn_hbm_share.mimo25`): K and V of `in_reach` ring rows, the
    sum over the step's rows of `min(context, sliding_window)`, a window
    layer each (the least: the walk reads whole pages)."""
    return in_reach * window_layers(hf) * kv_row_bytes(
        hf, hf["swa_num_key_value_heads"], weights.get("itemsize", 2))


def pair_flops(hf: dict, pairs: float) -> float:
    """Floating-point operations of `pairs` (query, key) pairs of ONE layer
    in a prompt piece's attention kernel (`window_chunk_flops_share.mimo25`,
    `full_chunk_flops_share.mimo25`): 2 x head_dim + 2 x v_head_dim a pair
    and query head."""
    return pairs * hf["num_attention_heads"] * 2.0 * (
        hf["head_dim"] + hf["v_head_dim"])


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
    (`moe_experts_hbm_share.mimo25`): three matrices of every held expert
    its rows touch. `touched` is the step's count over its layers as the
    program counts it on the device (`moe_experts_touched`); without it,
    the expectation under even routing at `rows` rows."""
    if touched is None:
        touched = expert_layers(hf) * experts_touched(hf, rows)
    return touched * expert_bytes(hf, weights.get("itemsize", 2))


def attention_params(hf: dict, kv_heads: int) -> int:
    """q, k, v and o of one layer whose cache has `kv_heads` KV heads."""
    h, dk, dv = hf["hidden_size"], hf["head_dim"], hf["v_head_dim"]
    hq = hf["num_attention_heads"]
    return h * (hq * dk + kv_heads * (dk + dv) + hq * dv)


def dense_weight_bytes(hf: dict, itemsize: int = 2) -> float:
    """Every streamed weight outside the routed experts: attention of both
    kinds, the dense layers' MLP, the norms, the head (a decode step gathers
    32 rows of the embedding, no more); router and its bias and the sinks
    are float32."""
    h = hf["hidden_size"]
    n_f, n_w, n_e = full_layers(hf), window_layers(hf), expert_layers(hf)
    n_d = hf["num_hidden_layers"] - n_e
    matrices = (n_f * attention_params(hf, hf["num_key_value_heads"])
                + n_w * attention_params(hf, hf["swa_num_key_value_heads"])
                + n_d * 3 * h * hf["intermediate_size"]
                + 2 * hf["num_hidden_layers"] * h + h
                + h * hf["vocab_size"])
    f32 = (n_e * (h + 1) * hf["n_routed_experts_published"]
           + n_w * hf["num_attention_heads"])
    return matrices * itemsize + f32 * 4


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes one decode step reads (`decode_hbm_share`): the weights, the
    touched experts, K and V of every live token in the full layers and of
    the ring rows in reach in the window ones (a row past the window holds
    `sliding_window` of them)."""
    item = weights.get("itemsize", 2)
    in_reach = min(live_tokens, rows * hf["sliding_window"])
    return (dense_weight_bytes(hf, item)
            + moe_experts_read_bytes(hf, weights, live_tokens, rows)
            + kv_read_bytes(hf, weights, live_tokens, rows)
            + window_read_bytes(hf, weights, in_reach, rows))
