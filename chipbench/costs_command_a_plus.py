"""What one step of Command A+'s language model (a 4,096-token window on a
GQA cache in three layers of four, a NoPE full layer on pages, 128
sigmoid-routed experts of which a chip holds a share, four shared experts
averaged, a tied head) has to move through HBM and the MXU, from shapes
alone: the `costs_module` of `command-a-plus-1chip` (see
`manifest.module_of`), kept with the benchmark so that no later PR can
change the yardstick.

A decode step streams every weight once (the tied embedding as the head it
also is) and of the routed experts those its rows touch. Per live row it
READS, in each SLIDING layer, K and V of the ring rows IN REACH of its
window (`min(context, sliding_window)` rows; the walk reads whole pages,
a page or two more) and in each FULL layer K and V of every cached token.
A prompt piece MULTIPLIES, a (query, key) pair inside the band or under
the causal mask and query head, `head_dim` for the score and `head_dim`
for the value sum, twice each.
"""

from __future__ import annotations

FULL = "full_attention"


def kinds(hf: dict) -> list:
    return list(hf["layer_types"][:hf["num_hidden_layers"]])


def full_layers(hf: dict) -> int:
    return sum(k == FULL for k in kinds(hf))


def sliding_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - full_layers(hf)


def kv_row_bytes(hf: dict, itemsize: int = 2) -> int:
    """K and V of one cached token, a layer: 2 x KV heads x head_dim."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * itemsize


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes the FULL layers' decode walks of one step read
    (`full_attn_hbm_share.cmdaplus`): K and V of every live token."""
    return live_tokens * full_layers(hf) * kv_row_bytes(
        hf, weights.get("itemsize", 2))


def window_read_bytes(hf, weights, in_reach, rows, kernels=True):
    """Bytes the SLIDING layers' decode walks of one step must read
    (`window_attn_hbm_share.cmdaplus`): K and V of `in_reach` ring rows, the
    sum over the step's rows of `min(context, sliding_window)`, a sliding
    layer each (the least: the walk reads whole pages)."""
    return in_reach * sliding_layers(hf) * kv_row_bytes(
        hf, weights.get("itemsize", 2))


def pair_flops(hf: dict, pairs: float) -> float:
    """Floating-point operations of `pairs` (query, key) pairs of ONE layer
    in a prompt piece's attention kernel (`window_chunk_flops_share.cmdaplus`,
    `full_chunk_flops_share.cmdaplus`): 4 x head_dim a pair and query head."""
    return 4.0 * pairs * hf["num_attention_heads"] * hf["head_dim"]


def experts_touched(hf: dict, rows: float) -> float:
    """Expected number of the experts HELD that `rows` rows touch a layer
    under even routing over all the router's experts."""
    e, k = hf["num_experts_published"], hf["num_experts_per_tok"]
    return hf["num_experts"] * (1.0 - (1.0 - k / e) ** rows)


def expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of one expert, routed or shared."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"] * itemsize


def moe_experts_read_bytes(hf, weights, live_tokens, rows, kernels=True,
                           touched=None):
    """Least bytes the grouped matmuls of one step read
    (`moe_experts_hbm_share.cmdaplus`): three matrices of every held expert
    its rows touch. `touched` is the step's count over its layers as the
    program counts it on the device (`moe_experts_touched`); without it,
    the expectation under even routing at `rows` rows."""
    if touched is None:
        touched = hf["num_hidden_layers"] * experts_touched(hf, rows)
    return touched * expert_bytes(hf, weights.get("itemsize", 2))


def attention_params(hf: dict) -> int:
    h, d = hf["hidden_size"], hf["head_dim"]
    return 2 * h * d * (hf["num_attention_heads"] + hf["num_key_value_heads"])


def dense_weight_bytes(hf: dict, itemsize: int = 2) -> float:
    """Every streamed weight outside the routed experts: attention, the
    shared experts, the norms, the tied embedding read as the head; the
    router is float32."""
    h, layers = hf["hidden_size"], hf["num_hidden_layers"]
    shared = hf["num_shared_experts"] * 3 * h * hf["intermediate_size"]
    return ((layers * (attention_params(hf) + shared + h) + h
             + h * hf["vocab_size"]) * itemsize
            + layers * h * hf["num_experts_published"] * 4)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes one decode step reads (`decode_hbm_share`): the weights, the
    touched experts, K and V of every live token in the full layers and of
    the ring rows in reach in the sliding ones (a row past the window holds
    `sliding_window` of them)."""
    item = weights.get("itemsize", 2)
    in_reach = min(live_tokens, rows * hf["sliding_window"])
    return (dense_weight_bytes(hf, item)
            + moe_experts_read_bytes(hf, weights, live_tokens, rows)
            + kv_read_bytes(hf, weights, live_tokens, rows)
            + window_read_bytes(hf, weights, in_reach, rows))
