"""What one step of Keye-VL-2.0's language model (GQA under a learned
indexer that chooses 2,048 tokens a query, 128 softmax-routed experts of
which a chip holds a share) has to move through HBM, from shapes alone:
the `costs_module` of `keye-vl2-30b-a3b-1chip` (see `manifest.module_of`),
kept with the benchmark so that no later PR can change the yardstick.

A decode step streams every weight but the embedding table once (it is
gathered from) and of the experts those its rows touch; per live row and
layer it READS the row's index keys (64 wide, one a cached token: the
indexer scores every one) and the K and V of every cached token (the
decode attention is the page walk under a bit a token: it fetches every
page of the row and masks the tokens not chosen, so what it READS is the
context, whatever it attends; a gather of the 2,048 chosen rows was
measured slower and deleted, PERF.md 6, PR 43). The tokens it ATTENDS are
the device's count (`walk_pages_named`, in tokens for this family).
"""

from __future__ import annotations


def layers(hf: dict) -> int:
    return hf["num_hidden_layers"]


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows of one token over the layers (head_dim 128: no lane
    padding)."""
    return (2 * layers(hf) * hf["num_key_value_heads"] * hf["head_dim"]
            * itemsize)


def index_key_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """One token's index keys over the layers."""
    return layers(hf) * hf["sa_config"]["indexer_head_dim"] * itemsize


def walk_read_bytes(hf: dict, weights: dict, tokens: float) -> float:
    """K and V bytes of `tokens` cached tokens, a layer each (the unit of
    the device counter `walk_pages_live` for this family: the tokens the
    decode rows hold, which the walk under bits fetches whole).
    `sparse_attn_hbm_share.keye` takes its bytes from here."""
    return (tokens * 2 * hf["num_key_value_heads"] * hf["head_dim"]
            * weights.get("itemsize", 2))


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes the decode attention of one step reads
    (`paged_attn_hbm_share`): every live token's K and V, since the walk
    fetches every page of a row whatever the selection names."""
    return live_tokens * kv_bytes_per_token(hf, weights.get("itemsize", 2))


def index_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """The index keys one decode step's scores read
    (`index_keys_hbm_share`): one a live token and layer."""
    return live_tokens * index_key_bytes_per_token(
        hf, weights.get("itemsize", 2))


def experts_touched(hf: dict, rows: float) -> float:
    """Expected number of the experts HELD that `rows` rows touch a layer
    under even routing over all the router's experts."""
    e, k = hf["num_local_experts"], hf["num_experts_per_tok"]
    return hf["num_experts"] * (1.0 - (1.0 - k / e) ** rows)


def routed_expert_bytes(hf: dict, rows: float, itemsize: int = 2,
                        touched=None) -> float:
    if touched is None:
        touched = experts_touched(hf, rows)
    return (layers(hf) * touched * 3 * hf["hidden_size"]
            * hf["moe_intermediate_size"] * itemsize)


def moe_experts_read_bytes(hf, weights, live_tokens, rows, kernels=True,
                           touched=None):
    """Least bytes the grouped matmuls of one decode step read
    (`moe_experts_hbm_share.keye`): three matrices of every held expert
    the rows touch."""
    return routed_expert_bytes(hf, rows, weights.get("itemsize", 2), touched)


def dense_weight_bytes(hf: dict, itemsize: int = 2) -> float:
    """Every streamed weight outside the routed experts (no embedding
    table: it is gathered from); the router is float32."""
    h, d = hf["hidden_size"], hf["head_dim"]
    sa = hf["sa_config"]
    qd, kvd = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    attn = 2 * h * qd + 2 * h * kvd + 2 * d + 2 * h  # and the four norms
    index = (h * sa["indexer_num_heads"] * sa["indexer_head_dim"]
             + h * sa["indexer_head_dim"] + h * sa["indexer_num_heads"]
             + 2 * sa["indexer_head_dim"])
    return ((layers(hf) * (attn + index) + h + h * hf["vocab_size"])
            * itemsize + layers(hf) * h * hf["num_local_experts"] * 4)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes one decode step reads (`decode_hbm_share`): the weights, the
    touched experts, the index keys and the K and V of every live token."""
    item = weights.get("itemsize", 2)
    return (dense_weight_bytes(hf, item)
            + routed_expert_bytes(hf, rows, item)
            + index_read_bytes(hf, weights, live_tokens, rows)
            + kv_read_bytes(hf, weights, live_tokens, rows))


def chunk_flops(hf: dict, pairs: float) -> float:
    """Floating-point operations of `pairs` (query, key) pairs a layer in
    the chunk kernel (`sparse_chunk_flops_share.keye`): q . k and p . v,
    `head_dim` wide, every query head."""
    return 4.0 * pairs * hf["num_attention_heads"] * hf["head_dim"]
