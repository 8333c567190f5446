"""What one decode step of DeepSeek-V2-Lite (an MLA + MoE decoder) has
to read from HBM, from shapes alone: the `costs_module` of
`deepseek-v2-lite-1chip` (see `manifest.module_of`), kept with the
benchmark so that no later PR can change the yardstick.

The cache holds one latent (`kv_lora_rank`) and one rope key
(`qk_rope_head_dim`) a token and layer, whatever the heads: 576 columns,
1152 B in bf16. That is the floor `kv_read_bytes` counts, as the ISSUE
set it; the program caches the rope key in a whole 128-lane tile (640
columns, 1280 B: `cached_bytes_per_token`), so a walk that reads every
live byte exactly once reads 10/9 of the floor and shows 90 %.

A step streams the attention weights, the dense layers' MLP, the routers
and shared experts, the output head, and of the routed experts those its
rows touch: each row picks `num_experts_per_tok` of `n_routed_experts`,
and under even routing the expected number of distinct experts is
E * (1 - (1 - k/E) ** rows) (63.9 of 64 at 64 rows). Seeded routers do
not route quite evenly (hidden states of a seeded model are correlated),
so `moe_experts_read_bytes` takes the number the reference's routing
probe measured on the served weights where the run has one.
"""

from __future__ import annotations

LANE = 128


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """Latent and rope key of one token across all layers: the least a
    decode step reads per token of live history."""
    return (hf["num_hidden_layers"]
            * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize)


def cached_bytes_per_token(hf: dict, itemsize: int = 2,
                           kernels: bool = True) -> int:
    """What a token takes in HBM as the program caches it: with the
    kernels on, the rope key fills whole lane tiles."""
    r = hf["qk_rope_head_dim"]
    if kernels:
        r = -(-r // LANE) * LANE
    return hf["num_hidden_layers"] * (hf["kv_lora_rank"] + r) * itemsize


def experts_touched(hf: dict, rows: float) -> float:
    e, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def expert_layers(hf: dict) -> int:
    return hf["num_hidden_layers"] - min(hf["first_k_dense_replace"],
                                         hf["num_hidden_layers"])


def routed_expert_bytes(hf: dict, rows: float, itemsize: int = 2) -> float:
    """Bytes of the distinct routed experts `rows` rows touch in one
    step, over all expert layers: three matrices an expert."""
    return (expert_layers(hf) * experts_touched(hf, rows)
            * 3 * hf["hidden_size"] * hf["moe_intermediate_size"] * itemsize)


def weight_bytes(hf: dict, rows: float, itemsize: int = 2) -> float:
    """Streamed weights of one step (no embedding table: it is gathered
    from), with the routed experts `rows` rows touch."""
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    c, n = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    r, vd = hf["qk_rope_head_dim"], hf["v_head_dim"]
    attn = (h * heads * (n + r) + h * (c + r) + c * heads * (n + vd)
            + heads * vd * h + 2 * h + c)  # wq wkv_a wkv_b wo + 3 norms
    layers = hf["num_hidden_layers"]
    mi = hf["moe_intermediate_size"]
    moe = h * hf["n_routed_experts"] + 3 * h * mi * hf["n_shared_experts"]
    total = (layers * attn
             + (layers - expert_layers(hf)) * 3 * h * hf["intermediate_size"]
             + expert_layers(hf) * moe + h)  # + final norm
    if not hf.get("tie_word_embeddings"):
        total += h * hf["vocab_size"]
    return total * itemsize + routed_expert_bytes(hf, rows, itemsize)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes one decode step reads (`decode_hbm_share`)."""
    item = weights.get("itemsize", 2)
    return (weight_bytes(hf, rows, item)
            + live_tokens * kv_bytes_per_token(hf, item))


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the latent walk of one decode step reads
    (`paged_attn_hbm_share`)."""
    return live_tokens * kv_bytes_per_token(hf, weights.get("itemsize", 2))


def moe_experts_read_bytes(hf, weights, live_tokens, rows, kernels=True,
                           touched=None):
    """Least bytes the grouped matmuls of one decode step read
    (`moe_experts_hbm_share`): the distinct experts the rows touch; the
    activations (rows x k x hidden) are a thousandth of that. `touched`
    is a measured number of distinct experts a layer (the reference's
    routing probe of the served weights); without it, the expectation
    under even routing."""
    nbytes = routed_expert_bytes(hf, rows, weights.get("itemsize", 2))
    if touched is not None:
        nbytes *= min(1.0, touched / experts_touched(hf, rows))
    return nbytes
