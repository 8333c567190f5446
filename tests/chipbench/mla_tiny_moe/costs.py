"""What one decode step of an MLA + MoE decoder has to read from HBM,
from shapes alone: the `costs_module` of the test configuration, and a
pattern for a real one. The cache holds one latent and one rope key a
token and layer, whatever the heads; a step streams the attention
weights, the dense layers' MLP, the routers and shared experts, and of
the routed experts those its rows touch (each row picks
`num_experts_per_tok` of `n_routed_experts`; with even routing the
expected number of distinct experts is E * (1 - (1 - k/E) ** rows))."""

from __future__ import annotations


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    return (hf["num_hidden_layers"]
            * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize)


def experts_touched(hf: dict, rows: float) -> float:
    e, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def weight_bytes(hf: dict, rows: float, itemsize: int = 2) -> float:
    """Streamed weights of one step (no embedding table: it is gathered
    from), with the routed experts `rows` rows touch."""
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    c, n = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    r, vd = hf["qk_rope_head_dim"], hf["v_head_dim"]
    attn = (h * heads * (n + r) + h * (c + r) + c * heads * (n + vd)
            + heads * vd * h + 2 * h + c)  # wq wkv_a wkv_b wo + 3 norms
    layers = hf["num_hidden_layers"]
    n_dense = min(hf["first_k_dense_replace"], layers)
    mi = hf["moe_intermediate_size"]
    moe = (h * hf["n_routed_experts"] + 3 * h * mi * hf["n_shared_experts"]
           + 3 * h * mi * experts_touched(hf, rows))
    total = (layers * attn + n_dense * 3 * h * hf["intermediate_size"]
             + (layers - n_dense) * moe + h)  # + final norm
    if not hf.get("tie_word_embeddings"):
        total += h * hf["vocab_size"]
    return total * itemsize


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    item = weights.get("itemsize", 2)
    return (weight_bytes(hf, rows, item)
            + live_tokens * kv_bytes_per_token(hf, item))


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    return live_tokens * kv_bytes_per_token(hf, weights.get("itemsize", 2))
