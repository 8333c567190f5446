"""What one step of Nemotron-H (Mamba-2 layers beside attention and
sparse experts) has to move through HBM, and what a prompt chunk has to
compute, from shapes alone: the `costs_module` of
`nemotron3-nano-30b-a3b-1chip` (see `manifest.module_of`), kept with the
benchmark so that no later PR can change the yardstick.

The layers are the first `num_hidden_layers` characters of
`hybrid_override_pattern`. A decode step streams every held weight
outside the routed experts once, of the routed experts HELD those its
rows touch (two matrices an expert, stored at `expert_width_stored`
columns where the file says 1856 is padded), the cached K and V of the
attention layers' live tokens, and per live row the recurrent state of
every Mamba-2 layer, read and written: the SSM state in float32 and the
conv window in the model dtype. The state is what no other configuration
of the benchmark has, and here it is the largest single part.
"""

from __future__ import annotations

LANE = 128
STATE_ITEMSIZE = 4  # the SSM state is float32 (the file's `assumed`)


def pattern(hf: dict) -> str:
    return hf["hybrid_override_pattern"][: hf["num_hidden_layers"]]


def layers(hf: dict, kind: str) -> int:
    return pattern(hf).count(kind)


def d_inner(hf: dict) -> int:
    return hf["mamba_num_heads"] * hf["mamba_head_dim"]


def conv_dim(hf: dict) -> int:
    return d_inner(hf) + 2 * hf["n_groups"] * hf["ssm_state_size"]


def expert_width(hf: dict) -> int:
    """Columns of an expert's matrices as stored."""
    return int(hf.get("expert_width_stored") or hf["moe_intermediate_size"])


def ssm_state_bytes_per_row(hf: dict, itemsize: int = 2) -> int:
    """One sequence's state over all M layers, once."""
    ssm = (hf["mamba_num_heads"] * hf["mamba_head_dim"]
           * hf["ssm_state_size"] * STATE_ITEMSIZE)
    conv = (hf["conv_kernel"] - 1) * conv_dim(hf) * itemsize
    return layers(hf, "M") * (ssm + conv)


def ssm_state_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the state update of one decode step moves
    (`ssm_scan_hbm_share`): every live row's SSM state and conv window of
    every M layer, read once and written once."""
    return 2.0 * rows * ssm_state_bytes_per_row(
        hf, weights.get("itemsize", 2))


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows of one token over the attention layers."""
    return (2 * layers(hf, "*") * hf["num_key_value_heads"]
            * hf["head_dim"] * itemsize)


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the page walk of one decode step reads
    (`paged_attn_hbm_share`): the attention layers alone keep pages."""
    return live_tokens * kv_bytes_per_token(hf, weights.get("itemsize", 2))


def experts_touched(hf: dict, rows: float) -> float:
    """Expected number of the experts HELD that `rows` rows touch a
    layer under even routing over all the router's experts."""
    e = hf.get("n_routed_experts_published", hf["n_routed_experts"])
    k = hf["num_experts_per_tok"]
    return hf["n_routed_experts"] * (1.0 - (1.0 - k / e) ** rows)


def routed_expert_bytes(hf: dict, rows: float, itemsize: int = 2,
                        touched=None) -> float:
    if touched is None:
        touched = experts_touched(hf, rows)
    return (layers(hf, "E") * touched
            * 2 * hf["hidden_size"] * expert_width(hf) * itemsize)


def moe_experts_read_bytes(hf, weights, live_tokens, rows, kernels=True,
                           touched=None):
    """Least bytes the grouped matmuls of one decode step read
    (`moe_experts_hbm_share.nano3`): two matrices of every held expert
    the rows touch, as stored. `touched` is the routing probe's count of
    distinct held experts a layer on the served weights."""
    return routed_expert_bytes(hf, rows, weights.get("itemsize", 2), touched)


def dense_weight_bytes(hf: dict, itemsize: int = 2) -> float:
    """Every streamed weight outside the routed experts (no embedding
    table: it is gathered from)."""
    h, di = hf["hidden_size"], d_inner(hf)
    nh = hf["mamba_num_heads"]
    mamba = (h * (2 * di + 2 * hf["n_groups"] * hf["ssm_state_size"] + nh)
             + (hf["conv_kernel"] + 1) * conv_dim(hf) + di * h + di + h)
    qd = hf["num_attention_heads"] * hf["head_dim"]
    kvd = hf["num_key_value_heads"] * hf["head_dim"]
    attn = h * (qd + 2 * kvd) + qd * h + h
    moe = 2 * h * hf["moe_shared_expert_intermediate_size"] + h
    total = (layers(hf, "M") * mamba + layers(hf, "*") * attn
             + layers(hf, "E") * moe + h + h * hf["vocab_size"])
    # float32 leaves: the router and its bias, A_log, D, dt_bias
    router = layers(hf, "E") * (h + 1) * hf.get(
        "n_routed_experts_published", hf["n_routed_experts"])
    return total * itemsize + (router + layers(hf, "M") * 3 * nh) * 4


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes one decode step moves (`decode_hbm_share`)."""
    item = weights.get("itemsize", 2)
    return (dense_weight_bytes(hf, item)
            + routed_expert_bytes(hf, rows, item)
            + live_tokens * kv_bytes_per_token(hf, item)
            + ssm_state_bytes(hf, weights, live_tokens, rows))


def ssm_chunk_flops(hf: dict, tokens: float, chunk: int | None = None):
    """Least floating-point operations the conv and the chunked scan of a
    prompt chunk of `tokens` tokens do over all M layers
    (`ssm_chunk_flops_share`), in the chunked (SSD) form at the file's
    `chunk_size`: a token's C against the B of its chunk's tokens (one
    triangle, so half the square) a group; those scores against the
    chunk's x a head; each token into its chunk's state and the state out
    to each token (head_dim x state, twice); the conv's taps."""
    q = min(chunk or hf["chunk_size"], max(tokens, 1))
    nh, p, n = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"]
    per_token = (
        2 * hf["n_groups"] * n * q / 2  # C B^T, causal half
        + 2 * nh * p * q / 2  # scores x X
        + 2 * 2 * nh * p * n  # into the state, and out of it
        + 2 * hf["conv_kernel"] * conv_dim(hf)
    )
    return layers(hf, "M") * tokens * per_token
