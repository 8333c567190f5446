"""What one step of Falcon-H1 (a Mamba-2 mixer and rotary GQA attention
side by side in every layer, a dense SwiGLU MLP) has to move through
HBM, and what a prompt chunk's scan has to compute, from shapes alone:
the `costs_module` of `falcon-h1-34b-1chip` (see `manifest.module_of`),
kept with the benchmark so that no later PR can change the yardstick.

A decode step streams every weight but the embedding table once (it is
gathered from), the cached K and V of every live token of EVERY layer,
and per live row the recurrent state of EVERY layer, read and written:
the SSM state in float32 and the conv window in the model dtype. Both
per-sequence caches belong to every layer here; at ~4,100 tokens a row's
pages and its state (read + written) are the same bytes.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4  # the SSM state is float32 (the file's `assumed`)


def layers(hf: dict) -> int:
    return hf["num_hidden_layers"]


def conv_dim(hf: dict) -> int:
    return (hf["mamba_d_ssm"]
            + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"])


def in_proj_dim(hf: dict) -> int:
    return hf["mamba_d_ssm"] + conv_dim(hf) + hf["mamba_n_heads"]


def ssm_state_bytes_per_row(hf: dict, itemsize: int = 2) -> int:
    """One sequence's state over all layers, once."""
    ssm = (hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]
           * STATE_ITEMSIZE)
    conv = (hf["mamba_d_conv"] - 1) * conv_dim(hf) * itemsize
    return layers(hf) * (ssm + conv)


def ssm_state_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the state update of one decode step moves
    (`ssm_scan_hbm_share.falconh1`): every live row's SSM state and conv
    window of every layer, read once and written once."""
    return 2.0 * rows * ssm_state_bytes_per_row(
        hf, weights.get("itemsize", 2))


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows of one token over all layers (head_dim 128: no lane
    padding)."""
    return (2 * layers(hf) * hf["num_key_value_heads"] * hf["head_dim"]
            * itemsize)


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the page walk of one decode step reads
    (`paged_attn_hbm_share`)."""
    return live_tokens * kv_bytes_per_token(hf, weights.get("itemsize", 2))


def layer_weight_params(hf: dict) -> dict:
    """Parameters of one layer by part."""
    h, i = hf["hidden_size"], hf["intermediate_size"]
    di, nh = hf["mamba_d_ssm"], hf["mamba_n_heads"]
    qd = hf["num_attention_heads"] * hf["head_dim"]
    kvd = hf["num_key_value_heads"] * hf["head_dim"]
    return {
        "attention": h * (qd + 2 * kvd) + qd * h,
        # in_proj, conv taps and bias, gated norm, out_proj (A_log, D and
        # dt_bias are float32: `mamba_f32`)
        "mamba": (h * in_proj_dim(hf) + (hf["mamba_d_conv"] + 1)
                  * conv_dim(hf) + di + di * h),
        "mamba_f32": 3 * nh,
        "mlp": 3 * h * i,
        "norms": 2 * h,
    }


def weight_bytes(hf: dict, itemsize: int = 2, with_embed: bool = False):
    """Every streamed weight: the layers, the final norm and the head
    (and the embedding table with `with_embed`: the whole tree)."""
    p = layer_weight_params(hf)
    per_layer = ((p["attention"] + p["mamba"] + p["mlp"] + p["norms"])
                 * itemsize + p["mamba_f32"] * 4)
    h, v = hf["hidden_size"], hf["vocab_size"]
    total = layers(hf) * per_layer + h * itemsize + h * v * itemsize
    return total + (v * h * itemsize if with_embed else 0)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes one decode step moves (`decode_hbm_share`)."""
    item = weights.get("itemsize", 2)
    return (weight_bytes(hf, item)
            + live_tokens * kv_bytes_per_token(hf, item)
            + ssm_state_bytes(hf, weights, live_tokens, rows))


def ssm_chunk_flops(hf: dict, tokens: float, chunk: int | None = None):
    """Least floating-point operations the conv and the chunked scan of a
    prompt chunk of `tokens` tokens do over all layers
    (`ssm_chunk_flops_share.falconh1`), in the chunked (SSD) form at the
    file's `mamba_chunk_size`: a token's C against the B of its chunk's
    tokens (one triangle, so half the square) a group; those scores
    against the chunk's x a head; each token into its chunk's state and
    the state out to each token (head size x state, twice); the conv's
    taps."""
    q = min(chunk or hf["mamba_chunk_size"], max(tokens, 1))
    nh, p, n = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    per_token = (
        2 * hf["mamba_n_groups"] * n * q / 2  # C B^T, causal half
        + 2 * nh * p * q / 2  # scores x X
        + 2 * 2 * nh * p * n  # into the state, and out of it
        + 2 * hf["mamba_d_conv"] * conv_dim(hf)
    )
    return layers(hf) * tokens * per_token
