"""What one step of MiniCPM-SALA (block-sparse attention layers beside
lightning linear-attention layers, a dense SwiGLU MLP) has to move
through HBM, from shapes alone: the `costs_module` of
`minicpm-sala-9b-1chip` (see `manifest.module_of`), kept with the
benchmark so that no later PR can change the yardstick.

A decode step streams every weight but the embedding table once (it is
gathered from); per live row and sparse layer the K and V of the pages
its two KV heads' lists NAME (at most `topk` a KV head once the row's
context has reached `dense_len`: NOT the row's whole context) and the
row's compressed keys, which the selection scores; per live row and
lightning layer the float32 state, read and written.
"""

from __future__ import annotations

STATE_ITEMSIZE = 4  # the lightning state is float32 (the file's `assumed`)
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def layers(hf: dict, kind: str) -> int:
    return sum(m == kind for m in hf["mixer_types"])


def ssm_state_bytes_per_row(hf: dict) -> int:
    """One sequence's state over all lightning layers, once."""
    d = hf["lightning_head_dim"]
    return layers(hf, LIGHTNING) * hf["lightning_nh"] * d * d * STATE_ITEMSIZE


def ssm_state_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Least bytes the state update of one decode step moves
    (`ssm_scan_hbm_share.sala`): every live row's state of every
    lightning layer, read once and written once."""
    return 2.0 * rows * ssm_state_bytes_per_row(hf)


def kv_bytes_per_token(hf: dict, itemsize: int = 2) -> int:
    """K and V rows of one token over the sparse layers (head_dim 128: no
    lane padding)."""
    return (2 * layers(hf, SPARSE) * hf["num_key_value_heads"]
            * hf["head_dim"] * itemsize)


def walk_bytes(hf: dict, weights: dict, pages_named: float) -> float:
    """K and V bytes of `pages_named` pages, a KV head and a sparse layer
    each (the unit of the program's device counter `walk_pages_named`:
    what the lists handed to the walk kernel name, whole pages, as its
    DMAs move them). `sparse_attn_hbm_share` takes its bytes from here."""
    return (pages_named * hf["sparse_config"]["block_size"] * hf["head_dim"]
            * 2 * weights.get("itemsize", 2))


def walked_tokens(hf: dict, live_tokens: float, rows: float) -> float:
    """Cached tokens whose K and V one decode step's walk reads, as far
    as the rows' MEAN context says (all the accepted readers of
    `paged_attn_hbm_share` and `decode_hbm_share` hand a cost module):
    every token where the mean lies under `dense_len`, else `topk` pages
    a row less the half page its last one lacks on average (a KV head's
    list each; `kv_bytes_per_token` counts both). EXACT where every row
    stands on one side of `dense_len`, which is `sala-longctx`'s window
    (its rows stand at 8.2k-17.9k tokens; the traced run's `walk_rows`
    note gives the shortest); an OVER-count where short and long rows
    decode side by side (12 rows at 600 tokens beside 20 at 13k: 130k
    tokens counted, 88k read), so a share computed from it is no proof
    of anything there. The exact count is the device's
    (`walk_pages_named`, `walk_bytes`)."""
    sp = hf["sparse_config"]
    if rows <= 0:
        return 0.0
    mean = live_tokens / rows
    if mean + 1 < sp["dense_len"]:
        return live_tokens
    return rows * min(mean, (sp["topk"] - 0.5) * sp["block_size"])


def kv_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes the page walk of one decode step reads (`paged_attn_hbm_share`):
    the pages the lists NAME, not the rows' whole context, from the mean
    context (`walked_tokens`: exact in this cell's window, not a floor in
    general)."""
    return walked_tokens(hf, live_tokens, rows) * kv_bytes_per_token(
        hf, weights.get("itemsize", 2))


def compressed_read_bytes(hf, weights, live_tokens, rows):
    """The compressed keys the selection of one decode step scores: one
    every `kernel_stride` tokens of a row's context, a KV head and a
    sparse layer, where the row's context has reached `dense_len`."""
    sp = hf["sparse_config"]
    if rows <= 0 or live_tokens / rows + 1 < sp["dense_len"]:
        return 0.0
    return (live_tokens / sp["kernel_stride"] * layers(hf, SPARSE)
            * hf["num_key_value_heads"] * hf["head_dim"]
            * weights.get("itemsize", 2))


def layer_weight_params(hf: dict) -> dict:
    """Parameters of one layer by part."""
    h, i = hf["hidden_size"], hf["intermediate_size"]
    qd = hf["num_attention_heads"] * hf["head_dim"]
    kvd = hf["num_key_value_heads"] * hf["head_dim"]
    ld = hf["lightning_nh"] * hf["lightning_head_dim"]
    return {
        # q, the gate, o; k, v; the two head norms
        SPARSE: 3 * h * qd + 2 * h * kvd + 2 * hf["head_dim"],
        # q, k, v, the gate, o; the three head norms
        LIGHTNING: 5 * h * ld + 3 * hf["lightning_head_dim"],
        "mlp": 3 * h * i,
        "norms": 2 * h,
    }


def weight_bytes(hf: dict, itemsize: int = 2, with_embed: bool = False):
    """Every streamed weight: the layers, the final norm and the head
    (and the embedding table with `with_embed`: the whole tree)."""
    p = layer_weight_params(hf)
    total = sum(p[kind] + p["mlp"] + p["norms"]
                for kind in hf["mixer_types"]) * itemsize
    h, v = hf["hidden_size"], hf["vocab_size"]
    total += h * itemsize + h * v * itemsize
    return total + (v * h * itemsize if with_embed else 0)


def step_read_bytes(hf, weights, live_tokens, rows, kernels=True):
    """Bytes one decode step moves (`decode_hbm_share`); its page part is
    `kv_read_bytes`', 4 % of the sum in `sala-longctx`."""
    return (weight_bytes(hf, weights.get("itemsize", 2))
            + kv_read_bytes(hf, weights, live_tokens, rows)
            + compressed_read_bytes(hf, weights, live_tokens, rows)
            + ssm_state_bytes(hf, weights, live_tokens, rows))
