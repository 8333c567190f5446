"""What one decode step has to read from HBM, from shapes alone. Kept
with the benchmark so that no later PR can change the yardstick; checked
against the program's own `kv_page_bytes` and parameter tree in
chipbench's tests.

This is the default cost module: a dense decoder (seven matrices a
layer, K and V rows per kv head). A configuration of another
architecture names its own (`costs_module` in its file, see
`manifest.module_of`), which answers the two questions the per-layer
readers ask, `step_read_bytes` and `kv_read_bytes`, from what the flight
record knows, or returns None where it cannot."""

from __future__ import annotations

import sys

LANE = 128  # Mosaic DMA tile: cached head rows are padded to 128 lanes


def head_dim(hf: dict) -> int:
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


def padded_head_dim(hf: dict, kernels: bool = True) -> int:
    d = head_dim(hf)
    return -(-d // LANE) * LANE if kernels else d


def kv_bytes_per_token(hf: dict, itemsize: int = 2,
                       kernels: bool = True) -> int:
    """K and V rows of one token across all layers, as the paged cache
    lays them out (head dim padded to the lane tile with the kernels
    on): the bytes a decode step reads per token of live history."""
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"]
            * padded_head_dim(hf, kernels) * itemsize)


def weight_bytes(hf: dict, dense_itemsize: int = 2, itemsize: int = 2,
                 with_embed: bool = False) -> int:
    """Bytes of the weights one decode step streams: the seven dense
    matrices of every layer (int8: 1 byte each plus f32 per-output-
    channel scales), norms, biases and the output head. The embedding
    table is only gathered from (rows x hidden), so it is left out
    unless `with_embed` (then this is the whole parameter tree)."""
    h, i = hf["hidden_size"], hf["intermediate_size"]
    d = head_dim(hf)
    qd, kvd = hf["num_attention_heads"] * d, hf["num_key_value_heads"] * d
    v, layers = hf["vocab_size"], hf["num_hidden_layers"]
    dense = h * qd + 2 * h * kvd + qd * h + 3 * h * i
    per_layer = dense * dense_itemsize + 2 * h * itemsize
    if dense_itemsize == 1:
        per_layer += (qd + 2 * kvd + h + 2 * i + h) * 4  # scales
    if hf.get("attention_bias"):
        per_layer += (qd + 2 * kvd) * itemsize
    total = layers * per_layer + h * itemsize  # + final norm
    if not hf.get("tie_word_embeddings"):
        total += h * v * itemsize  # output head
    if with_embed or hf.get("tie_word_embeddings"):
        total += v * h * itemsize
    return total


def decode_step_bytes(hf: dict, live_tokens: float, dense_itemsize: int = 2,
                      itemsize: int = 2, kernels: bool = True) -> float:
    """Least bytes one decode step reads: every streamed weight once,
    and the cached K/V of every live token once."""
    return (weight_bytes(hf, dense_itemsize, itemsize)
            + live_tokens * kv_bytes_per_token(hf, itemsize, kernels))


# -- the seam the per-layer readers call (ctx["costs"]) ----------------------
# `weights` is the configuration file's `weights` block (itemsizes),
# `live_tokens` the cached tokens of the decode rows, `rows` how many
# rows decode (a dense model reads every weight whatever the rows; a
# sparse one reads the experts its rows touch).


def asked(ctx: dict, name: str):
    """The function `name` of the cell's cost module (`ctx["costs"]`,
    this module where a reader's ctx names none), or None."""
    return getattr(ctx.get("costs") or sys.modules[__name__], name, None)


def step_read_bytes(hf: dict, weights: dict, live_tokens: float,
                    rows: float, kernels: bool = True) -> float | None:
    """Least bytes one decode step reads (`decode_hbm_share`)."""
    return decode_step_bytes(hf, live_tokens, weights.get("dense_itemsize", 2),
                             weights.get("itemsize", 2), kernels)


def kv_read_bytes(hf: dict, weights: dict, live_tokens: float,
                  rows: float, kernels: bool = True) -> float | None:
    """Least bytes the page walk of one decode step reads
    (`paged_attn_hbm_share`)."""
    return live_tokens * kv_bytes_per_token(
        hf, weights.get("itemsize", 2), kernels)
