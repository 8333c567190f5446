"""Model step: how near its HBM floor the FULL layers' decode attention
runs in the cell `mimo25-longctx` (%): K and V of every live token (2,560 B
a token and full layer: `kv_read_bytes` of `chipbench/costs_mimo_v2.py` on
the tokens the decode rows hold, counted on the device: `walk_pages_live`)
over the events of `paged_decode_attention` under scope `attn/paged` a
step (the walk of pages in lane parts, 16 query heads a KV head), over the
chip's peak HBM bandwidth. Bound: memory.

The reader is `full_attn_hbm_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("full_attn_hbm_share.cmdaplus")
