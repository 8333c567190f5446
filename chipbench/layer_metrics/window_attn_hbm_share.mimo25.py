"""Model step: how near its HBM floor the window layers' decode attention
runs in the cell `mimo25-longctx` (%): `min(context, 128)` x 5,120 B a
decode row and window layer (`window_read_bytes` of
`chipbench/costs_mimo_v2.py` on the device's own count, `walk_pages_named`)
over the events of `paged_decode_attention` under scope `attn/window` a
step, over the chip's peak HBM bandwidth. The bytes are the least a window
must read (the walk reads 3 whole pages, 192 rows, for 127), so the share
cannot pass ~66 (127 of 192): ISSUE 56 expected a walk bound by latency
and a low share; the chip reads 54-55 (PERF.md 6, PR 56).

The reader is `window_attn_hbm_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("window_attn_hbm_share.cmdaplus")
