"""Model step: device time of the decode rows' window attention a step in
MiMo-V2.5's 5 window layers: the kernel `paged_decode_attention` walking
the 3 of a slot's 10 ring pages a window of 128 can reach under a bit a
ring row, 64 query heads over 8 KV heads as 4 pair-heads of 384 | 256
(models/mimo_v2.py `window_attend`; the sink and the own token are merged
outside it), its own events under scope `attn/window` in the WHOLE
dispatches of the trace, ms a step. Bound: memory, at the grain of a page (0.66 MB a
row and layer, 21 MB a layer at 32 rows: 26 us at the chip's peak for the
rows in reach, 36 for the three whole pages read).

The reader is `window_attn_ms_per_step.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("window_attn_ms_per_step.cmdaplus")
