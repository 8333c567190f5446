"""Model step: what a prompt piece's banded pass multiplies over the MXU's
peak in MiMo-V2.5's window layers (%): the (query, key) pairs inside the
band of a mixed dispatch, counted on the device (`chunk_pages_read`, in
PAIRS summed over the 5 window layers), the mean a dispatch and layer,
times `pair_flops` of `chipbench/costs_mimo_v2.py` (64 heads x (2 x 192 + 2
x 128) a pair), over the events of `ring_prefill_attention` under scope
`attn/window` a WHOLE `jit_mixed_fn` dispatch and layer, over the chip's
peak bf16 FLOP/s. The kernel computes whole tiles of 128 x 128 for a band
of 128 and keys 256 wide for 192: it multiplies more than it is credited.
Bound: compute.

The reader is `window_chunk_flops_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("window_chunk_flops_share.cmdaplus")
