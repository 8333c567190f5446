"""Model step: what a prompt piece's pass over its paged history multiplies
over the MXU's peak in MiMo-V2.5's full layers (%): the (query, key) pairs
under the causal mask of a mixed dispatch, counted on the device
(`chunk_pages_named`, in PAIRS summed over the 2 full layers), the mean a
dispatch and layer, times `pair_flops` of `chipbench/costs_mimo_v2.py`,
over the events of `ring_prefill_attention` under scope `attn/flash` a
WHOLE `jit_mixed_fn` dispatch and layer, over the chip's peak bf16 FLOP/s.
The gathers and the lay-out before the kernel are not in its events; keys
go in 256 wide for 192. Bound: compute.

The reader is `full_chunk_flops_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("full_chunk_flops_share.cmdaplus")
