"""Model step: `ssm_scan_hbm_share` in the cell `sala-longctx`: how near
its HBM floor the lightning state's update runs (%), the roofline share
of the decode kernel `ssm_decode_step` at one group a head (32 heads x
128 x 128 float32, 2.10 MB a row and layer, the whole row a grid step).
`ssm_state_bytes` of `chipbench/costs_minicpm_sala.py` (every live row's
state of the twelve lightning layers, read once and written once) over
the device self time of scope `attn/ssm/scan` (the rotary embedding of q
and k and the kernel) per fused decode step over the chip's peak HBM
bandwidth. The bytes are the least the update must move, so the share
cannot pass 100. Bound: memory. The reader is `ssm_scan_hbm_share`'s
own."""
from chipbench import manifest

read = manifest.layer_reader("ssm_scan_hbm_share")
