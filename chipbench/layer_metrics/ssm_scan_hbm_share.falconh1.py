"""Model step: `ssm_scan_hbm_share` in the cell `falconh1-longdoc`: how
near its HBM floor the recurrent state's update runs (%), the roofline
share of the decode kernel blocked over heads (ops/ssm_state.py: one
group of 16 heads x 128 x 256 float32 a grid step, 4.19 MB a row and
layer). `ssm_state_bytes` of `chipbench/costs_falcon_h1.py` (every live
row's SSM state and conv window of the six layers, read once and written
once) over the device self time of scopes `attn/ssm/scan` +
`attn/ssm/conv` per fused decode step over the chip's peak HBM
bandwidth. The bytes are the least the update must move, so the share
cannot pass 100. Bound: memory. The reader is `ssm_scan_hbm_share`'s
own."""
from chipbench import manifest

read = manifest.layer_reader("ssm_scan_hbm_share")
