"""Model step: how near its HBM floor the routed experts' grouped matmuls
run at MiMo-V2.5's shape (4096 x 2048, three matrices an expert, 16 of 256
experts held, 6 expert layers) (%): the bytes of the distinct HELD experts
a MIXED step's rows chose (`moe_experts_read_bytes` of
`chipbench/costs_mimo_v2.py` on the program's own count,
`moe_experts_touched`) over the device self time of scope
`mlp/moe/experts` a mixed step, over the chip's peak HBM bandwidth. Bound:
memory.

The reader is `moe_experts_hbm_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("moe_experts_hbm_share.cmdaplus")
