"""Model step: `moe_experts_hbm_share` in the cell `keye-longctx`: how
near its HBM floor the routed experts' grouped matmuls run at this
family's shape (2048 x 768, three matrices an expert, 16 of 128 experts
held; `moe_experts_read_bytes` of `chipbench/costs_keye_vl.py`, the
expected count of held experts 32 rows touch) (%). The reader is
`moe_experts_hbm_share.nano3`'s own (no routing probe of this
configuration exists, so the expectation stands); a metric that lists its
cells cannot have one appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("moe_experts_hbm_share.nano3")
