"""Model step: `moe_route_ms_per_step` in the cell `keye-longctx`: device
self time of one fused decode step under scope `mlp/moe/route` (the
float32 router over 128 experts, the top 8, the sort of 256 assignments
by expert and the weighted un-sort, 8 layers), ms. The reader is
`moe_route_ms_per_step`'s own; a metric that lists its cells cannot have
one appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("moe_route_ms_per_step")
