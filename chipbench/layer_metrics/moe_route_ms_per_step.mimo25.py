"""Model step: device self time of a step under scope `mlp/moe/route` in
the cell `mimo25-longctx` (the float32 router over 256 experts at the
highest precision, the sigmoid, the correction bias, the 8 highest, the
share's assignments ordered by counting and the weighted sum back, 6
expert layers), a MIXED step, ms.

The reader is `moe_route_ms_per_step.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("moe_route_ms_per_step.cmdaplus")
