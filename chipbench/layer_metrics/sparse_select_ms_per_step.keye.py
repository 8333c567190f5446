"""Model step: `sparse_select_ms_per_step` in the cell `keye-longctx`:
device self time of one fused decode step under scope `attn/select` (the
exact top 2,048 of each row's index scores: 32 counting passes over the
scores' bits, no sort), ms. The reader is `sparse_select_ms_per_step`'s
own; a metric that lists its cells cannot have one appended, so the cell
reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("sparse_select_ms_per_step")
