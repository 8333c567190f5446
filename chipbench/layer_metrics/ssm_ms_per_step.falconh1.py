"""Model step: `ssm_ms_per_step` in the cell `falconh1-longdoc`: device
self time of one fused decode step spent in the Mamba-2 mixers (scopes
`attn/ssm/*`) of the six layers, each of which runs attention beside its
mixer, ms. The reader is `ssm_ms_per_step`'s own; a metric that lists its
cells cannot have one appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("ssm_ms_per_step")
