"""Model step: `ssm_ms_per_step` in the cell `sala-longctx`: device self
time of one fused decode step spent in the twelve lightning layers'
mixers (scopes `attn/ssm/{in_proj,scan,out}`), ms. The reader is
`ssm_ms_per_step`'s own; a metric that lists its cells cannot have one
appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("ssm_ms_per_step")
