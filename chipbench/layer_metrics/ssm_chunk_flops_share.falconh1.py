"""Model step: `ssm_chunk_flops_share` in the cell `falconh1-longdoc`: how
near the chip's peak the conv and the chunked (SSD) scan of a prompt
chunk run (%), at state 256 and `mamba_chunk_size` 128 in plain XLA:
`ssm_chunk_flops` of `chipbench/costs_falcon_h1.py` for the mixed steps'
real prompt tokens over the device self time of scopes `attn/ssm/scan` +
`attn/ssm/conv` inside `jit_mixed_fn` over the chip's peak bf16 rate.
The operations are the least the chunked form does, so the share cannot
pass 100. Bound: compute. The reader is `ssm_chunk_flops_share`'s own."""
from chipbench import manifest

read = manifest.layer_reader("ssm_chunk_flops_share")
