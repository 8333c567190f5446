"""Memory: `state_slots_live_share` in the cell `dots3-longctx`: the share
of the slot pool's slots (a sequence's rings, 6 sliding layers x 1,088 rows:
14.2 MB) held at the high watermark (%). The reader is
`state_slots_live_share`'s own; a metric that lists its cells cannot have
one appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("state_slots_live_share")
