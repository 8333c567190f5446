"""Memory: `state_slots_live_share` in the cell `cmdaplus-longctx`: the
share of the slot pool's slots (a sequence's rings, 3 sliding layers x
4,608 rows of K and V: 56.6 MB) held at the high watermark (%). The reader
is `state_slots_live_share`'s own; a metric that lists its cells cannot
have one appended, so the cell reads it under this name."""
from chipbench import manifest

read = manifest.layer_reader("state_slots_live_share")
