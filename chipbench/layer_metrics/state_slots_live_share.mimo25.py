"""Memory: `state_slots_live_share` in the cell `mimo25-longctx`: the share
of the slot pool's slots (a sequence's rings, 5 window layers x 640 rows
of 5,120 B: 16.4 MB) held at the high watermark (%).

The reader is `state_slots_live_share`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("state_slots_live_share")
