"""Memory: `state_slots_live_share` in the cell `falconh1-longdoc`: the
share of the recurrent-state pool's slots held at the high watermark (%).
The reader is `state_slots_live_share`'s own."""
from chipbench import manifest

read = manifest.layer_reader("state_slots_live_share")
