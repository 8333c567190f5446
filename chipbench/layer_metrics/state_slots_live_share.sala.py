"""Memory: `state_slots_live_share` in the cell `sala-longctx`: the share
of the lightning-state pool's slots held at the high watermark (%). The
reader is `state_slots_live_share`'s own."""
from chipbench import manifest

read = manifest.layer_reader("state_slots_live_share")
