"""Memory: what of the chip's HBM the cell `mimo25-longctx` really holds
live (%): the parameter tree, the full layers' pages at their high
watermark AND the slot-pool entries (rings) held at the slots' high
watermark, ONE generation a slot, over the chip's capacity.

The reader is `hbm_live_with_state_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("hbm_live_with_state_share.cmdaplus")
