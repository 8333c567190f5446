"""Memory: `hbm_live_with_state_share` in the cell `sala-longctx`: the
parameter tree, the pages at their high watermark (K, V and the
compressed keys beside them) and the lightning-state entries held at the
slots' high watermark, both generations, over the chip's capacity (%).
The reader is `hbm_live_with_state_share`'s own."""
from chipbench import manifest

read = manifest.layer_reader("hbm_live_with_state_share")
