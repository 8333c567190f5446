"""Model step: the share of the decode rows' cached tokens that their
windows hold in MiMo-V2.5's window layers (%): the flight records' deltas
of `walk_pages_named` / `walk_pages_live`, in KEYS a window layer (counted
ON THE DEVICE: `min(context, 128)` over `context` a row). 128 of
8.2k-17.9k read ~1; 100 would mean the windows never bound. It is also
what the rings save: pages for these layers would walk the second.

The reader is `window_tokens_attended_share.cmdaplus`'s own: it asks the cell's own cost module and
configuration (a metric that lists its cells cannot have one appended, so
the cell reads it under this name). None where there is nothing to read."""
from chipbench import manifest

read = manifest.layer_reader("window_tokens_attended_share.cmdaplus")
