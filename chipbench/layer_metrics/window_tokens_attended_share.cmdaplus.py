"""Model step: the share of the decode rows' cached tokens that their
windows hold in Command A+'s sliding layers (%), over the window: the
flight records' deltas of the engine's counters `walk_pages_named` /
`walk_pages_live`, in KEYS a sliding layer for this family (counted ON THE
DEVICE: `min(context, 4,096)` over `context` a row). 100 means no window
binds; 4,096 of 8.2k-17.9k read 23-50. It is also what the rings save:
pages for these layers would walk the second, the rings walk the first.
None for a program without the counters or a configuration without a
window."""
from chipbench import cmdaplusscopes


def read(ctx):
    if cmdaplusscopes.layers(ctx) is None:
        return None
    named = sum(r.get("walk_pages_named", 0) for r in ctx["flight"])
    live = sum(r.get("walk_pages_live", 0) for r in ctx["flight"])
    return 100.0 * named / live if live else None
