"""Device: share of the traced slice, first to last device event, in
which no operation ran on the chip: 1 - union of device-op intervals /
slice (%). Both sides are on the trace's clock."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
