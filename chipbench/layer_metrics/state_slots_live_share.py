"""Memory: the share of the recurrent-state pool's slots held at the high
watermark (%): `state_slots_live` over `state_slots` of the engine's
counters. `hbm_live_share` counts weights and pages only; in a
configuration whose per-sequence state is mostly recurrent this is the
rest of what the traffic really holds. None for a program or a
configuration without a state pool."""


def read(ctx):
    m = ctx["engine_now"]
    if not m.get("state_slots"):
        return None
    return 100.0 * m.get("state_slots_live", 0) / m["state_slots"]
