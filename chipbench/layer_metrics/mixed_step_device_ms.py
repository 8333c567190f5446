"""Model step: device time of one mixed step, the one-token step that
admits a request beside the decode batch (`jit_mixed_fn` in the trace:
seconds over count), ms."""


def read(ctx):
    tr = ctx["trace"]
    dev = tr and tr["modules"].get("jit_mixed_fn")
    if not dev or not dev["count"]:
        return None
    return 1e3 * dev["seconds"] / dev["count"]
