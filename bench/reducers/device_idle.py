"""Share of the traced window in which no op ran on the device: one minus
the union of the op intervals over the window, averaged over chips."""


def reduce(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.device:
        return None
    return 100.0 * trace.idle_share()
