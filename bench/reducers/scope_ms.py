"""Device milliseconds per step of the leaf ops inside one named scope of
the program (``bbmm.backward``, ``bbmm.precond``, ...), per chip."""

from bench import scopes


def reduce(ctx, *, scope: str):
    trace = scopes.of(ctx)
    steps = ctx.get("steps", 0)
    if trace is None or not steps or not trace.events_in(scope):
        return None
    return 1e3 * trace.seconds_in(scope) / steps
