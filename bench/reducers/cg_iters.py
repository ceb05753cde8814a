"""CG iterations per step, counted on the device: the output rows of the
kernel-matrix product's events inside the ``bbmm.mbcg`` scope over n and
over the window's steps (one product of all n rows per iteration, so a
product launched in row panels counts once)."""

from bench import scopes


def reduce(ctx, *, scope: str, pattern: str):
    trace = scopes.of(ctx)
    steps = ctx.get("steps", 0)
    rows = [r for *_, r in trace.events_in(scope, pattern)] if trace and steps else []
    if not rows or None in rows:
        return None
    return sum(rows) / ctx["n"] / steps
