"""Device milliseconds per CG iteration: every leaf op inside the
``bbmm.mbcg`` scope (kernel product, preconditioner solve, vector updates,
convergence mask) over the window's steps times the iterations per step
that ``cg_iters`` counts."""

from bench import scopes
from bench.reducers import cg_iters


def reduce(ctx, *, scope: str, pattern: str):
    trace = scopes.of(ctx)
    iters = cg_iters.reduce(ctx, scope=scope, pattern=pattern)
    if trace is None or not iters:
        return None
    return 1e3 * trace.seconds_in(scope) / (ctx["steps"] * iters)
