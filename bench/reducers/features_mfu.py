"""The feature net's share of the chip's bf16 peak: its forward and backward
matmul FLOPs per step (``work_ski.features_flops``) times the window's
steps, over the device time of the leaf ops under the ``dkl.features``
scope, over the published bf16 peak."""

from bench import scopes, work_ski


def reduce(ctx, *, scope: str):
    trace = scopes.of(ctx)
    steps = ctx.get("steps", 0)
    if trace is None or not steps or "widths" not in ctx or not trace.events_in(scope):
        return None
    flops = steps * work_ski.features_flops(ctx["n"], ctx["widths"])
    busy = trace.seconds_in(scope) * max(len(trace.device), 1)
    return 100.0 * flops / busy / ctx["peaks"]["flops_bf16"]
