"""A training step's share of the chip's peak: the step's required FLOPs
(``work.train_step_flops``) times steps per second, over chips times the
bf16 peak.  The kernel products per step are counted from the trace as the
rows the kernel's events produced over n, so row panels add up to one
product."""

from bench import work


def reduce(ctx, *, pattern: str):
    trace = ctx.get("trace")
    steps = ctx.get("steps", 0)
    if trace is None or not steps:
        return None
    rows = [r for *_, r in trace.events(pattern)]
    if not rows or None in rows:
        return None
    products = sum(rows) / ctx["n"]
    flops = work.train_step_flops(ctx["n"], ctx["d"], ctx["t"], products / steps)
    rate = flops * steps / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["flops_bf16"])
