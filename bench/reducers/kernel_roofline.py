"""A kernel's share of its roofline: the least time the chip could take for
the calls in the window over the device time of the kernel's trace events.
Each event is charged the work of its own output rows against all n
columns (required operations over peak FLOP/s, or required bytes over peak
bandwidth, whichever is longer), so a product launched in row panels
counts once, not once per panel."""

from bench import work


def reduce(ctx, *, pattern: str):
    trace = ctx.get("trace")
    events = trace.events(pattern) if trace is not None else []
    if not events or any(rows is None for *_, rows in events):
        return None
    peaks = ctx["peaks"]
    n, d, t = ctx["n"], ctx["d"], ctx["t"]
    min_s = sum(work.kernel_matmul_min_s(rows, n, d, t, peaks["flops_bf16"],
                                         peaks["hbm_bytes_per_s"])[0]
                for *_, rows in events)
    busy = sum(dur for _, _, dur, _ in events) / 1e9
    return 100.0 * min_s / busy
