"""The interpolation's share of its HBM roofline: the least bytes of the
applications in the window (``work_ski.interp_bytes`` each) over the device
time of the leaf ops under the ``ski.interp`` scope, over the peak HBM
bandwidth.  Applications are counted from the trace: one per gather or
scatter op under the scope, named ``gather.N``/``scatter.N`` or fused as
``fusion.N``, whose op_name ends in one of ``ops``
("…/ski.interp/scatter-add").  The ops that only prepare or move an
application's operands carry its op_name too (``pad_clamp_fusion`` clamps
the indices, ``copy`` lays out the result) and are left out."""

from bench import scopes, work_ski

APPLYING = ("fusion", "gather", "scatter")  # op names (before the ".N") that apply W


def applications(trace, scope: str, ops: list) -> int:
    return sum(1 for name, *_ in trace.events_in(scope)
               if name.split(".", 1)[0] in APPLYING
               and trace.scope_of(name).rsplit("/", 1)[-1].startswith(tuple(ops)))


def reduce(ctx, *, scope: str, ops: list):
    trace = scopes.of(ctx)
    apps = applications(trace, scope, ops) if trace is not None else 0
    if not apps or not all(k in ctx for k in ("n", "m", "q", "t")):
        return None
    least_s = apps * work_ski.interp_bytes(ctx["n"], ctx["m"], ctx["q"], ctx["t"]) / (
        ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (trace.seconds_in(scope) * max(len(trace.device), 1))
