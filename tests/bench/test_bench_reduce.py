"""The reduction from trace to metrics, on hand-built events and on a
small recorded trace: busy time as the union of intervals, the idle share,
kernel time by event name, idle gaps by host span, and the roofline and
MFU arithmetic against numbers worked out by hand."""

from __future__ import annotations

import math
import os

import pytest
from conftest import ROOT

from bench import work
from bench.reducers import device_idle, kernel_roofline, step_mfu
from bench.trace import Trace, is_container, op_name, op_rows, union

FIXTURE = os.path.join(ROOT, "bench", "fixtures", "trace_events.json.gz")
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def hand_trace(rows: int = 1000) -> Trace:
    # window 0..1000 ns; two chips; each kernel event one whole product
    return Trace(
        device={
            "/device:TPU:0": [["kernel_matmul_a", 100, 200, rows], ["fusion.1", 250, 100, None],
                              ["kernel_matmul_a", 600, 100, rows], ["late", 990, 50, 8]],
            "/device:TPU:1": [["kernel_matmul_a", 0, 500, rows]],
        },
        host=[["bench:window", 0, 1000], ["bench:step", 0, 500], ["bench:sync", 400, 600]],
    )


def test_op_names_and_containers():
    line = "%fused_kernel_matmul_prescaled.10 = f32[8,128]{1,0} custom-call(%fusion.3)"
    assert op_name(line) == "fused_kernel_matmul_prescaled.10"
    assert is_container("while.24") and not is_container("fusion.263")
    t = Trace({"/device:TPU:0": [["while.1", 0, 100, None], ["fusion.1", 10, 50, 4]]},
              [["bench:window", 0, 100]])
    assert dict(t.op_seconds()) == {"fusion.1": 50e-9}
    assert t.busy_s() == pytest.approx(100e-9)


@pytest.mark.parametrize("line, rows", [
    ("%fused_kernel_matmul_prescaled.10 = f32[16599,128]{1,0} custom-call(%fusion.3)", 16599),
    ("%custom-call.2 = f32[4,2048,128]{2,1,0} custom-call(%a, %b)", 4 * 2048),
    ("%tuple.1 = (f32[512,11]{1,0}, f32[4,11]{1,0}) custom-call(%x)", 512),
    ("%reduce.3 = f32[128]{0} reduce(%p, %c)", None),
    ("%copy.1 = pred[] copy(%p)", None),
    ("fusion.263", None),
])
def test_output_rows_from_the_hlo_line(line, rows):
    assert op_rows(line) == rows


def test_union_merges_overlaps_and_clips():
    assert union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert union([(0, 10), (5, 20)], lo=8, hi=15) == [(8, 15)]
    assert union([(5, 5)]) == []


def test_busy_and_idle_share():
    t = hand_trace()
    # chip 0: [100, 350) + [600, 700) + [990, 1000) = 360 ns; chip 1: 500 ns
    assert t.window_s() == pytest.approx(1e-6)
    assert t.busy_s() == pytest.approx((360 + 500) / 2 / 1e9)
    assert t.idle_share() == pytest.approx(1 - 430 / 1000)
    assert device_idle.reduce({"trace": t}) == pytest.approx(57.0)


def test_kernel_time_by_event_name_and_idle_gaps():
    t = hand_trace()
    ev = t.events("kernel_matmul")
    assert len(ev) == 3 and sum(d for _, _, d, _ in ev) == 800
    ops = dict(t.op_seconds())
    assert ops["kernel_matmul_a"] == pytest.approx(800 / 2 / 1e9)
    gaps = dict(t.idle_gaps())
    # chip 0 gaps: [0,100) mid 50 -> step; [350,600) mid 475 -> step (shorter
    # than sync); [700,990) mid 845 -> sync.  chip 1: [500,1000) -> sync.
    assert gaps["bench:step"] == pytest.approx((100 + 250) / 2 / 1e9)
    assert gaps["bench:sync"] == pytest.approx((290 + 500) / 2 / 1e9)


def test_roofline_and_mfu_arithmetic():
    n, d, t = 1000, 9, 11
    flops = n * n * (2 * d + 3 + 9 + 2 * t)  # 52e6
    assert work.kernel_matmul_flops(n, n, d, t) == flops
    assert work.kernel_matmul_bytes(n, n, d, t) == 4 * (2 * n * d + 2 * n * t)
    min_s, bound = work.kernel_matmul_min_s(n, n, d, t, 197e12, 819e9)
    assert bound == "flops" and min_s == pytest.approx(52e6 / 197e12)
    tr = hand_trace(rows=n)
    ctx = {"trace": tr, "peaks": PEAKS, "chips": 2, "steps": 3, "window_s": 2.0,
           "n": n, "d": d, "t": t}
    share = kernel_roofline.reduce(ctx, pattern="kernel_matmul")
    assert share == pytest.approx(100 * 3 * (52e6 / 197e12) / 800e-9)
    # 3 kernel events over 3 steps: one product per step
    step = flops + n * n * (2 * t + 6 + 3 * d)
    assert step_mfu.reduce(ctx, pattern="kernel_matmul") == pytest.approx(
        100 * step * 3 / 2.0 / (2 * 197e12))
    assert kernel_roofline.reduce(dict(ctx, trace=None), pattern="kernel_matmul") is None
    assert kernel_roofline.reduce(ctx, pattern="no_such_kernel") is None
    unshaped = hand_trace(rows=None)  # no output shape: nothing to read
    assert kernel_roofline.reduce(dict(ctx, trace=unshaped), pattern="kernel_matmul") is None
    assert step_mfu.reduce(dict(ctx, trace=unshaped), pattern="kernel_matmul") is None


def test_a_product_in_row_panels_counts_once():
    """One product launched as two row panels, in the same device time as
    one whole launch, reads the same shares: each event is charged its own
    rows, not a whole n x n product.  (At these sizes both panels stay
    FLOP-bound; a thin panel would be charged its re-read inputs.)"""
    n, d, t = 10000, 9, 11
    window = [["bench:window", 0, 1000]]
    whole = Trace({"/device:TPU:0": [["kernel_matmul_a", 0, 600, n]]}, window)
    panels = Trace({"/device:TPU:0": [["kernel_matmul_a", 0, 300, 6000],
                                      ["kernel_matmul_a", 300, 300, 4000]]}, window)
    ctx = {"peaks": PEAKS, "chips": 1, "steps": 1, "window_s": 1e-6, "n": n, "d": d, "t": t}
    for reducer in (kernel_roofline, step_mfu):
        one = reducer.reduce(dict(ctx, trace=whole), pattern="kernel_matmul")
        two = reducer.reduce(dict(ctx, trace=panels), pattern="kernel_matmul")
        assert two == pytest.approx(one)
    flops = n * n * (2 * d + 3 + 9 + 2 * t)
    assert kernel_roofline.reduce(dict(ctx, trace=panels), pattern="kernel_matmul") == (
        pytest.approx(100 * flops / 197e12 / 600e-9))


def test_recorded_trace_reduces():
    """Three whole steps of elevators.train traced on a TPU v5e, trimmed:
    20 products of the Pallas kernel per step (the CG iterations), about
    4.9 ms each, each event covering all n = 16 599 rows."""
    t = Trace.from_json(FIXTURE)
    kern = t.events("kernel_matmul")
    assert len(kern) == 60
    assert {n for n, *_ in kern} == {"fused_kernel_matmul_prescaled.10"}
    n, d, tt = 16599, 18, 11
    assert {r for *_, r in kern} == {n}
    flops = n * n * (2 * d + 3 + 9 + 2 * tt)
    ctx = {"trace": t, "peaks": PEAKS, "n": n, "d": d, "t": tt, "chips": 1, "steps": 3,
           "window_s": t.window_s()}
    share = kernel_roofline.reduce(ctx, pattern="kernel_matmul")
    assert share == pytest.approx(100 * 60 * flops / 197e12 / (sum(k[2] for k in kern) / 1e9))
    assert 0 < share < 100
    step = 20 * flops + n * n * (2 * tt + 6 + 3 * d)
    assert step_mfu.reduce(ctx, pattern="kernel_matmul") == pytest.approx(
        100 * step * 3 / t.window_s() / 197e12)
    lo, hi = t.window_ns()
    assert hi > lo and t.device
    busy = t.busy_s()
    assert 0 < busy <= t.window_s()
    per_plane = [sum(e - s for s, e in union(((s, s + d) for _, s, d, _ in evs), lo, hi))
                 for evs in t.device.values()]
    assert busy == pytest.approx(sum(per_plane) / len(per_plane) / 1e9)
    ops = t.op_seconds()
    assert ops == sorted(ops, key=lambda x: -x[1]) and len(ops) <= 10
    assert sum(s for _, s in t.idle_gaps(top=1000)) == pytest.approx(
        t.window_s() - busy, rel=1e-9, abs=1e-12)
    assert all(math.isfinite(s) for _, s in ops)
