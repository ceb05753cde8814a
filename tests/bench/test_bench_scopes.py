"""The solver-layer metrics read from the program's named scopes: which ops
a scope holds, the four reducers on hand-built events and on a trimmed
chip trace with its scope map, and None wherever a trace carries no map
(the trace of a program that names no scope)."""

from __future__ import annotations

import os
import re

import pytest
from conftest import ROOT

from bench import scopes
from bench.reducers import cg_iters, iter_ms, scope_ms
from bench.scopes import ScopedTrace, in_scope
from bench.spec import Spec
from bench.trace import Trace

FIXTURE = os.path.join(ROOT, "bench", "fixtures", "trace_scopes.json.gz")
OLD_FIXTURE = os.path.join(ROOT, "bench", "fixtures", "trace_events.json.gz")
MBCG = {"scope": "bbmm.mbcg", "pattern": "kernel_matmul"}
N, D, T = 16599, 18, 11

PATHS = {
    "kernel_matmul.10": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/closed_call/"
                        "jit(fused_kernel_matmul_prescaled)/kernel_matmul/pallas_call",
    "fusion.1": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/closed_call/mul",
    "while.2": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while",
    "fusion.2": "jit(step)/transpose(jvp(bbmm.backward))/transpose(transpose(jvp(bbmm.backward)))"
                "/jvp(transpose(jvp()))/while/body/closed_call/checkpoint/mul",
    "fusion.3": "jit(step)/jvp(bbmm.precond)/pivoted_cholesky/while/body/sub",
    "fusion.4": "jit(step)/jvp(bbmm.logdet)/eigh",
    "fusion.5": "jit(step)/optim.adam/sqrt",
    "fusion.6": "jit(step)/jvp()/exp",
}


def step_events(t0: int, rows: int = N) -> list:
    """One step on one chip: 2 kernel products of ``rows`` rows (100 ns
    each), 10 ns of CG vector work, a loop op holding them, 30 ns of
    backward, 5 of preconditioner, 3 of log-det, 2 of Adam, 1 unscoped."""
    return [["fusion.3", t0, 5, None], ["while.2", t0 + 5, 220, None],
            ["kernel_matmul.10", t0 + 5, 100, rows], ["fusion.1", t0 + 105, 10, None],
            ["kernel_matmul.10", t0 + 115, 100, rows], ["fusion.4", t0 + 225, 3, None],
            ["fusion.2", t0 + 228, 30, None], ["fusion.6", t0 + 258, 1, None],
            ["fusion.5", t0 + 259, 2, None]]


def hand_trace(steps: int = 3, paths=PATHS) -> ScopedTrace:
    device = [ev for k in range(steps) for ev in step_events(1000 * k)]
    host = [["bench:window", 0, 1000 * steps]]
    for k in range(steps):
        host += [["bench:dispatch", 1000 * k, 50], ["bench:sync", 1000 * k + 50, 900]]
    return ScopedTrace({"/device:TPU:0": device}, host, dict(paths))


def ctx_of(trace, steps: int = 3, n: int = N) -> dict:
    return {"trace": trace, "steps": steps, "n": n, "d": D, "t": T, "chips": 1}


@pytest.mark.parametrize("path, scope, inside", [
    (PATHS["kernel_matmul.10"], "bbmm.mbcg", True),
    (PATHS["fusion.2"], "bbmm.backward", True),
    (PATHS["fusion.3"], "bbmm.precond", True),
    (PATHS["fusion.5"], "optim.adam", True),
    (PATHS["fusion.6"], "bbmm.mbcg", False),
    ("jit(step)/bbmm.mbcgx/mul", "bbmm.mbcg", False),
    ("jit(step)/jvp(jit(_mbcg_jit))", "bbmm.mbcg", False),
    (None, "bbmm.mbcg", False),
])
def test_a_scope_is_a_path_component(path, scope, inside):
    assert in_scope(path, scope) is inside


def _key(number: int, wire: int) -> bytes:
    return _varint((number << 3) | wire)


def _varint(x: int) -> bytes:
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def _msg(*fields) -> bytes:
    """Protobuf wire bytes of (number, int | str | bytes) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _key(number, 0) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _key(number, 2) + _varint(len(data)) + data
    return out


def _plane(name: str, ops: dict, path_stat: str = "tf_op") -> bytes:
    """An XPlane with a stat named ``path_stat`` (id 26) and one event
    metadata entry per (HLO text, op_name path)."""
    fields = [(1, 7), (2, name), (5, _msg((1, 26), (2, _msg((1, 26), (2, path_stat)))))]
    for i, (text, path) in enumerate(ops.items(), start=1):
        stat = _msg((1, 26), (5, path))
        fields.append((4, _msg((1, i), (2, _msg((1, i), (2, text), (5, stat))))))
    return _msg(*fields)


def test_op_paths_from_the_event_metadata_of_device_planes():
    """The path of an op is the tf_op stat of its event metadata, on device
    planes only; a name two programs share with different paths is left out."""
    device = _plane("/device:TPU:0", {
        "%kernel_matmul.10 = f32[16599,128]{1,0} custom-call(%a)": PATHS["kernel_matmul.10"],
        "%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop": PATHS["fusion.2"],
        "%pad_add_fusion = u32[2]{0} fusion(%k)": "jit(step)/jvp(bbmm.precond)/concatenate:",
        "%pad_add_fusion = u32[2]{0} fusion(%key)": "jit(_threefry_split)/concatenate:",
    })
    host = _plane("/host:CPU", {"%fusion.9 = f32[8]{0} fusion(%p)": "jit(step)/bbmm.mbcg/x"})
    other_stat = _plane("/device:TPU:1", {"%fusion.3 = f32[8]{0} fusion(%p)": "x"}, "hlo_op")
    xspace = _msg((1, device), (1, host), (1, other_stat), (4, "hostname"))
    assert scopes.op_paths(xspace) == {"kernel_matmul.10": PATHS["kernel_matmul.10"],
                                       "fusion.2": PATHS["fusion.2"]}


def test_reducers_on_hand_built_steps():
    t = hand_trace()
    ctx = ctx_of(t)
    assert [ev[0] for ev in t.events_in("bbmm.mbcg")] == [
        "kernel_matmul.10", "fusion.1", "kernel_matmul.10"] * 3  # no loop op
    assert cg_iters.reduce(ctx, **MBCG) == pytest.approx(2.0)
    # 210 ns of leaf ops under bbmm.mbcg per step, 2 iterations
    assert iter_ms.reduce(ctx, **MBCG) == pytest.approx(210e-6 / 2)
    assert scope_ms.reduce(ctx, scope="bbmm.backward") == pytest.approx(30e-6)
    assert scope_ms.reduce(ctx, scope="bbmm.precond") == pytest.approx(5e-6)
    assert scope_ms.reduce(ctx, scope="bbmm.logdet") == pytest.approx(3e-6)
    assert scope_ms.reduce(ctx, scope="optim.adam") == pytest.approx(2e-6)


def test_a_product_in_row_panels_counts_one_iteration():
    """Each product launched as two row panels (10 000 and 6 599 rows) in
    the same device time reads the same iterations and the same ms."""
    whole = hand_trace()
    panels = hand_trace()
    for plane, evs in panels.device.items():
        split = []
        for name, s, d, rows in evs:
            if name == "kernel_matmul.10":
                split += [[name, s, d // 2, 10000], [name, s + d // 2, d // 2, rows - 10000]]
            else:
                split.append([name, s, d, rows])
        panels.device[plane] = split
    for reduce in (cg_iters.reduce, iter_ms.reduce):
        assert reduce(ctx_of(panels), **MBCG) == pytest.approx(reduce(ctx_of(whole), **MBCG))
    assert cg_iters.reduce(ctx_of(panels), **MBCG) == pytest.approx(2.0)


def test_none_without_a_scope_map_or_a_step():
    bare = hand_trace(paths={})
    for reduce, args in ((cg_iters.reduce, MBCG), (iter_ms.reduce, MBCG),
                         (scope_ms.reduce, {"scope": "bbmm.backward"})):
        assert reduce(ctx_of(bare), **args) is None
        assert reduce(ctx_of(None), **args) is None
        assert reduce(ctx_of(hand_trace(), steps=0), **args) is None
    assert scope_ms.reduce(ctx_of(hand_trace()), scope="bbmm.nothing") is None
    unshaped = hand_trace()
    for evs in unshaped.device.values():
        for ev in evs:
            ev[3] = None
    assert cg_iters.reduce(ctx_of(unshaped), **MBCG) is None
    assert iter_ms.reduce(ctx_of(unshaped), **MBCG) is None


def test_trimmed_keeps_whole_steps_and_their_scopes(tmp_path):
    t = hand_trace(steps=4)
    cut = t.trimmed(1, 2)
    assert cut.window_ns() == (1000, 2950)
    assert [n for n, *_ in cut.host] == ["bench:window"] + ["bench:dispatch", "bench:sync"] * 2
    assert all(1000 <= s < 2950 for evs in cut.device.values() for _, s, _, _ in evs)
    assert cut.scopes == PATHS
    path = str(tmp_path / "cut.json.gz")
    cut.to_json(path)
    back = ScopedTrace.from_json(path)
    assert back.scopes == cut.scopes and back.device == cut.device
    assert cg_iters.reduce(ctx_of(back, steps=2), **MBCG) == pytest.approx(2.0)


def test_the_run_is_matched_to_its_raw_trace_by_window(tmp_path, monkeypatch):
    """A run's plain Trace gets the scope map of the raw trace whose
    bench:window is its own; a raw trace of another run is not read."""
    t = hand_trace()
    plain = Trace(t.device, t.host)
    for name in ("a.trace", "b.trace"):
        (tmp_path / name).mkdir()
    raw = {str(tmp_path / "a.trace"): ({"kernel_matmul.10": "x/bbmm.mbcg/y"}, (7, 9)),
           str(tmp_path / "b.trace"): (dict(PATHS), t.window_ns())}
    def read_scopes(path):
        if path not in raw:
            raise FileNotFoundError(path)
        return raw[path]

    monkeypatch.setattr(scopes, "RAW_TRACES", str(tmp_path / "*.trace"))
    monkeypatch.setattr(scopes, "read_scopes", read_scopes)
    found = scopes.of(ctx_of(plain))
    assert found.scopes == PATHS and found.device is plain.device
    assert cg_iters.reduce(ctx_of(plain), **MBCG) == pytest.approx(2.0)
    del raw[str(tmp_path / "b.trace")]
    other = Trace(t.device, t.host)
    assert scopes.of(ctx_of(other)).scopes == {}
    assert cg_iters.reduce(ctx_of(other), **MBCG) is None


def test_none_on_the_fixture_recorded_before_the_scopes():
    old = ScopedTrace.from_json(OLD_FIXTURE)
    assert old.scopes == {} and old.events("kernel_matmul")
    ctx = ctx_of(old)
    assert cg_iters.reduce(ctx, **MBCG) is None
    assert iter_ms.reduce(ctx, **MBCG) is None
    assert scope_ms.reduce(ctx, scope="bbmm.backward") is None
    assert scope_ms.reduce(ctx, scope="bbmm.precond") is None


def test_reducers_on_the_recorded_trace():
    """Three whole steps of elevators.train traced on a TPU v5e with the
    scopes in place, trimmed with their scope map.  The values are worked
    out here from the events and paths by substring, apart from the
    reducers' component match: 20 products of all n rows per step under
    bbmm.mbcg; about 4.9 ms per iteration, 12 ms of backward and 0.09 ms of
    preconditioner per step; the four account for over 99% of the busy
    time."""
    t = ScopedTrace.from_json(FIXTURE)
    ctx = ctx_of(t, steps=3)

    def leaf_ns(scope):
        inside = re.compile(rf"[/(]{re.escape(scope)}[)/]")
        return sum(d for n, _, d, _ in t.events()
                   if not n.startswith("while") and inside.search(t.scopes.get(n) or ""))

    kern = t.events("kernel_matmul")
    assert len(kern) == 60 and {r for *_, r in kern} == {N}
    assert all("/bbmm.mbcg/" in t.scopes[n] for n, *_ in kern)
    iters = cg_iters.reduce(ctx, **MBCG)
    assert iters == 20.0
    per_iter = iter_ms.reduce(ctx, **MBCG)
    assert per_iter == pytest.approx(leaf_ns("bbmm.mbcg") / 1e6 / (3 * 20))
    assert per_iter == pytest.approx(4.905, rel=1e-3)
    backward = scope_ms.reduce(ctx, scope="bbmm.backward")
    assert backward == pytest.approx(leaf_ns("bbmm.backward") / 1e6 / 3)
    assert backward == pytest.approx(11.966, rel=1e-3)
    precond = scope_ms.reduce(ctx, scope="bbmm.precond")
    assert precond == pytest.approx(leaf_ns("bbmm.precond") / 1e6 / 3)
    assert precond == pytest.approx(0.0901, rel=1e-2)
    busy_ms_per_step = 1e3 * t.busy_s() / 3
    assert (iters * per_iter + backward + precond) / busy_ms_per_step > 0.99


def test_spec_has_no_problems_with_the_solver_metrics():
    spec = Spec(ROOT)
    assert spec.problems() == []
    solver = [m for m in spec.per_layer("elevators.train")
              if m["layer"] == "engine / solver (core/inference.py, core/mbcg.py)"]
    assert sorted(m["name"] for m in solver) == [
        "backward.ms.train", "cg_iters.train", "mbcg.iter_ms.train", "precond.ms.train"]
    assert all(m["source"] == "device_trace" and m["moves"] == "fit_step_s" for m in solver)
