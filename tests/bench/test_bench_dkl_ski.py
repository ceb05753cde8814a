"""The deep-kernel KISS-GP cell (``elevators-dkl-ski.train``): found from its
files, its two new reducers on hand-built traces, ``correct`` false on a
tiny CPU twin of the configuration when the timed step is broken
underneath, and the chip's readings judged by the cell's own limits.

The twin (``tiny-dkl-ski.json``: n = 256, d = 18, feature net 18-32-16-2, a
24 x 24 grid, the cell's solver settings) has limits set from CPU readings
at that size: sound runs (seeds 1-8) read at most loss.step0 0.00086
nats/row and change 0.24; half the rows read loss.step0 >= 0.010, a state
left unchanged reads change = 1, and so does the network when the
features' gradient is stopped."""

from __future__ import annotations

import json
import os

import jax
import pytest
from conftest import ROOT, make_tree

from bench import work_ski
from bench.calibrate_dkl_ski import stopped_features
from bench.common import checks_of
from bench.drivers import train_dkl_ski
from bench.reducers import features_mfu, interp_roofline
from bench.scopes import ScopedTrace
from bench.spec import Spec

CELL = "elevators-dkl-ski.train"
CALIBRATION = os.path.join(ROOT, "bench", "fixtures", "elevators-dkl-ski.calibration.jsonl")
# three whole steps of the cell's traced chip run with its scope map (seed
# 3141603202, trimmed by ``bench/scopes.py --first 5 --steps 3``)
TRIMMED = os.path.join(ROOT, "bench", "fixtures", "elevators-dkl-ski.trace_scopes.json.gz")
HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = {"features.ms.ski", "interp.ms.ski", "grid.ms.ski", "device.idle.ski",
               "interp_hbm_roofline.ski", "features.mfu.ski", "precond.ms.ski",
               "backward.ms.ski"}


def test_cell_is_found_from_its_files():
    spec = Spec(ROOT)
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-closed-dkl-ski"
    cfg = spec.config(cell["config"])
    assert (cfg["n"], cfg["d"], cfg["model"]["grid_size"]) == (16599, 18, 100)
    assert cfg["model"]["features"]["widths"] == [1000, 500, 50, 2]
    assert cfg["limits"]["train"], "the cell's correctness needs limits"
    entry = next(c for c in spec.bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == []
    traffic = spec.traffic(cell["traffic"])
    assert traffic["driver"] == "train_dkl_ski" and traffic["lr"] == 0.01
    assert {m["name"] for m in spec.end_to_end(CELL)} == {"setup_s", "fit_step_s"}
    assert {m["name"] for m in spec.per_layer(CELL)} == NEW_METRICS
    # the new metrics list this cell alone, and the old cell reads none of them
    assert not NEW_METRICS & {m["name"] for m in spec.per_layer("elevators.train")}


# one step on one chip: the feature net (forward 40 ns, its transpose 80 ns),
# building W (5 ns), two interpolation applications in mBCG (a fused scatter
# and an unfused gather, 20 ns each) and their transposes in the backward (25
# ns each), the clamp of a gather's indices and a layout copy of its result
# (2 and 3 ns: under the gather's op_name, but not applications), the grid
# product (15 ns), one unscoped op
PATHS = {
    "fusion.1": "jit(step)/jvp(dkl.features)/dot_general",
    "fusion.2": "jit(step)/transpose(jvp(dkl.features))/dot_general",
    "fusion.3": "jit(step)/jvp(ski.interp)/floor",
    "fusion.4": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/closed_call/ski.interp/"
                "scatter-add:",
    "gather.5": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/closed_call/ski.interp/gather:",
    "fusion.6": "jit(step)/transpose(jvp(bbmm.backward))/transpose(jvp(ski.interp))/gather:",
    "scatter.7": "jit(step)/transpose(jvp(bbmm.backward))/jvp(ski.interp)/scatter-add:",
    "pad_clamp_fusion.10": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/closed_call/"
                           "ski.interp/gather:",
    "copy.11": "jit(step)/transpose(jvp(bbmm.backward))/jvp(ski.interp)/gather:",
    "convolution.8": "jit(step)/jvp(jit(_mbcg_jit))/bbmm.mbcg/while/body/ski.grid/jit(fft)",
    "fusion.9": "jit(step)/optim.adam/sqrt",
}
DURS = {"fusion.1": 40, "fusion.2": 80, "fusion.3": 5, "fusion.4": 20, "gather.5": 20,
        "fusion.6": 25, "scatter.7": 25, "pad_clamp_fusion.10": 2, "copy.11": 3,
        "convolution.8": 15, "fusion.9": 2}
SHAPES = {"n": 16599, "m": 10000, "q": 16, "t": 11, "widths": [18, 1000, 500, 50, 2]}
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def hand_trace(steps: int = 3, paths=PATHS) -> ScopedTrace:
    device, t0 = [], 0
    for _ in range(steps):
        for name, dur in DURS.items():
            device.append([name, t0, dur, None])
            t0 += dur
        t0 += 100
    return ScopedTrace({"/device:TPU:0": device}, [["bench:window", 0, t0]], dict(paths))


def ctx_of(trace, steps: int = 3, **shapes) -> dict:
    return {"trace": trace, "steps": steps, "peaks": PEAKS, "chips": 1, **SHAPES, **shapes}


def test_reducers_on_hand_built_steps():
    ctx = ctx_of(hand_trace())
    n, m, q, t = (SHAPES[k] for k in "nmqt")
    one = 4.0 * (2 * n * q + n * q * t + (n + m) * t)
    assert work_ski.interp_bytes(n, m, q, t) == one
    # 4 applications a step, 100 ns of leaf ops under ski.interp a step
    want = 100.0 * (3 * 4 * one / PEAKS["hbm_bytes_per_s"]) / (3 * 100e-9)
    assert interp_roofline.reduce(ctx, scope="ski.interp", ops=["gather", "scatter"]) == (
        pytest.approx(want))
    flops = 6.0 * n * (18 * 1000 + 1000 * 500 + 500 * 50 + 50 * 2)
    assert work_ski.features_flops(n, SHAPES["widths"]) == flops
    want = 100.0 * 3 * flops / (3 * 120e-9) / PEAKS["flops_bf16"]
    assert features_mfu.reduce(ctx, scope="dkl.features") == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_metric_reads_the_chip_trace(metric):
    """Each of the cell's per-layer metrics, through its file, reads a value
    from the cell's trimmed chip trace; a share reads above 0 and at most
    100%."""
    from bench.run import per_layer

    got = per_layer(Spec(ROOT), CELL, ctx_of(ScopedTrace.from_json(TRIMMED)))
    assert metric in got, sorted(got)
    value = got[metric]["value"]
    assert value > 0
    if got[metric]["unit"] == "%":
        assert value <= 100.0


INTERP = (interp_roofline.reduce, {"scope": "ski.interp", "ops": ["gather", "scatter"]})
FEATURES = (features_mfu.reduce, {"scope": "dkl.features"})
BLIND = {
    "no-scope-map": lambda: ctx_of(hand_trace(paths={})),
    "no-trace": lambda: ctx_of(None),
    "no-shapes": lambda: {"trace": hand_trace(), "steps": 3, "peaks": PEAKS},
    "no-steps": lambda: ctx_of(hand_trace(), steps=0),
    "no-gather-or-scatter": lambda: ctx_of(hand_trace(paths={
        k: v for k, v in PATHS.items() if not v.endswith(("gather:", "scatter-add:"))})),
}


@pytest.mark.parametrize("reducer, case", [
    (INTERP, "no-scope-map"), (INTERP, "no-trace"), (INTERP, "no-shapes"),
    (INTERP, "no-gather-or-scatter"),
    (FEATURES, "no-scope-map"), (FEATURES, "no-trace"), (FEATURES, "no-shapes"),
    (FEATURES, "no-steps"),
], ids=lambda x: x if isinstance(x, str) else x[1]["scope"])
def test_new_reducers_read_nothing_rather_than_zero(reducer, case):
    """Where the trace holds nothing to read (a program that names no
    such scope), each reducer gives None, never 0."""
    reduce, args = reducer
    assert reduce(BLIND[case](), **args) is None


@pytest.fixture
def dkl_cell(tmp_path, monkeypatch, capsys):
    """Run the tiny twin through ``bench/run.py``'s main on the CPU as the
    cell ``tiny.train`` with the deep-kernel traffic; returns (exit code,
    result line)."""
    from bench import run

    with open(os.path.join(HERE, "tiny-dkl-ski.json")) as f:
        tree = make_tree(str(tmp_path), json.load(f))
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"][0]["traffic"] = "train-closed-dkl-ski"
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(run, "compile_cache", lambda: "off")

    def go(seed: int):
        rc = run.main(["--workload", "tiny.train", "--seed", str(seed), "--seconds", "0.5"],
                      require_chip=False, root=tree)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if out else None)

    return go


def _frozen(real):
    def make(model, lr):
        init, step = real(model, lr)

        def same(params, opt, data, y, key):
            _, _, loss = step(params, opt, data, y, key)
            return params, opt, loss

        return init, jax.jit(same)

    return make


def _half_batch(real):
    def make(model, lr):
        from repro.optim import adam

        init, _ = real(model, lr)
        _, update = adam(lr)

        def step(params, opt, data, y, key):
            h = y.shape[0] // 2
            loss, g = jax.value_and_grad(
                lambda p: 2.0 * model.loss(p, data[:h], y[:h], key))(params)
            params, opt = update(g, opt, params)
            return params, opt, loss

        return init, jax.jit(step)

    return make


@pytest.mark.parametrize("fault, target, failing", [
    (None, None, None),
    (_frozen, "make_step", "change"),
    (_half_batch, "make_step", "loss.step0"),
    (stopped_features, "make_model", "change"),  # the chip calibration's own fault
], ids=["sound", "state-unchanged", "half-the-rows", "features-gradient-stopped"])
def test_tiny_twin_is_correct_only_when_sound(dkl_cell, monkeypatch, fault, target, failing):
    if fault is not None:
        monkeypatch.setattr(train_dkl_ski, target, fault(getattr(train_dkl_ski, target)))
    rc, line = dkl_cell(2**33 + 17)  # seeds past 32 bits
    assert rc == 0 and line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "fit_step_s"}
    if fault is None:
        assert line["correct"], line["checks"]
    else:
        assert not line["correct"]
        assert line["checks"][failing]["value"] > line["checks"][failing]["limit"]


def test_chip_readings_separate_sound_runs_from_control_and_faults():
    limits = Spec(ROOT).config("elevators-dkl-ski")["limits"]["train"]
    with open(CALIBRATION) as f:
        records = [json.loads(line) for line in f]
    faults = {"control.bfloat16", "half_batch", "stopped_features"}
    assert {"sound", "control.bfloat16", "half_batch"} <= {r["kind"] for r in records}
    assert len({r["seed"] for r in records if r["kind"] == "sound"}) >= 8
    for r in records:
        if "readings" not in r:  # the conditioning line: K^'s spectrum, no comparison
            continue
        failed = [c.name for c in checks_of(r["readings"], limits, lambda s: None) if not c.ok]
        if r["kind"] == "sound":
            assert failed == [], r
            # the network left at its start, all else sound: its large leaves'
            # change reads exactly 1, and `change` alone tells it apart
            stopped = dict(r["readings"], change=1.0)
            assert [c.name for c in checks_of(stopped, limits, lambda s: None)
                    if not c.ok] == ["change"], r
        elif r["kind"] in faults:
            assert failed, r
        if r["kind"] == "stopped_features":
            assert "change" in failed, r
    unchanged = dict(records[0]["readings"], grad=1.0, change=1.0,  # a state left unchanged
                     **{"change.main": 1.0})
    assert [c.name for c in checks_of(unchanged, limits, lambda s: None) if not c.ok]
