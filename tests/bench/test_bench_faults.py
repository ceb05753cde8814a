"""``correct`` comes out false when the timed path is broken underneath,
and the cell's limits separate the chip's readings of the control.

The fault tests run a tiny cell through ``bench/run.py``'s main on the CPU
with the chip check skipped, so the whole run (set-up, window, the
comparison with the plain reference) is the one the chip runs.  The limits
of the tiny configuration (``tiny.json``) were set from CPU readings at
that size: sound runs (seeds 1-6) read at most loss 0.022 nats/row, grad
0.084 and change 0.0084; the faults below read loss >= 0.13 and
grad = change = 1.

The control (the reference in bf16, put in the program's place) moves the
outputscale gradient by 2.5-5% at the cell's own size, against at most
0.4% for sound runs; at a size a CPU test holds, the probe noise of the
program's estimator is larger than that step, so the control is kept as
the chip's readings (``bench/fixtures/elevators.calibration.jsonl``) and
judged here by the cell's own limits and comparison.
"""

from __future__ import annotations

import json
import os

import jax
from conftest import ROOT

from bench.common import checks_of
from bench.drivers import train
from bench.spec import Spec

CALIBRATION = os.path.join(ROOT, "bench", "fixtures", "elevators.calibration.jsonl")


def test_sound_cells_are_correct(run_cell):
    rc, line = run_cell("tiny.train")
    assert rc == 0 and line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "fit_step_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"


def test_step_that_returns_its_state_unchanged(run_cell, monkeypatch):
    real = train.make_step

    def frozen(model, lr):
        init, step = real(model, lr)

        def same(params, opt, data, y, key):
            _, _, loss = step(params, opt, data, y, key)
            return params, opt, loss

        return init, jax.jit(same)

    monkeypatch.setattr(train, "make_step", frozen)
    rc, line = run_cell("tiny.train", seed=2**33 + 7)  # seeds past 32 bits
    assert rc == 0 and not line["correct"]
    assert line["checks"]["grad"]["value"] == 1.0
    assert line["checks"]["change"]["value"] == 1.0


def test_half_the_batch_left_out(run_cell, monkeypatch):
    real = train.make_step

    def half(model, lr):
        init, _ = real(model, lr)
        from repro.optim import adam

        _, update = adam(lr)

        def step(params, opt, data, y, key):
            h = y.shape[0] // 2
            loss, g = jax.value_and_grad(
                lambda p: 2.0 * model.loss(p, data[:h], y[:h], key))(params)
            params, opt = update(g, opt, params)
            return params, opt, loss

        return init, jax.jit(step)

    monkeypatch.setattr(train, "make_step", half)
    rc, line = run_cell("tiny.train")
    assert rc == 0 and not line["correct"]
    assert line["checks"]["loss.step0"]["value"] > 0.06


def test_chip_readings_separate_sound_runs_from_control_and_faults():
    limits = Spec(ROOT).config("elevators")["limits"]["train"]
    with open(CALIBRATION) as f:
        records = [json.loads(line) for line in f]
    kinds = {r["kind"] for r in records}
    assert {"sound", "control.bfloat16", "half_batch"} <= kinds
    assert len({r["seed"] for r in records if r["kind"] == "sound"}) >= 12
    for r in records:
        failed = [c.name for c in checks_of(r["readings"], limits, lambda s: None) if not c.ok]
        if r["kind"] == "sound":
            assert failed == [], r
        elif r["kind"] in ("control.bfloat16", "half_batch"):
            assert failed, r
    unchanged = dict(records[0]["readings"], grad=1.0, change=1.0,  # a state left unchanged
                     **{"grad.raw_outputscale": 1.0, "change.main": 1.0})
    assert [c.name for c in checks_of(unchanged, limits, lambda s: None) if not c.ok]



def test_the_control_lowers_the_reference_precision():
    """The control's two precisions really are lower: one bf16 pass moves
    the answer by far more than three, and three by more than none."""
    import numpy as np

    from bench import data, reference

    X, y = data.regression(384, 4, 3)
    raw = reference.init_raw(4)
    loss = {}
    for p in reference.PRECISIONS:
        inv = reference.Inverse(X, y, raw, precision=p)
        loss[p] = inv.loss()
        inv.free()
    gap_high = abs(loss["high"] - loss["highest"])
    gap_bf16 = abs(loss["bfloat16"] - loss["highest"])
    assert np.isfinite(gap_bf16) and gap_bf16 > 10 * gap_high > 0
