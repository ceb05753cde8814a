"""Fixtures of the benchmark's own tests: the checkout on ``sys.path`` and a
tiny benchmark tree (its own ``BENCHMARK.json`` and configuration) that
the harness runs on the CPU, with the chip check skipped."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

HERE = os.path.dirname(os.path.abspath(__file__))


def make_tree(dest: str, config: dict) -> str:
    """A benchmark tree at ``dest`` with the real traffic, metric and peak
    files, one configuration ``tiny`` and its training cell."""
    for sub in ("traffic", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", sub), os.path.join(dest, "bench", sub))
    shutil.copy(os.path.join(ROOT, "bench", "peaks.json"), os.path.join(dest, "bench"))
    os.makedirs(os.path.join(dest, "bench", "configs"))
    with open(os.path.join(dest, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests/bench", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "CPU test size"}]
    bench["workloads"] = [{"name": "tiny.train", "config": "tiny", "traffic": "train-closed",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = sorted({"tiny." + w.split(".")[-1] for w in m["workloads"]})
    bench["per_layer"] = []
    metrics_dir = os.path.join(dest, "bench", "metrics")
    for name in sorted(os.listdir(metrics_dir)):
        path = os.path.join(metrics_dir, name)
        with open(path) as f:
            metric = json.load(f)
        metric["workloads"] = sorted({"tiny." + w.split(".")[-1] for w in metric["workloads"]})
        with open(path, "w") as f:
            json.dump(metric, f)
        bench["per_layer"].append({k: v for k, v in metric.items()
                                   if k not in ("reducer", "args", "formula")})
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


@pytest.fixture
def tiny_config() -> dict:
    with open(os.path.join(HERE, "tiny.json")) as f:
        return json.load(f)


@pytest.fixture
def tiny_tree(tmp_path, tiny_config) -> str:
    return make_tree(str(tmp_path), tiny_config)


@pytest.fixture
def run_cell(tiny_tree, monkeypatch, capsys):
    """Run a tiny cell through ``bench/run.py``'s main on the CPU; returns
    (exit code, result line or None)."""
    from bench import run

    monkeypatch.setattr(run, "compile_cache", lambda: "off")

    def go(workload: str, *extra: str, seed: int = 3, seconds: float = 0.5):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       str(seconds), *extra], require_chip=False, root=tiny_tree)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if out else None)

    return go
