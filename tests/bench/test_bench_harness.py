"""The harness is driven by data: cells, configurations, traffic mixes and
per-layer metrics are found by name; BENCHMARK.json keeps to the allowed
names, units and shapes; and no chip means no result."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT, make_tree

from bench.spec import NAME_RE, UNIT_RE, Spec

ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}


def test_benchmark_json_keeps_to_names_units_and_keys():
    spec = Spec(ROOT)
    b = spec.bench
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert spec.problems() == []
    for section, allowed in ENTRY_KEYS.items():
        for entry in b[section]:
            assert set(entry) <= allowed, (section, entry)
            assert NAME_RE.match(entry["name"])
            if "unit" in entry:
                assert UNIT_RE.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in b["workloads"]}
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in spec.end_to_end(w)}
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        spec.config(w["config"]), spec.traffic(w["traffic"])
        assert len(spec.end_to_end(w["name"])) >= 2 and spec.per_layer(w["name"])
    assert all(not p.startswith("/") and ".." not in p for p in b["paths"])


def test_cell_added_with_data_files_alone(tmp_path):
    tree = make_tree(str(tmp_path), {"name": "tiny", "n": 64, "d": 2})
    bench_dir = tmp_path / "bench"
    (bench_dir / "configs" / "wide.json").write_text(json.dumps({"name": "wide", "n": 128, "d": 40}))
    (bench_dir / "traffic" / "train-slow.json").write_text(json.dumps(
        {"driver": "train", "lr": 0.01}))
    metric = {"name": "device.idle.wide", "unit": "%", "better": "lower",
              "source": "device_trace", "layer": "device (TPU v5e)",
              "moves": "fit_step_s", "workloads": ["wide.train-slow"]}
    (bench_dir / "metrics" / "device.idle.wide.json").write_text(json.dumps(
        dict(metric, reducer="device_idle", args={})))
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "wide", "source": "x", "file": "bench/configs/wide.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "wide.train-slow", "config": "wide",
                           "traffic": "train-slow", "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("wide.train-slow")
    b["per_layer"].append(metric)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    spec = Spec(tree)
    assert spec.problems() == []
    cell = spec.cell("wide.train-slow")
    assert spec.config(cell["config"])["d"] == 40
    assert spec.traffic(cell["traffic"])["lr"] == 0.01
    assert {m["name"] for m in spec.end_to_end("wide.train-slow")} == {"setup_s", "fit_step_s"}
    names = [m["name"] for m in spec.per_layer("wide.train-slow")]
    assert names == ["device.idle.wide"]
    from bench.run import per_layer
    from bench.trace import Trace

    trace = Trace({"/device:TPU:0": [["fusion.1", 0, 250, None]]}, [["bench:window", 0, 1000]])
    assert per_layer(spec, "wide.train-slow", {"trace": trace}) == {
        "device.idle.wide": {"value": 75.0, "unit": "%"}}
    assert per_layer(spec, "wide.train-slow", {}) == {}  # found nothing to read: left out


def _bare_run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    cell = Spec(ROOT).bench["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**33 + 5), "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_no_tpu_means_no_result():
    p = _bare_run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    spec = Spec(ROOT)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec.bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _bare_run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
