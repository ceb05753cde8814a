"""Finds everything a cell needs by name: ``BENCHMARK.json`` at the
checkout root, ``bench/configs/<config>.json`` (path from the config
entry), ``bench/traffic/<traffic>.json``, ``bench/metrics/<metric>.json``
and ``bench/peaks.json``.  A new cell, configuration, traffic mix or
per-layer metric is a new file plus new entries; no code names one."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """A cell, file or entry that the benchmark cannot use."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


class Spec:
    """The benchmark as data, rooted at a checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts) -> str:
        return os.path.join(self.root, *parts)

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return load_json(self._path(c["file"]))
        raise SpecError(f"no config named {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self._path("bench", "traffic", f"{name}.json"))

    def metric(self, name: str) -> dict:
        return load_json(self._path("bench", "metrics", f"{name}.json"))

    def peaks(self) -> dict:
        return load_json(self._path("bench", "peaks.json"))

    @staticmethod
    def _in(entry: dict, cell: str) -> bool:
        return "workloads" not in entry or cell in entry["workloads"]

    def end_to_end(self, cell: str) -> list[dict]:
        """End-to-end metrics this cell reports (all without ``workloads``)."""
        return [m for m in self.bench["end_to_end"] if self._in(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        """Per-layer metrics read in this cell's traced run: those listing it,
        or, without ``workloads``, those whose moved metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [
            m for m in self.bench["per_layer"]
            if cell in m.get("workloads", ()) or ("workloads" not in m and m["moves"] in e2e)
        ]

    def problems(self) -> list[str]:
        """Names and units outside the allowed characters, and per-layer
        metric files that disagree with their ``BENCHMARK.json`` entry."""
        out = []
        b = self.bench
        names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
                 for e in b[k]]
        names += [w[k] for w in b["workloads"] for k in ("config", "traffic")]
        names += [r for c in b["configs"] for r in c["reduced"]]
        out += [f"bad name {n!r}" for n in names if not NAME_RE.match(n)]
        units = [m["unit"] for k in ("end_to_end", "per_layer") for m in b[k]]
        out += [f"bad unit {u!r}" for u in units if not UNIT_RE.match(u)]
        for m in b["per_layer"]:
            f = self.metric(m["name"])
            for k, v in m.items():
                if f.get(k) != v:
                    out.append(f"metrics/{m['name']}.json: {k}={f.get(k)!r}, "
                               f"BENCHMARK.json has {v!r}")
        return out
