#!/usr/bin/env python3
"""Named scopes of the device ops in a traced window.

The program names its phases with ``jax.named_scope`` (``bbmm.precond``,
``bbmm.mbcg``, ``bbmm.logdet``, ``bbmm.backward``, ``optim.adam``).  The
compiler keeps the name stack in each op's ``op_name`` metadata, and the
profiler writes it into the ``.xplane.pb`` as the ``tf_op`` stat of the
op's event metadata.  ``ScopedTrace`` is a ``bench.trace.Trace`` plus
``scopes``, a map from each device op's name to that path, read from the
same file (``op_paths``); ``events_in(scope)`` keeps
the window's leaf ops (control flow left out, as in ``op_seconds``) whose
path holds the scope as one of its components, bare or wrapped by a
transformation ("bbmm.mbcg", "jvp(bbmm.precond)",
"transpose(jvp(bbmm.backward))").

``of(ctx)`` gives the reducers the ScopedTrace of the run they reduce: the
run's ``Trace`` with the scope map of the raw trace under ``bench/out``
whose window is the same.  A trace with no map, or a program that names no
scope, leaves ``events_in`` empty and the reducers return None.

    python3 bench/scopes.py bench/out/elevators.train.trace out.json.gz --first 5 --steps 3

trims a raw trace to whole steps (a window from the ``first``-th step's
dispatch to the end of the last step's read-back) with its scope map: the
form of ``bench/fixtures/trace_scopes.json.gz``.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys

if __name__ == "__main__":  # run as a script from the checkout root
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.spec import ROOT  # noqa: E402
from bench.trace import (  # noqa: E402
    DEVICE_PREFIX, WINDOW_SPAN, Trace, is_container, op_name)

RAW_TRACES = os.path.join(ROOT, "bench", "out", "*.trace")
PATH_STAT = "tf_op"  # the stat of an op's event metadata that holds its op_name


def in_scope(path: str | None, scope: str) -> bool:
    """Whether ``scope`` is a component of an op_name path, bare or wrapped
    by transformations ("jvp(bbmm.precond)")."""
    if not path:
        return False
    return any(c.rstrip(")").rsplit("(", 1)[-1] == scope for c in path.split("/"))


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of one protobuf message's fields: an int for a
    varint, a (start, end) span of ``buf`` for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_paths(xspace: bytes) -> dict:
    """op name -> op_name path of the device ops in a serialized XSpace.

    An op's path is the ``tf_op`` stat of its event metadata (XPlane field
    4, map id -> XEventMetadata{name = 2: the HLO text, stats = 5}; XStat
    {metadata_id = 1, str_value = 5}; the stat's name from XPlane field 5,
    map id -> XStatMetadata{name = 2}).  ``jax.profiler.ProfileData`` does
    not expose metadata stats, so the wire format is read here.  An op name
    that two programs of the trace share with different paths is left out."""
    found: dict = {}
    for number, plane in _fields(xspace):
        if number != 1:  # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for field, value in _fields(xspace, *plane):
            if field == 2:
                name = _text(xspace, value)
            elif field == 5:  # stat_metadata entry {key = 1, value = 2}
                entry = dict(_fields(xspace, *value))
                stat = dict(_fields(xspace, *entry[2])) if 2 in entry else {}
                if 2 in stat:
                    stat_names[entry.get(1, 0)] = _text(xspace, stat[2])
            elif field == 4:  # event_metadata entry
                metadata.append(value)
        path_ids = {k for k, v in stat_names.items() if v == PATH_STAT}
        if not name.startswith(DEVICE_PREFIX) or not path_ids:
            continue
        for value in metadata:
            entry = dict(_fields(xspace, *value))
            if 2 not in entry:
                continue
            op, path = None, None
            for field, v in _fields(xspace, *entry[2]):
                if field == 2:
                    op = op_name(_text(xspace, v))
                elif field == 5:
                    stat = dict(_fields(xspace, *v))
                    if stat.get(1) in path_ids and 5 in stat:
                        path = _text(xspace, stat[5])
            if op and path:
                found.setdefault(op, set()).add(path)
    return {op: paths.pop() for op, paths in found.items() if len(paths) == 1}


def read_scopes(path: str) -> tuple[dict, tuple | None]:
    """(op name -> op_name path, bench:window (start, end) in ns) of a raw
    trace directory."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    with open(files[-1], "rb") as f:
        xspace = f.read()
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_serialized_xspace(xspace).planes
              if not plane.name.startswith(DEVICE_PREFIX)
              for ln in plane.lines for e in ln.events if e.name == WINDOW_SPAN]
    lo_hi = (min(s for s, _ in window), max(e for _, e in window)) if window else None
    return op_paths(xspace), lo_hi


class ScopedTrace(Trace):
    def __init__(self, device: dict, host: list, scopes: dict | None = None):
        super().__init__(device, host)
        # scopes: op name -> op_name path ("jit(step)/.../bbmm.mbcg/while/...")
        self.scopes = scopes or {}

    @classmethod
    def from_dir(cls, path: str) -> "ScopedTrace":
        t = Trace.from_dir(path)
        return cls(t.device, t.host, read_scopes(path)[0])

    @classmethod
    def from_json(cls, path: str) -> "ScopedTrace":
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            obj = json.load(f)
        return cls(obj["device"], obj["host"], obj.get("scopes", {}))

    def to_json(self, path: str) -> None:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump({"device": self.device, "host": self.host, "scopes": self.scopes}, f)

    def scope_of(self, op: str) -> str | None:
        return self.scopes.get(op)

    def events_in(self, scope: str, pattern: str | None = None):
        """(name, start_ns, dur_ns, rows) of the window's leaf ops under
        ``scope`` whose name holds ``pattern``, over all device planes."""
        return [ev for ev in self.events(pattern)
                if not is_container(ev[0]) and in_scope(self.scope_of(ev[0]), scope)]

    def seconds_in(self, scope: str, pattern: str | None = None) -> float:
        """Device seconds of those ops, per chip."""
        total = sum(d for _, _, d, _ in self.events_in(scope, pattern)) / 1e9
        return total / max(len(self.device), 1)

    def trimmed(self, first: int, steps: int) -> "ScopedTrace":
        """Whole steps ``first`` .. ``first + steps - 1`` of the window, as a
        window of their own: from that step's bench:dispatch to the end of
        the last one's bench:sync."""
        starts = sorted(s for n, s, _ in self.host if n == "bench:dispatch")
        syncs = sorted((s, s + d) for n, s, d in self.host if n == "bench:sync")
        lo = starts[first]
        hi = [e for s, e in syncs if s >= lo][steps - 1]
        host = [["bench:window", lo, hi - lo]] + [
            [n, s, d] for n, s, d in self.host if n != WINDOW_SPAN and lo <= s and s + d <= hi]
        device = {p: [ev for ev in evs if lo <= ev[1] < hi] for p, evs in self.device.items()}
        kept = {ev[0] for evs in device.values() for ev in evs}
        return ScopedTrace(device, host, {k: v for k, v in self.scopes.items() if k in kept})


def of(ctx) -> ScopedTrace | None:
    """The ScopedTrace of the traced run in ``ctx``, or None; kept in
    ``ctx`` so that the reducers of one run read the raw trace once."""
    tr = ctx.get("trace")
    if tr is None or isinstance(tr, ScopedTrace):
        return tr
    if "scoped_trace" not in ctx:
        ctx["scoped_trace"] = ScopedTrace(tr.device, tr.host, _scopes_for(tr))
    return ctx["scoped_trace"]


def _scopes_for(tr: Trace) -> dict:
    """The scope map of the raw trace under bench/out with ``tr``'s window."""
    try:
        window = tr.window_ns()
    except ValueError:
        return {}
    for path in sorted(glob.glob(RAW_TRACES), key=os.path.getmtime, reverse=True):
        try:
            scopes, raw_window = read_scopes(path)
        except FileNotFoundError:
            continue
        if raw_window == window:
            return scopes
    return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Trim a raw trace to whole steps, with scopes.")
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--first", type=int, default=5)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    ScopedTrace.from_dir(args.trace_dir).trimmed(args.first, args.steps).to_json(args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
