"""Device trace of the measured window, and its reduction.

``capture(dir)`` wraps the window in ``jax.profiler`` tracing; ``Trace``
reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` and
keeps two things: the op events of each device plane (name, start, length
in ns, output rows) and the benchmark's own host spans (``bench:*``
annotations, which the profiler puts on the same clock).  A device plane
is one with an "XLA Ops" line; its events are named by the op's own HLO
name, and carry the rows of the op's output (every dimension but the
last), so a product launched in row panels is charged per panel.
Everything else in the file is dropped.  ``Trace.to_json`` / ``from_json``
give the trimmed form the reduction tests read.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import math
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str:
    """An XLA op event is named by its whole HLO line; keep the op's own
    name ("%fused_kernel_matmul_prescaled.10 = f32[...] custom-call(...)"
    -> "fused_kernel_matmul_prescaled.10"), so a pattern matches the op and
    not the operands it reads."""
    return text.split(" = ", 1)[0].lstrip("%")


SHAPE_RE = re.compile(r"^\(?\s*[a-z0-9]+\[([0-9,]*)\]")


def op_rows(text: str) -> int | None:
    """Rows of an op's output from its HLO line: the product of every
    dimension but the last, of the first output ("... = f32[16599,128]{1,0}
    custom-call(...)" -> 16599).  None where the line gives no such shape."""
    parts = text.split(" = ", 1)
    m = SHAPE_RE.match(parts[1]) if len(parts) == 2 else None
    dims = [int(x) for x in m.group(1).split(",") if x] if m else []
    return math.prod(dims[:-1]) if len(dims) >= 2 else None


def is_container(name: str) -> bool:
    """Control-flow ops whose interval holds the ops of their body."""
    return name.split(".", 1)[0] in CONTAINERS


@contextlib.contextmanager
def capture(out_dir: str):
    import jax

    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, on: bool):
    """A host span on the profiler's clock while tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def union(intervals, lo=None, hi=None) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, clipped to [lo, hi] when given."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    def __init__(self, device: dict, host: list):
        # device: plane name -> [[op name, start_ns, dur_ns, rows], ...]
        self.device = device
        # host: [[span name, start_ns, dur_ns], ...]
        self.host = host

    # -- reading ----------------------------------------------------------
    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        device: dict = {}
        host: list = []
        for plane in ProfileData.from_file(files[-1]).planes:
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if plane.name.startswith(DEVICE_PREFIX) and ops:
                device[plane.name] = [
                    [op_name(e.name), e.start_ns, e.duration_ns, op_rows(e.name)]
                    for e in ops[0].events if e.duration_ns > 0
                ]
            else:
                for ln in plane.lines:
                    host += [[e.name, e.start_ns, e.duration_ns] for e in ln.events
                             if e.name.startswith(SPAN_PREFIX)]
        return cls(device, host)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            obj = json.load(f)
        return cls(obj["device"], obj["host"])

    def to_json(self, path: str) -> None:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as f:
            json.dump({"device": self.device, "host": self.host}, f)

    # -- reduction --------------------------------------------------------
    def window_ns(self) -> tuple[float, float]:
        spans = [(s, s + d) for n, s, d in self.host if n == WINDOW_SPAN]
        if not spans:
            raise ValueError("trace has no bench:window span")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def window_s(self) -> float:
        lo, hi = self.window_ns()
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        """Seconds in the window in which some op ran, averaged over the
        device planes."""
        lo, hi = self.window_ns()
        per = [
            sum(e - s for s, e in union(((s, s + d) for _, s, d, _ in evs), lo, hi))
            for evs in self.device.values()
        ]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def events(self, pattern: str | None = None):
        """(name, start_ns, dur_ns, rows) of ops inside the window whose name
        holds ``pattern``, over all device planes."""
        lo, hi = self.window_ns()
        return [
            (n, s, d, r) for evs in self.device.values() for n, s, d, r in evs
            if lo <= s < hi and (pattern is None or pattern in n)
        ]

    def op_seconds(self, top: int = 10) -> list[list]:
        """Ops with the most device time in the window, per chip; control
        flow (a while loop and the like) is left out, its body ops count."""
        tot: dict = {}
        for n, _, d, _ in self.events():
            if not is_container(n):
                tot[n] = tot.get(n, 0.0) + d / 1e9
        k = max(len(self.device), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[n, s / k] for n, s in ranked]

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle device time in the window, by the innermost host span that
        covers each gap's midpoint ("none" where no span does), per chip."""
        lo, hi = self.window_ns()
        spans = [(n, s, s + d) for n, s, d in self.host if n != WINDOW_SPAN]
        tot: dict = {}
        for evs in self.device.values():
            busy = union(((s, s + d) for _, s, d, _ in evs), lo, hi)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) / 2
                cover = [(e - s, n) for n, s, e in spans if s <= mid < e]
                name = min(cover)[1] if cover else "none"
                tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
        k = max(len(self.device), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        return [[n, s / k] for n, s in ranked]
