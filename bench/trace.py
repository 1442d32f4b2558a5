"""From a profiler trace to the numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists: per device, the intervals in which an XLA operation ran (the "XLA
Ops" line, where a ``while`` op's span holds the ops of its body), and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names starting
``bench.``). ``Trace`` then reduces those lists, so the arithmetic is tested
on small hand-made traces. All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.federation"
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Event:
    name: str
    start: float
    end: float


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def is_container(name: str) -> bool:
    """A control-flow op whose span holds the ops of its body."""
    return name.rsplit(".", 1)[0] in CONTAINERS


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


@dataclass
class Trace:
    device_ops: dict[int, list[Event]]           # device id -> its XLA ops
    host_spans: list[Event] = field(default_factory=list)

    def window(self) -> tuple[float, float]:
        """From the start of the first timed federation to the end of the
        last."""
        spans = [s for s in self.host_spans if s.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return min(s.start for s in spans), max(s.end for s in spans)

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def busy_s(self, device: int) -> float:
        """Seconds of the window in which some operation ran on ``device``."""
        lo, hi = self.window()
        ops = [(e.start, e.end) for e in self.device_ops.get(device, [])]
        return covered(clip(ops, lo, hi)) / 1e9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.device_ops) / len(self.device_ops)

    def idle_share(self) -> float:
        """1 - busy / window, averaged over the devices."""
        return 1.0 - self.mean_busy_s() / self.window_s()

    def top_ops(self, n: int = 10, device: int = 0) -> list[list]:
        """The ``n`` operation names with the most device seconds in the
        window, on ``device``; control-flow ops, whose spans hold their
        bodies' ops, are left out."""
        lo, hi = self.window()
        total: dict[str, float] = {}
        for e in self.device_ops.get(device, []):
            if is_container(e.name):
                continue
            for s, t in clip([(e.start, e.end)], lo, hi):
                total[e.name] = total.get(e.name, 0.0) + (t - s) / 1e9
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, device: int = 0) -> list[list]:
        """The ``n`` longest gaps between operations on ``device`` inside the
        window, each named by the innermost benchmark span the host was in at
        the gap's middle (``host`` where it was in none)."""
        lo, hi = self.window()
        busy = union(clip([(e.start, e.end)
                           for e in self.device_ops.get(device, [])], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            inside = [sp for sp in self.host_spans if sp.start <= mid <= sp.end]
            name = (min(inside, key=lambda sp: sp.end - sp.start).name
                    if inside else "host")
            out.append([name, (e - s) / 1e9])
        return out


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: the "XLA Ops" line
    of every ``/device:TPU:<n>`` plane, and the host spans named
    ``bench.*``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)
