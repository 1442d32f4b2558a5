"""The program's own spans and counters (``repro.telemetry``) on the clock of
the profiler trace.

The program stamps its spans with ``time.time_ns()``; ``jax.profiler``
stamps host events with the same clock, but ``ProfileData`` gives them, and
the device's operations, relative to the profile's start, which
``bench.trace`` does not keep. So the offset between the two is recovered
from the timed federations: the k-th ``bench.federation`` annotation of the
trace wraps the k-th of the last ``fed.federation`` spans the program
recorded. Any offset that puts every ``fed.federation`` inside its
``bench.federation`` will do; when no single offset does, the clocks do not
agree and the readers give nothing.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass

from bench.trace import WINDOW_SPAN, clip, covered, union

FEDERATION = "fed.federation"


def note(metric: str, why: str) -> None:
    print(f"bench: {metric}: {why}; left out", file=sys.stderr, flush=True)


def program_spans():
    """The program's finished spans, or None where it records none."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry.spans()


def first(metric: str, name: str):
    """The earliest ``name`` span the program recorded, or None."""
    spans = program_spans() or []
    found = [s for s in spans if s.name == name]
    if not found:
        note(metric, f"the program recorded no {name} span")
        return None
    return min(found, key=lambda s: s.start_ns)


@dataclass
class Window:
    """The program's spans of the timed window, on the trace's clock."""
    federations: list        # the window's fed.federation spans, in order
    spans: list              # every span of those federations
    offset: float            # program time - trace time, in ns

    def intervals(self, name: str) -> list[tuple[float, float]]:
        """The window's ``name`` spans as trace-clock intervals."""
        return [(s.start_ns - self.offset, s.end_ns - self.offset)
                for s in self.spans if s.name == name]

    def total_count(self, counter: str) -> int:
        return sum(f.counts.get(counter, 0) for f in self.federations)


def window(run, metric: str) -> Window | None:
    """The timed window's program spans, or None (with a line on stderr)
    where the trace or the program has none, or the clocks disagree."""
    spans = program_spans()
    if spans is None:
        note(metric, "the program has no telemetry")
        return None
    marks = sorted((s for s in run.trace.host_spans if s.name == WINDOW_SPAN),
                   key=lambda s: s.start)
    feds = [s for s in spans if s.name == FEDERATION]
    if not marks or len(feds) < len(marks):
        note(metric, f"{len(feds)} {FEDERATION} spans for {len(marks)} "
             f"timed federations")
        return None
    feds = feds[-len(marks):]
    low = max(f.end_ns - m.end for f, m in zip(feds, marks))
    high = min(f.start_ns - m.start for f, m in zip(feds, marks))
    if low > high:
        note(metric, f"no clock offset puts every {FEDERATION} span inside "
             f"its {WINDOW_SPAN} span ({(low - high) / 1e3:.1f} us short)")
        return None
    ids = {f.id for f in feds}
    return Window(feds, [s for s in spans if s.federation in ids],
                  (low + high) / 2)


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two disjoint, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] in which none of ``ops`` ran."""
    busy = union(clip([(e.start, e.end) for e in ops], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_ms_per_federation(trace, win: Window, name: str) -> float:
    """Milliseconds a federation in which the device was idle while the
    program was inside a ``name`` span, averaged over the devices."""
    lo, hi = trace.window()
    inside = union(win.intervals(name))
    total = sum(covered(intersect(idle(ops, lo, hi), inside))
                for ops in trace.device_ops.values())
    return total / len(trace.device_ops) / len(win.federations) / 1e6


def device_idle_ms(run, metric: str, name: str) -> float | None:
    """``idle_ms_per_federation`` of the timed window, or None where the
    trace has no device or the program no spans."""
    if not run.trace.device_ops:
        note(metric, "the trace has no device operations")
        return None
    win = window(run, metric)
    if win is None:
        return None
    return idle_ms_per_federation(run.trace, win, name)
