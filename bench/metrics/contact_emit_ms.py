"""Milliseconds of host contact emission a federation in the traced window:
the mean total of the program's ``fed.contacts`` spans
(``fed.engine.ContactStream.window``) in each timed federation
(bench.spans)."""
from bench import spans


def read(run):
    win = spans.window(run, "contact_emit_ms")
    if win is None:
        return None
    total = sum(s.end_ns - s.start_ns for s in win.spans
                if s.name == "fed.contacts")
    return total / len(win.federations) / 1e6
