"""Seconds of the process's first window dispatch (the program's first
``fed.dispatch`` span, in the warm-up federation of set-up): tracing the
window and compiling it, or loading it from the compile cache."""
from bench import spans


def read(run):
    first = spans.first("first_dispatch_s", "fed.dispatch")
    return None if first is None else first.seconds
