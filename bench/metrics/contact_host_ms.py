"""Milliseconds of host time to emit one federation's contact windows on a
fresh stream (``fed.engine.ContactStream.window``: mobility, contacts,
neighbour lists), called by the benchmark outside the window."""
from bench.harness import fresh_stream, time_calls


def read(run):
    epochs = run.cfg.epochs
    return time_calls(lambda: fresh_stream(run, traced=False).window(epochs))
