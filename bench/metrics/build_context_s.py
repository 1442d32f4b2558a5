"""Seconds of the process's first ``fed.engine.build_context`` (the
program's first ``fed.build_context`` span): partition, data on the device,
mobility stream, model init and the eager programs they compile."""
from bench import spans


def read(run):
    first = spans.first("build_context_s", "fed.build_context")
    return None if first is None else first.seconds
