"""Seconds from process start to the first timed federation: imports, data,
``build_context``, the D_max probe and one warm-up federation."""


def read(run):
    return run.setup_s
