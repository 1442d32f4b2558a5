"""Milliseconds a federation in which the device was idle while the host
waited for and copied back a window's trajectory (the program's
``fed.collect`` spans), in the traced window, averaged over the chips
(bench.spans)."""
from bench import spans


def read(run):
    return spans.device_idle_ms(run, "collect_idle_ms", "fed.collect")
