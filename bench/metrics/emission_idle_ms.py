"""Milliseconds a federation in which the device was idle while the host
emitted contacts (the program's ``fed.contacts`` spans), in the traced
window, averaged over the chips (bench.spans)."""
from bench import spans


def read(run):
    return spans.device_idle_ms(run, "emission_idle_ms", "fed.contacts")
