"""Device milliseconds an epoch under the program's ``state_vector`` named
scope in the traced window: the state-vector update and its KL and entropy
(``core.state_vector``) (bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "state_vector_device_ms", "state_vector")
