"""Device milliseconds an epoch under the program's ``eval`` named scope in the
traced window: the in-scan eval (``evaluate`` in
``fed.engine.build_window_fn``), averaged over every epoch (bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "eval_device_ms", "eval")
