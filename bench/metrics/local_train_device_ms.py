"""Device milliseconds an epoch under the program's ``local_train`` named scope
in the traced window: local training (``fed.engine.make_local_train_fn``)
(bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "local_train_device_ms", "local_train")
