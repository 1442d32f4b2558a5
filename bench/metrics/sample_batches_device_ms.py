"""Device milliseconds an epoch under the program's ``sample_batches`` named
scope in the traced window: batch sampling
(``data.pipeline.sample_batches_sliced``) (bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "sample_batches_device_ms", "sample_batches")
