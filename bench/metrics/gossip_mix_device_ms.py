"""Device milliseconds an epoch under the program's ``gossip_mix`` named scope
in the traced window: the gossip mix of the models
(``core.aggregation.mix_params``) (bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "gossip_mix_device_ms", "gossip_mix")
