"""Share of the traced window's busy device time in operations under none of
the program's named scopes, in percent, averaged over the chips
(bench.scopes)."""
from bench import scopes


def read(run):
    got = scopes.seconds_by_scope(run, "unscoped_device_share")
    if got is None:
        return None
    return 100.0 * got.get(None, 0.0) / run.trace.mean_busy_s()
