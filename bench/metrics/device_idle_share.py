"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in percent (bench.trace)."""


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    return 100.0 * run.trace.idle_share()
