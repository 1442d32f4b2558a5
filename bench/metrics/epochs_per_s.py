"""Global federation epochs completed in the timed window over its wall
time: host contact emission, the device scan and the copy-back of each
federation's trajectory, all of it."""


def read(run):
    return run.epochs_per_s
