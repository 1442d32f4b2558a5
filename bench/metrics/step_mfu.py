"""Model FLOPs per second of the traced window over the chips' bf16 peak, in
percent: the federation's FLOPs counted from shapes (bench.flops) times the
traced window's federations per second."""
from bench import flops


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    import jax

    peak = flops.peak_flops(jax.devices()[0].device_kind) * run.cell.chips
    rate = run.federations / run.window_s
    return 100.0 * flops.federation_flops(run.cell) * rate / peak
