"""Device milliseconds an epoch under the program's ``p1_solve`` named scope in
the traced window: the P1 solve and its mixing weights
(``core.kl_solver.solve_p1_all``, ``core.aggregation.mixing_from_alpha``)
(bench.scopes)."""
from bench import scopes


def read(run):
    return scopes.device_ms_per_epoch(run, "p1_solve_device_ms", "p1_solve")
