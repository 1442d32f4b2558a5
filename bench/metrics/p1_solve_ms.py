"""Milliseconds of one epoch's P1 solve (``core.kl_solver.solve_p1_all``) at
the cell's K, D_max and P1 steps, on the cell's own contacts (its fleet's
first epoch) and the state vectors the last timed federation ended with,
timed from outside with ``block_until_ready``."""
from bench.harness import fresh_stream, time_calls


def read(run):
    import jax
    import jax.numpy as jnp
    from repro.core import kl_solver
    from repro.core.contacts import SparseContacts

    window = fresh_stream(run, traced=False).window(1)
    contacts = SparseContacts(jnp.asarray(window.idx[0]),
                              jnp.asarray(window.mask[0]))
    states = run.last.final_state.state_matrix
    target = run.ctx.target

    def solve():
        jax.block_until_ready(kl_solver.solve_p1_all(
            states, target, contacts, num_steps=run.cfg.p1_steps,
            step_size=run.cfg.p1_step_size))

    return time_calls(solve)
