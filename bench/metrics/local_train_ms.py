"""Milliseconds of one epoch's local training (``fed.engine
.make_local_train_fn``, vmapped: E SGD steps of B samples) for the vehicles
one chip holds, K / chips, timed from outside with ``block_until_ready``."""
from bench.harness import time_calls


def read(run):
    import jax

    ctx = run.ctx
    n = ctx.total_nodes // run.cell.chips
    rows = lambda tree: jax.tree_util.tree_map(lambda x: x[:n], tree)
    params = rows(ctx.setup.params_stack)
    opt = rows(ctx.setup.opt_stack)
    key = jax.random.PRNGKey(run.seed)
    batch = rows(ctx.sample_fn(ctx.fed_data, key))
    keys = jax.random.split(key, n)
    train = jax.jit(jax.vmap(ctx.setup.local_train_fn))
    return time_calls(lambda: jax.block_until_ready(
        train(params, opt, batch, keys)))
