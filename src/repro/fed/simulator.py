"""The synchronized DFL simulator (paper Sec. IV/VI).

Wires together: road network + Manhattan mobility (time-varying contact
graphs), partitioned federated data, per-vehicle local training, and one of
the three algorithms {DFL-DDS, DFL (decentralized FedAvg), SP
(subgradient-push)}. The whole federation state is stacked on a leading
vehicle axis.

``run_simulation`` is a thin wrapper over the fused scan engine
(``repro.fed.engine``): setup is shared via ``engine.build_context``, and by
default whole epoch windows run inside one jitted ``lax.scan``. The original
per-epoch host loop is kept here behind ``SimulationConfig.use_scan_engine =
False`` — it is the parity reference the engine is tested against
(tests/test_engine.py) and the baseline for the engine-vs-loop benchmark
(benchmarks/kernel_micro.py).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import aggregation
from ..core import contacts as contacts_lib
from . import engine as engine_lib
# re-exports: the public simulation API lives here for backwards
# compatibility; definitions moved to engine.py with the fused-engine
# refactor.
from .engine import (  # noqa: F401
    EngineContext, SimulationConfig, SimulationResult, make_local_train_fn,
)


def run_simulation(cfg: SimulationConfig, dataset=None, progress: bool = False) -> SimulationResult:
    ctx = engine_lib.build_context(cfg, dataset=dataset)
    if cfg.use_scan_engine:
        return engine_lib.run_with_context(ctx, progress=progress)
    return run_legacy_loop(ctx, progress=progress)


def run_legacy_loop(ctx: EngineContext, progress: bool = False) -> SimulationResult:
    """The pre-engine path: one host-dispatched jitted round per epoch."""
    cfg = ctx.cfg
    if cfg.overlap != "sync":
        raise ValueError(
            "overlap='delayed' needs the scan engine's double-buffered carry "
            "(set use_scan_engine=True)")
    t0 = time.time()
    result = SimulationResult(config=cfg)
    state, rng = ctx.init_state, ctx.init_rng
    round_fn, eval_all = ctx.round_jit, ctx.eval_jit
    payload_mb = engine_lib.exchange_payload_mb(ctx)

    for epoch in range(cfg.epochs):
        # one epoch of the contact stream, in the run's contact format
        # (dense [K, K] matrix or single-epoch SparseContacts)
        contacts = jax.tree_util.tree_map(lambda x: jnp.asarray(x[0]),
                                          ctx.contacts.window(1))
        rng, kb, kr = jax.random.split(rng, 3)
        batch = ctx.sample_fn(ctx.fed_data, kb)
        state, diags = round_fn(state, contacts, ctx.target, batch, kr,
                                ctx.fed_data)
        result.kl_trace.append(float(np.mean(np.asarray(diags["kl_divergence"]))))
        result.loss_trace.append(float(np.mean(np.asarray(diags["loss"]))))
        result.comm_mb.append(
            float(np.asarray(contacts_lib.count_edges(contacts))) * payload_mb)
        if (epoch + 1) % cfg.eval_every == 0 or epoch == cfg.epochs - 1:
            _record(result, epoch, ctx.model_of(state), diags, eval_all,
                    progress, num_vehicles=cfg.num_vehicles)

    result.wall_time = time.time() - t0
    return result


def _record(result, epoch, params_stack, diags, eval_all, progress,
            num_vehicles=None):
    accs = np.asarray(eval_all(params_stack))
    if num_vehicles is not None:  # report vehicle metrics only (RSUs excluded)
        accs = accs[:num_vehicles]
    result.epochs_evaluated.append(epoch + 1)
    result.avg_accuracy.append(float(accs.mean()))
    result.vehicle_accuracy.append(accs)
    result.entropy.append(np.asarray(diags["entropy"]))
    result.kl_divergence.append(np.asarray(diags["kl_divergence"]))
    result.consensus_distance.append(float(aggregation.consensus_distance(params_stack)))
    if progress:
        print(f"  epoch {epoch + 1:4d}  avg_acc={accs.mean():.4f}  "
              f"min={accs.min():.4f}  max={accs.max():.4f}", flush=True)
