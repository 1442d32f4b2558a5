"""vmap-vs-shard_map engine benchmark: steady-state epochs/sec at K in
{8, 64} on the same synthetic-MNIST DDS workload.

Run as its OWN process so the host-device count can be forced before jax
initializes (the way ``kernel_micro.engine_backend_rows`` invokes it):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m benchmarks.engine_backends

Prints ONE JSON object to stdout (machine-readable; the parent merges it
into the CSV report and BENCH_engine.json). On a single CPU socket the
sharded path mostly measures shard_map's collective overhead — the point of
the benchmark is tracking the trajectory as real multi-device hosts pick it
up, from this PR onward.
"""
from __future__ import annotations

import json
import time

from repro.data.synthetic import synthetic_mnist
from repro.fed import backends as backends_lib
from repro.fed import engine as engine_lib
from repro.roofline import scenario_cost
from repro.roofline.bench_schema import device_fields

VEHICLE_COUNTS = (8, 64)


def _steady_state_eps(cfg, ds, backend_name: str) -> float:
    """Second, compile-free run on one context, epochs per second."""
    backend = backends_lib.get_backend(backend_name)
    ctx = engine_lib.build_context(cfg, dataset=ds)
    backend.run(ctx)                  # compile + warm the jit caches
    ctx.contacts = engine_lib.ContactStream(cfg, ctx.contacts.mob.net)
    t0 = time.perf_counter()
    backend.run(ctx)
    return cfg.epochs / (time.perf_counter() - t0)


def main() -> dict:
    ds = synthetic_mnist(n_train=1_000, n_test=200)
    results = []
    for k in VEHICLE_COUNTS:
        # the workload is defined ONCE, next to the cost model that predicts
        # it — tests/test_scenario_cost.py replays the same configs against
        # the committed BENCH_engine.json rows
        cfg = scenario_cost.bench_engine_config(k)
        vmap_eps = _steady_state_eps(cfg, ds, "vmap")
        shard_eps = _steady_state_eps(cfg, ds, "shard_map")
        results.append({
            "num_vehicles": k,
            "epochs": cfg.epochs,
            "vehicle_shards": backends_lib.vehicle_shards(k),
            "vmap_epochs_per_s": round(vmap_eps, 3),
            "shard_map_epochs_per_s": round(shard_eps, 3),
            "shard_vs_vmap": round(shard_eps / vmap_eps, 3),
        })
    return {
        "benchmark": "engine_backends",
        "workload": "synthetic_mnist dds E=1 B=4 steady-state",
        **device_fields(),
        "results": results,
    }


if __name__ == "__main__":
    print(json.dumps(main()))
