"""The Algorithm protocol and the string-keyed algorithm registry.

An *algorithm* is everything the fused engine needs to run one federation
round, bundled behind four hooks (plus a sharding spec):

* ``init_state(setup)``   — the stacked federation state pytree;
* ``round(setup, state, contacts_t, target, batch, rng, fed_data)`` — one
  synchronized global iteration, returning ``(state, diags)`` with at least
  ``entropy`` / ``kl_divergence`` / ``loss`` diagnostics;
* ``sample(setup, fed_data, rng)`` — the per-epoch device-side batch;
* ``model_of(setup, state)``      — the evaluable parameter stack;
* ``state_pspec(setup, axis_name)`` — PartitionSpecs for the state under a
  vehicle-sharded mesh (big [K, ...] stacks on the axis, tiny [K, K]
  matrices replicated).

``AlgorithmSetup`` carries the per-run context the engine builds once
(``engine.build_context``): config, one sample's shape, local-train fn,
initial stacks, the resolved gossip-mix fn, and the vehicle-axis sharding
regime. Execution backends rebind ``shard`` (and wrap ``mix_params_fn``)
without the algorithm knowing which backend it runs under.

Registering a new algorithm makes it addressable by name from
``SimulationConfig.algorithm`` and the sweep runner with zero engine edits:

    @register_algorithm
    class MyAlgo(Algorithm):
        name = "my_algo"
        ...
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
from jax.sharding import PartitionSpec as P

from ...core.vehicle_axis import GLOBAL, VehicleSharding
from ...data import pipeline

Array = jax.Array
PyTree = Any


@dataclass(frozen=True)
class AlgorithmSetup:
    """Per-run context shared by every algorithm hook.

    Built once per (config, seed) by ``engine.build_context``; rebound (new
    ``shard`` + wrapped ``mix_params_fn``) by sharded execution backends.
    """
    cfg: Any                        # SimulationConfig (duck-typed; no engine import)
    total_nodes: int                # vehicles + RSUs
    sample_shape: tuple[int, ...]   # one sample's (H, W, C); fed_data.x is flat
    loss_fn: Callable               # loss(params, x, y, rng) for one vehicle
    local_train_fn: Callable        # E local SGD steps for one vehicle
    params_stack: PyTree            # [K, ...] identical-init model stack
    opt_stack: PyTree               # [K, ...] optimizer state stack
    local_mask: Array | None        # [K] 1 = runs local iterations (RSUs 0)
    mix_params_fn: Callable         # resolved gossip mix (jnp | pallas | shard-wrapped)
    shard: VehicleSharding = field(default=GLOBAL)


class Algorithm:
    """Base class for registered algorithms (see module docstring)."""

    name: str = "?"

    def init_state(self, setup: AlgorithmSetup) -> PyTree:
        raise NotImplementedError

    def round(self, setup: AlgorithmSetup, state: PyTree, contacts_t: Array,
              target: Array, batch: PyTree, rng: Array,
              fed_data: pipeline.FederatedData) -> tuple[PyTree, dict]:
        raise NotImplementedError

    def sample(self, setup: AlgorithmSetup, fed_data: pipeline.FederatedData,
               rng: Array) -> PyTree:
        """Default: per-vehicle [E, B] minibatches from the partition table
        (full pick tensor drawn before any shard slice — random streams are
        identical across backends). The unsharded path goes through the
        jitted sampler so the legacy per-epoch loop (which samples outside
        jit) keeps its fused dispatch."""
        cfg = setup.cfg
        if setup.shard.is_sharded:
            return pipeline.sample_batches_sliced(
                fed_data, rng, cfg.local_steps, cfg.batch_size,
                setup.sample_shape, take_rows=setup.shard.local_rows)
        return pipeline.sample_batches(fed_data, rng, cfg.local_steps,
                                       cfg.batch_size, setup.sample_shape)

    def model_of(self, setup: AlgorithmSetup, state: PyTree) -> PyTree:
        raise NotImplementedError

    def state_pspec(self, setup: AlgorithmSetup, axis_name: str) -> PyTree:
        raise NotImplementedError


def federation_state_pspec(setup: AlgorithmSetup, axis_name: str):
    """PartitionSpecs for a ``dfl_dds.FederationState``: params / optimizer
    stacks sharded on the vehicle axis, [K, K] state matrix + epoch counter
    replicated."""
    from ...core.dfl_dds import FederationState

    row = P(axis_name)
    return FederationState(
        params=jax.tree_util.tree_map(lambda _: row, setup.params_stack),
        opt_state=jax.tree_util.tree_map(lambda _: row, setup.opt_stack),
        state_matrix=P(),
        epoch=P(),
    )


_ALGORITHMS: dict[str, Algorithm] = {}


def register_algorithm(cls: type[Algorithm]) -> type[Algorithm]:
    """Class decorator: instantiate and register under ``cls.name``."""
    _ALGORITHMS[cls.name] = cls()
    return cls


def get_algorithm(name: str) -> Algorithm:
    try:
        return _ALGORITHMS[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r} "
            f"(registered: {'|'.join(available_algorithms())})") from None


def available_algorithms() -> list[str]:
    return sorted(_ALGORITHMS)


def algorithm_registry() -> dict[str, Algorithm]:
    """Snapshot of the registry (name -> instance), for the docs tables."""
    return dict(_ALGORITHMS)
