"""Fused multi-epoch simulation engine: whole epoch windows in one lax.scan.

The legacy ``run_simulation`` drove every global epoch through a host Python
loop (host mobility step -> one jitted round -> host sync), so dispatch
overhead dominated the paper's multi-hundred-epoch runs and scenario sweeps
ran strictly serially. This module restructures the hot path:

* **Contact-window precompute** — the Manhattan mobility process stays
  host-side (it is inherently sequential) but is batched up front:
  ``ContactStream.window(T)`` advances T epochs of motion and converts the
  stacked [T, K, 2] position snapshots into the contact representation the
  run's ``contact_format`` names (core.contacts registry): padded
  neighbour lists [T, K, D_max] (the sparse, fleet-scale default) or the
  dense [T, K, K] contact tensor (``topology`` + ``extensions`` helpers) —
  including RSU relays and Bernoulli edge drops either way. The stream
  consumes its RNGs epoch by epoch, so trajectories are independent of
  window chunking AND of the contact format.

* **Scanned round** — ``lax.scan`` runs the whole window on device: per step
  it folds fresh PRNG keys off the scan carry, gathers per-vehicle
  minibatches device-side (``data.pipeline``), applies the algorithm round
  (DDS / DFL / SP — local training, gossip model mix, state-vector update),
  and evaluates accuracy + consensus distance *in-scan* under ``lax.cond``
  on the epochs the eval mask selects. One dispatch per window instead of
  3-4 per epoch.

* **Seed vmap** — ``run_seeds`` stacks S independent federations (their own
  partitions, mobility traces, and model inits) and vmaps the same scanned
  window over the seed axis; the scenario sweep runner
  (``repro.launch.sweep``) maps this over road-net x distribution x
  algorithm grids.

``simulator.run_simulation`` is now a thin wrapper over this engine; the
legacy per-epoch loop survives behind ``SimulationConfig.use_scan_engine =
False`` as the parity reference (tests/test_engine.py holds the two paths to
identical eval trajectories).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core import aggregation, state_vector, vehicle_axis
from ..core import contacts as contacts_lib
from ..data import datasets as data_lib
from ..data import pipeline
from ..kernels.gossip_mix import ops as gossip_ops
from ..models import cnn as cnn_lib
from ..optim import apply_updates, sgd
from . import algorithms as algorithms_lib
from . import extensions as extensions_lib
from . import mobility as mobility_lib
from . import partition as partition_lib
from . import topology as topology_lib

Array = jax.Array


@dataclass
class SimulationConfig:
    algorithm: str = "dds"            # any registered algorithm (fed.algorithms)
    dataset: str = "mnist"            # mnist | cifar10
    road_net: str = "grid"            # any registered road network (fed.topology)
    distribution: str = "balanced_noniid"  # balanced_noniid | unbalanced_iid
    num_vehicles: int = 100
    epochs: int = 300
    lr: float = 0.1                   # paper Table II
    local_steps: int = 8              # E
    batch_size: int = 80              # B
    comm_range: float = 100.0
    epoch_duration: float = 30.0
    eval_every: int = 10
    eval_samples: int = 2000
    p1_steps: int = 200
    p1_step_size: float = 2.0
    seed: int = 0
    mobility: str = "manhattan"       # any registered mobility model (fed.mobility)
    # contact-window representation (core.contacts registry): "sparse" packs
    # each epoch's graph into padded neighbour lists [T, K, D_max] — O(K *
    # D_max) memory/compute, the fleet-scale default; "dense" keeps the
    # [T, K, K] matrices. Trajectories are format-independent (parity-tested
    # to tolerance). See docs/SCALING.md.
    contact_format: str = "sparse"
    # neighbour-slot budget for the sparse format: d_max pins the slot count
    # directly; contact_density sizes it as a fleet fraction (ceil(density *
    # K)); with both unset, a probe replays the exact contact stream and
    # picks the run's true maximum contact-set size (no overflow possible).
    # Overflowing an explicit budget is a loud error, never a truncation.
    d_max: int = 0
    contact_density: float | None = None
    # how the gossip mix W @ w executes: "jnp" (XLA tensordot / slot scan,
    # the default) | "pallas" (the gossip_mix kernels: compiled on a TPU, in
    # interpret mode elsewhere; never the jnp path in disguise)
    mixing_backend: str = "jnp"
    # communication/compute overlap (docs/SCALING.md "Overlap & multi-host"):
    # comm_bucket_mb packs the sharded mix's flattened param leaves into
    # ~this many MiB of partial-sum payload per psum_scatter, pipelined so
    # the next bucket's partial matmul issues while the previous scatter is
    # in flight. Semantics-preserving (cross-shard sums are elementwise;
    # parity-tested), ignored outside the shard_map backend; 0 restores one
    # scatter per leaf.
    comm_bucket_mb: float = 4.0
    # "sync" mixes each round's own params (paper Eq. 10). "delayed" double-
    # buffers the exchange: round t's neighbour payloads are the params that
    # were on the air while round t trained — one round stale — while each
    # vehicle's own contribution stays current (core.vehicle_axis
    # .delayed_gossip_mix). A SEMANTIC knob (changes trajectories; campaign-
    # hashed when != "sync"); scan-engine only.
    overlap: str = "sync"
    # extensions (paper Sec. V-C / Sec. VII): data-less static RSUs join the
    # federation as relays; V2V exchanges fail with probability p_drop
    num_rsus: int = 0
    p_drop: float = 0.0
    # engine controls: the fused scan engine is the default; the legacy
    # per-epoch host loop remains as the parity reference. window_size = 0
    # scans the whole run in one dispatch; > 0 chunks it (bounds host memory
    # for the [T, K, K] contact tensor on very long runs).
    use_scan_engine: bool = True
    window_size: int = 0
    # execution backend (fed.backends): "vmap" fuses the whole federation on
    # one device; "shard_map" shards the stacked vehicle axis over the
    # federation mesh's vehicle axis (launch.mesh.make_federation_mesh)
    backend: str = "vmap"
    # how the execution knobs above are chosen: "manual" runs them exactly as
    # set; "auto" resolves backend / contact_format / mixing_backend / d_max
    # at engine build time from the analytical cost model
    # (roofline.scenario_cost) — the choice and its predicted epochs/s are
    # recorded on the result's ``execution_plan``. Trajectory-neutral like
    # the knobs it resolves (hash-neutral in the campaign store).
    execution: str = "manual"


def resolve_mix_params_fn(cfg: SimulationConfig) -> Callable:
    """The gossip-mix implementation named by the ``mixing_backend`` knob.

    (The deprecated ``SimulationConfig.mix_params_fn`` callable field is
    REMOVED — it broke dataclass equality and defeated the compiled-window
    and campaign caches; register an execution backend or pass
    ``mixing_backend`` instead.)"""
    if cfg.mixing_backend == "jnp":
        return aggregation.mix_params
    if cfg.mixing_backend == "pallas":
        return gossip_ops.mix_params_pallas
    raise ValueError(
        f"unknown mixing_backend {cfg.mixing_backend!r} (jnp|pallas)")


@dataclass
class SimulationResult:
    config: SimulationConfig
    epochs_evaluated: list[int] = field(default_factory=list)
    avg_accuracy: list[float] = field(default_factory=list)
    vehicle_accuracy: list[np.ndarray] = field(default_factory=list)   # [K] per eval
    entropy: list[np.ndarray] = field(default_factory=list)            # [K] per eval
    kl_divergence: list[np.ndarray] = field(default_factory=list)      # [K] per eval
    consensus_distance: list[float] = field(default_factory=list)
    # full per-epoch traces (every global epoch, not just eval epochs):
    # mean state-vector KL-to-target (the paper's diversity measure, Eq. 9)
    # and the communication volume of that round's V2V exchanges in MB
    kl_trace: list[float] = field(default_factory=list)
    comm_mb: list[float] = field(default_factory=list)
    # mean local-training loss over the federation, every global epoch
    loss_trace: list[float] = field(default_factory=list)
    wall_time: float = 0.0
    # set when cfg.execution == "auto": the cost-model plan this run resolved
    # to (chosen knobs, predicted epochs/s, per-candidate breakdowns)
    execution_plan: dict | None = None
    # the federation state after the last epoch, as the backend left it on
    # its devices (single-federation runs of the scan engine)
    final_state: Any = field(default=None, repr=False, compare=False)

    def final_accuracy(self) -> float:
        return self.avg_accuracy[-1] if self.avg_accuracy else float("nan")

    def total_comm_mb(self) -> float:
        return float(np.sum(self.comm_mb)) if self.comm_mb else 0.0


def model_payload_bytes(params_stack) -> int:
    """Bytes of ONE vehicle's flattened model (the stack divided by its
    leading vehicle axis) — the parameter payload of a single V2V exchange."""
    leaves = jax.tree_util.tree_leaves(params_stack)
    return sum(l.size // l.shape[0] * l.dtype.itemsize for l in leaves)


def exchange_payload_mb(ctx: "EngineContext") -> float:
    """MB one directed V2V exchange ships: the model plus the [K] state
    vector (paper Sec. V-A: vehicles exchange both every contact)."""
    return (model_payload_bytes(ctx.setup.params_stack)
            + ctx.total_nodes * 4) / 1e6


def make_local_train_fn(loss_fn, optimizer):
    """Per-vehicle E local SGD steps via lax.scan (Eq. 3)."""

    @jax.named_scope("local_train")
    def local_train(params, opt_state, batch, rng):
        xs, ys = batch  # [E, B, ...], [E, B]
        steps = xs.shape[0]
        rngs = jax.random.split(rng, steps)

        def step(carry, inp):
            p, s = carry
            x, y, r = inp
            loss, grads = jax.value_and_grad(loss_fn)(p, x, y, r)
            updates, s = optimizer.update(grads, s, p)
            return (apply_updates(p, updates), s), loss

        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state), (xs, ys, rngs))
        return params, opt_state, {"loss": jnp.mean(losses)}

    return local_train


def _partition(ds, cfg: SimulationConfig):
    if cfg.distribution == "balanced_noniid":
        idx = partition_lib.balanced_noniid(ds.train_y, cfg.num_vehicles, seed=cfg.seed)
    elif cfg.distribution == "unbalanced_iid":
        sizes = (125, 375, 1125) if "cifar" in ds.name else (150, 450, 1350)
        idx = partition_lib.unbalanced_iid(len(ds.train_y), cfg.num_vehicles,
                                           size_choices=sizes, seed=cfg.seed)
    else:
        raise ValueError(cfg.distribution)
    return idx


def probe_d_max(cfg: SimulationConfig, net: topology_lib.RoadNetwork,
                chunk: int = 0) -> int:
    """The exact neighbour-slot demand of a run: replay the (deterministic,
    seeded) contact stream over the full horizon and return the largest
    contact-set size (incl. self) any participant ever sees.

    Mobility / drop streams are clones of the real run's, so an auto-probed
    ``D_max`` can never overflow. Host cost is the same O(T * K^2) distance
    precompute the dense path pays per window, chunked so the transient
    probe buffer stays ~16 MB at any fleet size (the whole point of the
    sparse format is never holding O(T * K^2)); for very long large-K runs
    pin ``cfg.d_max`` / ``cfg.contact_density`` instead to skip the probe
    (see docs/SCALING.md).
    """
    mob = mobility_lib.make_mobility(
        cfg.mobility, net, mobility_lib.MobilityConfig(
            num_vehicles=cfg.num_vehicles, epoch_duration=cfg.epoch_duration,
            comm_range=cfg.comm_range, seed=cfg.seed))
    rsu_pos = (extensions_lib.place_rsus(net, cfg.num_rsus, seed=cfg.seed)
               if cfg.num_rsus else None)
    drop_rng = np.random.default_rng(cfg.seed + 7)
    if chunk <= 0:
        total = cfg.num_vehicles + cfg.num_rsus
        chunk = max(1, min(64, (16 << 20) // (4 * total * total)))
    d_max, remaining = 1, cfg.epochs
    while remaining > 0:
        t = min(chunk, remaining)
        remaining -= t
        dense = extensions_lib.contact_window(
            mob.advance_positions(t), rsu_pos, cfg.comm_range, cfg.p_drop,
            drop_rng)
        d_max = max(d_max, topology_lib.max_contact_degree(dense))
    return d_max


class ContactStream:
    """Host-side mobility -> batched contact windows.

    ``window(T)`` advances the Manhattan process T epochs and returns the
    window in the representation named by ``cfg.contact_format``
    (core.contacts registry): the dense [T, Ktot, Ktot] contact tensor, or
    ``SparseContacts`` neighbour lists [T, Ktot, D_max] built one epoch at a
    time (RSU columns appended, dropped edges removed in both). Both RNG
    streams (mobility, drops) advance one epoch at a time, so ``window(a);
    window(b)`` equals ``window(a + b)`` row for row, and sparse windows see
    the same dropped edges as dense ones.

    For the sparse format, ``d_max`` is resolved once at construction:
    ``cfg.d_max`` if pinned, else ``ceil(contact_density * Ktot)``, else the
    exact full-horizon probe (``probe_d_max``).
    """

    def __init__(self, cfg: SimulationConfig, net: topology_lib.RoadNetwork):
        self.cfg = cfg
        self.mob = mobility_lib.make_mobility(
            cfg.mobility, net, mobility_lib.MobilityConfig(
                num_vehicles=cfg.num_vehicles, epoch_duration=cfg.epoch_duration,
                comm_range=cfg.comm_range, seed=cfg.seed))
        self.rsu_pos = (extensions_lib.place_rsus(net, cfg.num_rsus, seed=cfg.seed)
                        if cfg.num_rsus else None)
        self.drop_rng = np.random.default_rng(cfg.seed + 7)
        self.format = contacts_lib.get_contact_format(cfg.contact_format)
        self.d_max = self._resolve_d_max(net) if self.format.sparse else 0

    def _resolve_d_max(self, net: topology_lib.RoadNetwork) -> int:
        total = self.cfg.num_vehicles + self.cfg.num_rsus
        if self.cfg.d_max > 0:
            return min(self.cfg.d_max, total)
        if self.cfg.contact_density is not None:
            return max(1, min(total, int(np.ceil(
                self.cfg.contact_density * total))))
        return probe_d_max(self.cfg, net)

    def window(self, num_epochs: int):
        """The next ``num_epochs`` epochs of contacts, as a ``fed.contacts``
        span. A sparse window also counts its neighbour slots
        (``fed.contact_slots``, T x K x D_max) and the slots that hold a
        contact (``fed.contact_edges``, self slots included)."""
        with telemetry.span("fed.contacts"):
            positions = self.mob.advance_positions(num_epochs)
            if self.format.sparse:
                idx, mask = extensions_lib.neighbour_window(
                    positions, self.rsu_pos, self.cfg.comm_range,
                    self.cfg.p_drop, self.drop_rng, self.d_max)
                telemetry.count("fed.contact_slots", mask.size)
                telemetry.count("fed.contact_edges", int(mask.sum()))
                return contacts_lib.SparseContacts(idx, mask)
            return extensions_lib.contact_window(
                positions, self.rsu_pos, self.cfg.comm_range, self.cfg.p_drop,
                self.drop_rng)


@dataclass
class EngineContext:
    """Everything one federation run needs, built once per (config, seed).

    ``round_fn(state, contacts, target, batch, rng, fed_data)`` applies one
    algorithm round (the extra ``fed_data`` arg lets DFL read per-seed sample
    counts under vmap); ``sample_fn(fed_data, key)`` draws the per-epoch
    device-side batch; ``model_of(state)`` extracts the evaluable parameter
    stack (SP de-biases by the push-sum weights). All three are the
    registered algorithm's hooks bound to this run's ``setup``
    (fed.algorithms); ``bind`` rebinds them to a sharded vehicle axis for
    the shard_map backend.
    """
    cfg: SimulationConfig
    total_nodes: int
    fed_data: pipeline.FederatedData
    target: Array
    local_mask: Array | None
    contacts: ContactStream
    init_state: Any
    init_rng: Array
    round_fn: Callable
    sample_fn: Callable
    model_of: Callable
    eval_fn: Callable
    algorithm: algorithms_lib.Algorithm
    setup: algorithms_lib.AlgorithmSetup
    execution_plan: dict | None = None
    _jit_cache: dict = field(default_factory=dict, repr=False)

    def bind(self, shard) -> "EngineContext":
        """Rebind the algorithm hooks to a vehicle-axis sharding regime
        (core.vehicle_axis.VehicleSharding): the gossip mix becomes the
        sharded partial-matmul + psum_scatter contraction, and the hooks
        slice per-vehicle rows to this shard. A fresh jit cache is attached
        — the bound context traces different programs."""
        setup = replace(
            self.setup, shard=shard,
            mix_params_fn=vehicle_axis.sharded_mix(
                self.setup.mix_params_fn, shard,
                comm_bucket_mb=self.cfg.comm_bucket_mb))
        algo = self.algorithm
        return replace(
            self, setup=setup,
            round_fn=partial(algo.round, setup),
            sample_fn=partial(algo.sample, setup),
            model_of=partial(algo.model_of, setup),
            _jit_cache={})

    @property
    def window_jit(self):
        if "window" not in self._jit_cache:
            self._jit_cache["window"] = jax.jit(build_window_fn(self))
        return self._jit_cache["window"]

    @property
    def round_jit(self):
        if "round" not in self._jit_cache:
            self._jit_cache["round"] = jax.jit(self.round_fn)
        return self._jit_cache["round"]

    @property
    def eval_jit(self):
        if "eval" not in self._jit_cache:
            self._jit_cache["eval"] = jax.jit(self.eval_fn)
        return self._jit_cache["eval"]


def resolve_execution(cfg: SimulationConfig) -> tuple[SimulationConfig, dict | None]:
    """Resolve ``execution="auto"`` to a concrete configuration via the
    analytical cost model (roofline.scenario_cost) — no-op for "manual".
    Returns ``(resolved config, plan)``; the plan records the choice and is
    stamped on results / campaign rows."""
    if cfg.execution != "auto":
        return cfg, None
    from ..roofline import scenario_cost

    return scenario_cost.resolve_auto(cfg)


def build_context(cfg: SimulationConfig, dataset=None) -> EngineContext:
    """Shared setup for both the fused engine and the legacy loop: data
    partition, mobility stream, model init — then the registered algorithm
    (``fed.algorithms``) supplies state init, round, sampling, and model
    extraction. No algorithm dispatch lives here: new algorithms register
    themselves and are addressable by ``cfg.algorithm`` immediately.

    ``execution="auto"`` configs are resolved here (cost-model backend /
    format selection); the resulting plan rides on ``ctx.execution_plan``.
    The whole build is one ``fed.build_context`` span."""
    with telemetry.span("fed.build_context"):
        return _build_context(cfg, dataset)


def _build_context(cfg: SimulationConfig, dataset) -> EngineContext:
    cfg, execution_plan = resolve_execution(cfg)
    ds = dataset or data_lib.load_dataset(cfg.dataset, seed=cfg.seed)
    init_fn, loss_fn, accuracy_fn = cnn_lib.make_cnn_task(ds.name)

    idx = _partition(ds, cfg)
    # extension: RSUs are extra data-less participants appended after vehicles
    total_nodes = cfg.num_vehicles + cfg.num_rsus
    if cfg.num_rsus:
        idx = idx + [np.array([0])] * cfg.num_rsus  # dummy index, zero weight
    dense, counts = partition_lib.pad_to_uniform(idx, seed=cfg.seed)
    if cfg.num_rsus:
        counts = counts.copy()
        counts[cfg.num_vehicles:] = 0
    fed_data = pipeline.make_federated_data(ds.train_x, ds.train_y, dense, counts)
    target = state_vector.target_state(jnp.asarray(counts))
    local_mask = (jnp.asarray(extensions_lib.rsu_local_step_mask(
        cfg.num_vehicles, cfg.num_rsus)) if cfg.num_rsus else None)

    net = topology_lib.make_road_network(cfg.road_net, seed=cfg.seed)
    contacts = ContactStream(cfg, net)

    # identical random init on every vehicle (paper Alg. 1 line 1)
    rng = jax.random.PRNGKey(cfg.seed)
    rng, kinit = jax.random.split(rng)
    params0 = init_fn(kinit)
    params_stack = jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p, (total_nodes,) + p.shape).copy(), params0)

    optimizer = sgd(cfg.lr)
    local_train_fn = make_local_train_fn(loss_fn, optimizer)
    opt_stack = jax.vmap(optimizer.init)(params_stack)

    eval_x = jnp.asarray(ds.test_x[: cfg.eval_samples])
    eval_y = jnp.asarray(ds.test_y[: cfg.eval_samples])
    eval_fn = jax.vmap(lambda p: accuracy_fn(p, eval_x, eval_y))

    algo = algorithms_lib.get_algorithm(cfg.algorithm)
    setup = algorithms_lib.AlgorithmSetup(
        cfg=cfg, total_nodes=total_nodes,
        sample_shape=tuple(ds.train_x.shape[1:]), loss_fn=loss_fn,
        local_train_fn=local_train_fn, params_stack=params_stack,
        opt_stack=opt_stack, local_mask=local_mask,
        mix_params_fn=resolve_mix_params_fn(cfg))

    if cfg.overlap not in ("sync", "delayed"):
        raise ValueError(f"unknown overlap {cfg.overlap!r} (sync|delayed)")
    init_state = algo.init_state(setup)
    if cfg.overlap == "delayed":
        # the double buffer: the params each vehicle last put on the air.
        # Round 0 mixes the identical broadcast init — exactly what a real
        # fleet's first in-flight exchange would carry. Lives in the scan
        # carry so trajectories stay window-chunk-invariant.
        init_state = (init_state, params_stack)

    return EngineContext(
        cfg=cfg, total_nodes=total_nodes, fed_data=fed_data, target=target,
        local_mask=local_mask, contacts=contacts,
        init_state=init_state, init_rng=rng,
        round_fn=partial(algo.round, setup),
        sample_fn=partial(algo.sample, setup),
        model_of=partial(algo.model_of, setup),
        eval_fn=eval_fn, algorithm=algo, setup=setup,
        execution_plan=execution_plan)


def build_window_fn(ctx: EngineContext) -> Callable:
    """The fused window: scan the algorithm round over the window's contact
    graphs — dense [T, K, K] matrices or [T, K, D_max] neighbour lists.

    Returns ``window(state, rng, fed_data, target, contacts, eval_mask) ->
    (state, rng, traj)`` where ``traj`` stacks per-epoch diagnostics;
    accuracy / consensus rows are NaN on epochs the mask skips (lax.cond
    keeps the eval compute off those steps entirely).
    """
    round_fn, sample_fn = ctx.round_fn, ctx.sample_fn
    model_of, eval_fn = ctx.model_of, ctx.eval_fn
    shard = ctx.setup.shard
    # rows this trace sees: the full stack, or this shard's block
    local_nodes = vehicle_axis.local_nodes(ctx.total_nodes, shard)
    payload_mb = exchange_payload_mb(ctx)
    delayed = ctx.cfg.overlap == "delayed"
    if delayed:
        algo, setup = ctx.algorithm, ctx.setup
        # the stale-buffer combine over the (possibly shard-wrapped) mix;
        # the carried state widens to (algo state, stale params)
        delayed_mix = vehicle_axis.delayed_gossip_mix(setup.mix_params_fn,
                                                      shard)

    def delayed_round(st, contacts_t, target, batch, kr, fed_data):
        """One round under overlap="delayed": the algorithm's mix call is
        rerouted through the stale buffer, and whatever pytree the algorithm
        put on the air this round (its mix input) becomes the next buffer —
        algorithm-agnostic, whether it mixes before training (dds/dfl/d_sgd),
        after (d_fedavg), or a bias-corrected stack (sp)."""
        algo_st, stale = st
        sent = {}

        def mix(mixing, params):
            sent["payload"] = params
            return delayed_mix(mixing, params, stale)

        algo_st, diags = algo.round(replace(setup, mix_params_fn=mix),
                                    algo_st, contacts_t, target, batch, kr,
                                    fed_data)
        return (algo_st, sent.get("payload", stale)), diags

    def window(state, rng, fed_data, target, contacts, eval_mask):
        @jax.named_scope("eval")
        def evaluate(st):
            model = model_of(st)
            consensus = aggregation.consensus_distance(
                model, axis_name=shard.axis_name if shard.is_sharded else None)
            return eval_fn(model), consensus.astype(jnp.float32)

        def skip(st):
            return (jnp.full((local_nodes,), jnp.nan, jnp.float32),
                    jnp.float32(jnp.nan))

        def step(carry, inp):
            st, key = carry
            contacts_t, do_eval = inp
            key, kb, kr = jax.random.split(key, 3)
            batch = sample_fn(fed_data, kb)
            fn = delayed_round if delayed else round_fn
            st, diags = fn(st, contacts_t, target, batch, kr, fed_data)
            algo_st = st[0] if delayed else st
            accs, consensus = jax.lax.cond(do_eval, evaluate, skip, algo_st)
            # directed V2V exchanges this round: contact edges minus the
            # always-on self loops (contacts are replicated on every shard;
            # the dense matrix and the neighbour list count identically)
            edges = contacts_lib.count_edges(contacts_t)
            out = {
                "accuracy": accs,
                "consensus": consensus,
                "entropy": diags["entropy"],
                "kl_divergence": diags["kl_divergence"],
                "kl_mean": jnp.mean(diags["kl_divergence"]),
                "comm_mb": edges.astype(jnp.float32) * payload_mb,
                # per-shard mean of equal row counts -> pmean == global mean
                "loss": shard.pmean(jnp.mean(diags["loss"])),
            }
            return (st, key), out

        (state, rng), traj = jax.lax.scan(step, (state, rng), (contacts, eval_mask))
        return state, rng, traj

    return window


def _default_window(cfg: SimulationConfig, progress: bool) -> int:
    """Resolve the scan window length. With ``window_size = 0`` the whole run
    fuses into one scan — except under ``progress``, where windows align to
    the eval cadence so progress lines stream like the legacy loop did
    (trajectories are chunk-invariant, so only dispatch granularity changes).
    """
    if cfg.window_size > 0:
        return cfg.window_size
    if progress:
        return max(cfg.eval_every, 1)
    return max(cfg.epochs, 1)


def _eval_mask(cfg: SimulationConfig, start: int, length: int) -> np.ndarray:
    """Host-side eval schedule for window epochs [start, start + length)."""
    epochs = start + np.arange(length)
    return ((epochs + 1) % cfg.eval_every == 0) | (epochs == cfg.epochs - 1)


def _append_window(result: SimulationResult, traj, mask: np.ndarray, start: int,
                   num_vehicles: int, progress: bool) -> None:
    """Copy a window's trajectory back to the host (waiting for the device
    to finish it) and append it to ``result``, as a ``fed.collect`` span."""
    with telemetry.span("fed.collect"):
        acc = np.asarray(traj["accuracy"])
        ent = np.asarray(traj["entropy"])
        kl = np.asarray(traj["kl_divergence"])
        consensus = np.asarray(traj["consensus"])
        # full per-epoch traces (no eval mask): diversity + communication volume
        result.kl_trace.extend(float(v) for v in np.asarray(traj["kl_mean"]))
        result.comm_mb.extend(float(v) for v in np.asarray(traj["comm_mb"]))
        result.loss_trace.extend(float(v) for v in np.asarray(traj["loss"]))
        for i in np.nonzero(mask)[0]:
            accs = acc[i, :num_vehicles]
            result.epochs_evaluated.append(start + int(i) + 1)
            result.avg_accuracy.append(float(accs.mean()))
            result.vehicle_accuracy.append(accs)
            result.entropy.append(ent[i])
            result.kl_divergence.append(kl[i])
            result.consensus_distance.append(float(consensus[i]))
            if progress:
                print(f"  epoch {start + int(i) + 1:4d}  "
                      f"avg_acc={accs.mean():.4f}  min={accs.min():.4f}  "
                      f"max={accs.max():.4f}", flush=True)


def run_with_context(ctx: EngineContext, progress: bool = False) -> SimulationResult:
    """Drive one federation through the fused engine on the execution
    backend named by ``cfg.backend`` (fed.backends registry)."""
    from . import backends as backends_lib

    return backends_lib.get_backend(ctx.cfg.backend).run(ctx, progress=progress)


def run(cfg: SimulationConfig, dataset=None, progress: bool = False) -> SimulationResult:
    """Build a context and run it through the fused engine."""
    return run_with_context(build_context(cfg, dataset=dataset), progress=progress)


def run_seeds(cfg: SimulationConfig, seeds, dataset=None,
              progress: bool = False) -> list[SimulationResult]:
    """Run S independent federations (seeded partitions, mobility traces and
    inits) on the execution backend named by ``cfg.backend`` — one vmapped
    scan over the seed axis on the vmap backend, vehicle-sharded runs on the
    shard_map backend.

    The dataset is shared across seeds (loaded once from ``cfg`` when not
    given). Returns one ``SimulationResult`` per seed, in ``seeds`` order.
    Batch wall time is the caller's to record (the sweep runner tracks it
    per scenario): when the backend fuses all seeds into one dispatch
    (vmap), per-seed ``wall_time`` stays 0 — no per-seed attribution exists;
    when seeds run individually (shard_map), each result carries its own
    genuine wall time.

    ``execution="auto"`` is resolved HERE, before backend dispatch — the
    backend name itself is one of the knobs the cost model picks.
    """
    from . import backends as backends_lib

    cfg, plan = resolve_execution(cfg)
    results = backends_lib.get_backend(cfg.backend).run_seeds(
        cfg, seeds, dataset=dataset, progress=progress)
    if plan is not None:
        for r in results:
            r.execution_plan = plan
    return results
