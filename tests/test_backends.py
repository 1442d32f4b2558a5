"""Plugin layers of the fused engine: the algorithm/scenario registries and
the execution backends.

The registry-completeness parity test runs EVERY registered algorithm
through the legacy per-epoch loop, the vmap backend, and the shard_map
backend and holds all three to identical eval trajectories. In the default
single-device suite the shard_map leg exercises the full shard_map program
(mesh, specs, psum_scatter) at one shard; the dedicated CI job re-runs this
file under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` where the
vehicle axis genuinely splits 4 ways, and a subprocess smoke below keeps
that multi-device path exercised even in the single-device suite.
"""
import os
import subprocess
import sys
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.core import aggregation
from repro.data.synthetic import synthetic_mnist
from repro.fed import algorithms, backends, engine
from repro.fed import mobility as mobility_lib
from repro.fed import topology as topology_lib
from repro.fed.simulator import SimulationConfig, run_simulation
from repro.launch import sweep as sweep_lib
from repro.launch.mesh import make_federation_mesh


@pytest.fixture(scope="module")
def tiny_ds():
    return synthetic_mnist(n_train=1200, n_test=240)


def _tiny_cfg(**kw):
    # 8 nodes: divides over 1, 2, and 4 vehicle shards
    base = dict(algorithm="dds", num_vehicles=8, epochs=4, eval_every=2,
                eval_samples=240, local_steps=2, batch_size=8, p1_steps=30,
                lr=0.15, seed=0)
    base.update(kw)
    return SimulationConfig(**base)


# ---------------------------------------------------------------------------
# registries


def test_algorithm_registry_contents():
    names = algorithms.available_algorithms()
    assert {"dds", "dfl", "sp", "d_fedavg", "d_sgd"} <= set(names)
    assert algorithms.get_algorithm("dds").name == "dds"


def test_unknown_names_raise_with_choices():
    with pytest.raises(ValueError, match="d_fedavg"):
        algorithms.get_algorithm("nope")
    with pytest.raises(ValueError, match="highway"):
        topology_lib.make_road_network("nope")
    with pytest.raises(ValueError, match="manhattan"):
        mobility_lib.make_mobility("nope", None, None)
    with pytest.raises(ValueError, match="shard_map"):
        backends.get_backend("nope")
    with pytest.raises(ValueError, match="pallas"):
        engine.resolve_mix_params_fn(SimulationConfig(mixing_backend="nope"))


def test_backend_registry_contents():
    assert {"vmap", "shard_map"} <= set(backends.available_backends())


def test_road_network_registry_and_highway():
    # registry resolution only — highway's geometry is covered in
    # tests/test_topology_mobility.py::test_highway_structure_and_mobility
    assert {"grid", "random", "spider", "highway"} <= set(
        topology_lib.available_road_networks())
    assert topology_lib.make_road_network("highway").name == "highway"


def test_mobility_registry():
    assert "manhattan" in mobility_lib.available_mobility_models()
    net = topology_lib.make_road_network("grid")
    mob = mobility_lib.make_mobility(
        "manhattan", net, mobility_lib.MobilityConfig(num_vehicles=3))
    assert isinstance(mob, mobility_lib.ManhattanMobility)
    assert mob.advance_positions(2).shape == (2, 3, 2)


def test_register_new_algorithm_reaches_engine(tiny_ds):
    """The extension contract: registering = runnable by name, no engine
    edits. A thin subclass that reuses DDS hooks under a new name."""

    @algorithms.register_algorithm
    class Echo(algorithms.Algorithm):
        name = "_test_echo"

        def init_state(self, setup):
            return algorithms.get_algorithm("dds").init_state(setup)

        def round(self, setup, *a):
            return algorithms.get_algorithm("dds").round(setup, *a)

        def model_of(self, setup, state):
            return state.params

        def state_pspec(self, setup, axis_name):
            return algorithms.federation_state_pspec(setup, axis_name)

    try:
        cfg = _tiny_cfg(algorithm="_test_echo", epochs=2, eval_every=2)
        res = run_simulation(cfg, dataset=tiny_ds)
        assert np.isfinite(res.final_accuracy())
    finally:
        algorithms.base._ALGORITHMS.pop("_test_echo", None)


# ---------------------------------------------------------------------------
# config ergonomics (mixing_backend knob; mix_params_fn field is REMOVED)


def test_config_equality_and_replace():
    # the bare-callable field used to break dataclass equality
    assert SimulationConfig() == SimulationConfig()
    assert replace(SimulationConfig(), epochs=7).epochs == 7


def test_mixing_backend_resolution():
    assert engine.resolve_mix_params_fn(
        SimulationConfig()) is aggregation.mix_params
    from repro.kernels.gossip_mix.ops import mix_params_pallas
    assert engine.resolve_mix_params_fn(
        SimulationConfig(mixing_backend="pallas")) is mix_params_pallas


def test_mix_params_fn_field_is_removed():
    """The PR-2 deprecation shim is gone: pass mixing_backend (or register a
    backend) — a callable config field can't key any of the caches."""
    with pytest.raises(TypeError):
        SimulationConfig(mix_params_fn=aggregation.mix_params)


@pytest.mark.parametrize("contact_format", ["dense", "sparse"])
def test_pallas_mixing_backend_matches_jnp(tiny_ds, contact_format):
    cfg = _tiny_cfg(epochs=3, eval_every=3, contact_format=contact_format)
    jnp_res = run_simulation(cfg, dataset=tiny_ds)
    pallas_res = run_simulation(replace(cfg, mixing_backend="pallas"),
                                dataset=tiny_ds)
    np.testing.assert_allclose(pallas_res.avg_accuracy, jnp_res.avg_accuracy,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# registry completeness: every algorithm, all three execution paths, both
# contact formats


@pytest.mark.parametrize("contact_format", ["dense", "sparse"])
@pytest.mark.parametrize("algorithm", algorithms.available_algorithms())
def test_every_algorithm_parity_across_backends(tiny_ds, algorithm,
                                                contact_format):
    """Legacy loop == vmap backend == shard_map backend, per algorithm and
    contact format."""
    cfg = _tiny_cfg(algorithm=algorithm, contact_format=contact_format)
    legacy = run_simulation(replace(cfg, use_scan_engine=False), dataset=tiny_ds)
    vmap_res = run_simulation(cfg, dataset=tiny_ds)
    shard_res = run_simulation(replace(cfg, backend="shard_map"), dataset=tiny_ds)

    for res in (vmap_res, shard_res):
        assert res.epochs_evaluated == legacy.epochs_evaluated
        np.testing.assert_allclose(res.avg_accuracy, legacy.avg_accuracy,
                                   atol=1e-5)
        np.testing.assert_allclose(res.vehicle_accuracy,
                                   legacy.vehicle_accuracy, atol=1e-5)
        np.testing.assert_allclose(res.entropy, legacy.entropy, atol=1e-5)
        np.testing.assert_allclose(res.kl_divergence, legacy.kl_divergence,
                                   atol=1e-5)
        np.testing.assert_allclose(res.consensus_distance,
                                   legacy.consensus_distance, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(res.loss_trace, legacy.loss_trace,
                                   rtol=1e-5)
        assert len(res.loss_trace) == cfg.epochs


@pytest.mark.parametrize("algorithm", algorithms.available_algorithms())
def test_every_algorithm_sparse_matches_dense(tiny_ds, algorithm):
    """The tentpole acceptance: the sparse neighbour-list engine reproduces
    the dense trajectories for every registered algorithm at K=8."""
    cfg = _tiny_cfg(algorithm=algorithm)
    dense = run_simulation(replace(cfg, contact_format="dense"), dataset=tiny_ds)
    sparse = run_simulation(cfg, dataset=tiny_ds)
    assert sparse.epochs_evaluated == dense.epochs_evaluated
    np.testing.assert_allclose(sparse.avg_accuracy, dense.avg_accuracy,
                               atol=1e-5)
    np.testing.assert_allclose(sparse.vehicle_accuracy,
                               dense.vehicle_accuracy, atol=1e-5)
    np.testing.assert_allclose(sparse.entropy, dense.entropy, atol=1e-5)
    np.testing.assert_allclose(sparse.kl_divergence, dense.kl_divergence,
                               atol=1e-5)
    np.testing.assert_allclose(sparse.comm_mb, dense.comm_mb, rtol=1e-6)


def test_d_max_overflow_is_a_loud_error(tiny_ds):
    """An explicit slot budget smaller than a real contact set must raise,
    not truncate: comm_range=3000 makes the 8-vehicle fleet a clique (9
    slots incl. self with an RSU), d_max=4 cannot hold it."""
    cfg = _tiny_cfg(epochs=2, eval_every=2, comm_range=3000.0, d_max=4)
    with pytest.raises(ValueError, match="overflow"):
        run_simulation(cfg, dataset=tiny_ds)
    # the auto probe sizes the slots from the exact stream instead: no error
    auto = run_simulation(replace(cfg, d_max=0), dataset=tiny_ds)
    assert np.isfinite(auto.final_accuracy())


def test_contact_density_knob_sets_slots(tiny_ds):
    """contact_density pins D_max as a fleet fraction (here 4 of 8 slots):
    plenty for the sparse grid contacts at K=8, so the run succeeds and the
    stream reports the density-derived width."""
    cfg = _tiny_cfg(epochs=2, eval_every=2, contact_density=0.5)
    ctx = engine.build_context(cfg, dataset=tiny_ds)
    assert ctx.contacts.d_max == 4
    res = engine.run_with_context(ctx)
    assert np.isfinite(res.final_accuracy())


def test_shard_map_parity_with_rsus_and_drops(tiny_ds):
    """RSU local-mask row slicing + dropped edges under the sharded axis
    (6 vehicles + 2 RSUs = 8 nodes, divisible over 1/2/4 shards)."""
    cfg = _tiny_cfg(num_vehicles=6, num_rsus=2, p_drop=0.25, epochs=5,
                    eval_every=2)
    vmap_res = run_simulation(cfg, dataset=tiny_ds)
    shard_res = run_simulation(replace(cfg, backend="shard_map"),
                               dataset=tiny_ds)
    assert shard_res.epochs_evaluated == vmap_res.epochs_evaluated
    np.testing.assert_allclose(shard_res.avg_accuracy, vmap_res.avg_accuracy,
                               atol=1e-5)
    np.testing.assert_allclose(shard_res.entropy, vmap_res.entropy, atol=1e-5)
    assert all(len(a) == cfg.num_vehicles for a in shard_res.vehicle_accuracy)


def test_shard_map_handles_indivisible_vehicle_count(tiny_ds):
    """7 nodes on any device count: the backend picks the largest feasible
    shard count (possibly 1) instead of failing."""
    cfg = _tiny_cfg(num_vehicles=7, epochs=2, eval_every=2,
                    backend="shard_map")
    res = run_simulation(cfg, dataset=tiny_ds)
    assert np.isfinite(res.final_accuracy())


def test_shard_map_run_seeds_matches_vmap(tiny_ds):
    cfg = _tiny_cfg(epochs=3, eval_every=3)
    vmap_seeds = engine.run_seeds(cfg, seeds=(0, 1), dataset=tiny_ds)
    shard_seeds = engine.run_seeds(replace(cfg, backend="shard_map"),
                                   seeds=(0, 1), dataset=tiny_ds)
    for v, s in zip(vmap_seeds, shard_seeds):
        assert s.epochs_evaluated == v.epochs_evaluated
        np.testing.assert_allclose(s.avg_accuracy, v.avg_accuracy, atol=1e-5)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="multi-device sharding needs >= 4 devices "
                           "(the forced-host-device CI job)")
def test_shard_map_actually_uses_all_devices():
    assert backends.vehicle_shards(8) == 4
    mesh = make_federation_mesh(vehicle=4, fsdp=1, model=1,
                                devices=np.asarray(jax.devices()[:4]))
    assert mesh.shape == {"vehicle": 4, "fsdp": 1, "model": 1}


def test_multi_device_shard_parity_subprocess(tiny_ds):
    """Force 4 host devices in a child process and require vmap==shard_map
    trajectories with the vehicle axis genuinely split 4 ways — the
    acceptance-criterion run, kept alive in single-device suites."""
    if jax.device_count() >= 4:
        pytest.skip("already multi-device; the parametrized parity test "
                    "covers the sharded path in-process")
    script = """
import numpy as np
from dataclasses import replace
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.data.synthetic import synthetic_mnist
from repro.fed.simulator import SimulationConfig, run_simulation

ds = synthetic_mnist(n_train=800, n_test=160)
cfg = SimulationConfig(algorithm="dds", num_vehicles=8, epochs=3, eval_every=3,
                       eval_samples=160, local_steps=1, batch_size=8,
                       p1_steps=20, lr=0.15, seed=0)
vmap_res = run_simulation(cfg, dataset=ds)
shard_res = run_simulation(replace(cfg, backend="shard_map"), dataset=ds)
np.testing.assert_allclose(shard_res.avg_accuracy, vmap_res.avg_accuracy, atol=1e-5)
np.testing.assert_allclose(shard_res.vehicle_accuracy, vmap_res.vehicle_accuracy, atol=1e-5)
# the final state lives on all four devices, the model rows split over them
leaves = jax.tree_util.tree_leaves(shard_res.final_state)
assert all(len(l.sharding.device_set) == 4 for l in leaves)
assert any(not l.sharding.is_fully_replicated for l in leaves)
print("SHARD_PARITY_OK")
"""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SHARD_PARITY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# bucketed collectives (comm_bucket_mb) and delayed gossip (overlap="delayed")


def test_comm_bucketing_is_semantics_preserving(tiny_ds):
    """The bucketed exchange regroups the sharded mix's psum_scatters —
    off (per-leaf), default (4 MB), and tiny (per-leaf-sized buckets) must
    all reproduce the vmap trajectories."""
    cfg = _tiny_cfg(epochs=3, eval_every=3)
    vmap_res = run_simulation(cfg, dataset=tiny_ds)
    for bucket_mb in (0.0, 4.0, 0.001):
        shard = run_simulation(
            replace(cfg, backend="shard_map", comm_bucket_mb=bucket_mb),
            dataset=tiny_ds)
        np.testing.assert_allclose(shard.avg_accuracy, vmap_res.avg_accuracy,
                                   atol=1e-5)
        np.testing.assert_allclose(shard.vehicle_accuracy,
                                   vmap_res.vehicle_accuracy, atol=1e-5)


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
@pytest.mark.parametrize("algorithm", algorithms.available_algorithms())
def test_delayed_gossip_degenerate_parity_is_exact(tiny_ds, algorithm,
                                                   backend):
    """With no live contacts (p_drop=1.0 -> W = I) the delayed mode's
    neighbour term is exactly zero and its self weight exactly one, so the
    trajectory must be BITWISE identical to synchronous gossip — every
    algorithm, both backends."""
    cfg = _tiny_cfg(algorithm=algorithm, backend=backend, p_drop=1.0,
                    epochs=3, eval_every=3)
    sync = run_simulation(cfg, dataset=tiny_ds)
    delayed = run_simulation(replace(cfg, overlap="delayed"), dataset=tiny_ds)
    np.testing.assert_array_equal(delayed.avg_accuracy, sync.avg_accuracy)
    np.testing.assert_array_equal(delayed.vehicle_accuracy,
                                  sync.vehicle_accuracy)


def test_delayed_gossip_learns_and_differs_from_sync(tiny_ds):
    """With live contacts the one-round-stale neighbour payloads change the
    trajectory (it would be a no-op bug if they didn't) but training still
    converges to a finite model."""
    cfg = _tiny_cfg(epochs=4, eval_every=2)
    sync = run_simulation(cfg, dataset=tiny_ds)
    delayed = run_simulation(replace(cfg, overlap="delayed"), dataset=tiny_ds)
    assert np.isfinite(delayed.final_accuracy())
    assert not np.array_equal(delayed.avg_accuracy, sync.avg_accuracy)


def test_delayed_gossip_shard_map_matches_vmap(tiny_ds):
    """The double-buffered carry shards like the model stack: delayed
    trajectories agree across backends with live contacts."""
    cfg = _tiny_cfg(epochs=4, eval_every=2, overlap="delayed")
    vmap_res = run_simulation(cfg, dataset=tiny_ds)
    shard_res = run_simulation(replace(cfg, backend="shard_map"),
                               dataset=tiny_ds)
    assert shard_res.epochs_evaluated == vmap_res.epochs_evaluated
    np.testing.assert_allclose(shard_res.avg_accuracy, vmap_res.avg_accuracy,
                               atol=1e-5)
    np.testing.assert_allclose(shard_res.vehicle_accuracy,
                               vmap_res.vehicle_accuracy, atol=1e-5)


def test_delayed_gossip_requires_scan_engine(tiny_ds):
    cfg = _tiny_cfg(overlap="delayed", use_scan_engine=False)
    with pytest.raises(ValueError, match="scan engine"):
        run_simulation(cfg, dataset=tiny_ds)


def test_unknown_overlap_mode_rejected(tiny_ds):
    with pytest.raises(ValueError, match="delayed"):
        engine.build_context(_tiny_cfg(overlap="nope"), dataset=tiny_ds)


# ---------------------------------------------------------------------------
# sweep integration: new names by registry, scenario-level wall time


def test_sweep_accepts_new_algorithms_and_road_nets(tiny_ds):
    base = _tiny_cfg(epochs=2, eval_every=2)
    spec = sweep_lib.SweepSpec(road_nets=("highway",),
                               algorithms=("d_fedavg", "d_sgd"),
                               seeds=(0,), base=base)
    results = sweep_lib.run_sweep(spec, dataset=tiny_ds)
    assert [sr.key for sr in results] == [
        ("highway", "balanced_noniid", "d_fedavg"),
        ("highway", "balanced_noniid", "d_sgd")]
    for sr in results:
        assert np.isfinite(sr.final_accuracies()).all()


def test_sweep_records_wall_time_once_per_scenario(tiny_ds):
    base = _tiny_cfg(epochs=2, eval_every=2)
    spec = sweep_lib.SweepSpec(algorithms=("dds",), seeds=(0, 1), base=base)
    (sr,) = sweep_lib.run_sweep(spec, dataset=tiny_ds)
    # scenario owns the batch wall time; seed results no longer replicate it
    assert sr.wall_time > 0.0
    assert all(r.wall_time == 0.0 for r in sr.results)
    rows = sweep_lib.summary_rows([sr])
    assert rows[1].split(",")[-1] == f"{sr.wall_time:.1f}"
