"""Multi-process vehicle mesh (launch.mesh): the single-process fallback is
spec-compatible in-process, and a 2-process gloo-backed smoke test runs the
REAL cross-host path — ``initialize_multihost`` + the global-device
federation mesh + ``vehicle_axis.sharded_mix``'s psum_scatter — in
subprocesses (each process is a "host" with its own CPU device)."""
import os
import socket
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.launch import mesh as mesh_lib

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def test_single_process_initialize_is_a_noop():
    # no coordinator, no jax.distributed state touched — just the fallback
    assert mesh_lib.initialize_multihost(num_processes=1) == 1
    assert mesh_lib.initialize_multihost() == 1


def test_single_process_multihost_mesh_matches_local_spec():
    mesh = mesh_lib.make_multihost_federation_mesh()
    assert mesh.axis_names == ("vehicle", "fsdp", "model")
    assert mesh.shape["vehicle"] == jax.device_count()
    assert mesh.shape["fsdp"] == mesh.shape["model"] == 1
    # identical contract to the explicit-devices local mesh
    local = mesh_lib.make_federation_mesh(
        vehicle=jax.device_count(), fsdp=1, model=1,
        devices=np.asarray(jax.devices()))
    assert mesh.shape == local.shape and mesh.axis_names == local.axis_names


_CHILD = textwrap.dedent("""
    import sys
    port, pid = sys.argv[1], int(sys.argv[2])

    from repro.launch import mesh as mesh_lib
    n = mesh_lib.initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2,
        process_id=pid)
    assert n == 2, n

    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import aggregation, vehicle_axis

    assert jax.process_count() == 2
    mesh = mesh_lib.make_multihost_federation_mesh()
    veh = mesh.shape["vehicle"]          # global device count, spans hosts
    assert veh == jax.device_count() >= 2

    K = 2 * veh                          # two vehicle rows per shard
    rng = np.random.default_rng(0)
    W_np = rng.random((K, K)).astype(np.float32)
    W_np /= W_np.sum(axis=1, keepdims=True)
    X_np = rng.random((K, 5)).astype(np.float32)

    def put(arr, spec):
        return jax.make_array_from_callback(
            arr.shape, NamedSharding(mesh, spec), lambda i: arr[i])

    W = put(W_np, P())                   # replicated mixing matrix
    X = put(X_np, P("vehicle"))          # row-sharded vehicle stack

    shard = vehicle_axis.VehicleSharding("vehicle", veh)
    mix = vehicle_axis.sharded_mix(aggregation.mix_params, shard,
                                   comm_bucket_mb=4.0)

    def body(w, x):
        return mix(w, {"a": x, "b": 2.0 * x})["a"]

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("vehicle")),
        out_specs=P("vehicle"), check_vma=False))(W, X)

    ref = W_np @ X_np                    # the cross-host gossip contraction
    for s in out.addressable_shards:
        np.testing.assert_allclose(np.asarray(s.data), ref[s.index],
                                   atol=1e-5)
    print(f"MULTIHOST_OK {pid}", flush=True)
""")


def test_two_process_vehicle_mesh_gossip(tmp_path):
    """Two jax processes on localhost form one vehicle mesh; the sharded
    (bucketed) gossip contraction crosses the process boundary and every
    process's output shards match the dense reference."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one device per process: a host each
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CHILD, str(port), str(pid)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for pid in (0, 1)]
    outs = [p.communicate(timeout=300) for p in procs]
    for pid, (p, (out, err)) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{err[-4000:]}"
        assert f"MULTIHOST_OK {pid}" in out
