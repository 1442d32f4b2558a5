"""Sized collective benchmark on the vehicle mesh axis: payload MB vs GB/s
for the three exchange shapes the gossip contraction can take —

* ``all_gather``             — every shard materializes the full stack (the
                               path ``sharded_mix`` exists to avoid);
* ``psum_scatter_per_leaf``  — one tiled psum_scatter per param leaf (the
                               pre-bucketing sharded mix);
* ``psum_scatter_bucketed``  — the leaves packed into one sized payload per
                               launch (``comm_bucket_mb``, the default).

BMTrain-style methodology: sweep the payload size, fit ``time = launch +
bytes / bandwidth`` on the bucketed rows, and probe how much of a scatter's
wire time a co-issued partial matmul hides (the ``overlap_fraction`` the
cost model's collective term consumes — roofline.scenario_cost
.profile_from_collective_bench). Runs in its OWN child process so the
forced host-device count binds before jax initializes:

  python -m benchmarks.collective_sweep --smoke    # CI: 3 payloads, fast
  python -m benchmarks.collective_sweep            # adds 16 / 64 MB points

Writes ``BENCH_collective.json`` (validated by roofline.bench_schema, like
the engine/scale reports; docs/SCALING.md quotes the bucket-size guidance).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

SMOKE_PAYLOADS_MB = (0.25, 1.0, 4.0)
FULL_PAYLOADS_MB = (0.25, 1.0, 4.0, 16.0, 64.0)
NUM_LEAVES = 8          # MNIST-CNN leaf count: the per-leaf path's launches
ROWS_PER_SHARD = 2      # benchmark arrays are [2 * axis, cols]
COLLECTIVES = ("all_gather", "psum_scatter_per_leaf", "psum_scatter_bucketed")


def _time_best(fn, args, reps: int) -> float:
    """Best-of-reps wall time of a jitted fn (warmup call first)."""
    import time

    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def child_main(payloads_mb, reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch import mesh as mesh_lib
    from repro.roofline.bench_schema import device_fields

    n = jax.device_count()
    mesh = mesh_lib.make_federation_mesh(
        vehicle=n, fsdp=1, model=1, devices=np.asarray(jax.devices()))
    K = ROWS_PER_SHARD * n

    def shmap(body):
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("vehicle"),),
                                     out_specs=P("vehicle"), check_vma=False))

    def gather(x):                       # [K/n, cols] -> [K, cols]
        return jax.lax.all_gather(x, "vehicle", axis=0, tiled=True)

    def scatter(x):
        # each shard contributes a same-shaped partial; broadcast the local
        # block to the full row count so the scatter moves `payload` bytes
        t = jnp.tile(x, (n, 1))          # [K, cols] partial stack
        return jax.lax.psum_scatter(t, "vehicle", scatter_dimension=0,
                                    tiled=True)

    def scatter_per_leaf(x):
        t = jnp.tile(x, (n, 1))
        chunks = jnp.split(t, NUM_LEAVES, axis=1)
        return jnp.concatenate(
            [jax.lax.psum_scatter(c, "vehicle", scatter_dimension=0,
                                  tiled=True) for c in chunks], axis=1)

    results = []
    for mb in payloads_mb:
        cols = max(NUM_LEAVES, int(mb * 2**20 / (4 * K)) // NUM_LEAVES
                   * NUM_LEAVES)
        x = jnp.asarray(np.random.default_rng(0).random((K, cols)), jnp.float32)
        payload = 4 * K * cols
        wire = (n - 1) / n * payload     # ring: per-device bytes on the wire
        for name, body in (("all_gather", gather),
                           ("psum_scatter_per_leaf", scatter_per_leaf),
                           ("psum_scatter_bucketed", scatter)):
            t = _time_best(shmap(body), (x,), reps)
            results.append({
                "collective": name,
                "payload_mb": round(payload / 2**20, 4),
                "time_s": round(t, 6),
                "wire_mb": round(wire / 2**20, 4),
                "gbytes_per_s": round(wire / t / 1e9, 4),
            })

    # overlap probe: does a co-issued (independent) partial matmul hide the
    # scatter's wire time? fraction of the cheaper term's time actually
    # hidden when the two run in one program — 0 on a synchronous backend,
    # toward 1 with genuinely async collectives
    cols = max(NUM_LEAVES, int(4.0 * 2**20 / (4 * K)))
    x = jnp.asarray(np.random.default_rng(1).random((K, cols)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(2).random((K, K)), jnp.float32)

    def mm_body(x):
        full = jnp.tile(x, (n, 1))
        return (w @ full)[:x.shape[0]]

    def fused(x):
        full = jnp.tile(x, (n, 1))
        s = jax.lax.psum_scatter(full, "vehicle", scatter_dimension=0,
                                 tiled=True)
        return s + (w @ full)[:x.shape[0]]

    t_mm = _time_best(shmap(mm_body), (x,), reps)
    t_sc = _time_best(shmap(scatter), (x,), reps)
    t_fused = _time_best(shmap(fused), (x,), reps)
    overlap = (t_mm + t_sc - t_fused) / max(min(t_mm, t_sc), 1e-12)
    overlap = float(np.clip(overlap, 0.0, 1.0))

    # BMTrain-style fit on the bucketed rows: time = launch + bytes / bw
    buck = [r for r in results if r["collective"] == "psum_scatter_bucketed"]
    xs = np.array([r["wire_mb"] * 2**20 for r in buck])
    ys = np.array([r["time_s"] for r in buck])
    slope, intercept = np.polyfit(xs, ys, 1)
    if slope <= 0:                       # degenerate on tiny sweeps
        slope = float(ys.max() / xs.max())
        intercept = 0.0
    return {
        "benchmark": "collective_sweep",
        "workload": f"[{K}, cols] f32 over a {n}-shard vehicle mesh axis, "
                    f"best of {reps}",
        **device_fields(),
        "axis_size": n,
        "num_leaves": NUM_LEAVES,
        "results": results,
        "derived": {
            "collective_launch_s": round(float(max(intercept, 1e-7)), 7),
            "collective_bytes_per_s": round(float(1.0 / slope), 1),
            "overlap_fraction": round(overlap, 4),
        },
    }


def run(payloads_mb, reps: int, devices: int,
        out_path: str = "BENCH_collective.json") -> dict:
    repo_root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # forced host devices, even beside a chip
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    env["PYTHONPATH"] = (f"{repo_root / 'src'}{os.pathsep}"
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.collective_sweep", "--child",
           "--reps", str(reps), "--payloads"] + [str(p) for p in payloads_mb]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800, cwd=repo_root)
    if proc.returncode != 0:
        raise RuntimeError("collective_sweep child failed:\n"
                           + proc.stderr[-4000:])
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    out_file = repo_root / out_path
    out_file.write_text(json.dumps(report, indent=2) + "\n")
    for r in report["results"]:
        print(f"# {r['collective']:>24} {r['payload_mb']:8.2f} MB  "
              f"{r['gbytes_per_s']:8.2f} GB/s", flush=True)
    d = report["derived"]
    print(f"# derived: launch={d['collective_launch_s']:.2e} s  "
          f"bw={d['collective_bytes_per_s'] / 1e9:.1f} GB/s  "
          f"overlap={d['overlap_fraction']:.2f}", flush=True)
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI payload set (0.25/1/4 MB) and fewer reps")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--out", default="BENCH_collective.json")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the sweep in-process, print JSON")
    ap.add_argument("--reps", type=int, default=0)
    ap.add_argument("--payloads", nargs="+", type=float, default=None)
    args = ap.parse_args()

    if args.child:
        print(json.dumps(child_main(tuple(args.payloads or SMOKE_PAYLOADS_MB),
                                    args.reps or 5)))
    else:
        payloads = SMOKE_PAYLOADS_MB if args.smoke else FULL_PAYLOADS_MB
        run(payloads, reps=3 if args.smoke else 8, devices=args.devices,
            out_path=args.out)
