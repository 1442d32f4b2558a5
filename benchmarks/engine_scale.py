"""Dense vs sparse contact-engine scaling: K in {8, 64, 256, 1024}.

Every (K, contact_format) cell runs in its OWN child process so peak RSS is
attributable per cell (ru_maxrss is monotonic within a process) and XLA
state never leaks across cells:

  python -m benchmarks.engine_scale                     # CI smoke: K 8, 64
  python -m benchmarks.engine_scale --ks 8 64 256 1024  # the committed sweep

Workload: the paper's DFL-DDS (P1 solve at the default 200 EG steps — the
round's dominant cost at fleet scale, O(K^3) dense vs O(K^2 * D_max)
sparse) on synthetic MNIST, E=1, B=1, eval only at the final epoch, whole
run in one scan window. The road network **grows with the fleet**
(``scale_grid``: grid side = sqrt(K) at the paper's vehicles-per-junction
density) — the physically honest scaling regime, where a bigger fleet
covers a bigger city, vehicle density and therefore D_max stay roughly
constant, and only the dense representation's O(K^2) grows.

The steady-state run is timed on a warmed jit cache with a fresh contact
stream (same pattern as benchmarks/engine_backends.py); peak RSS is the
child's ru_maxrss at exit, which covers host precompute + XLA buffers —
the dense cell holds the [T, K, K] window on host and device, the sparse
cell the [T, K, D_max] neighbour lists.

Writes ``BENCH_scale.json`` (machine-readable; docs/SCALING.md quotes it)
and prints CSV rows when driven by ``benchmarks.run --only engine_scale``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

DEFAULT_KS = (8, 64)
FULL_KS = (8, 64, 256, 1024)
FORMATS = ("dense", "sparse")

# per-K workload scaling: epochs shrink as the dense O(K^3) P1 round grows
# so the K=1024 dense cell stays minutes, not hours, on the CI-class CPU —
# K=256 runs a longer window so the [T, K, K] contact tensor (not jit-arena
# noise) dominates the peak-memory comparison; the train split keeps >= 4
# samples per vehicle under balanced_noniid
_EPOCHS = {8: 96, 64: 48, 256: 48, 1024: 10}
_N_TRAIN = {8: 2048, 64: 2048, 256: 4096, 1024: 8192}


def child_main(k: int, contact_format: str, epochs: int) -> dict:
    import resource
    import time

    from repro.data.synthetic import synthetic_mnist
    from repro.fed import engine as engine_lib
    from repro.fed import topology
    from repro.roofline import scenario_cost
    from repro.roofline.bench_schema import device_fields

    # the fleet covers a road net sized to the paper's density: ~1 vehicle
    # per junction, so contact sets (D_max) stay roughly constant with K
    side = max(3, int(round(k ** 0.5)))

    @topology.register_road_network("scale_grid")
    def scale_grid(seed: int = 0) -> topology.RoadNetwork:
        """Paper-density grid scaled with the fleet (side = sqrt(K))."""
        return topology.grid_net(side=side)

    # B=1 / E=1 / 4 eval samples keep per-vehicle conv training (identical
    # across formats) from drowning the contact-representation cost under
    # measurement; the workload is defined ONCE, next to the cost model that
    # predicts it (tests/test_scenario_cost.py replays the same configs
    # against the committed BENCH_scale.json rows)
    cfg = scenario_cost.bench_scale_config(k, contact_format, epochs)
    ds = synthetic_mnist(n_train=_N_TRAIN[k], n_test=256)

    ctx = engine_lib.build_context(cfg, dataset=ds)
    d_max = ctx.contacts.d_max
    engine_lib.run_with_context(ctx)          # compile + warm the jit caches
    ctx.contacts = engine_lib.ContactStream(cfg, ctx.contacts.mob.net)
    t0 = time.perf_counter()
    engine_lib.run_with_context(ctx)
    eps = epochs / (time.perf_counter() - t0)

    total = cfg.num_vehicles
    window_mb = (epochs * total * total * 4 / 1e6 if contact_format == "dense"
                 else epochs * total * d_max * 8 / 1e6)
    return {
        "num_vehicles": k,
        "contact_format": contact_format,
        "epochs": epochs,
        "d_max": d_max,
        "epochs_per_s": round(eps, 4),
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
        "contact_window_mb": round(window_mb, 3),
        "device": device_fields(),
    }


def run_cells(ks, out_path: str = "BENCH_scale.json") -> dict:
    repo_root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the cells are CPU measurements by design
    # pin the glibc malloc arena count: multi-arena growth is the dominant
    # run-to-run RSS noise and would swamp the contact-window delta
    env.setdefault("MALLOC_ARENA_MAX", "2")
    env["PYTHONPATH"] = f"{repo_root / 'src'}{os.pathsep}" + env.get("PYTHONPATH", "")

    results = []
    for k in ks:
        for fmt in FORMATS:
            cmd = [sys.executable, "-m", "benchmarks.engine_scale", "--cell",
                   "--k", str(k), "--format", fmt,
                   "--epochs", str(_EPOCHS.get(k, 8))]
            proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                                  timeout=3600, cwd=repo_root)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"engine_scale cell K={k} {fmt} failed:\n"
                    + proc.stderr[-4000:])
            results.append(json.loads(proc.stdout.strip().splitlines()[-1]))
            device = results[-1].pop("device")  # the same in every cell
            print(f"# K={k} {fmt}: "
                  f"{results[-1]['epochs_per_s']:.3f} epochs/s, "
                  f"{results[-1]['peak_rss_mb']:.0f} MB peak", flush=True)

    by_cell = {(r["num_vehicles"], r["contact_format"]): r for r in results}
    ratios = []
    for k in ks:
        dense, sparse = by_cell[(k, "dense")], by_cell[(k, "sparse")]
        ratios.append({
            "num_vehicles": k,
            "d_max": sparse["d_max"],
            "sparse_vs_dense_epochs_per_s": round(
                sparse["epochs_per_s"] / dense["epochs_per_s"], 3),
            "dense_minus_sparse_peak_mb": round(
                dense["peak_rss_mb"] - sparse["peak_rss_mb"], 1),
        })
    report = {
        "benchmark": "engine_scale",
        "workload": "synthetic_mnist dds (p1_steps=200) E=1 B=1 steady-state, "
                    "one scan window, paper-density scale_grid road net",
        **device,
        "results": results,
        "sparse_vs_dense": ratios,
    }
    out_file = repo_root / out_path
    out_file.write_text(json.dumps(report, indent=2) + "\n")
    return report


def main(ks=DEFAULT_KS) -> list[str]:
    """CSV rows for benchmarks.run (CI smoke scale by default)."""
    from .common import csv_row

    report = run_cells(tuple(ks))
    rows = [csv_row("name", "epochs_per_s", "peak_rss_mb", "d_max")]
    for r in report["results"]:
        rows.append(csv_row(
            f"engine_{r['contact_format']}_{r['num_vehicles']}v",
            f"{r['epochs_per_s']:.3f}", f"{r['peak_rss_mb']:.0f}",
            str(r["d_max"])))
    for r in report["sparse_vs_dense"]:
        rows.append(csv_row(
            f"sparse_vs_dense_{r['num_vehicles']}v",
            f"{r['sparse_vs_dense_epochs_per_s']:.2f}x",
            f"{r['dense_minus_sparse_peak_mb']:+.0f}MB", ""))
    rows.append(csv_row("engine_scale_json", "BENCH_scale.json",
                        "machine_readable", ""))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ks", nargs="+", type=int, default=list(DEFAULT_KS))
    ap.add_argument("--out", default="BENCH_scale.json")
    ap.add_argument("--cell", action="store_true",
                    help="internal: run ONE (k, format) cell in-process and "
                         "print its JSON row")
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--format", dest="contact_format", default="sparse",
                    choices=FORMATS)
    ap.add_argument("--epochs", type=int, default=8)
    args = ap.parse_args()

    if args.cell:
        print(json.dumps(child_main(args.k, args.contact_format, args.epochs)))
    else:
        run_cells(tuple(args.ks), args.out)
