"""End-to-end driver: the paper's experiment, start to finish.

Trains the paper's 21,840-parameter MNIST CNN with DFL-DDS across a 24-vehicle
federation on a grid road network for 150 global epochs (600 local steps per
vehicle), evaluating per-vehicle accuracy, diversity (entropy / KL), and
consensus distance along the way — then prints the paper's headline
comparison against the DFL and SP baselines.

Runtime: ~15-25 min on one CPU core (use --epochs 40 for a quick pass).

  PYTHONPATH=src python examples/vehicular_mnist_e2e.py [--epochs 150]
"""
import argparse
import sys

import numpy as np

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.data.synthetic import synthetic_mnist
from repro.fed import metrics
from repro.fed.simulator import SimulationConfig, run_simulation


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=150)
    ap.add_argument("--vehicles", type=int, default=24)
    ap.add_argument("--road-net", default="grid")
    args = ap.parse_args()
    enable_compile_cache()

    ds = synthetic_mnist(n_train=24_000, n_test=2_000)
    results = {}
    for algo in ("dds", "dfl", "sp"):
        print(f"=== {algo.upper()} ===")
        cfg = SimulationConfig(
            algorithm=algo, road_net=args.road_net,
            num_vehicles=args.vehicles, epochs=args.epochs,
            local_steps=4, batch_size=32, lr=0.15,
            eval_every=max(args.epochs // 10, 1), eval_samples=1_000,
            p1_steps=80, seed=0)
        results[algo] = run_simulation(cfg, dataset=ds, progress=True)

    print("\n================= summary =================")
    print(f"{'algorithm':12s} {'final avg acc':>14s} {'min vehicle':>12s} "
          f"{'entropy':>9s} {'consensus':>10s}")
    for algo, res in results.items():
        accs = res.vehicle_accuracy[-1]
        print(f"{algo:12s} {res.final_accuracy():14.4f} {accs.min():12.4f} "
              f"{res.entropy[-1].mean():9.3f} {res.consensus_distance[-1]:10.5f}")

    dds, dfl, sp = (results[a] for a in ("dds", "dfl", "sp"))
    print("\npaper claims on this run:")
    print(f"  DFL-DDS >= DFL   (avg acc): {dds.final_accuracy() >= dfl.final_accuracy() - 0.02}")
    print(f"  DFL-DDS >= SP    (avg acc): {dds.final_accuracy() >= sp.final_accuracy() - 0.02}")
    corr = metrics.pearson(sp.vehicle_accuracy[-1], sp.entropy[-1])
    print(f"  accuracy-diversity Pearson (SP): {corr:.3f} (paper: strongly positive)")
    cd = np.mean(dds.consensus_distance) <= np.mean(dfl.consensus_distance) * 1.1
    print(f"  DDS consensus distance <= DFL: {cd}")


if __name__ == "__main__":
    main()
