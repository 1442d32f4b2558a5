"""Quickstart: DFL-DDS in ~40 lines.

Ten vehicles drive a grid road network; each holds a non-IID shard of
(synthetic) MNIST; every global epoch they exchange models with whoever is
in radio range, choose aggregation weights by minimizing the KL divergence
of their state vectors (the paper's P1), and take local SGD steps. All 30
epochs run fused on-device in one lax.scan (the default engine; set
use_scan_engine=False for the legacy per-epoch loop).

  python examples/quickstart.py            # pip install -e . first,
                                           # or prefix with PYTHONPATH=src
  python examples/quickstart.py --smoke    # tiny run (the CI smoke test)
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.data.synthetic import synthetic_mnist
from repro.fed.simulator import SimulationConfig, run_simulation


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings so the run finishes in seconds")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = SimulationConfig(
        algorithm="dds",          # the paper's algorithm ("dfl" / "sp" = baselines)
        road_net="grid",
        num_vehicles=6 if args.smoke else 10,
        epochs=4 if args.smoke else 30,
        local_steps=2 if args.smoke else 4,  # E
        batch_size=16 if args.smoke else 32,  # B
        lr=0.15,
        eval_every=2 if args.smoke else 10,
        eval_samples=200 if args.smoke else 500,
        p1_steps=30 if args.smoke else 80,  # EG iterations for the convex problem P1
        seed=0,
    )

    n = (1_500, 300) if args.smoke else (6_000, 1_000)
    dataset = synthetic_mnist(n_train=n[0], n_test=n[1])
    result = run_simulation(cfg, dataset=dataset, progress=True)

    print("\nepoch history:", result.epochs_evaluated)
    print("avg accuracy :", [round(a, 3) for a in result.avg_accuracy])
    print("state-vector entropy (diversity) first->last: "
          f"{result.entropy[0].mean():.3f} -> {result.entropy[-1].mean():.3f} bits")
    print(f"V2V traffic: {result.total_comm_mb():.2f} MB over {cfg.epochs} epochs")
    print(f"quickstart OK: final average accuracy over {cfg.num_vehicles} "
          f"vehicles = {result.final_accuracy():.3f}")
    return result.final_accuracy()


if __name__ == "__main__":
    main()
