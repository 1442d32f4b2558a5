"""Scenario sweep: a miniature Fig. 8/9-style grid in one call.

The sweep runner maps the fused scan engine over scenario axes (here
road_net x algorithm) and vmaps it over seeds inside each scenario — three
seeds of DDS advance through one jitted scan, not three serial runs. Every
axis value is registry-resolved, so the beyond-paper 'highway' corridor net
and the 'd_fedavg'/'d_sgd' baselines are sweepable by name exactly like the
paper's scenarios. Scale the same script up (vehicles/epochs/seeds, + 'sp',
+ 'random', cifar10, backend='shard_map' on multi-device hosts) to
reproduce the paper's full figure grids — or use the campaign runner
(python -m benchmarks.run --campaign smoke), which drives this same path
declaratively per paper figure. See also: python -m repro.launch.sweep
--help.

  python examples/scenario_sweep.py            # pip install -e . first,
                                               # or prefix with PYTHONPATH=src
  python examples/scenario_sweep.py --smoke    # tiny run (the CI smoke test)
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.data.synthetic import synthetic_mnist
from repro.fed.simulator import SimulationConfig
from repro.launch.sweep import SweepSpec, run_sweep, summary_rows


def main(argv=None) -> list:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings so the run finishes in seconds")
    args = ap.parse_args(argv)
    enable_compile_cache()

    base = SimulationConfig(
        num_vehicles=6 if args.smoke else 8,
        epochs=4 if args.smoke else 20,
        local_steps=2 if args.smoke else 4,
        batch_size=16 if args.smoke else 32,
        lr=0.15,
        eval_every=2 if args.smoke else 10,
        eval_samples=200 if args.smoke else 400,
        p1_steps=30 if args.smoke else 60,
    )

    spec = SweepSpec(
        road_nets=("grid", "highway"),     # 'highway' is a beyond-paper registry entry
        algorithms=("dds", "d_fedavg"),    # so is train-then-aggregate 'd_fedavg'
        seeds=(0, 1, 2),
        base=base,
    )

    n = (1_500, 300) if args.smoke else (4_000, 800)
    results = run_sweep(spec, dataset=synthetic_mnist(n_train=n[0], n_test=n[1]))

    print()
    print("\n".join(summary_rows(results)))
    print()
    for sr in results:
        epochs, curve = sr.mean_curve()
        print(f"{'/'.join(sr.key):40s} seed-mean curve "
              f"{[round(float(a), 3) for a in curve]} @ epochs {epochs}")
    print(f"scenario_sweep OK: {len(results)} scenarios x "
          f"{len(spec.seeds)} seeds")
    return results


if __name__ == "__main__":
    main()
