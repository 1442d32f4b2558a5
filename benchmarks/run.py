"""Benchmark orchestrator: one module per paper figure/table + the roofline
and kernel microbenchmarks (incl. the fused-engine vs legacy-loop
comparison) + the scenario sweep. Prints CSV blocks per benchmark.

With the package installed (pip install -e .), from the repo root:

  python -m benchmarks.run                     # everything
  python -m benchmarks.run --only fig8_mnist kernel_micro sweep_scenarios

Campaign mode runs a whole figure set through the campaign runner
(repro.launch.campaign) — every scenario multi-seed through the fused scan
engine, cached in the JSONL results store — and regenerates docs/RESULTS.md:

  python -m benchmarks.run --campaign smoke                # figs 2/3/8/9/10
  python -m benchmarks.run --campaign smoke --figures fig6 fig7
  python -m benchmarks.run --campaign full                 # paper scale

(from a bare checkout, prefix with PYTHONPATH=src)
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache
from repro.launch import campaign as campaign_lib

from . import (common, engine_scale, fig2_cdf, fig3_correlation, fig6_7_cifar,
               fig8_mnist, fig9_epochs_to_target, fig10_consensus, fig_overlap,
               kernel_micro, roofline_table, sweep_scenarios)

BENCHMARKS = {
    "fig2_cdf": fig2_cdf.main,
    "fig3_correlation": fig3_correlation.main,
    "fig8_mnist": fig8_mnist.main,
    "fig9_epochs_to_target": fig9_epochs_to_target.main,
    "fig6_7_cifar": fig6_7_cifar.main,
    "fig10_consensus": fig10_consensus.main,
    "fig_overlap": fig_overlap.main,
    "kernel_micro": kernel_micro.main,
    "engine_scale": engine_scale.main,   # smoke K by default; full sweep via
                                         # `python -m benchmarks.engine_scale`
    "roofline_table": roofline_table.main,
    "sweep_scenarios": sweep_scenarios.main,
}


def run_campaign(args) -> int:
    spec = common.campaign_spec(
        tier=args.campaign,
        figures=tuple(args.figures or common.DEFAULT_FIGURES),
        seeds=tuple(args.seeds or common.SMOKE_SEEDS),
        store_path=args.store,
        results_md=args.results_md,
        **{k: v for k, v in (("num_vehicles", args.vehicles),
                             ("epochs", args.epochs)) if v is not None})
    t0 = time.time()
    results = campaign_lib.run_campaign(spec, force=args.force, progress=True)
    for fr in results:
        print(f"\n### {fr.spec.name}: {fr.spec.title}", flush=True)
        print("\n".join(common.figure_csv(fr)), flush=True)
    n_checks = sum(len(fr.checks) for fr in results)
    n_passed = sum(c.passed for fr in results for c in fr.checks)
    print(f"\n# campaign {spec.name}: {len(results)} figures, "
          f"{n_passed}/{n_checks} ordering checks passed, "
          f"store={spec.store_path}, results_md={spec.results_md}, "
          f"{time.time() - t0:.1f}s", flush=True)
    if args.strict and n_passed < n_checks:
        return 1
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--only", nargs="*", choices=sorted(BENCHMARKS), default=None)
    ap.add_argument("--campaign", choices=("smoke", "full"), default=None,
                    help="run a figure campaign through the scan engine and "
                         "regenerate docs/RESULTS.md + the JSONL store")
    ap.add_argument("--figures", nargs="+", default=None,
                    help=f"campaign figure subset (default: "
                         f"{' '.join(common.DEFAULT_FIGURES)})")
    ap.add_argument("--seeds", nargs="+", type=int, default=None)
    ap.add_argument("--vehicles", type=int, default=None,
                    help="override the tier's vehicle count")
    ap.add_argument("--epochs", type=int, default=None,
                    help="override the tier's epoch count")
    ap.add_argument("--store", default=None,
                    help="results-store path (default results/campaign_<tier>.jsonl)")
    ap.add_argument("--results-md", default=None,
                    help="rendered report path ('' disables; defaults to "
                         "docs/RESULTS.md for the full default figure set, "
                         "no file for --figures subsets so a partial run "
                         "never overwrites the committed report)")
    ap.add_argument("--force", action="store_true",
                    help="ignore cached store rows and re-run every scenario")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if any ordering check fails")
    args = ap.parse_args()
    enable_compile_cache()

    if args.campaign:
        if args.results_md is None:
            # docs/RESULTS.md documents the DEFAULT campaign exactly; any
            # override (figure subset, seeds, scale) renders to stdout only
            # unless an explicit --results-md is given
            is_default = (
                set(args.figures or common.DEFAULT_FIGURES)
                >= set(common.DEFAULT_FIGURES)
                and args.seeds in (None, list(common.SMOKE_SEEDS))
                and args.vehicles is None and args.epochs is None)
            args.results_md = "docs/RESULTS.md" if is_default else None
        elif args.results_md == "":
            args.results_md = None
        sys.exit(run_campaign(args))

    names = args.only or list(BENCHMARKS)
    for name in names:
        t0 = time.time()
        print(f"### {name}", flush=True)
        try:
            for row in BENCHMARKS[name]():
                print(row, flush=True)
            print(f"### {name} done in {time.time() - t0:.1f}s\n", flush=True)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            print(f"### {name} FAILED: {type(e).__name__}: {e}\n", flush=True)
            raise


if __name__ == "__main__":
    main()
