"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU chips the cell asks for and exits non-zero, printing no
result, without them. Standard error ends with each number compared for
``correct`` beside its limit; standard output ends with one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``, then ``checks``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def require_chips(chips: int) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"bench: the cell needs {chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform} device(s). Nothing was run.")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import harness

    harness.prepare_jax()
    cell = harness.load_cell(ROOT, args.workload)
    require_chips(cell.chips)
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
