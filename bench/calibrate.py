"""Readings from which the limits in ``bench/limits/`` are set.

  python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... \
      [--faults half_batch state_answer] [--fault-seeds 1 2 3] [--out FILE]

For each seed, at the cell's own size and on the cell's chips: one
federation of the program (as the timed window runs it), the float32
reference, and the control (the reference in bfloat16), each program and
control federation compared with the reference as ``bench.check`` does.
For each fault and fault seed, the program with that fault planted. One
JSON line per reading goes to standard output and to ``--out``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]


def half_batch():
    """The loss of each local step over the first half of its batch."""
    from repro.models import cnn

    nll = cnn.nll_loss

    def nll_half(log_probs, labels):
        h = labels.shape[0] // 2
        return nll(log_probs[:h], labels[:h])

    return mock.patch.object(cnn, "nll_loss", nll_half)


def state_answer():
    """Vehicle 0's aggregated state vector altered where it is produced:
    zeroed, so that the vehicle forgets every mix."""
    from repro.core import state_vector

    aggregate = state_vector.aggregate

    def altered(state, mixing):
        return aggregate(state, mixing).at[0].set(0.0)

    return mock.patch.object(state_vector, "aggregate", altered)


def state_unchanged():
    """Every round returns the federation state it was given."""
    from repro.core import dfl_dds

    step = dfl_dds.dds_round

    def frozen(fed, *a, **kw):
        _, diags = step(fed, *a, **kw)
        return fed, diags

    return mock.patch.object(dfl_dds, "dds_round", frozen)


FAULTS = {"half_batch": half_batch, "state_answer": state_answer,
          "state_unchanged": state_unchanged}


def program_numbers(harness, check, cell, seed, fault=None):
    """One federation of the program (with ``fault`` planted) against the
    float32 reference."""
    with (FAULTS[fault]() if fault else contextlib.nullcontext()):
        run = harness.setup(cell, seed, time.perf_counter())
        res = harness.federation(run)
    run.answers = [harness.answer_of(res)]
    run.last = res
    change = check.program_change(run)
    run.ctx = run.last = None
    ref = check.reference(run)
    return run, ref, check.numbers(run, ref, change)


def control_numbers(harness, check, run, ref):
    """The bfloat16 reference in the program's place."""
    import jax.numpy as jnp

    low = check.reference(run, dtype=jnp.bfloat16)
    run.answers = [harness.Answer(low["loss"], low["kl"], low["accuracy"])]
    return check.numbers(run, ref, check.change_norms(low["last"], low["first"]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[], choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from bench import check, harness

    harness.prepare_jax()
    cell = harness.load_cell(ROOT, args.workload)
    out = args.out.open("a") if args.out else None

    def emit(kind, seed, numbers, seconds):
        line = json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                           "seconds": seconds, **numbers})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in args.seeds:
        t = time.perf_counter()
        run, ref, got = program_numbers(harness, check, cell, seed)
        emit("program", seed, got, time.perf_counter() - t)
        t = time.perf_counter()
        emit("control", seed, control_numbers(harness, check, run, ref),
             time.perf_counter() - t)
    for fault in args.faults:
        for seed in args.fault_seeds:
            t = time.perf_counter()
            _, _, got = program_numbers(harness, check, cell, seed, fault)
            emit(fault, seed, got, time.perf_counter() - t)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
