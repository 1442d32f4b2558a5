"""The comparison that decides ``correct``.

Once the window has closed, every timed federation's answers are held to
the plain reference (``bench.reference.federation``) of the same seed:

- ``loss_gap``: the mean training loss of each of the first
  ``LOSS_EPOCHS`` epochs, as a share of the reference's;
- ``kl_gap``: the mean state-vector KL to the target in every epoch, as a
  share of the reference's (it depends on the contacts and P1 alone, not on
  the models, so it stays close for the whole federation);
- ``acc_gap``: the mean eval accuracy at the last evaluated epoch, as an
  absolute difference;
- ``change_gap``: for the last federation, per parameter leaf (keyed by
  its path in the parameter tree), the gap between the norm of the
  program's change over the federation and the reference's, as a share of
  the larger of that leaf's reference norm and the median leaf's; the worst
  leaf counts. Leaves whose reference change is under a thousandth of the
  median leaf's are left out.

The limit of each number is in ``bench/limits/<workload>.json``; a number
without a limit there is not compared.
"""
from __future__ import annotations

import gc

import numpy as np
from jax import tree_util

LOSS_EPOCHS = 3
QUIET_LEAF = 1e-3


def change_norms(last, first) -> dict[str, float]:
    """Per leaf of a stacked parameter tree, keyed by its path
    (``jax.tree_util.keystr``), ||last - first|| over the whole stack, in
    float32 on the host."""
    firsts = dict(_leaves(first))
    return {path: float(np.linalg.norm(np.asarray(leaf, np.float32)
                                       - np.asarray(firsts[path], np.float32)))
            for path, leaf in _leaves(last)}


def _leaves(tree) -> list[tuple[str, object]]:
    flat, _ = tree_util.tree_flatten_with_path(tree)
    return [(tree_util.keystr(path), leaf) for path, leaf in flat]


def rel(a, b) -> float:
    return abs(a - b) / abs(b)


def answer_numbers(answer, ref: dict) -> dict[str, float]:
    """The numbers of one federation's answers against the reference."""
    return {
        "loss_gap": max(rel(a, b) for a, b in
                        zip(answer.loss[:LOSS_EPOCHS], ref["loss"][:LOSS_EPOCHS])),
        "kl_gap": max(rel(a, b) for a, b in zip(answer.kl, ref["kl"])),
        "acc_gap": abs(float(np.mean(answer.accuracy[-1]))
                       - float(np.mean(ref["accuracy"][-1]))),
    }


def change_gap(prog: dict[str, float], ref: dict[str, float]) -> float:
    if set(prog) != set(ref):
        raise ValueError(
            "the program's and the reference's parameter trees differ: "
            f"only the program's {sorted(set(prog) - set(ref))}, only the "
            f"reference's {sorted(set(ref) - set(prog))}")
    floor = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor)
               for k in ref if ref[k] >= QUIET_LEAF * floor)


def reference(run, dtype=None):
    import jax.numpy as jnp

    from bench.reference.federation import Federation
    from bench.harness import reference_job

    return Federation(run.cell.model, reference_job(run.cell), run.data,
                      run.seed, dtype=dtype or jnp.float32).run()


def program_change(run) -> dict[str, float]:
    return change_norms(run.last.final_state.params,
                        run.ctx.setup.params_stack)


def numbers(run, ref: dict, prog_change: dict) -> dict[str, float]:
    """Every number, the worst over the window's federations."""
    per = [answer_numbers(a, ref) for a in run.answers]
    out = {k: max(p[k] for p in per) for k in per[0]}
    out["change_gap"] = change_gap(
        prog_change, change_norms(ref["last"], ref["first"]))
    return out


def compare(run) -> dict[str, dict]:
    """Free the program's state, run the reference, and return each
    compared number with its limit. Keeps the per-federation numbers on
    ``run.per_answer`` for ``failed_answers``."""
    prog_change = program_change(run)
    run.ctx = run.last = None
    gc.collect()
    ref = reference(run)
    run.per_answer = [answer_numbers(a, ref) for a in run.answers]
    got = numbers(run, ref, prog_change)
    run.per_answer[-1]["change_gap"] = got["change_gap"]
    return {k: {"value": got[k], "limit": float(v)}
            for k, v in run.cell.limits.items()}


def failed_answers(run, checks: dict) -> int:
    """Federations of the window whose own numbers miss a limit."""
    return sum(any(p[k] > checks[k]["limit"] for k in p if k in checks)
               for p in run.per_answer)
