"""Smoke check: the DFL-DDS engine's main path, end to end, on TPU chips.

  python chip_smoke.py             # one chip: vmap backend, jnp and Pallas mix
  python chip_smoke.py --chips 4   # four chips: the shard_map backend only

The run is the paper's deployment, the ``SimulationConfig`` defaults: the
MNIST CNN at full width, K=100 vehicles on the 10x10 grid, E=8, B=80,
lr 0.1, 200 P1 steps, 2000 eval samples, sparse contacts, algorithm dds,
cut to 20 global epochs. It goes through the engine's own entry points
(``build_context`` -> ``run_with_context`` -> execution backend -> scanned
window). Times and memory it prints are one smoke reading on the named
device, not a benchmark.

One chip: the run twice on one context (cold, then warm with a fresh contact
stream), its learning checks, the same run with ``mixing_backend="pallas"``
held to the jnp trajectory, and both gossip-mix kernels called directly at
real widths against their jnp references.

Four chips: the same run on the shard_map backend, with jnp and Pallas
mixing, each held to the vmap run on one chip of the same host; the vehicle
axis must split four ways and the final state must live on all four chips.

Without a TPU it exits non-zero and runs nothing. Any failed check exits
non-zero. Only on success is the last line of standard output the JSON
object ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from dataclasses import replace

import numpy as np

EPOCHS = 20
EVAL_EVERY = 5
CHANCE = 0.1  # ten classes

# Tolerances for two runs of one federation that differ only in how the sum
# of a gossip mix is ordered (Pallas kernel vs XLA; psum_scatter over four
# shards vs one device). Each mix then differs by float32 rounding, and SGD
# amplifies that epoch by epoch until the runs are two equally valid
# realizations: at K=20 with the paper's E and B on CPU devices, the loss of
# shard_map against vmap differed by 1e-7 (relative) in epoch 0, 1e-5 in
# epoch 4, percents from epoch 7 on, and the mean accuracy by up to 1.25
# points. Hence:
# - the state-vector KL does not depend on model parameters (P1 and the
#   state aggregation see only contacts), so it must agree closely in every
#   epoch;
# - the training loss of epochs 0 and 1 must agree to 1e-3: epoch 1 trains
#   on the first mix of trained models, so a mixing fault shows there, by
#   far more;
# - the mean eval accuracy must stay within three points at every eval,
#   which catches a shard or a kernel that stops the federation learning.
# On the TPU, float32 convolutions at the default precision run one bfloat16
# pass, and two differently compiled programs (vmap over 100 vehicles,
# shard_map over 25 per chip) round differently from the first step on: the
# epoch-0 loss differed by 0.65% on a v5e and the mean accuracy by up to 4
# points. So the shard_map-vs-vmap comparison runs both programs a second
# time at float32 precision ("highest") and holds that pair to these
# tolerances; at the default precision it checks that the sharded runs learn
# and match the KL. At float32 the in-scan eval of 100 vehicles x 2000
# samples needs 18.5 GB on one v5e, more than it has, so that pair evaluates
# FLOAT32_EVAL_SAMPLES samples.
KL_RTOL = 1e-4
EARLY_EPOCHS = 2
EARLY_LOSS_RTOL = 1e-3
ACC_ATOL = 3e-2
FLOAT32_EVAL_SAMPLES = 500

# The dense mix kernel lets Mosaic choose the matmul precision. With bf16
# passes each product is off by at most 2**-8 of its size, so |err| <=
# 2**-8 * sum_j w_kj |x_j| <= 2**-8 * max|x| for a row-stochastic W. The
# gather kernel is float32 multiply-adds on the vector unit.
MATMUL_REL_BOUND = 2.0 ** -8
GATHER_ATOL = 1e-5

REPO = os.path.dirname(os.path.abspath(__file__))


def smoke_config(**overrides):
    from repro.fed.engine import SimulationConfig

    return SimulationConfig(epochs=EPOCHS, eval_every=EVAL_EVERY, **overrides)


def peak_hbm() -> str:
    """Largest ``peak_bytes_in_use`` over this host's devices."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    if not all(stats):
        return "not reported"
    return f"{max(s['peak_bytes_in_use'] for s in stats) / 2**20:.1f} MiB"


def cold_and_warm(cfg, ds, label: str):
    """Run ``cfg`` twice on one context, the second time warm on a fresh
    contact stream; print the smoke readings; return both results."""
    from repro.fed import engine

    t0 = time.perf_counter()
    ctx = engine.build_context(cfg, dataset=ds)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    cold = engine.run_with_context(ctx)
    cold_s = time.perf_counter() - t0
    ctx.contacts = engine.ContactStream(cfg, ctx.contacts.mob.net)
    t0 = time.perf_counter()
    warm = engine.run_with_context(ctx)
    warm_s = time.perf_counter() - t0
    print(f"[{label}] smoke reading, not a benchmark: set-up {setup_s:.2f} s, "
          f"cold run {cold_s:.2f} s (compilation included), warm "
          f"{cfg.epochs / warm_s:.3f} epochs/s, peak HBM {peak_hbm()}, "
          f"D_max {ctx.contacts.d_max}",
          flush=True)
    print(f"[{label}] eval epochs {warm.epochs_evaluated} mean accuracy "
          f"{[round(a, 4) for a in warm.avg_accuracy]}", flush=True)
    print(f"[{label}] mean KL first/last {warm.kl_trace[0]:.4f}/"
          f"{warm.kl_trace[-1]:.4f}, loss first/last "
          f"{warm.loss_trace[0]:.4f}/{warm.loss_trace[-1]:.4f}", flush=True)
    return cold, warm


def learning_problems(res) -> list[str]:
    """The run trained: finite loss and accuracies, accuracy above chance and
    rising, state-vector KL falling."""
    problems = []
    if not np.isfinite(res.loss_trace).all():
        problems.append("non-finite training loss")
    if not all(np.isfinite(a).all() for a in res.vehicle_accuracy):
        problems.append("non-finite eval accuracy")
    first, last = res.avg_accuracy[0], res.avg_accuracy[-1]
    if not (last > CHANCE and last > first):
        problems.append(f"accuracy did not rise above chance: first {first}, "
                        f"last {last}")
    if not res.kl_trace[-1] < res.kl_trace[0]:
        problems.append(f"mean KL did not fall: {res.kl_trace[0]} -> "
                        f"{res.kl_trace[-1]}")
    return problems


def kl_problems(got, ref, label: str) -> list[str]:
    """The state-vector KL of ``got`` follows ``ref`` in every epoch."""
    kl = np.abs(np.subtract(got.kl_trace, ref.kl_trace)
                / np.abs(ref.kl_trace)).max()
    print(f"[{label}] max rel d KL {kl:.3g} (tol {KL_RTOL})", flush=True)
    return [f"state-vector KL off by {kl} (relative)"] if kl > KL_RTOL else []


def trajectory_problems(got, ref, label: str) -> list[str]:
    """``got`` follows ``ref`` within the tolerances above."""
    if got.epochs_evaluated != ref.epochs_evaluated:
        return [f"eval epochs {got.epochs_evaluated} != {ref.epochs_evaluated}"]
    problems = kl_problems(got, ref, label)
    acc = np.abs(np.subtract(got.avg_accuracy, ref.avg_accuracy))
    early = np.abs(np.subtract(got.loss_trace[:EARLY_EPOCHS],
                               ref.loss_trace[:EARLY_EPOCHS])
                   / np.abs(ref.loss_trace[:EARLY_EPOCHS]))
    bitwise = (got.avg_accuracy == ref.avg_accuracy
               and got.loss_trace == ref.loss_trace)
    print(f"[{label}] |d mean acc| per eval {np.round(acc, 4).tolist()} (tol "
          f"{ACC_ATOL}), rel d loss in epochs 0-{EARLY_EPOCHS - 1} "
          f"{[float(f'{e:.3g}') for e in early]} (tol {EARLY_LOSS_RTOL}), "
          f"bitwise equal: {bitwise}", flush=True)
    if acc.max() > ACC_ATOL:
        problems.append(f"mean accuracy off by {acc.max()}")
    if early.max() > EARLY_LOSS_RTOL:
        problems.append(f"early loss off by {early.max()} (relative)")
    return problems


def kernel_problems(k: int, p: int, d: int) -> list[str]:
    """Both gossip-mix kernels once at [K, P] against their jnp references:
    the dense matmul on a row-stochastic [K, K], the gather on D-slot
    neighbour lists."""
    import jax.numpy as jnp

    from repro.kernels.gossip_mix import (gossip_mix_gather,
                                          gossip_mix_gather_ref,
                                          gossip_mix_matmul,
                                          gossip_mix_matmul_ref)

    r = np.random.default_rng(k + p + d)
    x = jnp.asarray(r.normal(size=(k, p)), jnp.float32)
    problems = []
    w = jnp.asarray(r.dirichlet(np.ones(k), size=k), jnp.float32)
    err = float(jnp.abs(gossip_mix_matmul(w, x)
                        - gossip_mix_matmul_ref(w, x)).max())
    bound = MATMUL_REL_BOUND * float(jnp.abs(x).max())
    print(f"[kernels] gossip_mix_matmul {k}x{k} @ {k}x{p}: max err {err:.3g} "
          f"(bound {bound:.3g})", flush=True)
    if not err <= bound:
        problems.append(f"gossip_mix_matmul at K={k} off by {err}")
    idx = jnp.asarray(r.integers(0, k, size=(k, d)), jnp.int32)
    wg = jnp.asarray(r.dirichlet(np.ones(d), size=k), jnp.float32)
    err = float(jnp.abs(gossip_mix_gather(idx, wg, x)
                        - gossip_mix_gather_ref(idx, wg, x)).max())
    print(f"[kernels] gossip_mix_gather K={k} D={d} P={p}: max err "
          f"{err:.3g} (tol {GATHER_ATOL})", flush=True)
    if not err <= GATHER_ATOL:
        problems.append(f"gossip_mix_gather at K={k} D={d} off by {err}")
    return problems


def state_placement_problems(res, n: int) -> list[str]:
    """Every leaf of the final state lives on ``n`` devices, and the
    per-vehicle leaves are split over them rather than copied."""
    import jax

    leaves = jax.tree_util.tree_leaves(res.final_state)
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    print(f"[placement] final state: {len(leaves)} leaves on "
          f"{sorted(spans)} devices, {split} split over the vehicle axis",
          flush=True)
    problems = []
    if spans != {n}:
        problems.append(f"final state spans {sorted(spans)} devices, not {n}")
    if not split:
        problems.append("no leaf of the final state is split over devices")
    return problems


class Phases:
    """Runs named phases, records every failure and keeps going, so one run
    on the chip reports all it can."""

    def __init__(self):
        self.failures: list[str] = []

    def run(self, name: str, fn, *args):
        print(f"== {name}", flush=True)
        try:
            out = fn(*args)
        except Exception:  # noqa: BLE001 — report and go on to the next phase
            traceback.print_exc()
            self.failures.append(f"{name}: raised (traceback above)")
            return None
        return out

    def check(self, name: str, problems: list[str]):
        for p in problems:
            print(f"FAIL [{name}] {p}", flush=True)
            self.failures.append(f"{name}: {p}")


def one_chip(ds, cfg, phases: Phases):
    """The default mode: vmap backend with jnp and Pallas mixing, learning
    checks, and both kernels at the model's width for this fleet and for a
    thousand-vehicle one, with the paper grid's neighbour-list width."""
    import jax

    from repro.models import cnn

    width = cnn.count_params(cnn.make_cnn_task(cfg.dataset)[0](
        jax.random.PRNGKey(0)))
    runs = phases.run("vmap, jnp mix", cold_and_warm, cfg, ds, "vmap jnp")
    if runs:
        cold, warm = runs
        phases.check("vmap, jnp mix", learning_problems(warm))
        phases.check("cold vs warm", trajectory_problems(warm, cold,
                                                         "warm vs cold"))
    pallas = phases.run("vmap, pallas mix", cold_and_warm,
                        replace(cfg, mixing_backend="pallas"), ds,
                        "vmap pallas")
    if runs and pallas:
        phases.check("vmap, pallas mix",
                     trajectory_problems(pallas[1], runs[1], "pallas vs jnp"))
    for k in (cfg.num_vehicles, 1024):
        problems = phases.run(f"kernels K={k}", kernel_problems, k, width, 12)
        phases.check(f"kernels K={k}", problems or [])


def float32_run(cfg, ds, label: str):
    """One run of ``cfg`` with float32 matmuls and convolutions."""
    import jax

    from repro.fed import engine

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        res = engine.run(cfg, dataset=ds)
    print(f"[{label}] float32 run {time.perf_counter() - t0:.2f} s, loss "
          f"epochs 0-{EARLY_EPOCHS - 1} {res.loss_trace[:EARLY_EPOCHS]}",
          flush=True)
    return res


def four_chips(ds, cfg, phases: Phases):
    """``--chips 4``: shard_map with jnp and Pallas mixing against the vmap
    run on one chip of the same host, at the default precision and at
    float32."""
    from repro.fed import backends

    shards = backends.vehicle_shards(cfg.num_vehicles + cfg.num_rsus)
    print(f"[shard_map] vehicle shards: {shards}", flush=True)
    if shards != 4:
        phases.check("shard_map", [f"{shards} vehicle shards, not 4"])
    ref = phases.run("vmap, one chip", cold_and_warm, cfg, ds, "vmap jnp")
    if ref:
        phases.check("vmap, one chip", learning_problems(ref[1]))
    cfg32 = replace(cfg, eval_samples=FLOAT32_EVAL_SAMPLES)
    ref32 = phases.run("vmap, one chip, float32", float32_run, cfg32, ds,
                       "vmap jnp")
    for mixing in ("jnp", "pallas"):
        name = f"shard_map, {mixing} mix"
        label = f"shard_map {mixing}"
        scfg = replace(cfg, backend="shard_map", mixing_backend=mixing)
        runs = phases.run(name, cold_and_warm, scfg, ds, label)
        if runs:
            phases.check(name, state_placement_problems(runs[1], 4))
            phases.check(name, learning_problems(runs[1]))
            if ref:
                phases.check(name, kl_problems(runs[1], ref[1],
                                               f"{label} vs vmap"))
        run32 = phases.run(f"{name}, float32", float32_run,
                           replace(scfg, eval_samples=FLOAT32_EVAL_SAMPLES),
                           ds, label)
        if run32 and ref32:
            phases.check(f"{name}, float32", trajectory_problems(
                run32, ref32, f"{label} vs vmap, float32"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map path over four chips and "
                         "the one-chip vmap run it is held to")
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    count = len(jax.devices())
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {device.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    print(f"device: {device.device_kind}, count {count}", flush=True)
    if args.chips == 4 and count != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, JAX sees {count}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.data import datasets

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    ds = datasets.load_dataset("mnist")
    kind = "synthetic" if ds.name.startswith("synthetic") else "real"
    print(f"data: {kind} MNIST ({ds.name}), {len(ds.train_y)} train / "
          f"{len(ds.test_y)} test", flush=True)

    phases = Phases()
    cfg = smoke_config()
    (four_chips if args.chips == 4 else one_chip)(ds, cfg, phases)

    if phases.failures:
        print(f"chip_smoke: {len(phases.failures)} check(s) failed:",
              file=sys.stderr)
        for f in phases.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
