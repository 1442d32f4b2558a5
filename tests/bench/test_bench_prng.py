"""The harness pins JAX's PRNG to threefry. Under an ``rbg`` PRNG, JAX's
batching rule draws a whole vmapped batch of random bits from the batch's
first key, so the program (which vmaps all its vehicles) and the reference
(which maps blocks of 25) would draw different dropout masks once a fleet
has more than 25 vehicles: the training numbers would part while the
state-vector KL stayed exact."""
import json
import os
import subprocess
import sys

from tiny_bench import REPO

SCRIPT = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{tests!r}]
import tiny_bench
from bench import harness
harness.prepare_jax()
import jax
jax.config.update("jax_enable_compilation_cache", False)
if {override!r}:
    jax.config.update("jax_default_prng_impl", {override!r})
root = tiny_bench.make_root(Path(tempfile.mkdtemp()), num_vehicles=30, grid_side=6)
r = tiny_bench.run(root)
print(json.dumps([str(jax.config.jax_default_prng_impl), r["correct"],
                  {{k: c["value"] for k, c in r["checks"].items()}}]))
"""


def run(override: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_DEFAULT_PRNG_IMPL="rbg")
    code = SCRIPT.format(tests=str(REPO / "tests" / "bench"), override=override)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_rbg_from_the_environment_is_overridden():
    impl, correct, pinned = run("")
    assert impl == "threefry2x32"
    assert correct, pinned
    # the same run with rbg put back after the pin: the KL still agrees,
    # the training numbers part by an order of magnitude
    impl, _, rbg = run("rbg")
    assert impl == "rbg"
    assert rbg["kl_gap"] < 10 * pinned["kl_gap"] + 1e-6
    assert rbg["loss_gap"] > 10 * pinned["loss_gap"]
    assert rbg["change_gap"] > 10 * pinned["change_gap"]
