"""A tiny copy of the benchmark's tree for the CPU tests: the repository's
metric readers and files, plus one cell small enough for a test run (8
vehicles on a 3x3 grid, 4 epochs of the MNIST CNN on 2,000 samples) held to
the committed limits of ``mnist_cnn.grid_k16``."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

WORKLOAD = "tiny_cnn.tiny_fleet"
LIMITS_OF = "mnist_cnn.grid_k16"
SEED = 3_000_000_019   # above 2**31: seeds wider than 32 signed bits must work


def make_root(tmp: Path, num_vehicles: int = 8, grid_side: int = 3) -> Path:
    """A benchmark tree under ``tmp`` with the tiny cell; returns its root."""
    bench = tmp / "bench"
    for d in ("configs", "traffic", "metrics", "limits"):
        shutil.copytree(REPO / "bench" / d, bench / d)
    cfg = json.loads((bench / "configs" / "mnist_cnn.json").read_text())
    cfg.update(name="tiny_cnn", n_train=2000, n_test=200, eval_samples=200,
               local_steps=2, batch_size=16, p1_steps=30)
    (bench / "configs" / "tiny_cnn.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "mnist_cnn.py", bench / "configs" / "tiny_cnn.py")
    mix = json.loads((bench / "traffic" / "grid_k16.json").read_text())
    mix.update(name="tiny_fleet", num_vehicles=num_vehicles,
               federation_epochs=4, eval_every=2, d_max_floor=8,
               road_net={"grid_side": grid_side, "spacing_m": 100.0})
    (bench / "traffic" / "tiny_fleet.json").write_text(json.dumps(mix))
    shutil.copy(bench / "limits" / f"{LIMITS_OF}.json",
                bench / "limits" / f"{WORKLOAD}.json")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny_cnn", "source": "test",
                        "file": "bench/configs/tiny_cnn.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": WORKLOAD, "config": "tiny_cnn",
                          "traffic": "tiny_fleet", "chips": 1, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, trace: bool = False, seconds: float = 0.5) -> dict:
    from bench import harness

    return harness.run_cell(root, WORKLOAD, SEED, seconds, trace)
