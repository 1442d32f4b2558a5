"""A configuration brings its own data, loss, accuracy, parameter tree and
program fields through files of its own, and the committed cell's
configuration, data and check numbers stay what they were."""
import json
import shutil
import types

import numpy as np
import pytest
import tiny_bench
from tiny_bench import REPO

OWN_CELL = "own_cnn.own_fleet"

# Appended to a copy of the MNIST CNN's module, whose loss and accuracy it
# keeps: data of its own sizes.
OWN_FUNCTIONS = '''

def make_dataset(seed, config):
    from bench.data.synthetic import make_dataset as synthetic

    return synthetic("mnist", seed, config["train_rows"], config["test_rows"])
'''

# Metric readers of the new cell, to see what the run was given.
READERS = {"program_window_size": "run.cfg.window_size",
           "train_rows_seen": "run.data.train_x.shape[0]"}


def add_own_config(root, program: dict):
    """Add the cell ``own_cnn.own_fleet`` to the tiny tree at ``root`` as new
    files and BENCHMARK.json entries only."""
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny_cnn.json").read_text())
    del cfg["n_train"], cfg["n_test"]
    cfg.update(name="own_cnn", train_rows=1500, test_rows=200, program=program)
    (bench / "configs" / "own_cnn.json").write_text(json.dumps(cfg))
    (bench / "configs" / "own_cnn.py").write_text(
        (bench / "configs" / "mnist_cnn.py").read_text() + OWN_FUNCTIONS)
    mix = json.loads((bench / "traffic" / "tiny_fleet.json").read_text())
    mix.update(name="own_fleet")
    (bench / "traffic" / "own_fleet.json").write_text(json.dumps(mix))
    shutil.copy(bench / "limits" / f"{tiny_bench.WORKLOAD}.json",
                bench / "limits" / f"{OWN_CELL}.json")
    for name, expr in READERS.items():
        (bench / "metrics" / f"{name}.py").write_text(
            f"def read(run):\n    return {expr}\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "own_cnn", "source": "test",
                            "file": "bench/configs/own_cnn.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": OWN_CELL, "config": "own_cnn",
                              "traffic": "own_fleet", "chips": 1, "why": "t"})
    spec["end_to_end"] += [{"name": name, "unit": "count", "better": "higher",
                            "bound": 0.25, "source": "host_clock",
                            "workloads": [OWN_CELL]} for name in READERS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_config_of_new_files_brings_its_data_loss_accuracy_and_fields(tmp_path):
    from bench import harness

    root = add_own_config(tiny_bench.make_root(tmp_path), {"window_size": 2})
    result = harness.run_cell(root, OWN_CELL, tiny_bench.SEED, 0.5, trace=False)
    assert result["correct"], result["checks"]
    assert result["metrics"]["program_window_size"]["value"] == 2
    assert result["metrics"]["train_rows_seen"]["value"] == 1500


def exits_before_any_compile(root, monkeypatch, name: str) -> None:
    """``run_cell`` exits with ``name`` before data, context or compile."""
    from bench import harness
    from repro.fed import engine

    def never(*_, **__):
        raise AssertionError("the run went past its program fields")

    monkeypatch.setattr(harness, "make_data", never)
    monkeypatch.setattr(engine, "build_context", never)
    counter = harness.CompileCounter()
    counter.active = True
    try:
        with pytest.raises(SystemExit, match=name):
            harness.run_cell(root, OWN_CELL, tiny_bench.SEED, 0.5, trace=False)
    finally:
        counter.close()
    assert counter.count == 0


def test_an_unknown_program_field_exits_with_its_name_before_any_compile(
        tmp_path, monkeypatch):
    root = add_own_config(tiny_bench.make_root(tmp_path),
                          {"window_size": 2, "no_such_field": 1})
    exits_before_any_compile(root, monkeypatch, "no_such_field")


@pytest.mark.parametrize("name,value", [("lr", 0.05), ("num_vehicles", 6),
                                        ("seed", 1)])
def test_a_program_field_the_harness_sets_exits_with_its_name(
        tmp_path, monkeypatch, name, value):
    """The job's, the traffic's and the run's own fields are set from the
    files the reference reads too; ``program`` may not set them apart."""
    root = add_own_config(tiny_bench.make_root(tmp_path),
                          {"window_size": 2, name: value})
    exits_before_any_compile(root, monkeypatch, name)


VOCAB, WIDTH, LENGTH = 16, 8, 9


def bigram_model():
    """A bigram language model: integer tokens in, nested parameters, a
    per-token loss and next-token accuracy; no ``apply``."""
    import jax
    import jax.numpy as jnp

    def init(key):
        ke, kw = jax.random.split(key)
        return {"embed": 0.5 * jax.random.normal(ke, (VOCAB, WIDTH)),
                "head": {"w": 0.5 * jax.random.normal(kw, (WIDTH, VOCAB)),
                         "b": jnp.zeros((VOCAB,))}}

    def logits(p, x):
        return p["embed"][x] @ p["head"]["w"] + p["head"]["b"]

    def loss(p, x, y, rng):
        logp = jax.nn.log_softmax(logits(p, x[:, :-1]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))

    def accuracy(p, x, y):
        pred = jnp.argmax(logits(p, x[:, :-1]), axis=-1)
        return jnp.mean((pred == x[:, 1:]).astype(jnp.float32))

    return types.SimpleNamespace(init=init, loss=loss, accuracy=accuracy)


def token_set(seed: int, n: int, domains: int = 4):
    """[n, LENGTH] int32 sequences, each stepping through the vocabulary by
    its domain's stride; the domain is the per-sequence label."""
    rng = np.random.default_rng(seed)
    domain = rng.integers(0, domains, n).astype(np.int32)
    start = rng.integers(0, VOCAB, n)
    x = (start[:, None] + (domain[:, None] + 1) * np.arange(LENGTH)) % VOCAB
    return x.astype(np.int32), domain


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_trains_a_token_model_with_nested_parameters(dtype):
    import jax.numpy as jnp

    from bench import check
    from bench.reference.federation import Federation, Job

    (train_x, train_y), (test_x, test_y) = token_set(1, 64), token_set(2, 16)
    data = types.SimpleNamespace(train_x=train_x, train_y=train_y,
                                 test_x=test_x, test_y=test_y,
                                 num_classes=VOCAB, name="bigram")
    job = Job(num_vehicles=4, epochs=2, eval_every=2, eval_samples=16,
              local_steps=2, batch_size=8, lr=0.5, p1_steps=10,
              p1_step_size=2.0, grid_side=3, grid_spacing=100.0,
              comm_range=100.0, epoch_duration=30.0, shards_per_vehicle=2)
    fed = Federation(bigram_model(), job, data, tiny_bench.SEED,
                     dtype=getattr(jnp, dtype))
    assert fed.x.dtype == jnp.int32 and fed.eval_x.dtype == jnp.int32
    out = fed.run()
    assert np.isfinite(out["loss"]).all() and np.isfinite(out["kl"]).all()
    acc = out["accuracy"][-1]
    assert acc.shape == (4,) and ((acc >= 0) & (acc <= 1)).all()
    norms = check.change_norms(out["last"], out["first"])
    assert set(norms) == {"['embed']", "['head']['b']", "['head']['w']"}
    assert all(np.isfinite(v) and v > 0 for v in norms.values())


def test_change_gap_refuses_trees_whose_paths_differ():
    from bench import check

    prog = {"['embed']": 1.0, "['head']['w']": 2.0}
    ref = {"['embed']": 1.0, "['head']['kernel']": 2.0}
    with pytest.raises(ValueError, match=r"\['head'\]\['w'\].*\['head'\]\['kernel'\]"):
        check.change_gap(prog, ref)


def test_grid_k16_program_config_is_unchanged():
    from bench import harness
    from repro.fed.engine import SimulationConfig

    cell = harness.load_cell(REPO, "mnist_cnn.grid_k16")
    assert harness.sim_config(cell, tiny_bench.SEED, 16) == SimulationConfig(
        algorithm="dds", dataset="mnist", distribution="balanced_noniid",
        lr=0.1, local_steps=8, batch_size=80, eval_samples=2000,
        p1_steps=200, p1_step_size=2.0, road_net="bench.grid_k16",
        num_vehicles=16, epochs=10, eval_every=10, comm_range=100.0,
        epoch_duration=30.0, mobility="manhattan", contact_format="sparse",
        mixing_backend="jnp", backend="vmap", d_max=16, seed=tiny_bench.SEED)


def test_grid_k16_data_is_the_synthetic_mnist_set():
    from bench import harness
    from bench.data.synthetic import make_dataset

    got = harness.make_data(harness.load_cell(REPO, "mnist_cnn.grid_k16"),
                            tiny_bench.SEED)
    want = make_dataset("mnist", tiny_bench.SEED)
    for name in ("train_x", "train_y", "test_x", "test_y"):
        assert np.array_equal(np.asarray(getattr(got, name)),
                              np.asarray(getattr(want, name))), name
    assert (got.num_classes, got.name) == (10, "synthetic-mnist")


def test_change_norms_and_gap_of_a_flat_dict_are_the_old_formulas():
    from bench import check

    def old_norms(last, first):
        return {k: float(np.linalg.norm(np.asarray(last[k], np.float32)
                                        - np.asarray(first[k], np.float32)))
                for k in last}

    def old_gap(prog, ref):
        floor = float(np.median(list(ref.values())))
        return max(abs(prog[k] - ref[k]) / max(ref[k], floor)
                   for k in ref if ref[k] >= check.QUIET_LEAF * floor)

    rng = np.random.default_rng(7)
    shapes = {"conv1_w": (16, 5, 5, 1, 10), "conv1_b": (16, 10),
              "fc1_w": (16, 320, 50), "fc1_b": (16, 50)}
    first = {k: rng.normal(size=s).astype(np.float32) for k, s in shapes.items()}

    def moved(scale):
        out = {k: v + rng.normal(scale=scale, size=v.shape).astype(np.float32)
               for k, v in first.items()}
        out["fc1_b"] = first["fc1_b"] + np.float32(1e-6)   # a quiet leaf
        return out

    ref_last, prog_last = moved(0.1), moved(0.12)
    assert check.change_norms(ref_last, first) == {
        f"['{k}']": v for k, v in old_norms(ref_last, first).items()}
    assert check.change_gap(check.change_norms(prog_last, first),
                            check.change_norms(ref_last, first)) == old_gap(
        old_norms(prog_last, first), old_norms(ref_last, first))
