"""A whole run of a tiny cell on the CPU: the result line, the files found
by name, and the comparison with the reference."""
import json

import pytest
import tiny_bench


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_bench.make_root(tmp_path_factory.mktemp("bench"))


def test_result_line_keys(root):
    result = tiny_bench.run(root)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"epochs_per_s", "setup_s"}
    assert result["metrics"]["epochs_per_s"]["unit"] == "epochs/s"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] >= 1 and result["failed"] == 0
    # the compiles of set-up are reported apart from setup_s
    assert result["setup_compiled"] >= 0 and result["setup_compile_s"] > 0
    # the program on the CPU computes in float32 like the reference
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == set(json.loads(
        (root / "bench/limits" / f"{tiny_bench.WORKLOAD}.json").read_text()))
    json.dumps(result)


def test_new_config_mix_and_metric_are_found_by_name(root):
    """A new configuration, traffic mix and per-layer metric are files and
    BENCHMARK.json entries; nothing else changes.

    A configuration is ``bench/configs/<config>.json`` and
    ``<config>.py``. The module defines ``init`` (the program's own
    parameter tree, path for path), ``loss``, ``accuracy``,
    ``make_dataset(seed, config)`` and ``forward_flops_per_sample``. Its
    JSON may hold a ``"program"`` object of the program's
    ``SimulationConfig`` fields that the harness does not set itself. This
    test adds a copy of the MNIST CNN; ``test_bench_config_contract.py``
    adds one with its own data and program fields, and a token model with
    nested parameters."""
    bench = root / "bench"
    (bench / "metrics" / "answers_counted.py").write_text(
        "def read(run):\n    return run.federations\n")
    mix = json.loads((bench / "traffic" / "tiny_fleet.json").read_text())
    mix.update(name="tiny_fleet_6", num_vehicles=6)
    (bench / "traffic" / "tiny_fleet_6.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny_cnn.tiny_fleet_6", "config": "tiny_cnn",
                              "traffic": "tiny_fleet_6", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "answers_counted", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "test", "moves": "epochs_per_s",
                              "workloads": ["tiny_cnn.tiny_fleet_6"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "limits" / "tiny_cnn.tiny_fleet_6.json").write_text(
        (bench / "limits" / f"{tiny_bench.WORKLOAD}.json").read_text())
    from bench import harness

    cell = harness.load_cell(root, "tiny_cnn.tiny_fleet_6")
    assert cell.traffic["num_vehicles"] == 6
    result = harness.run_cell(root, "tiny_cnn.tiny_fleet_6", 7, 0.5, trace=True)
    assert result["metrics"]["answers_counted"]["value"] == result["attempted"]
    # a device metric finds nothing to read in a CPU trace and is left out
    assert "device_idle_share" not in result["metrics"]
    assert "step_mfu" not in result["metrics"]
    assert {"p1_solve_ms", "local_train_ms", "contact_host_ms"} <= set(result["metrics"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_same_seed_makes_the_same_data():
    from bench.data.synthetic import make_dataset

    a = make_dataset("cifar10", tiny_bench.SEED, 64, 16)
    b = make_dataset("cifar10", tiny_bench.SEED, 64, 16)
    c = make_dataset("cifar10", tiny_bench.SEED + 1, 64, 16)
    assert a.train_x.shape == (64, 32, 32, 3) and a.test_y.shape == (16,)
    assert (a.train_x == b.train_x).all() and (a.train_y == b.train_y).all()
    assert not (a.train_x == c.train_x).all()
