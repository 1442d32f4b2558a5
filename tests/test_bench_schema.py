"""BENCH_*.json schema: the committed benchmark artifacts satisfy the
contract the cost-model validation suite replays, and drifted output (missing
keys, wrong types, inconsistent ratios, missing cells) fails loudly."""
import copy
import json
from pathlib import Path

import pytest

from repro.roofline.bench_schema import (
    BenchSchemaError, load_collective_report, load_engine_report,
    load_scale_report, validate_collective_report, validate_engine_report,
    validate_scale_report)

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def engine_report():
    return load_engine_report(str(REPO_ROOT / "BENCH_engine.json"))


@pytest.fixture(scope="module")
def scale_report():
    return load_scale_report(str(REPO_ROOT / "BENCH_scale.json"))


@pytest.fixture(scope="module")
def collective_report():
    return load_collective_report(str(REPO_ROOT / "BENCH_collective.json"))


def test_committed_engine_report_valid(engine_report):
    assert engine_report["benchmark"] == "engine_backends"
    assert engine_report["device_count"] >= 1
    assert {r["num_vehicles"] for r in engine_report["results"]} >= {8, 64}


def test_committed_scale_report_valid(scale_report):
    ks = {r["num_vehicles"] for r in scale_report["results"]}
    assert ks >= {8, 64, 256, 1024}
    # every K carries both formats (validator guarantees it; assert anyway)
    cells = {(r["num_vehicles"], r["contact_format"])
             for r in scale_report["results"]}
    assert all((k, fmt) in cells for k in ks for fmt in ("dense", "sparse"))


def test_engine_missing_key_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    del bad["results"][0]["vmap_epochs_per_s"]
    with pytest.raises(BenchSchemaError, match="vmap_epochs_per_s"):
        validate_engine_report(bad)


def test_engine_wrong_type_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    bad["results"][0]["num_vehicles"] = "8"
    with pytest.raises(BenchSchemaError, match="num_vehicles"):
        validate_engine_report(bad)


def test_engine_inconsistent_ratio_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    bad["results"][0]["shard_vs_vmap"] = 99.0
    with pytest.raises(BenchSchemaError, match="inconsistent"):
        validate_engine_report(bad)


def test_engine_nonpositive_rate_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    bad["results"][0]["vmap_epochs_per_s"] = 0.0
    with pytest.raises(BenchSchemaError, match="out of range"):
        validate_engine_report(bad)


def test_engine_wrong_benchmark_name_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    bad["benchmark"] = "something_else"
    with pytest.raises(BenchSchemaError, match="expected benchmark"):
        validate_engine_report(bad)


def test_scale_missing_cell_rejected(scale_report):
    bad = copy.deepcopy(scale_report)
    bad["results"] = [r for r in bad["results"]
                      if not (r["num_vehicles"] == 64
                              and r["contact_format"] == "dense")]
    with pytest.raises(BenchSchemaError, match="missing the dense cell"):
        validate_scale_report(bad)


def test_scale_sparse_without_d_max_rejected(scale_report):
    bad = copy.deepcopy(scale_report)
    sparse = next(r for r in bad["results"] if r["contact_format"] == "sparse")
    sparse["d_max"] = 0
    with pytest.raises(BenchSchemaError, match="d_max"):
        validate_scale_report(bad)


def test_scale_unknown_format_rejected(scale_report):
    bad = copy.deepcopy(scale_report)
    bad["results"][0]["contact_format"] = "csr"
    with pytest.raises(BenchSchemaError, match="contact_format"):
        validate_scale_report(bad)


def test_committed_collective_report_valid(collective_report):
    assert collective_report["benchmark"] == "collective_sweep"
    assert collective_report["device_count"] >= 1
    assert collective_report["axis_size"] >= 1
    names = {r["collective"] for r in collective_report["results"]}
    assert {"psum_scatter_per_leaf", "psum_scatter_bucketed"} <= names
    d = collective_report["derived"]
    assert d["collective_launch_s"] > 0
    assert d["collective_bytes_per_s"] > 0
    assert 0.0 <= d["overlap_fraction"] <= 1.0


def test_collective_missing_derived_key_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    del bad["derived"]["overlap_fraction"]
    with pytest.raises(BenchSchemaError, match="overlap_fraction"):
        validate_collective_report(bad)


def test_collective_overlap_out_of_range_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    bad["derived"]["overlap_fraction"] = 1.5
    with pytest.raises(BenchSchemaError, match="overlap_fraction"):
        validate_collective_report(bad)


def test_collective_unknown_name_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    bad["results"][0]["collective"] = "all_to_all"
    with pytest.raises(BenchSchemaError, match="collective"):
        validate_collective_report(bad)


def test_collective_missing_bucketed_rows_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    bad["results"] = [r for r in bad["results"]
                      if r["collective"] != "psum_scatter_bucketed"]
    with pytest.raises(BenchSchemaError, match="psum_scatter_bucketed"):
        validate_collective_report(bad)


def test_collective_bool_derived_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    bad["derived"]["overlap_fraction"] = True
    with pytest.raises(BenchSchemaError, match="overlap_fraction"):
        validate_collective_report(bad)


def test_collective_nonpositive_rate_rejected(collective_report):
    bad = copy.deepcopy(collective_report)
    bad["results"][0]["gbytes_per_s"] = 0.0
    with pytest.raises(BenchSchemaError, match="out of range"):
        validate_collective_report(bad)


def test_collective_feeds_the_cost_model_profile(collective_report):
    from repro.roofline import scenario_cost

    prof = scenario_cost.profile_from_collective_bench(collective_report)
    d = collective_report["derived"]
    assert prof.collective_bytes_per_s == d["collective_bytes_per_s"]
    assert prof.overlap_fraction == d["overlap_fraction"]
    assert prof.collective_launch_s >= d["collective_launch_s"]


def test_empty_results_rejected(engine_report):
    bad = copy.deepcopy(engine_report)
    bad["results"] = []
    with pytest.raises(BenchSchemaError, match="non-empty"):
        validate_engine_report(bad)


def test_bool_is_not_an_int(engine_report):
    """bool is an int subclass — the validator must still reject it."""
    bad = copy.deepcopy(engine_report)
    bad["results"][0]["epochs"] = True
    with pytest.raises(BenchSchemaError, match="epochs"):
        validate_engine_report(bad)


def test_reports_are_plain_json(engine_report, scale_report,
                                collective_report):
    json.dumps(engine_report)
    json.dumps(scale_report)
    json.dumps(collective_report)


@pytest.mark.parametrize("key", ["platform", "device_kind", "device_count"])
def test_report_without_its_device_rejected(engine_report, scale_report,
                                            collective_report, key):
    """Every report names the devices it ran on: a timing without them
    could pass for a chip's."""
    for report, validate in ((engine_report, validate_engine_report),
                             (scale_report, validate_scale_report),
                             (collective_report, validate_collective_report)):
        bad = copy.deepcopy(report)
        del bad[key]
        with pytest.raises(BenchSchemaError, match=key):
            validate(bad)
