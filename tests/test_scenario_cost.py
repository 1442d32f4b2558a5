"""Cost-model validation suite: the analytical model (roofline.scenario_cost)
must reproduce the measured ranking of every configuration pair recorded in
the committed BENCH_engine.json / BENCH_scale.json, and ``execution="auto"``
must select the measured-fastest configuration for the K=8 / K=1024 smoke
scenarios. Future engine changes that invalidate the model fail here, loudly.
"""
import json
from dataclasses import replace
from pathlib import Path

import pytest

from repro.data.synthetic import synthetic_mnist
from repro.fed import engine
from repro.roofline import bench_schema, scenario_cost

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def engine_report():
    return bench_schema.load_engine_report(str(REPO_ROOT / "BENCH_engine.json"))


@pytest.fixture(scope="module")
def scale_report():
    return bench_schema.load_scale_report(str(REPO_ROOT / "BENCH_scale.json"))


# ----------------------------------------------- measured-ranking replay ----

def test_bench_engine_ranking_reproduced(engine_report):
    """Every recorded (vmap, shard_map) pair: the model's predicted-faster
    config matches the measured-faster one (near-ties exempt, but even there
    the predicted ratio must stay inside the loose band)."""
    rows = scenario_cost.replay_bench_engine(engine_report)
    assert len(rows) == len(engine_report["results"])  # every pair replayed
    for r in rows:
        assert r["verdict"] != "MISMATCH", r
        if not (1 / scenario_cost.NEAR_TIE_RATIO <= r["measured_ratio"]
                <= scenario_cost.NEAR_TIE_RATIO):
            # decisive pair: signs must agree exactly
            assert (r["measured_ratio"] > 1) == (r["predicted_ratio"] > 1), r


def test_bench_scale_ranking_reproduced(scale_report):
    """Every recorded (sparse, dense) pair at every K: predicted-faster
    matches measured-faster, same tolerance regime."""
    rows = scenario_cost.replay_bench_scale(scale_report)
    ks = {int(r["num_vehicles"]) for r in scale_report["results"]}
    assert len(rows) == len(ks)  # one pair per fleet size, all covered
    for r in rows:
        assert r["verdict"] != "MISMATCH", r
        if not (1 / scenario_cost.NEAR_TIE_RATIO <= r["measured_ratio"]
                <= scenario_cost.NEAR_TIE_RATIO):
            assert (r["measured_ratio"] > 1) == (r["predicted_ratio"] > 1), r


def test_decisive_pairs_exist(engine_report, scale_report):
    """The suite is not vacuous: the committed files contain decisive
    (non-near-tie) pairs in both directions' workloads."""
    rows = (scenario_cost.replay_bench_engine(engine_report)
            + scenario_cost.replay_bench_scale(scale_report))
    decisive = [r for r in rows
                if not (1 / scenario_cost.NEAR_TIE_RATIO <= r["measured_ratio"]
                        <= scenario_cost.NEAR_TIE_RATIO)]
    assert len(decisive) >= 3


def test_ranking_verdict_bands():
    v = scenario_cost.ranking_verdict
    assert v(2.0, 1.5) == "ok"           # decisive, signs agree
    assert v(2.0, 0.8) == "MISMATCH"     # decisive, signs disagree
    assert v(0.5, 0.9) == "ok"
    assert v(1.05, 0.9) == "tie-ok"      # near-tie, prediction close enough
    assert v(1.05, 3.0) == "MISMATCH"    # near-tie but prediction way off


# -------------------------------------------------------- model structure ----

def test_sparse_beats_dense_whenever_d_max_smaller():
    """The structural sign property the scale rankings rest on: with shared
    per-op-class rates, the sparse format is predicted faster than dense
    whenever D_max < K — for every committed (K, D_max)."""
    for k, d in ((8, 7), (64, 12), (256, 12), (1024, 11)):
        dense = scenario_cost.predict_scenario(
            scenario_cost.bench_scale_config(k, "dense", 10), d_max=d)
        sparse = scenario_cost.predict_scenario(
            scenario_cost.bench_scale_config(k, "sparse", 10, d_max=d), d_max=d)
        assert sparse.epochs_per_s > dense.epochs_per_s


def test_breakdown_terms_positive_and_jsonable():
    cfg = scenario_cost.bench_engine_config(8)
    bd = scenario_cost.predict_scenario(
        replace(cfg, backend="shard_map"), d_max=3, device_count=4)
    assert bd.num_shards == 4
    assert "collective" in bd.terms
    assert all(v >= 0 for v in bd.terms.values())
    assert bd.total_s == pytest.approx(sum(bd.terms.values()))
    assert bd.epochs_per_s == pytest.approx(1 / bd.total_s)
    json.dumps(bd.jsonable())


def test_p1_term_only_for_dds():
    cfg = replace(scenario_cost.bench_engine_config(8), algorithm="dfl")
    bd = scenario_cost.predict_scenario(cfg, d_max=3)
    assert "p1" not in bd.terms


def test_local_train_stats_measured_shapes():
    s = scenario_cost.local_train_stats("mnist", 1, 1)
    assert s["params"] == 21840                    # the MNIST CNN
    assert s["flops"] > 2 * s["params"]            # > one matvec
    assert s["leaves"] >= 4
    # E=2 doubles the scanned train flops (trip-count multiplication)
    s2 = scenario_cost.local_train_stats("mnist", 2, 1)
    assert s2["flops"] == pytest.approx(2 * s["flops"], rel=0.05)


# --------------------------------------------------- execution = "auto" ----

def test_auto_selects_measured_fastest_k8(engine_report, scale_report):
    """Acceptance: the K=8 smoke scenario resolves to the measured-fastest
    (backend, contact_format) — read from the committed benchmarks, not
    hard-coded."""
    row8 = next(r for r in engine_report["results"] if r["num_vehicles"] == 8)
    measured_backend = ("shard_map" if row8["shard_vs_vmap"] > 1.0 else "vmap")
    sparse8 = next(r for r in scale_report["sparse_vs_dense"]
                   if r["num_vehicles"] == 8)
    measured_format = ("sparse"
                       if sparse8["sparse_vs_dense_epochs_per_s"] > 1.0
                       else "dense")

    cfg = replace(scenario_cost.bench_engine_config(8), execution="auto")
    resolved, plan = scenario_cost.resolve_auto(
        cfg, device_count=int(engine_report["device_count"]))
    assert resolved.execution == "manual"
    assert resolved.backend == measured_backend
    assert resolved.contact_format == measured_format
    assert plan["resolved"]["backend"] == resolved.backend
    assert plan["predicted_epochs_per_s"] > 0
    assert len(plan["candidates"]) >= 4   # vmap/shard x sparse/dense
    json.dumps(plan)


def test_auto_selects_measured_fastest_k1024(scale_report):
    """Acceptance: the K=1024 smoke scenario (recorded D_max pinned, single
    device) resolves to the measured-fastest contact format."""
    pair = next(r for r in scale_report["sparse_vs_dense"]
                if r["num_vehicles"] == 1024)
    measured_format = ("sparse"
                       if pair["sparse_vs_dense_epochs_per_s"] > 1.0
                       else "dense")
    epochs = next(r["epochs"] for r in scale_report["results"]
                  if r["num_vehicles"] == 1024)
    cfg = replace(
        scenario_cost.bench_scale_config(1024, "dense", epochs,
                                         d_max=pair["d_max"]),
        execution="auto")
    resolved, plan = scenario_cost.resolve_auto(cfg, device_count=1)
    assert resolved.contact_format == measured_format
    assert resolved.backend == "vmap"          # single device: no shard_map
    assert plan["resolved"]["d_max"] == pair["d_max"]  # pin honoured


def test_auto_resolution_chain_uses_density():
    """resolve_auto honours the pin -> density -> probe chain: an explicit
    contact_density sizes D_max without probing."""
    cfg = replace(scenario_cost.bench_engine_config(8), execution="auto",
                  contact_density=0.5)
    _, plan = scenario_cost.resolve_auto(cfg, device_count=1)
    assert plan["resolved"]["d_max"] == 4      # ceil(0.5 * 8)


# ------------------------------------------------------ engine integration ----

def test_auto_run_stamps_plan_and_resolved_config():
    """End-to-end: a tiny execution="auto" run resolves before dispatch and
    stamps the plan on every seed result; the resolved config is concrete."""
    ds = synthetic_mnist(n_train=600, n_test=120)
    cfg = engine.SimulationConfig(
        num_vehicles=6, epochs=4, eval_every=2, eval_samples=60,
        local_steps=1, batch_size=4, p1_steps=10, execution="auto")
    results = engine.run_seeds(cfg, [0, 1], dataset=ds)
    assert len(results) == 2
    for r in results:
        assert r.execution_plan is not None
        assert r.execution_plan["requested"] == "auto"
        assert r.config.execution == "manual"
        assert r.config.backend in ("vmap", "shard_map")
        json.dumps(r.execution_plan)
    # manual runs carry no plan
    manual = engine.run_seeds(replace(cfg, execution="manual"), [0],
                              dataset=ds)
    assert manual[0].execution_plan is None


def test_auto_matches_manual_trajectories():
    """execution="auto" is trajectory-neutral: it only picks among the
    parity-tested execution knobs, so eval curves match a manual run."""
    import numpy as np

    ds = synthetic_mnist(n_train=600, n_test=120)
    base = dict(num_vehicles=6, epochs=4, eval_every=2, eval_samples=60,
                local_steps=1, batch_size=4, p1_steps=10)
    auto = engine.run_seeds(
        engine.SimulationConfig(execution="auto", **base), [0], dataset=ds)[0]
    manual = engine.run_seeds(
        engine.SimulationConfig(**base), [0], dataset=ds)[0]
    np.testing.assert_allclose(auto.avg_accuracy, manual.avg_accuracy,
                               atol=1e-5)


def test_predicted_vs_measured_table_renders(engine_report, scale_report):
    table = scenario_cost.predicted_vs_measured_table(
        scenario_cost.replay_bench_engine(engine_report),
        scenario_cost.replay_bench_scale(scale_report))
    assert "MISMATCH" not in table
    assert "sparse-vs-dense K=1024" in table


# ------------------------------------------------------ profile by device ----

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,expected", [
    ("cpu", "cpu", scenario_cost.CI_HOST),
    ("tpu", "TPU v5 lite", scenario_cost.TPU_V5E),
])
def test_host_profile_keyed_by_device_kind(monkeypatch, platform, kind,
                                           expected):
    monkeypatch.setattr("jax.devices", lambda: [_FakeDevice(platform, kind)])
    assert scenario_cost.default_host_profile() is expected


def test_unknown_accelerator_kind_raises(monkeypatch):
    """Every TPU used to get the v5e profile; an unknown kind is an error."""
    monkeypatch.setattr("jax.devices",
                        lambda: [_FakeDevice("tpu", "TPU v4")])
    with pytest.raises(ValueError, match="TPU v4"):
        scenario_cost.default_host_profile()
