"""The engine's in-process spans and counters (repro.telemetry)."""
import collections
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.data.synthetic import synthetic_mnist
from repro.fed import engine


@pytest.fixture(autouse=True)
def _fresh_recorder():
    telemetry.reset()
    yield
    telemetry.reset()


def test_spans_nest_under_parents_and_share_a_federation_id():
    with telemetry.span("outer"):
        with telemetry.span("fed", federation=True) as fed:
            with telemetry.span("a") as a:
                with telemetry.span("b") as b:
                    pass
            with telemetry.span("c") as c:
                pass
    with telemetry.span("after") as after:
        pass
    got = telemetry.spans()
    # closed spans are recorded as they close: innermost first
    assert [s.name for s in got] == ["b", "a", "c", "fed", "outer", "after"]
    outer = got[4]
    assert outer.parent is None and outer.federation is None
    assert fed.parent == outer.id and fed.federation == fed.id
    assert a.parent == fed.id and b.parent == a.id and c.parent == fed.id
    assert {s.federation for s in (a, b, c)} == {fed.id}
    assert after.parent is None and after.federation is None
    assert len({s.id for s in got}) == len(got)
    for s in got:
        assert s.start_ns <= s.end_ns
    assert fed.start_ns <= a.start_ns and b.end_ns <= a.end_ns <= fed.end_ns


def test_a_second_federation_gets_its_own_id():
    ids = []
    for _ in range(2):
        with telemetry.span("fed", federation=True) as fed:
            with telemetry.span("inner") as inner:
                pass
        ids.append((fed.federation, inner.federation))
    assert ids[0][0] == ids[0][1] and ids[1][0] == ids[1][1]
    assert ids[0][0] != ids[1][0]


def test_counters_add_up_in_total_and_per_open_span():
    telemetry.count("slots", 4)
    with telemetry.span("fed", federation=True) as fed:
        with telemetry.span("emit") as emit:
            telemetry.count("slots", 10)
            telemetry.count("edges", 3)
        telemetry.count("edges")
    assert telemetry.counters() == {"slots": 14, "edges": 4}
    assert emit.counts == {"slots": 10, "edges": 3}
    assert fed.counts == {"slots": 10, "edges": 4}
    # snapshots: changing what was returned changes nothing recorded
    telemetry.counters()["slots"] = 0
    assert telemetry.counters()["slots"] == 14
    telemetry.reset()
    assert telemetry.counters() == {} and telemetry.spans() == []


def test_the_span_store_is_bounded(monkeypatch):
    monkeypatch.setattr(telemetry, "_spans", collections.deque(maxlen=3))
    for i in range(5):
        with telemetry.span(f"s{i}"):
            pass
    assert [s.name for s in telemetry.spans()] == ["s2", "s3", "s4"]
    assert telemetry.MAX_SPANS == 65_536


def test_a_span_closes_on_an_exception():
    with pytest.raises(ValueError):
        with telemetry.span("fails"):
            raise ValueError
    with telemetry.span("next") as nxt:
        pass
    assert [s.name for s in telemetry.spans()] == ["fails", "next"]
    assert nxt.parent is None


def test_one_federation_records_exactly_its_spans():
    ds = synthetic_mnist(n_train=400, n_test=80)
    cfg = engine.SimulationConfig(
        num_vehicles=4, epochs=3, eval_every=3, eval_samples=80,
        local_steps=1, batch_size=8, p1_steps=5, road_net="grid", seed=1)
    ctx = engine.build_context(cfg, dataset=ds)
    (build,) = telemetry.spans()
    assert build.name == "fed.build_context" and build.parent is None
    telemetry.reset()

    res = engine.run_with_context(ctx)
    got = telemetry.spans()
    assert [s.name for s in got] == ["fed.contacts", "fed.dispatch",
                                     "fed.collect", "fed.federation"]
    fed = got[-1]
    assert fed.federation == fed.id and fed.parent is None
    for s in got[:-1]:
        assert s.parent == fed.id and s.federation == fed.id
    assert res.wall_time == fed.seconds
    # one sparse window of T x K x D_max slots, the self slots among them
    slots = 3 * 4 * ctx.contacts.d_max
    assert telemetry.counters()["fed.contact_slots"] == slots
    assert 3 * 4 <= telemetry.counters()["fed.contact_edges"] <= slots
    assert fed.counts == telemetry.counters() == got[0].counts


def test_a_span_and_its_profiler_annotation_share_the_clock(tmp_path):
    """Kept in memory and read back from the profile, the same span starts
    and ends within 1 ms: the profile's times count from its
    ``profile_start_time``, on the clock the recorder reads."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    with telemetry.span("clock.check") as kept:
        jnp.ones((32, 32)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    start = [v for p in data.planes for k, v in p.stats
             if k == "profile_start_time"]
    mirrors = [e for p in data.planes if p.name.startswith("/host:")
               for line in p.lines for e in line.events
               if e.name == "clock.check"]
    assert len(start) == 1 and len(mirrors) == 1
    assert abs(start[0] + mirrors[0].start_ns - kept.start_ns) < 1e6
    assert abs(start[0] + mirrors[0].end_ns - kept.end_ns) < 1e6
