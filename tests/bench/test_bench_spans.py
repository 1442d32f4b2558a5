"""The program's spans and counters on the trace's clock (bench.spans): the
offset, the clock check, the idle overlap on hand-made traces, and a tiny
traced cell that reads them."""
import json
from dataclasses import dataclass, field
from types import SimpleNamespace

import pytest
import tiny_bench

from bench import spans
from bench.trace import Event, Trace

OFFSET = 1_000_000     # program time = trace time + OFFSET


@dataclass
class FakeSpan:
    name: str
    id: int
    federation: int
    start_ns: int
    end_ns: int
    counts: dict = field(default_factory=dict)


def program(shift: int = 0):
    """Two federations, each with emission, dispatch and collection, plus a
    warm-up federation before the window and a stray emission after it."""
    out = [FakeSpan("fed.federation", 1, 1, 0, 5)]
    for fid, lo, hi in ((10, 2, 48), (20, 52, 98)):
        at = lambda t: OFFSET + t + (shift if fid == 20 else 0)
        out += [FakeSpan("fed.contacts", fid + 1, fid, at(lo), at(lo + 10),
                         {"fed.contact_slots": 40, "fed.contact_edges": 10}),
                FakeSpan("fed.dispatch", fid + 2, fid, at(lo + 10), at(lo + 12)),
                FakeSpan("fed.collect", fid + 3, fid, at(lo + 30), at(hi)),
                FakeSpan("fed.federation", fid, fid, at(lo), at(hi),
                         {"fed.contact_slots": 40, "fed.contact_edges": 10})]
    return out + [FakeSpan("fed.contacts", 30, None, OFFSET + 200, OFFSET + 210)]


def make_run(monkeypatch, shift: int = 0):
    # device 0 busy 14-40 and 64-90; device 1 busy 12-46 and 62-96
    ops = {0: [Event("fusion.1", 14, 40), Event("fusion.1", 64, 90)],
           1: [Event("fusion.1", 12, 46), Event("fusion.1", 62, 96)]}
    marks = [Event("bench.federation", 0, 50), Event("bench.federation", 50, 100)]
    monkeypatch.setattr(spans, "program_spans", lambda: program(shift))
    return SimpleNamespace(trace=Trace(ops, marks))


def test_the_offset_puts_each_federation_inside_its_mark(monkeypatch):
    win = spans.window(make_run(monkeypatch), "m")
    assert [f.id for f in win.federations] == [10, 20]
    # any offset from OFFSET - 2 to OFFSET + 2 nests both; the middle is taken
    assert win.offset == OFFSET
    assert {s.name for s in win.spans} == {"fed.federation", "fed.contacts",
                                           "fed.dispatch", "fed.collect"}
    assert win.intervals("fed.contacts") == [(2, 12), (52, 62)]
    assert win.total_count("fed.contact_edges") == 20
    assert win.total_count("fed.contact_slots") == 80


def test_clocks_that_disagree_give_nothing(monkeypatch, capsys):
    # the second federation 10 ns late against the first: no single offset
    # nests both
    assert spans.window(make_run(monkeypatch, shift=10), "m") is None
    assert "no clock offset" in capsys.readouterr().err


def test_too_few_program_federations_give_nothing(monkeypatch, capsys):
    run = make_run(monkeypatch)
    monkeypatch.setattr(spans, "program_spans", lambda: program()[5:])
    assert spans.window(run, "m") is None
    assert "1 fed.federation spans for 2" in capsys.readouterr().err


def test_idle_and_its_overlap():
    assert spans.idle([Event("a", 2, 4), Event("b", 3, 6)], 0, 10) == [
        (0, 2), (6, 10)]
    assert spans.intersect([(0, 2), (6, 10)], [(1, 7), (9, 12)]) == [
        (1, 2), (6, 7), (9, 10)]


def test_idle_per_federation_under_each_span(monkeypatch):
    run = make_run(monkeypatch)
    win = spans.window(run, "m")
    # idle on device 0: 0-14, 40-64, 90-100; on device 1: 0-12, 46-62, 96-100
    # emission 2-12 and 52-62: device 0 idle 10 + 10, device 1 idle 10 + 10
    assert spans.idle_ms_per_federation(run.trace, win, "fed.contacts") == (
        pytest.approx(10e-6))
    # collection 32-48 and 82-98: device 0 idle 8 + 8, device 1 idle 2 + 2
    assert spans.idle_ms_per_federation(run.trace, win, "fed.collect") == (
        pytest.approx(5e-6))
    # the two never add up past the idle time a federation: 100 ns less a
    # mean busy of (52 + 68) / 2, over two federations
    assert 10e-6 + 5e-6 <= (100 - (52 + 68) / 2) / 2 * 1e-6


def test_readers_without_a_device_or_telemetry(monkeypatch, capsys):
    run = make_run(monkeypatch)
    run.trace.device_ops = {}
    assert spans.device_idle_ms(run, "m", "fed.contacts") is None
    run = make_run(monkeypatch)
    monkeypatch.setattr(spans, "program_spans", lambda: None)
    assert spans.window(run, "m") is None
    err = capsys.readouterr().err
    assert "no device operations" in err and "no telemetry" in err


NEW_METRICS = {"sample_batches_device_ms", "local_train_device_ms",
               "p1_solve_device_ms", "gossip_mix_device_ms",
               "state_vector_device_ms", "eval_device_ms",
               "unscoped_device_share", "contact_emit_ms", "emission_idle_ms",
               "collect_idle_ms", "contact_slot_use", "build_context_s",
               "first_dispatch_s"}
HOST_METRICS = {"contact_emit_ms", "contact_slot_use", "build_context_s",
                "first_dispatch_s"}


def test_a_tiny_traced_cell_reports_the_program_spans(tmp_path, capsys):
    root = tiny_bench.make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["per_layer"] if "workloads" in m}
    assert NEW_METRICS <= listed
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(tiny_bench.WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    result = tiny_bench.run(root, trace=True)
    got = result["metrics"]
    assert HOST_METRICS <= set(got)
    # the device metrics find no device in a CPU trace and are left out
    assert not (NEW_METRICS - HOST_METRICS) & set(got)
    err = capsys.readouterr().err
    for name in NEW_METRICS - HOST_METRICS:
        assert f"bench: {name}: the trace has no device operations" in err
    assert 0 < got["contact_emit_ms"]["value"]
    assert 0 < got["contact_slot_use"]["value"] <= 100
    assert got["contact_slot_use"]["unit"] == "%"
    assert 0 < got["build_context_s"]["value"]
    assert 0 < got["first_dispatch_s"]["value"]
