"""The reduction from a profiler trace to the benchmark's numbers."""
import pytest
from tiny_bench import REPO  # noqa: F401  (puts the repository on sys.path)

from bench.trace import Event, Trace, clip, union


def make_trace():
    # window 0..100 ns; device 0 busy 10-30 and 20-40 (overlap), 60-70 (an
    # all-reduce) and 90-120 (runs past the window); device 1 busy 0-50
    ops = {0: [Event("fusion.1", 10, 30), Event("fusion.1", 20, 40),
               Event("all-reduce.3", 60, 70), Event("convolution.2", 90, 120)],
           1: [Event("fusion.1", 0, 50)]}
    spans = [Event("bench.federation", 0, 100),
             Event("bench.contact_stream", 42, 58)]
    return Trace(ops, spans)


def test_union_and_clip():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert clip([(0, 5), (8, 20), (30, 40)], 2, 25) == [(2, 5), (8, 20)]


def test_busy_idle_and_window():
    t = make_trace()
    assert t.window_s() == pytest.approx(100e-9)
    assert t.busy_s(0) == pytest.approx(50e-9)    # 30 + 10 + 10
    assert t.busy_s(1) == pytest.approx(50e-9)
    assert t.idle_share() == pytest.approx(0.5)


def test_op_names_and_containers():
    from bench.trace import is_container, op_name

    assert op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.12"
    assert is_container("while.223") and not is_container("fusion.3")


def test_top_ops_leave_out_loops():
    t = make_trace()
    t.device_ops[0].append(Event("while.7", 0, 100))
    assert "while.7" not in [name for name, _ in t.top_ops()]
    assert t.busy_s(0) == pytest.approx(100e-9)


def test_top_ops_counts_each_op_once_per_event():
    top = make_trace().top_ops()
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(40e-9)
    assert {name for name, _ in top[1:]} == {"convolution.2", "all-reduce.3"}
    assert [s for _, s in top[1:]] == pytest.approx([10e-9, 10e-9])


def test_idle_gaps_named_by_innermost_host_span():
    gaps = make_trace().idle_gaps()
    # gaps on device 0: 0-10, 40-60, 70-90
    assert [round(s * 1e9) for _, s in gaps] == [20, 20, 10]
    assert gaps[0][0] == "bench.contact_stream"   # 40-60, middle 50
    assert gaps[1][0] == "bench.federation"       # 70-90
    assert gaps[2][0] == "bench.federation"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        Trace({0: []}, [Event("bench.contact_stream", 0, 1)]).window()


def test_load_finds_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench import trace

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.federation"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    got = trace.load(str(tmp_path))
    assert [s.name for s in got.host_spans] == ["bench.federation"]
    assert got.device_ops == {}        # no TPU planes in a CPU trace
