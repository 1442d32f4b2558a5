"""Device time by the program's named scopes (bench.scopes): the matching of
a scope in an ``op_name``, the reduction over a hand-made trace, and the
scopes the compiled window of the program really carries."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest
from tiny_bench import REPO

from bench import scopes
from bench.trace import Event, Trace


@pytest.mark.parametrize("op_name, scope", [
    ("jit(window)/while/body/closed_call/transpose(jvp(local_train))/add_any",
     "local_train"),
    ("jit(window)/while/body/vmap(local_train)/while/body/conv", "local_train"),
    ("jit(window)/while/body/jit(sample_batches)/sample_batches/gather",
     "sample_batches"),
    ("jit(window)/while/body/gossip_mix/gossip_mix/psum_scatter", "gossip_mix"),
    # the innermost listed scope wins
    ("jit(window)/while/body/p1_solve/state_vector/log", "state_vector"),
    ("jit(window)/while/body/cond/branch_1_fun/eval/dot_general", "eval"),
    ("jit(window)/while/body/p1_solve", "p1_solve"),
    # a scope is a whole token of the path, never part of a name
    ("jit(window)/while/body/evaluate/dot_general", None),
    ("jit(eval_fn)/local_train_fn/p1_solver/mul", None),
    ("jit(window)/while/body/split", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """\
HloModule jit_window

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(window)/while/body/vmap(local_train)/mul" source_file="x.py"}
}

%body.2 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %copy.5 = f32[8]{0} copy(%p.2)
}

%cond.3 (p.3: f32[8]) -> pred[] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %lt.1 = pred[] constant(true), metadata={op_name="jit(window)/while/cond/lt"}
}

%branch.4 (p.4: f32[8]) -> f32[8] {
  ROOT %p.4 = f32[8]{0} parameter(0)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.383 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(window)/while/body/sample_batches/gather"}
  %while.7 = f32[8]{0} while(%fusion.383), condition=%cond.3, body=%body.2, metadata={op_name="jit(window)/while/body/gossip_mix/while"}
  %conditional.1 = f32[8]{0} conditional(%p, %while.7), branch_computations={%branch.4, %body.2}, metadata={op_name="jit(window)/while/body/cond"}
  %copy.2 = f32[8]{0} copy(%conditional.1), metadata={op_name="jit(window)/while/body/split"}
  ROOT %select-and-scatter.11 = f32[8]{0} add(%copy.2, %p), metadata={op_name="jit(window)/transpose(jvp(local_train))/add"}
}
"""


def test_layer_map_reads_each_instruction():
    assert scopes.layer_map(HLO) == {
        # a fused computation's instructions keep their own op_name; one
        # without takes the scope of the instruction that calls it
        "param_0.1": "sample_batches", "mul.1": "local_train",
        "fusion.383": "sample_batches",
        # a loop's body made without op_names belongs to the loop's scope
        "while.7": "gossip_mix", "p.2": "gossip_mix", "copy.5": "gossip_mix",
        "p.3": "gossip_mix", "lt.1": None,
        "conditional.1": None, "p.4": None,
        "p": None, "copy.2": None, "select-and-scatter.11": "local_train"}


def test_device_seconds_by_scope():
    # window 0..100 ns; device 0: the batch gather 10-30, a copy 30-35, a
    # loop holding both (left out), local training 90-120 (clipped at the
    # window's end) and an operation of no known instruction 50-60; device
    # 1: the batch gather 0-40
    ops = {0: [Event("while.9", 5, 40), Event("fusion.383", 10, 30),
               Event("copy.2", 30, 35), Event("select-and-scatter.11", 90, 120),
               Event("custom.1", 50, 60)],
           1: [Event("fusion.383", 0, 40)]}
    trace = Trace(ops, [Event("bench.federation", 0, 100)])
    got = scopes.device_seconds(trace, scopes.layer_map(HLO))
    assert got == pytest.approx({"sample_batches": 30e-9, None: 7.5e-9,
                                 "local_train": 5e-9})


def fake_run(ops: dict):
    return SimpleNamespace(trace=Trace(ops, [Event("bench.federation", 0, 100)]),
                           cfg=SimpleNamespace(backend="vmap"), epochs=10)


@pytest.mark.parametrize("ops, expected", [
    # every operation is an instruction of the compiled window: 20 ns of
    # the batch gather over 10 epochs
    ({0: [Event("while.9", 5, 40), Event("fusion.383", 10, 30)]}, 2e-6),
    # one is not (another module's, or a map of the wrong window): nothing
    ({0: [Event("fusion.383", 10, 30), Event("custom.1", 50, 60)]}, None),
])
def test_scope_metrics_read_only_the_traced_module(monkeypatch, capsys, ops,
                                                   expected):
    monkeypatch.setattr(scopes, "window_lengths", lambda cfg: [10])
    monkeypatch.setattr(scopes, "window_text", lambda run, length: HLO)
    monkeypatch.setattr(scopes.spans, "window", lambda run, metric: object())
    got = scopes.device_ms_per_epoch(fake_run(ops), "m", "sample_batches")
    assert got == (None if expected is None else pytest.approx(expected))
    assert ("custom.1" in capsys.readouterr().err) == (expected is None)


def test_unmapped_leaves_out_containers_and_operations_outside_the_window():
    ops = {0: [Event("while.9", 5, 40), Event("custom.1", 50, 60),
               Event("custom.2", 150, 160)],
           1: [Event("custom.3", 95, 105), Event("fusion.383", 0, 40)]}
    trace = Trace(ops, [Event("bench.federation", 0, 100)])
    assert scopes.unmapped(trace, scopes.layer_map(HLO)) == ["custom.1",
                                                             "custom.3"]


def test_merged_leaves_out_a_name_two_modules_scope_differently():
    assert scopes.merged([{"a": "eval", "b": "p1_solve"},
                          {"a": "eval", "b": "gossip_mix", "c": None}]) == {
        "a": "eval", "c": None}


@pytest.mark.parametrize("window_size, lengths", [(0, [10]), (5, [5]),
                                                  (4, [2, 4])])
def test_window_lengths_are_those_a_federation_scans(window_size, lengths):
    from repro.fed import engine

    cfg = engine.SimulationConfig(epochs=10, window_size=window_size)
    assert scopes.window_lengths(cfg) == lengths


def window_scopes(algorithm: str, backend: str) -> set:
    from repro.data.synthetic import synthetic_mnist
    from repro.fed import backends, engine

    ds = synthetic_mnist(n_train=300, n_test=60)
    cfg = engine.SimulationConfig(
        algorithm=algorithm, backend=backend, num_vehicles=4, epochs=2,
        eval_every=2, eval_samples=60, local_steps=1, batch_size=8,
        p1_steps=3, seed=0)
    ctx = engine.build_context(cfg, dataset=ds)
    window = (ctx.window_jit if backend == "vmap"
              else backends.get_backend(backend)._sharded_window(ctx))
    import jax
    import jax.numpy as jnp
    import numpy as np

    contacts = jax.tree_util.tree_map(jnp.asarray, ctx.contacts.window(2))
    text = window.lower(ctx.init_state, ctx.init_rng, ctx.fed_data,
                        ctx.target, contacts,
                        jnp.asarray(np.ones(2, bool))).compile().as_text()
    return set(scopes.layer_map(text).values()) - {None}


# d_fedavg mixes by sample counts: no P1, so no p1_solve scope
EXPECTED = {"dds": set(scopes.SCOPES),
            "d_fedavg": set(scopes.SCOPES) - {"p1_solve"}}


@pytest.mark.parametrize("algorithm", sorted(EXPECTED))
def test_each_scope_owns_instructions_of_the_window(algorithm):
    assert window_scopes(algorithm, "vmap") == EXPECTED[algorithm]


def test_each_scope_owns_instructions_of_the_sharded_window():
    """shard_map over two host devices: the vehicle axis splits, and the
    scopes survive the sharded mix and the per-shard rows."""
    code = ("import sys; sys.path[:0] = [{tests!r}]\n"
            "import jax; assert jax.device_count() == 2\n"
            "from test_bench_scopes import window_scopes\n"
            "for a in ('dds', 'd_fedavg'):\n"
            "    print(a, sorted(window_scopes(a, 'shard_map')))\n"
            ).format(tests=str(REPO / "tests" / "bench"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in p.stdout.splitlines())
    for algorithm, expected in EXPECTED.items():
        assert lines[algorithm] == str(sorted(expected))
