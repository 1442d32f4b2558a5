"""One run of one benchmark cell, driven by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. Whatever belongs to one
model is in the configuration's own files, so a new model is new files and
new entries, and nothing here changes:

- ``bench/configs/<config>.json``: the job (``algorithm``, ``dataset``,
  ``distribution``, ``lr``, ``local_steps``, ``batch_size``,
  ``eval_samples``, ``p1_steps``, ``p1_step_size``,
  ``shards_per_vehicle``) and, optionally, ``"program"``: further fields
  of the program's ``SimulationConfig`` (a ``model``, say), set as they
  stand. A key that is no such field, or one that ``sim_config`` already
  sets from the job or the traffic, ends the run before any data is made:
  the program and the reference have to run the same job.
- ``bench/configs/<config>.py``: the plain reference model. It defines
  ``init(key)``, one vehicle's parameters as the program's own parameter
  tree, path for path (``bench/check.py`` compares them leaf by leaf);
  ``loss(params, x, y, rng)``, the mean training loss of a batch (per
  sample or per token); ``accuracy(params, x, y)``, the eval accuracy of
  a batch (argmax, or next-token); ``make_dataset(seed, config)``, the
  data, with the fields of ``bench.data.synthetic.Dataset`` (``train_y``
  the per-sample label the balanced non-IID partition sorts on: for a
  token set, a per-sequence domain); and ``forward_flops_per_sample()``
  (``bench/flops.py``).

The traffic mix (``bench/traffic/<traffic>.json``) holds the fleet, its
road net and how the federation is run. Each metric is read by
``bench/metrics/<metric>.py``; the limits that decide ``correct`` are in
``bench/limits/<workload>.json``. Nothing here names a cell.

The timed path is the program's own entry: ``repro.fed.engine.build_context``
once, then ``run_with_context`` for whole federations back to back, each on
a fresh ``ContactStream`` so that its host contact emission is timed too.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

import numpy as np

REPO = Path(__file__).resolve().parents[1]
CACHE_DIR = REPO / ".jax_cache"


def prepare_jax() -> None:
    """Put JAX's compilation cache at the checkout's fixed path, caching
    every program, before the program is imported; and pin the PRNG to
    threefry, whose draws do not depend on how a computation is batched (an
    ``rbg`` PRNG set in the environment gives the program, which vmaps its
    vehicles, other dropout masks than the reference draws one block of
    vehicles at a time)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for path in (str(REPO), str(REPO / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    jax.config.update("jax_default_prng_impl", "threefry2x32")
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    model: ModuleType
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path

    def metric_specs(self, trace: bool) -> list[dict]:
        specs = self.per_layer if trace else self.end_to_end
        return [m for m in specs if self.name in m.get("workloads", [self.name])]


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == workload)
    except StopIteration:
        raise SystemExit(f"unknown workload {workload!r}") from None
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench = root / "bench"
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=c["name"],
        config=json.loads((root / c["file"]).read_text()),
        model=load_module(bench / "configs" / f"{c['name']}.py"),
        traffic_name=w["traffic"],
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{workload}.json").read_text()),
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"], root=root)


def register_road_net(cell: Cell) -> str:
    """Register the traffic's grid with the program under the traffic's own
    name; return that name."""
    from repro.fed import topology

    net = cell.traffic["road_net"]
    name = f"bench.{cell.traffic_name}"
    topology.register_road_network(name)(
        lambda seed=0: topology.grid_net(side=net["grid_side"],
                                         spacing=net["spacing_m"]))
    return name


def program_fields(cell: Cell, set_here: dict) -> dict:
    """The configuration's ``"program"`` object. A key that is no field of
    the program's ``SimulationConfig``, or one in ``set_here`` (what the
    harness sets from the job and the traffic, which the reference runs
    too), is a ``SystemExit`` that names it."""
    from dataclasses import fields

    from repro.fed.engine import SimulationConfig

    extra = cell.config.get("program", {})
    source = f"bench/configs/{cell.config_name}.json"
    known = {f.name for f in fields(SimulationConfig)}
    for bad, why in ((set(extra) - known,
                      "no field of the program's SimulationConfig"),
                     (set(extra) & set(set_here),
                      "fields the harness sets from the job and the traffic")):
        if bad:
            raise SystemExit(f"{source}: \"program\" names {sorted(bad)}, {why}")
    return extra


def sim_config(cell: Cell, seed: int, d_max: int = 0):
    """The program's ``SimulationConfig`` for this cell and seed, with the
    configuration's ``program`` fields beside those set here."""
    from repro.fed.engine import SimulationConfig

    c, t = cell.config, cell.traffic
    here = dict(
        algorithm=c["algorithm"], dataset=c["dataset"],
        distribution=c["distribution"], lr=c["lr"],
        local_steps=c["local_steps"], batch_size=c["batch_size"],
        eval_samples=c["eval_samples"], p1_steps=c["p1_steps"],
        p1_step_size=c["p1_step_size"],
        road_net=register_road_net(cell), num_vehicles=t["num_vehicles"],
        epochs=t["federation_epochs"], eval_every=t["eval_every"],
        comm_range=t["comm_range_m"], epoch_duration=t["epoch_duration_s"],
        mobility=t["mobility"], contact_format=t["contact_format"],
        mixing_backend=t["mixing_backend"], backend=t["backend"],
        d_max=d_max, seed=seed)
    return SimulationConfig(**here, **program_fields(cell, here))


def reference_job(cell: Cell):
    from bench.reference.federation import Job

    c, t = cell.config, cell.traffic
    return Job(
        num_vehicles=t["num_vehicles"], epochs=t["federation_epochs"],
        eval_every=t["eval_every"], eval_samples=c["eval_samples"],
        local_steps=c["local_steps"], batch_size=c["batch_size"], lr=c["lr"],
        p1_steps=c["p1_steps"], p1_step_size=c["p1_step_size"],
        grid_side=t["road_net"]["grid_side"],
        grid_spacing=t["road_net"]["spacing_m"],
        comm_range=t["comm_range_m"], epoch_duration=t["epoch_duration_s"],
        shards_per_vehicle=c["shards_per_vehicle"])


def make_data(cell: Cell, seed: int):
    """The cell's data from ``seed``, as the configuration module makes it."""
    return cell.model.make_dataset(seed, cell.config)


class CompileCounter:
    """Counts traces, compilations and compile-cache loads while active;
    apart from that, from its creation on, the programs compiled because
    the compile cache missed and the seconds spent compiling or loading."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self.misses = 0
        self.compile_s = 0.0
        self.active = False
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_):
        if name == self.EVENTS[1]:
            self.compile_s += secs
        if self.active and name in self.EVENTS:
            self.count += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        if self.active and name == "/jax/compilation_cache/cache_hits":
            self.count += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


@dataclass
class Answer:
    """What one timed federation produced, copied to the host."""
    loss: list[float]
    kl: list[float]
    accuracy: list[np.ndarray]


def answer_of(res) -> Answer:
    return Answer(list(res.loss_trace), list(res.kl_trace),
                  [np.asarray(a) for a in res.vehicle_accuracy])


@dataclass
class Run:
    """Everything a metric reader may read. ``ctx`` is the program's context
    and ``last`` the last timed federation's result; both are dropped before
    the reference runs."""
    cell: Cell
    seed: int
    cfg: object
    ctx: object
    net: object
    setup_s: float
    epochs: int = 0
    federations: int = 0
    window_s: float = 0.0
    compiles: int = 0
    setup_misses: int = 0
    setup_compile_s: float = 0.0
    answers: list[Answer] = field(default_factory=list)
    last: object = None
    trace: object = None
    data: object = None
    per_answer: list[dict] = field(default_factory=list)

    @property
    def epochs_per_s(self) -> float:
        return self.epochs / self.window_s


def fresh_stream(run: Run, traced: bool):
    """A new contact stream of this run's fleet, from the start of its
    horizon; under ``traced`` its emission is a ``bench.contact_stream``
    span."""
    from repro.fed import engine

    if not traced:
        return engine.ContactStream(run.cfg, run.net)

    import jax

    class Traced(engine.ContactStream):
        def window(self, num_epochs):
            with jax.profiler.TraceAnnotation("bench.contact_stream"):
                return super().window(num_epochs)

    return Traced(run.cfg, run.net)


def federation(run: Run, traced: bool = False):
    """One whole federation through the program's entry point."""
    import jax
    from repro.fed import engine

    run.ctx.contacts = fresh_stream(run, traced)
    if not traced:
        return engine.run_with_context(run.ctx)
    with jax.profiler.TraceAnnotation("bench.federation"):
        return engine.run_with_context(run.ctx)


def timed_window(run: Run, seconds: float, counter: CompileCounter,
                 traced: bool) -> None:
    """Whole federations back to back until ``seconds`` have passed; the
    window closes at the end of the federation that crosses it."""
    counter.count, counter.active = 0, True
    t0 = time.perf_counter()
    while True:
        res = federation(run, traced)
        run.answers.append(answer_of(res))
        run.federations += 1
        run.epochs += len(res.loss_trace)
        if time.perf_counter() - t0 >= seconds:
            break
    run.window_s = time.perf_counter() - t0
    counter.active = False
    run.compiles = counter.count
    run.last = res


def setup(cell: Cell, seed: int, t_start: float) -> Run:
    """Data, context, D_max and one warm-up federation of the cell's own
    shapes; ``setup_s`` runs from ``t_start``."""
    from repro.fed import engine, topology

    probe_cfg = sim_config(cell, seed)  # a bad program field exits here
    data = make_data(cell, seed)
    net = topology.make_road_network(probe_cfg.road_net)
    d_max = max(cell.traffic["d_max_floor"], engine.probe_d_max(probe_cfg, net))
    cfg = sim_config(cell, seed, d_max)
    ctx = engine.build_context(cfg, dataset=data)
    run = Run(cell=cell, seed=seed, cfg=cfg, ctx=ctx, net=net, setup_s=0.0,
              data=data)
    federation(run)  # warm-up: compiles, or loads from the cache
    run.setup_s = time.perf_counter() - t_start
    return run


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices[:chips]]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats)}


def read_metrics(run: Run, trace: bool) -> dict:
    out = {}
    for spec in run.cell.metric_specs(trace):
        reader = load_module(run.cell.root / "bench" / "metrics" / f"{spec['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def time_calls(fn, min_seconds: float = 0.25) -> float:
    """Milliseconds per call of ``fn`` (which waits for its own result),
    over as many calls as fill ``min_seconds`` after one untimed call."""
    fn()
    calls, t0 = 0, time.perf_counter()
    while True:
        fn()
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= min_seconds:
            return elapsed / calls * 1e3


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float | None = None) -> dict:
    """Set up, time, read and check one run; return the result line's
    object. The caller has made sure the chips are there."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(root, workload)
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        run = setup(cell, seed, t_start)
        run.setup_misses, run.setup_compile_s = counter.misses, counter.compile_s
        if trace:
            jax.profiler.start_trace(trace_dir)
        timed_window(run, seconds, counter, traced=trace)
        if trace:
            jax.profiler.stop_trace()
            from bench import trace as trace_lib

            run.trace = trace_lib.load(trace_dir)
    finally:
        counter.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(cell.chips)
    print(f"window: {run.federations} federations, {run.epochs} epochs in "
          f"{run.window_s:.3f} s, {run.compiles} compilations inside it; "
          f"set-up {run.setup_s:.3f} s, {run.setup_compile_s:.3f} s of it "
          f"compiling or loading programs, {run.setup_misses} compiled on a "
          f"compile-cache miss; D_max {run.cfg.d_max}",
          file=sys.stderr, flush=True)
    metrics = read_metrics(run, trace)
    if trace and run.trace.device_ops:
        device["busy_s"] = run.trace.mean_busy_s()
        device["window_s"] = run.trace.window_s()
    from bench import check

    checks = check.compare(run)
    result = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
              "attempted": run.federations,
              "failed": check.failed_answers(run, checks),
              "metrics": metrics, "device": device,
              "setup_compiled": run.setup_misses,
              "setup_compile_s": run.setup_compile_s}
    if trace:
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    result["checks"] = checks
    return result
