"""Device time of the engine's layers, by the named scopes the program puts
on them.

The program wraps six layers in ``jax.named_scope``: ``SCOPES``. XLA keeps
the scope in the ``op_name`` metadata of every instruction it compiles from
that layer, under transforms too (``vmap(transpose(jvp(local_train)))/...``),
so a scope is matched as a whole token of the path: after ``/`` or ``(``,
before ``)`` or ``/``. An instruction belongs to the innermost scope on its
path; one the compiler made without an ``op_name`` belongs to the scope of
the loop, branch or fusion that runs it. A fusion carries the metadata XLA
gave it, so a fusion that crosses two layers counts for one of them.

A device operation in the trace carries only its instruction's name, and
a name is unique only within one module. So ``seconds_by_scope`` compiles
the cell's window once more after the timed window, at each length a
federation scans, on a fresh contact window of the same shapes (a
compile-cache load), reads each instruction's scope from the compiled
text, and keeps the result for the run. Where an operation of the traced
window is in none of those modules, or two of them give its name
different scopes, the map is not the traced program's, and nothing is
reported.
"""
from __future__ import annotations

import re

import numpy as np

from bench import spans
from bench.trace import clip, is_container

SCOPES = ("sample_batches", "local_train", "p1_solve", "gossip_mix",
          "state_vector", "eval")
_TOKEN = re.compile(r"(?<=[/(])(" + "|".join(SCOPES) + r")(?=[)/]|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLEES = re.compile(
    r"\b(?:body|condition|calls|branch_computations|true_computation|"
    r"false_computation)=\{?([^}]*?)\}?(?=,\s*[a-z_]+=|$)")

_memo: list = [None, None, None]   # (run, seconds by scope, why none)


def scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` on an ``op_name`` path, or None."""
    found = _TOKEN.findall(op_name)
    return found[-1] if found else None


def layer_map(hlo_text: str) -> dict[str, str | None]:
    """Instruction name -> its scope (None: under no listed scope), for
    every instruction of a compiled module's text. An instruction the
    compiler made without an ``op_name`` (a layout copy, an async slice in
    a loop) belongs to the scope of the loop, branch or call that runs its
    computation."""
    own: dict[str, str | None] = {}          # instruction -> its op_name
    home: dict[str, str] = {}                # instruction -> computation
    caller: dict[str, str] = {}              # computation -> instruction
    computation = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line[0].isspace():
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name = m.group(1)
        op_name = _OP_NAME.search(line)
        own[name] = op_name.group(1) if op_name else None
        home[name] = computation
        for callees in _CALLEES.findall(line):
            for callee in callees.split(","):
                caller.setdefault(callee.strip().lstrip("%"), name)

    def scope(name: str, depth: int = 0) -> str | None:
        if own[name] is not None:
            return scope_of(own[name])
        up = caller.get(home[name])
        return scope(up, depth + 1) if up and depth < 64 else None

    return {name: scope(name) for name in own}


def device_seconds(trace, layers: dict) -> dict[str | None, float]:
    """Seconds of the traced window's device operations by scope (None: no
    listed scope), averaged over the devices; control-flow containers,
    whose spans hold their bodies' operations, are left out."""
    lo, hi = trace.window()
    out: dict[str | None, float] = {}
    for ops in trace.device_ops.values():
        for e in ops:
            if is_container(e.name):
                continue
            for s, t in clip([(e.start, e.end)], lo, hi):
                key = layers.get(e.name)
                out[key] = out.get(key, 0.0) + (t - s) / 1e9
    return {k: v / len(trace.device_ops) for k, v in out.items()}


def unmapped(trace, layers: dict) -> list[str]:
    """Names of the traced window's device operations, containers left
    out, that ``layers`` does not know."""
    lo, hi = trace.window()
    return sorted({e.name for ops in trace.device_ops.values() for e in ops
                   if not is_container(e.name) and e.name not in layers
                   and clip([(e.start, e.end)], lo, hi)})


def window_lengths(cfg) -> list[int]:
    """The lengths of the windows a federation is scanned in
    (``fed.backends._drive_windows`` without progress lines)."""
    from repro.fed import engine

    size = engine._default_window(cfg, False)
    return sorted({min(size, cfg.epochs - start)
                   for start in range(0, cfg.epochs, size)})


def window_text(run, length: int) -> str:
    """The compiled text of the program's window of ``length`` epochs at
    the cell's shapes."""
    import jax
    import jax.numpy as jnp

    from bench.harness import fresh_stream

    ctx = run.ctx
    contacts = jax.tree_util.tree_map(
        jnp.asarray, fresh_stream(run, traced=False).window(length))
    mask = jnp.asarray(np.zeros(length, bool))
    lowered = ctx.window_jit.lower(ctx.init_state, ctx.init_rng, ctx.fed_data,
                                   ctx.target, contacts, mask)
    return lowered.compile().as_text()


def merged(maps: list[dict]) -> dict:
    """One map of several modules' ``layer_map``s; a name they give
    different scopes is left out."""
    out: dict = {}
    clash: set = set()
    for layers in maps:
        for name, scope in layers.items():
            if out.setdefault(name, scope) != scope:
                clash.add(name)
    return {k: v for k, v in out.items() if k not in clash}


def seconds_by_scope(run, metric: str) -> dict | None:
    """``device_seconds`` of the run's traced window, or None (with a line
    on stderr) where the trace has no device, the program no spans or no
    scopes, an operation of the trace is not in the compiled window, or the
    cell runs another backend than ``vmap``."""
    if not run.trace.device_ops:
        spans.note(metric, "the trace has no device operations")
        return None
    if run.cfg.backend != "vmap":
        spans.note(metric, f"scopes are read for the vmap window only, not "
                   f"{run.cfg.backend!r}")
        return None
    if spans.window(run, metric) is None:
        return None
    if _memo[0] is not run:
        layers = merged([layer_map(window_text(run, n))
                         for n in window_lengths(run.cfg)])
        missing = unmapped(run.trace, layers)
        if missing:
            got, why = None, (f"{len(missing)} device operations of the "
                              f"window are in no compiled window of the "
                              f"cell's shapes ({', '.join(missing[:3])})")
        elif not any(layers.values()):
            got, why = None, "no instruction of the window is in a scope"
        else:
            got, why = device_seconds(run.trace, layers), None
        _memo[:] = [run, got, why]
    if _memo[1] is None:
        spans.note(metric, _memo[2])
    return _memo[1]


def device_ms_per_epoch(run, metric: str, scope: str) -> float | None:
    """Device milliseconds an epoch under ``scope`` in the traced window."""
    got = seconds_by_scope(run, metric)
    if got is None:
        return None
    return 1e3 * got.get(scope, 0.0) / run.epochs
