"""Compile the gossip-mix Pallas kernels, and the engine's batch gather, for
a described TPU v5e (2x2) without a chip: shapes only, nothing runs. The TPU
compiler refuses here what interpret mode cannot see (scalar-prefetch
operands that overflow SMEM, tiles that overflow VMEM, slices not aligned to
the tiling), and its layouts show where a whole array is copied, so these
tests guard the chip path at CPU cost.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the worker given this file
is the one that loads it. All chip-compile tests live in this one file for
the same reason.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip_mix import gossip_mix_gather, gossip_mix_matmul

# MNIST CNN parameter count: the row width one gossip mix moves per vehicle
PARAMS = 21_840


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k_out,k_in,p", [
    (100, 100, 16_000),      # the paper fleet's full gossip mix
    (100, 25, PARAMS),       # one shard's column block, 4-way sharded mix
    (1024, 1024, PARAMS),    # a thousand-vehicle fleet, dense contacts
])
def test_gossip_mix_matmul_compiles_for_v5e(one_chip, k_out, k_in, p):
    compiled = jax.jit(gossip_mix_matmul).lower(
        _spec((k_out, k_in), jnp.float32, one_chip),
        _spec((k_in, p), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("k_out,k_in,d", [
    (100, 100, 12),          # the paper fleet, sparse contacts
    (100, 25, 12),           # sharded: global rows gather one shard's sources
    (1024, 1024, 12),        # the fleet size the sparse format exists for
])
def test_gossip_mix_gather_compiles_for_v5e(one_chip, k_out, k_in, d):
    compiled = jax.jit(gossip_mix_gather).lower(
        _spec((k_out, d), jnp.int32, one_chip),
        _spec((k_out, d), jnp.float32, one_chip),
        _spec((k_in, PARAMS), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled)


# the MNIST train set as the engine stores it: N images of 28x28x1, flat.
# The real N: a set that fits the chip's VMEM (128 MiB) is prefetched into it
# whole, which would be one more whole-set buffer.
N_TRAIN, IMAGE = 60_000, (28, 28, 1)


def _instructions(hlo_text):
    """``(computation, name, opcode, [(dtype, dims, layout)], operands)`` of
    every HLO instruction; a tuple result lists each of its arrays."""
    out, computation = [], None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            computation = line.split()[0]  # "ENTRY" for the entry
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):  # tuple type: up to its matching paren
            depth = 0
            for end, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            typ, rest = rest[:end + 1], rest[end + 1:]
        else:
            typ, _, rest = rest.partition(" ")
        op = re.match(r"\s*([\w-]+)\((.*)", rest)
        if not op:
            continue
        shapes = [(d, tuple(int(n) for n in dims.split(",") if n), lay)
                  for d, dims, lay in re.findall(r"(\w+)\[([\d,]*)\]\{([^}]*)\}",
                                                 typ)]
        out.append((computation, name, op.group(1), shapes, op.group(2)))
    return out


def test_batch_gather_copies_whole_rows_for_v5e(one_chip):
    """Two scanned epochs of batch sampling and the vmapped MNIST local
    train, compiled for a described v5e with the flat train set in the
    layout a TPU gives it by default (N minor, which pads least): the image
    gather reads whole rows of a row-major [N, 784] array, and the set is
    relaid out once, in the entry, not in every epoch."""
    from jax.experimental.layout import Format, Layout

    from repro.data import pipeline
    from repro.fed.engine import make_local_train_fn
    from repro.models import cnn
    from repro.optim import sgd

    k, e, b, w, epochs = 4, 2, 8, 100, 2
    row = int(np.prod(IMAGE))
    init_fn, loss_fn, _ = cnn.make_cnn_task("mnist")
    optimizer = sgd(0.1)
    train = jax.vmap(make_local_train_fn(loss_fn, optimizer))

    def window(data, key, params, opt_state):
        def epoch(carry, key):
            kb, kt = jax.random.split(key)
            batch = pipeline.sample_batches_sliced(data, kb, e, b, IMAGE)
            params, opt_state, _ = train(*carry, batch,
                                         jax.random.split(kt, k))
            return (params, opt_state), None

        return jax.lax.scan(epoch, (params, opt_state),
                            jax.random.split(key, epochs))[0]

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda p: _spec((k,) + p.shape, p.dtype, one_chip), tree)

    params0 = init_fn(jax.random.PRNGKey(0))
    data = pipeline.FederatedData(
        x=_spec((N_TRAIN, row), jnp.float32, Format(Layout((1, 0)), one_chip)),
        y=_spec((N_TRAIN,), jnp.int32, one_chip),
        index_table=_spec((k, w), jnp.int32, one_chip),
        counts=_spec((k,), jnp.int32, one_chip))
    text = jax.jit(window).lower(
        data, _spec((2,), jnp.uint32, one_chip), stack(params0),
        stack(jax.eval_shape(optimizer.init, params0))).compile().as_text()

    instrs = _instructions(text)
    shape_of = {name: shapes[0] for _, name, _, shapes, _ in instrs if shapes}
    whole_set = N_TRAIN * row
    gathers = []
    for _, name, opcode, _, operands in instrs:
        src = shape_of.get(operands.split(",")[0].strip().lstrip("%"))
        if opcode == "gather" and src and np.prod(src[1]) == whole_set:
            gathers.append((name, src, operands))
    assert gathers, "no gather reads the train set"
    for name, (_, dims, layout), operands in gathers:
        assert dims == (N_TRAIN, row), (name, dims)
        assert layout.split(":")[0] == "1,0", (name, layout)
        assert f"slice_sizes={{1,{row}}}" in operands, (name, operands)
    # arrays of the whole set's size that an instruction computes (the
    # compiler may fold the conv's cast to bfloat16 into the relayout)
    made = [(comp, name, shapes[0]) for comp, name, opcode, shapes, _ in instrs
            if len(shapes) == 1 and np.prod(shapes[0][1]) == whole_set
            and opcode not in ("parameter", "get-tuple-element", "bitcast")]
    assert len(made) == 1, made
    comp, name, (_, dims, layout) = made[0]
    assert comp == "ENTRY" and dims == (N_TRAIN, row), made
    assert layout.split(":")[0] == "1,0", made
