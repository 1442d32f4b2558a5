"""DFL-DDS is architecture-agnostic: run one federated round over any of the
10 assigned architectures (reduced variants on CPU) with the SAME launch-layer
train step that the multi-pod dry-run lowers.

  PYTHONPATH=src python examples/multiarch_dfl.py --archs qwen3-1.7b rwkv6-3b mixtral-8x7b
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.compile_cache import enable_compile_cache
from repro.configs import assigned_architectures, get_config
from repro.launch import steps as steps_lib


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="*", default=["qwen3-1.7b", "rwkv6-3b",
                                                   "granite-moe-1b-a400m"],
                    choices=assigned_architectures())
    ap.add_argument("--vehicles", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                ("vehicle", "fsdp", "model"))
    v = args.vehicles
    contact = jnp.asarray(np.minimum(
        np.eye(v) + np.roll(np.eye(v), 1, 1) + np.roll(np.eye(v), -1, 1), 1),
        jnp.float32)
    target = jnp.ones((v,)) / v

    for arch in args.archs:
        cfg = get_config(arch).reduced()
        ts = steps_lib.build_dds_train_step(cfg, mesh, lr=1e-3, remat=False,
                                            p1_steps=60)
        rng = jax.random.PRNGKey(0)
        params, opt_state, sm = steps_lib.init_train_state(cfg, v, rng)
        step = jax.jit(ts.fn)
        print(f"--- {arch} ({cfg.family}) reduced: d={cfg.d_model} L={cfg.num_layers}")
        for it in range(args.rounds):
            rng, kd, kr = jax.random.split(rng, 3)
            tokens = jax.random.randint(kd, (v, 2, 32), 0, cfg.true_vocab_size)
            extra = ()
            if cfg.embed_input:
                extra = (0.02 * jax.random.normal(
                    kd, (v, 2, cfg.frontend_tokens, cfg.d_model)),)
            t0 = time.time()
            params, opt_state, sm, m = step(params, opt_state, sm, tokens,
                                            contact, target, kr, *extra)
            jax.block_until_ready(m["loss"])
            print(f"  round {it}: loss={float(m['loss']):.4f} "
                  f"mean-KL={float(m['kl']):.4f} ({time.time()-t0:.1f}s)")


if __name__ == "__main__":
    main()
