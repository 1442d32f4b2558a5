"""Batched serving: prefill a batch of requests, then decode tokens for all
of them in lock-step — the serve_step the decode_32k / long_500k dry-runs
lower, at CPU scale (reduced configs).

Demonstrates all three cache families: KV cache (dense/MoE), RWKV recurrent
state (attention-free), and hybrid KV+SSM state (hymba).

  PYTHONPATH=src python examples/serve_batched.py --arch hymba-1.5b --batch 4
  PYTHONPATH=src python examples/serve_batched.py --smoke   # CI smoke test
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import assigned_architectures, get_config
from repro.models import multimodal, transformer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b", choices=assigned_architectures())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings so the run finishes in seconds")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.batch, args.prompt_len, args.gen = 1, 8, 4

    cfg = get_config(args.arch).reduced()
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    b, s = args.batch, args.prompt_len
    prompts = jax.random.randint(rng, (b, s), 0, cfg.true_vocab_size)
    prefix = None
    if cfg.embed_input:
        raw = jax.random.normal(
            rng, (b, cfg.frontend_tokens, multimodal.frontend_feature_dim(cfg)))
        prefix = multimodal.frontend_embeddings(cfg, raw)

    total = s + (cfg.frontend_tokens if cfg.embed_input else 0) + args.gen

    # prefill into a generation-sized cache
    prefill = jax.jit(lambda p, t, pre: transformer.prefill(
        p, t, cfg, prefix_embeds=pre, cache_dtype=jnp.float32))
    t0 = time.time()
    logits, st = prefill(params, prompts, prefix)
    jax.block_until_ready(logits)
    print(f"{cfg.name}: prefill {b}x{s} in {time.time()-t0:.2f}s")

    state = transformer.init_decode_state(cfg, b, total, cache_dtype=jnp.float32)
    if st.kv is not None:
        pl = st.kv.k.shape[2]
        state = state._replace(kv=state.kv._replace(
            k=state.kv.k.at[:, :, :pl].set(st.kv.k),
            v=state.kv.v.at[:, :, :pl].set(st.kv.v),
            length=jnp.broadcast_to(st.kv.length, state.kv.length.shape)))
    state = state._replace(rwkv=st.rwkv, ssm=st.ssm, position=st.position)

    decode = jax.jit(lambda p, t, s_: transformer.decode_step(p, t, s_, cfg))
    cur = jnp.argmax(logits, axis=-1)[:, None]
    generated = [cur]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, state = decode(params, cur, state)
        cur = jnp.argmax(logits, axis=-1)[:, None]
        generated.append(cur)
    jax.block_until_ready(logits)
    dt = time.time() - t0
    toks = jnp.concatenate(generated, axis=1)
    print(f"decoded {args.gen} tokens x {b} requests in {dt:.2f}s "
          f"({dt/max(args.gen-1,1)*1000:.0f} ms/step, batched)")
    for i in range(b):
        print(f"  req{i}: {toks[i, :12].tolist()}...")
    assert toks.shape == (b, args.gen)
    print(f"serve_batched OK: {cfg.name} decoded {args.gen}x{b} tokens")


if __name__ == "__main__":
    main()
