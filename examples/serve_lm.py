"""Batched serving example: prefill + greedy decode with a KV cache.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --tokens 24
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import all_configs
from repro.models import build_model
from repro.train.train_step import make_serve_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = all_configs()[args.arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = args.batch
    meta = getattr(cfg, "num_meta_tokens", 0)
    cache = model.init_cache(B, meta + args.prompt_len + args.tokens + 4)
    serve_step = jax.jit(make_serve_step(model))

    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, (B, args.prompt_len))
    tok = jnp.asarray(prompt[:, :1], jnp.int32)
    generated = []
    t0 = time.time()
    for i in range(args.prompt_len + args.tokens - 1):
        nxt, cache = serve_step(params, cache, tok,
                                jnp.asarray(meta + i + 1, jnp.int32))
        if i + 1 < args.prompt_len:
            tok = jnp.asarray(prompt[:, i + 1:i + 2], jnp.int32)
        else:
            tok = nxt[:, None]
            generated.append(np.asarray(nxt))
    dt = time.time() - t0
    gen = np.stack(generated, 1)
    print(f"[serve] {args.arch}: generated {gen.shape[1]} tokens × "
          f"batch {B} in {dt:.1f}s ({B * gen.shape[1] / dt:.1f} tok/s)")
    print("[serve] first sequence:", gen[0].tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
