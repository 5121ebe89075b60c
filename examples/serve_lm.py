"""Serving example: batched prefill + decode with any assigned architecture.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-370m --tokens 16

Runs the smoke-sized config of the chosen architecture: prefills a batch of
prompts, then decodes tokens autoregressively against the KV/SSM cache —
the same serve_step the multi-pod dry-run lowers at production shape.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.models import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params, _ = model.init(rng)
    B, P, T = args.batch, args.prompt_len, args.tokens
    cache_len = P + T
    prompts = jax.random.randint(rng, (B, P), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(rng, (B, cfg.n_patches, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(rng, (B, P, cfg.d_model))

    t0 = time.time()
    logits, caches, _ = model.apply(batch=batch, params=params,
                                    make_cache=True, cache_len=cache_len)
    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
    print(f"prefill {B}x{P} in {time.time()-t0:.2f}s "
          f"({args.arch}, {cfg.n_layers}L smoke config)")

    decode = jax.jit(model.decode_step)
    out = [tok]
    t0 = time.time()
    for i in range(T - 1):
        logits, caches = decode(params, caches, tok, jnp.int32(P + i))
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        out.append(tok)
    seqs = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    print(f"decoded {T-1} steps x {B} seqs in {dt:.2f}s "
          f"({(T-1)*B/max(dt,1e-9):.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  seq[{b}]: {seqs[b].tolist()}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
