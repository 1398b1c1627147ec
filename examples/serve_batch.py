"""Serve a small model with batched requests: prefill via the decode path,
then greedy generation with the KV-cache/SSM-state machinery — the same
serve_step ``launch/serve.py`` runs.

    PYTHONPATH=src python examples/serve_batch.py [--arch rwkv6-7b]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch, list_archs
from repro.models import model as M
from repro.serve.decode import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(42)
    if cfg.n_codebooks:
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len, cfg.n_codebooks), 0,
            cfg.vocab_size)
    else:
        prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
    vision = None
    if cfg.family == "vlm":
        vision = jax.random.normal(
            jax.random.PRNGKey(7),
            (args.batch, cfg.n_vision_tokens, cfg.vision_dim)
        ).astype(jnp.bfloat16)

    print(f"serving {args.arch} (reduced), batch={args.batch}")
    out = greedy_generate(cfg, params, prompt, args.max_new, vision=vision)
    print("prompt :", prompt[0].tolist())
    print("output :", out[0].tolist())
    assert out.shape[1] == args.max_new
    print("ok — generated", out.shape, "tokens")


if __name__ == "__main__":
    main()
