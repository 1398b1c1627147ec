"""Batched serving driver: continuous-batching loop over the one-token
serve step (the same program the decode dry-run cells lower for the
production meshes). ``serve`` takes any config; ``main`` runs it on the
reduced one.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --requests 6
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, get_arch, list_archs
from repro.core.trace import span
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.models import transformer as T
from repro.serve.decode import make_serve_step


@dataclasses.dataclass
class ServeResult:
    produced: dict[int, list[int]]        # request -> generated tokens
    # request -> logits (V,) of the step that fed its last prompt token,
    # i.e. the distribution of its first generated token
    prompt_logits: dict[int, np.ndarray]
    ticks: int


def serve(cfg: ArchConfig, params, prompts: list[list[int]], *,
          slots: int, buffer_len: int, max_new: int) -> ServeResult:
    """Serve ``prompts`` through ``slots`` decode slots, each holding a
    ``buffer_len``-token KV cache. Prompts are fed one token per step;
    a slot that finishes its request takes the next one from the queue
    without stalling the others."""
    if cfg.family == "vlm" or cfg.n_codebooks:
        raise ValueError("the serving driver supports token-only archs")
    longest = max(len(p) for p in prompts) + max_new
    if longest > buffer_len:
        raise ValueError(f"a request needs {longest} cache positions; "
                         f"buffer_len is {buffer_len}")
    with span("serve/init"):
        states = T.init_decode_state(cfg, slots, buffer_len)
        # states donated: the step writes the new K/V rows in place
        step = jax.jit(make_serve_step(cfg, buffer_len), donate_argnums=(1,))

    slot_req = [-1] * slots
    slot_prompt: list[list[int]] = [[] for _ in range(slots)]
    produced: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    prompt_logits: dict[int, np.ndarray] = {}
    cur = np.zeros((slots, 1), np.int32)
    next_req = 0
    done = 0

    def refill(s):
        nonlocal next_req
        if next_req < len(prompts):
            slot_req[s] = next_req
            slot_prompt[s] = list(prompts[next_req])
            cur[s, 0] = slot_prompt[s].pop(0)
            next_req += 1
            return True
        slot_req[s] = -1
        return False

    for s in range(slots):
        refill(s)
    cache_len = jnp.zeros((slots,), jnp.int32)

    ticks = 0
    max_ticks = len(prompts) * longest
    while done < len(prompts) and ticks < max_ticks:
        ticks += 1
        with span("serve/dispatch"):
            batch = {"tokens": jnp.asarray(cur), "cache_len": cache_len}
            logits, states, nxt = step(params, states, batch)
            cache_len = cache_len + 1
        with span("serve/sync"):
            nxt = np.asarray(nxt)
        with span("serve/host"):
            for s in range(slots):
                r = slot_req[s]
                if r < 0:
                    continue
                if slot_prompt[s]:                      # still prefilling
                    cur[s, 0] = slot_prompt[s].pop(0)
                    continue
                if not produced[r]:
                    prompt_logits[r] = np.asarray(logits[s, -1], np.float32)
                produced[r].append(int(nxt[s]))
                cur[s, 0] = int(nxt[s])
                if len(produced[r]) >= max_new:
                    done += 1
                    # reset this slot's cache and grab the next request
                    cache_len = cache_len.at[s].set(0)
                    refill(s)
    if done < len(prompts):
        raise RuntimeError(f"served {done}/{len(prompts)} requests in "
                           f"{ticks} steps")
    return ServeResult(produced, prompt_logits, ticks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    queue = [rng.integers(0, cfg.vocab_size, rng.integers(3, 8)).tolist()
             for _ in range(args.requests)]
    res = serve(cfg, params, queue, slots=args.slots, buffer_len=32,
                max_new=args.max_new)
    for r, toks in res.produced.items():
        print(f"request {r}: prompt={queue[r]} -> {toks}")
    print(f"served {len(queue)}/{len(queue)} requests in {res.ticks} decode "
          f"ticks ({args.slots} slots)")


if __name__ == "__main__":
    main()
