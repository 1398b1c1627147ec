"""One batch-generation job per run: a queue of prompts, all there at the
start, served by ``AcaiEngine.submit`` -> ``LocalRunner`` ->
``launch/serve.py`` ``serve`` through its decode slots.

The queue holds ``max(slots, round(seconds x requests_per_s))`` prompts
(``pace/<cell>.json``); their lengths are the same set for every seed
(``traffic_gen.prompt_set``). Weights are drawn on the device from the
seed in one jitted call, in bfloat16, as the reference draws them.
"""
from __future__ import annotations

import numpy as np

from bench import reference as R
from bench import train_common as TC
from bench.traffic_gen import prompt_set


def _n_requests(run) -> int:
    t = run.cell.traffic
    return max(t["slots"], round(run.seconds * run.cell.pace["requests_per_s"]))


def _prompts(run):
    t = run.cell.traffic
    return prompt_set(run.seed, _n_requests(run), run.arch["vocab_size"],
                      median=t["prompt_median"], sigma=t["prompt_sigma"],
                      lo=t["prompt_min"], hi=t["prompt_max"])


def make_weights(arch: dict, seed: int):
    import jax
    import jax.numpy as jnp

    def draw(key):
        return jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                            R.init_params_key(arch, key))
    return jax.jit(draw)(jax.random.PRNGKey(seed))


def setup(run) -> None:
    from repro.launch.serve import serve

    t = run.cell.traffic
    cfg = TC.arch_config(run)
    TC.make_engine(run)
    run.state["params"] = make_weights(run.arch, run.seed)
    run.state["prompts"] = _prompts(run)
    # the same step program, buffer and slot count as the window; every
    # slot finishes and refills at least once
    warm = [[1, 2]] * (t["slots"] + 1)
    serve(cfg, run.state["params"], warm, slots=t["slots"],
          buffer_len=t["buffer_len"], max_new=2)
    TC.submit(run, "warm-up", lambda wd, job: {})


def window(run) -> dict:
    from repro.launch.serve import serve

    t = run.cell.traffic
    cfg = TC.arch_config(run)
    sink: dict = {}

    def fn(workdir, job):
        with run.spans.span("job_fn"):
            res = serve(cfg, run.state["params"], run.state["prompts"],
                        slots=t["slots"], buffer_len=t["buffer_len"],
                        max_new=t["max_new"])
        sink["produced"] = res.produced
        sink["ticks"] = res.ticks
        return {"ticks": res.ticks}

    with run.spans.span("turnaround"):
        ok = TC.submit(run, "generate", fn)
    (t0, t1), = run.spans.of("turnaround")
    run.state["sink"] = sink
    produced = sink.get("produced", {})
    vocab = run.arch["vocab_size"]
    good = [r for r, toks in produced.items()
            if len(toks) == t["max_new"] and all(0 <= x < vocab for x in toks)]
    if run.fault == "altered_token":
        for r in good:
            k = t["max_new"] // 2
            produced[r][k] = (produced[r][k] + vocab // 2) % vocab
    n = _n_requests(run)
    return {"metrics": {"gen_tokens_per_s": len(good) * t["max_new"]
                        / (t1 - t0)},
            "attempted": n, "failed": n - len(good) if ok else n}


def release(run) -> None:
    run.state.pop("params", None)
    run.state.pop("engine", None)


def sample(run) -> list[int]:
    """Requests compared: the one with the longest prompt and others drawn
    from the seed."""
    prompts = run.state["prompts"]
    k = min(run.cell.traffic["check_requests"], len(prompts))
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    rng = np.random.default_rng((run.seed, 0x5A))
    rest = [i for i in rng.permutation(len(prompts)) if i != longest]
    return [longest] + [int(i) for i in rest[:k - 1]]


def gaps(run, quant=None) -> float:
    """Widest gap by which a served token's logit lies below the
    reference's best, over the sampled requests. With ``quant`` set the
    tokens compared are those that precision's logits put first."""
    t = run.cell.traffic
    prompts, produced = run.state["prompts"], run.state["sink"]["produced"]
    reqs = sample(run)
    seqs = [prompts[r] + produced[r][:-1] for r in reqs]
    pos = [list(range(len(prompts[r]) - 1, len(prompts[r]) - 1
                      + t["max_new"])) for r in reqs]
    pad = t["prompt_max"] + t["max_new"]
    ref = R.served_logits(run.arch, run.seed, seqs, pos, pad)
    if quant is None:
        picks = [np.asarray(produced[r]) for r in reqs]
    else:
        low = R.served_logits(run.arch, run.seed, seqs, pos, pad,
                              quant=quant)
        picks = [np.asarray(lg).argmax(-1) for lg in low]
    worst = 0.0
    for lg, tok in zip(ref, picks):
        lg = np.asarray(lg)
        worst = max(worst, float((lg.max(-1) - lg[np.arange(len(tok)),
                                                  tok]).max()))
    return worst


def check(run) -> list:
    with run.spans.span("reference"):
        gap = gaps(run)
    return [("logit_gap", gap, run.cell.limits["logit_gap"])]
