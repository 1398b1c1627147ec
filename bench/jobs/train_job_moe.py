"""One training job per run of a DeepSeek-V3-style MoE configuration:
``AcaiEngine.submit`` -> ``LocalRunner`` -> ``launch/train.py`` ``train``,
ending with the job's one checkpoint save, as ``train_job`` runs the dense
cells.

The configuration file holds the published config.json's keys (cut where
``BENCHMARK.json``'s ``reduced`` says) and the share held here
(``n_experts_held``, ``first_expert_held``); ``arch`` builds the
program's ``ArchConfig`` from all of them and refuses a setting the
program does not run. The window's first three steps are checked against
``reference_moe.py``.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import reference_moe as RM
from bench import train_common as TC

# settings of the published config that the program runs only as given
FIXED = {"model_type": "deepseek_v3", "hidden_act": "silu",
         "attention_bias": False, "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc", "scoring_func":
         "sigmoid", "norm_topk_prob": True, "num_nextn_predict_layers": 0,
         "ep_size": 1}


def arch(config: dict):
    """The program's ArchConfig for the configuration file ``config``."""
    from repro.configs.base import ArchConfig, MoEConfig

    for k, v in FIXED.items():
        if config[k] != v:
            raise ValueError(f"{config['name']}: {k}={config[k]!r}; the "
                             f"program runs {v!r}")
    return ArchConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        moe=MoEConfig(
            n_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            d_ff_expert=config["moe_intermediate_size"],
            n_shared_experts=config["n_shared_experts"],
            d_ff_shared=config["moe_intermediate_size"],
            score_func=config["scoring_func"],
            routed_scaling=config["routed_scaling_factor"],
            bias_std=config["router_bias_std"],
            aux_coef=config["aux_loss_alpha"], seq_aux=config["seq_aux"],
            n_held=config["n_experts_held"],
            first_held=config["first_expert_held"],
            moe_every=config["moe_layer_freq"]))


def _steps(run) -> int:
    return max(TC.CHECKED_STEPS + 1,
               round(run.seconds * run.cell.pace["steps_per_s"]))


def _ocfg(run):
    from repro.train.optimizer import OptimizerConfig

    t = run.cell.traffic
    return OptimizerConfig(lr=t["lr"], warmup_steps=t["warmup_steps"],
                           total_steps=_steps(run), weight_decay=0.0)


def setup(run) -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.train import init_train
    from repro.train.train_step import TrainConfig

    rec = TC.Recorder(run.fault)
    TC.install(run, rec)
    run.state["recorder"] = rec
    run.state["arch"] = arch(run.cell.config)
    TC.make_engine(run)
    # compile (or load) and run once every program the job runs: the
    # step (its schedule bakes in the learning rate and step count) and
    # the recorder's copy and norms. Not the copy of the whole state that
    # only the "unchanged" fault takes: it would not fit beside the state.
    step, params, opt = init_train(
        run.state["arch"], TrainConfig(remat=run.cell.traffic["remat"]),
        _ocfg(run), seed=run.seed)
    batch = jax.tree.map(jnp.asarray, TC.stream(run).batch_at(0))
    start = rec.copy(params)
    params, opt, metrics = step(params, opt, batch)
    float(metrics["loss"])
    [float(v) for v in rec.norms(opt["mu"])]
    [float(v) for v in rec.diff(params, start)]
    del step, params, opt, start, metrics
    TC.submit(run, "warm-up", lambda wd, job: {})


def window(run) -> dict:
    from repro.launch.train import train
    from repro.train.train_step import TrainConfig

    steps, t = _steps(run), run.cell.traffic
    sink: dict = {}
    run.state["sink"] = sink
    run.state["recorder"].armed = True

    def fn(workdir, job):
        with run.spans.span("job_fn"):
            res = train(run.state["arch"], run.state["project"], "train",
                        steps=steps, seq_len=t["seq_len"],
                        global_batch=t["global_batch"],
                        data_vocab=t["data_vocab"], save_every=steps,
                        tcfg=TrainConfig(remat=t["remat"]), lr=t["lr"],
                        seed=run.seed)
            sink["losses"] = res.losses
            sink["steps"] = res.report.steps_run
            sink["counters"] = res.report.counter_means()
        return {"steps": res.report.steps_run}

    with run.spans.span("turnaround"):
        ok = TC.submit(run, "train", fn)
    (t0, t1), = run.spans.of("turnaround")
    tokens = sink.get("steps", 0) * t["global_batch"] * t["seq_len"]
    return {"metrics": {"train_tokens_per_s": tokens / (t1 - t0)},
            "attempted": 1, "failed": 0 if ok and sink.get("steps") == steps
            else 1}


def release(run) -> None:
    run.state.pop("engine", None)


def _reference(run, quant=None):
    t = run.cell.traffic
    return RM.train_steps(run.cell.config, run.seed, run.state["batches"],
                          lr=t["lr"], warmup=t["warmup_steps"],
                          total=_steps(run), quant=quant)


def check(run) -> list:
    """The job's first three steps against the reference, each number
    beside its limit (``train_common.compare``)."""
    rec = run.state["recorder"]
    data = TC.stream(run)
    batches = [data.batch_at(i) for i in range(TC.CHECKED_STEPS)]
    run.state["batches"] = batches
    mismatch = sum(int(not np.array_equal(b["tokens"], got))
                   for b, got in zip(batches, rec.tokens))
    mismatch += TC.CHECKED_STEPS - len(rec.tokens)
    with run.spans.span("reference"):
        ref = _reference(run)
    run.state["reference"] = ref
    losses = run.state["sink"].get("losses", [])[:TC.CHECKED_STEPS]
    if len(losses) < TC.CHECKED_STEPS or rec.change is None:
        print("the job ran fewer than three steps", file=sys.stderr)
        return [("data_mismatch", float(TC.CHECKED_STEPS),
                 float(run.cell.limits["data_mismatch"]))]
    return TC.compare(losses, rec.readings(), ref, mismatch,
                      run.cell.limits)


def control(run, quant="fp8") -> list:
    """The reference computed at ``quant`` in the program's place, held to
    the same numbers and limits (run after ``check``)."""
    losses, grad, change = _reference(run, quant)
    return TC.compare(losses, (grad, change), run.state["reference"], 0,
                      run.cell.limits)
