"""The MoE training cell's reference (``bench/reference_moe.py``) against
the program at tiny sizes, and a whole tiny run of its job kind
(``bench/jobs/train_job_moe.py``): the same weights from the same seed,
the same latent attention, the same experts when the router sends every
token to one of them, the same three AdamW steps in float32; the run is
correct and each planted fault and the float8 control is not. The tiny
configuration is Moonlight-shaped: one dense layer and two expert layers,
16 experts of which 8 are held, top-4, 2 shared, d 64."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_moe as F
from bench import reference_moe as RM
from bench.jobs import train_job_moe as J
from bench.traffic_gen import MarkovStream

SEED = 2**31 + 77

TINY = {
    "name": "tinymoe", "attention_bias": False, "ep_size": 1,
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "kv_lora_rank": 32,
    "max_position_embeddings": 256, "model_type": "deepseek_v3",
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 16, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 256, "n_experts_held": 8, "first_expert_held": 4,
    "aux_loss_alpha": 0.001, "router_bias_std": 0.01,
    "published": {"num_hidden_layers": 27, "n_experts_held": 64}}
TRAFFIC = {"job": "train_job_moe", "global_batch": 4, "seq_len": 32,
           "data_vocab": 64, "markov_temp": 2.5, "lr": 0.003,
           "warmup_steps": 5, "remat": "full"}
# Set as the chip cell's are, from three seeds at these sizes: above what
# the program reads (bfloat16 compute; at most 3.9e-3, 1.14e-2, 6.7e-3),
# below what the half-batch fault reads (at least 1.5e-2, 0.10, 0.041);
# the float8 control reads a gradient gap of 3.7e-2 or more.
LIMITS = {"data_mismatch": 0, "loss_gap": 8e-3, "grad_norm_gap": 2.5e-2,
          "param_change_gap": 2e-2, "window_compiles": 0}
CELL = "tinymoe.train"


def _program():
    from repro.models import model as M
    arch = J.arch(TINY)
    return arch, jax.jit(lambda k: M.init_params(arch, k))(
        jax.random.PRNGKey(SEED))


def test_weights_match_the_program():
    _, prog = _program()
    ref = RM.init_params(TINY, SEED)
    pa, ra = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (prog, ref))
    assert [p for p, _ in pa] == [p for p, _ in ra]
    for (_, a), (_, b) in zip(pa, ra):
        np.testing.assert_array_equal(a, b)


def test_latent_attention_matches_the_reference():
    from repro.models import blocks as B
    arch, prog = _program()
    p = jax.tree.map(lambda a: a[0], prog["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64), jnp.float32)
    rope = B.rope_table(24, arch.rope_dim, arch.rope_theta)
    with jax.default_matmul_precision("highest"):
        got = B.mla_block(p, x, arch, rope=rope)
    np.testing.assert_allclose(got, RM.mla(p, x, TINY), atol=2e-5, rtol=1e-4)


def test_experts_take_every_token_sent_to_one_expert():
    """The correction bias of one held expert is raised so that every
    token picks it: dropless, the layer still gives the reference's
    output, which computes every expert over every token."""
    from repro.models import blocks as B
    arch, prog = _program()
    p = jax.tree.map(lambda a: a[0], prog["layers"]["moe"])
    p["router_bias"] = p["router_bias"].at[6].set(10.0)
    y = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, aux, stats = B.moe_block(p, y, arch)
    want, want_aux = RM.experts(p, y, TINY)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    assert float(aux) == pytest.approx(float(want_aux) / 2, rel=1e-5)
    assert int(stats["moe/max_expert_rows"]) == 2 * 24


def test_three_steps_match_the_program_in_float32():
    from repro.launch.train import init_train
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import TrainConfig

    data = MarkovStream(SEED, 64, 16, 2, 2.5)
    batches = [data.batch_at(i) for i in range(3)]
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=10,
                           weight_decay=0.0)
    step, params, opt = init_train(
        J.arch(TINY), TrainConfig(compute_dtype="float32", remat="none"),
        ocfg, seed=SEED)
    start = jax.tree.map(jnp.copy, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            params, opt, m = step(params, opt, jax.tree.map(jnp.asarray, b))
            losses.append(float(m["loss"]))
            if i == 0:
                mu1 = RM.leaf_norms(opt["mu"])
    ref_losses, ref_grad, ref_change = RM.train_steps(
        TINY, SEED, batches, lr=3e-3, warmup=5, total=10)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    for k, v in ref_grad.items():
        assert abs(mu1[k] / 0.1 - v) <= 1e-4 * max(v, 1e-6), k
    change = RM.leaf_norms(jax.tree.map(jnp.subtract, params, start))
    for k, v in ref_change.items():
        assert abs(change[k] - v) <= 1e-3 * max(v, 1e-6), k
    assert ref_grad["layers/moe/router_bias"] == 0.0
    assert change["layers/moe/router_bias"] == 0.0


def test_step_flops_by_hand():
    cfg = dict(TINY, num_hidden_layers=3)
    mla = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    expert = 3 * 64 * 32
    per_token = 3 * mla + 3 * 64 * 128 + 2 * (64 * 16 + 2 * expert) \
        + 64 * 256
    attn = 2 * 4 * 32 * (24 + 16) * 3
    want = 3 * (10 * (2 * per_token + attn) + 2 * 7 * expert)
    assert F.train_step_flops(cfg, 10, 32, 7) == want
    flops, nbytes = F.expert_gemm_cost(cfg, 256)
    assert flops == 4 * 2 * 256 * expert
    assert nbytes == 4 * 3 * 256 * (64 + 32) * 2 + 4 * 2 * 8 * expert * 2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tinymoe")
    b = root / "bench"
    for sub in ("configs", "traffic", "pace", "limits"):
        (b / sub).mkdir(parents=True)
    (b / "configs" / "tinymoe.json").write_text(json.dumps(TINY))
    (b / "traffic" / "t.json").write_text(json.dumps(TRAFFIC))
    (b / "pace" / f"{CELL}.json").write_text('{"steps_per_s": 2.0}')
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(LIMITS))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tinymoe", "file": "bench/configs/tinymoe.json"}],
        "workloads": [{"name": CELL, "config": "tinymoe", "traffic": "t",
                       "chips": 1}],
        "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s",
                        "workloads": [CELL]},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return root


def _run(root, fault=None, after=None):
    from bench import harness, spec

    cell = spec.load_cell(CELL, root, root / "bench")
    return harness.execute(cell, SEED, 4, False, t_start=time.perf_counter(),
                           root=root, require_tpu=False, fault=fault,
                           after=after)


def test_program_is_correct_and_its_control_is_not(root):
    seen = {}

    def after(run):
        seen["control"] = J.control(run)
        seen["counters"] = run.state["sink"]["counters"]
    res = _run(root, after=after)
    assert res["correct"], res["compared"]
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    failed = [n for n, v, lim in seen["control"] if v > lim]
    assert failed, seen["control"]
    counters = seen["counters"]
    # two expert layers, 4 x 32 tokens, top-4, 8 of 16 experts held
    assert 0 < counters["moe/held_rows"] <= 2 * 128 * 4
    assert counters["moe/gemm_rows"] >= counters["moe/held_rows"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(root, fault):
    res = _run(root, fault=fault)
    assert not res["correct"]
    failed = {n for n, c in res["compared"].items()
              if c["value"] > c["limit"]} - {"window_compiles"}
    assert failed, res["compared"]
