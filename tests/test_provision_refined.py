"""Active-refinement provisioning (beyond-paper) + feature templates."""

from repro.core.provision.autoprovision import AutoProvisioner
from repro.core.provision.features import template_for
from repro.core.provision.pricing import TPU_PRICING
from repro.core.provision.profiler import CommandTemplate, Profiler


def wall_oracle(cfg):
    """Compute 1/chips scaling up to a hard collective wall at 2s/step —
    the regime where the paper's plain log-linear extrapolation fails."""
    per_step = max(600.0 / cfg["chips"], 2.0)
    return cfg["steps"] * per_step


TEMPLATE = CommandTemplate(
    name="walled",
    hints={"steps": [10, 20]},
    resource_hints={"chips": [8, 32, 128], "hbm_gb": [4, 16]})


def _profiler():
    prof = Profiler(engine=None)
    grid = TEMPLATE.grid()
    prof.fit_offline(TEMPLATE, grid, [wall_oracle(c) for c in grid])
    return prof


def test_refined_search_respects_budget_at_the_wall():
    """Against a hard collective wall the log-linear fit mispredicts
    beyond the profiled hull; refinement must end feasible-and-faster with
    its final measured prediction accurate. (The full overshoot-then-fix
    drama on the realistic oracle is exercised by bench_table23.)"""
    prof = _profiler()
    ap = AutoProvisioner(prof, TPU_PRICING)
    values = {"steps": 100}
    baseline = {"chips": 32, "hbm_gb": 16}
    t_base = wall_oracle({**values, **baseline})
    c_base = TPU_PRICING.job_cost(baseline, t_base)

    dec, hist = ap.refined_search(TEMPLATE.name, values,
                                  measure_fn=wall_oracle,
                                  objective="runtime", max_cost=c_base,
                                  rounds=4)
    assert dec.feasible and len(hist) >= 1
    t_true = wall_oracle({**values, **dec.resources})
    assert t_true < t_base                    # actually faster
    # final accepted round's prediction is accurate
    assert hist[-1]["rel_err"] <= 0.10
    # every refinement observation entered the training set
    cfgs, _ = prof.training_sets[TEMPLATE.name]
    assert len(cfgs) >= len(TEMPLATE.grid()) + len(hist) - 1


def test_refined_search_converges_when_model_is_right():
    prof = Profiler(engine=None)
    grid = TEMPLATE.grid()
    exact = lambda c: c["steps"] * 600.0 / c["chips"]   # pure power law
    prof.fit_offline(TEMPLATE, grid, [exact(c) for c in grid])
    ap = AutoProvisioner(prof, TPU_PRICING)
    dec, hist = ap.refined_search(TEMPLATE.name, {"steps": 50},
                                  measure_fn=exact, objective="runtime",
                                  max_cost=1e9)
    assert len(hist) == 1                     # first measurement confirms
    assert hist[0]["rel_err"] < 0.05


def test_template_for_families():
    from repro.configs.base import get_arch
    dense = template_for(get_arch("qwen3-8b"), "train_4k")
    assert set(dense.resource_hints) == {"chips", "hbm_gb"}
    moe = template_for(get_arch("olmoe-1b-7b"), "train_4k")
    assert "ep_width" in moe.resource_hints
    assert all(64 % w == 0 for w in moe.resource_hints["ep_width"])
    ssm = template_for(get_arch("rwkv6-7b"), "long_500k")
    assert "kv_shard" in ssm.resource_hints
    assert len(dense.grid()) == 27

