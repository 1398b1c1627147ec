"""Plain reference of the DeepSeek-V3 block that the MoE training cell runs
(Moonlight-16B-A3B): latent attention (MLA) and sigmoid-routed experts.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, written
from the papers (DeepSeek-V2, arXiv:2405.04434, section 2.1; DeepSeek-V3,
arXiv:2412.19437, section 2.1.2 and eqs. 17-20, the sequence-wise balance
loss) and the configuration file's keys, which are the published
config.json's. It
imports nothing of the program: no sort, no grouped product, no scan, no
cache. Each layer's experts run as a dense loop over the experts held,
over every token, with a 0/1 mask of the top-k. Weights are drawn from the
seed exactly as the program's ``init_params`` draws them (same keys,
shapes and order).

The layer, for x (S tokens, d):

- MLA, no q latent: q = x W_q split per head into q_nope and q_pe;
  [c, k_pe] = x W_kva; c = RMSNorm(c) (its own scale, eps 1e-6);
  [k_nope, v] = c W_kvb per head; RoPE (theta ``rope_theta``) on q_pe and
  on k_pe, one head shared by all; scores (q_nope.k_nope + q_pe.k_pe) /
  sqrt(qk_nope + qk_rope), causal softmax; out = (sum p v) W_o.
- h = x + MLA(RMSNorm(x)); y = h + FFN(RMSNorm(h)): SwiGLU of
  ``intermediate_size`` in the first ``first_k_dense_replace`` layers,
  else the experts: s = sigmoid(h W_r) over all experts; the top-k of
  s + b (b the layer's correction bias) picked; g = s / sum of the picked
  s x ``routed_scaling_factor``; out = sum over the picked experts held
  here of g_e SwiGLU_e(h), plus the shared experts' SwiGLU of
  ``n_shared_experts`` x ``moe_intermediate_size``.
- Balance loss, per sequence, averaged over the batch: alpha sum_i f_i P_i,
  f_i = E / (k S) #{t: i picked}, P_i = mean_t s_i,t / sum_j s_j,t.
- RMSNorm eps ``rms_norm_eps``; untied head; the loss is over the
  vocabulary held here (``vocab_size``).

Departures from the published model, made by the program alike: RoPE
rotates halves of the rope part (the published code rotates interleaved
pairs, a fixed permutation of W_q's and W_kva's rope columns); only the
experts held here (``n_experts_held`` from ``first_expert_held``) add to
a layer's output, the others lying on other chips; the correction bias is
drawn from the seed (std ``router_bias_std``) and never updated, the rule
that moves it in published training being left out (it takes no
gradient, so AdamW leaves it as it is here too).

``quant="fp8"`` rounds both operands of every matrix product to
float8_e4m3 first: the control, one precision step below the bfloat16
compute that the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import (_adamw, _dense, leaf_norms, leaf_paths, mm,
                             rope, schedule)

LATENT_NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# weights, drawn as the program draws them
# ---------------------------------------------------------------------------

def _rms_scale(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _swiglu_w(cfg, key, ff):
    d = cfg["hidden_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense(k1, (d, ff)), "w_up": _dense(k2, (d, ff)),
            "w_down": _dense(k3, (ff, d), fan_in=ff)}


def _mla_w(cfg, key):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rope_d = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": _dense(k1, (d, h * (nope + rope_d))),
            "wkv_a": _dense(k2, (d, r + rope_d)),
            "kv_norm": jnp.ones((r,), jnp.float32),
            "wkv_b": _dense(k3, (r, h * (nope + dv))),
            "wo": _dense(k4, (h * dv, d), fan_in=h * dv)}


def _experts_w(cfg, key):
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    ff = cfg["moe_intermediate_size"]
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    ids = range(cfg["first_expert_held"],
                cfg["first_expert_held"] + cfg["n_experts_held"])

    def held(k, shape, fan_in=None):
        return jnp.stack([_dense(jax.random.fold_in(k, i), shape, fan_in)
                          for i in ids])

    return {"router": _dense(k1, (d, e)),
            "router_bias": cfg["router_bias_std"] * jax.random.normal(
                jax.random.fold_in(k1, 1), (e,), jnp.float32),
            "w_gate": held(k2, (d, ff)), "w_up": held(k3, (d, ff)),
            "w_down": held(k4, (ff, d), fan_in=ff),
            "shared": _swiglu_w(cfg, k5, cfg["n_shared_experts"] * ff)}


def _layer_w(cfg, key, dense: bool):
    ka, kf, _ = jax.random.split(key, 3)
    d = cfg["hidden_size"]
    out = {"attn": _mla_w(cfg, ka), "ln1": _rms_scale(d),
           "ln2": _rms_scale(d)}
    if dense:
        out["mlp"] = _swiglu_w(cfg, kf, cfg["intermediate_size"])
    else:
        out["moe"] = _experts_w(cfg, kf)
    return out


def _stack(layers):
    return jax.tree.map(lambda *a: jnp.stack(a), *layers)


def init_params_key(cfg, key):
    """The whole tree, in the program's layout: the leading dense layers
    and the expert layers each stacked."""
    k1, k2, k3 = jax.random.split(key, 3)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    lead = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - lead
    return {
        "embed": jax.random.normal(k1, (v, d), jnp.float32) * 0.02,
        "final_norm": _rms_scale(d),
        "lm_head": _dense(k3, (d, v)),
        "lead_layers": _stack([_layer_w(cfg, k, True) for k in
                               jax.random.split(jax.random.fold_in(k2, 1),
                                                lead)]),
        "layers": _stack([_layer_w(cfg, k, False)
                          for k in jax.random.split(k2, n_moe)]),
    }


def _items(cfg) -> tuple:
    """The configuration's numbers and flags, hashable (prose left out)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


@functools.partial(jax.jit, static_argnums=(0,))
def _all_w(cfg_items, key):
    return init_params_key(dict(cfg_items), key)


def init_params(cfg, seed):
    return _all_w(_items(cfg), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def mla(p, x, cfg, quant=None, head_groups=4):
    """Latent attention over one causal sequence batch; x (B, S, d). The
    heads are computed in ``head_groups`` groups to bound the scores'
    memory."""
    b, s, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope_d, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    pos = jnp.arange(s)
    theta = cfg["rope_theta"]
    q = mm("bsd,de->bse", x, p["wq"], quant).reshape(b, s, h, nope + rope_d)
    kva = mm("bsd,de->bse", x, p["wkv_a"], quant)
    c = rms({"scale": p["kv_norm"]}, kva[..., :r], LATENT_NORM_EPS)
    kv = mm("bsr,re->bse", c, p["wkv_b"], quant).reshape(b, s, h, nope + dv)
    q_pe = rope(q[..., nope:], pos, theta)
    k_pe = rope(kva[..., None, r:], pos, theta)[:, :, 0]     # (B, S, R)
    mask = pos[:, None] >= pos[None, :]
    outs = []
    g = h // head_groups
    for i in range(0, h, g):
        sc = mm("bqhd,bkhd->bhqk", q[:, :, i:i + g, :nope],
                kv[:, :, i:i + g, :nope], quant)
        sc = sc + mm("bqhd,bkd->bhqk", q_pe[:, :, i:i + g], k_pe, quant)
        sc = jnp.where(mask[None, None], sc * (nope + rope_d) ** -0.5,
                       -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("bhqk,bkhd->bqhd", pr, kv[:, :, i:i + g, nope:],
                       quant))
    o = jnp.concatenate(outs, axis=2).reshape(b, s, h * dv)
    return mm("bse,ed->bsd", o, p["wo"], quant)


def swiglu(p, y, quant=None):
    g = jax.nn.silu(mm("...d,df->...f", y, p["w_gate"], quant))
    u = mm("...d,df->...f", y, p["w_up"], quant)
    return mm("...f,fd->...d", g * u, p["w_down"], quant)


def picked_mask(pick, k):
    """1 where an expert is among a token's top k of ``pick`` (ties to the
    lower id), by counting, for each expert, the experts ranked above it."""
    e = pick.shape[-1]
    ids = jnp.arange(e)
    above = (pick[..., None, :] > pick[..., :, None]) | (
        (pick[..., None, :] == pick[..., :, None])
        & (ids[None, :] < ids[:, None]))
    return (above.sum(-1) < k).astype(jnp.float32)


def experts(p, y, cfg, quant=None):
    """The routed experts held here and the shared experts; y (B, S, d).
    Returns (out, balance loss summed over the B sequences)."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s_ = jax.nn.sigmoid(mm("bsd,de->bse", y, p["router"], quant))
    picked = picked_mask(s_ + p["router_bias"], k)
    g = picked * s_
    if cfg["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    out = swiglu(p["shared"], y, quant)
    e0 = cfg["first_expert_held"]
    for i in range(cfg["n_experts_held"]):
        w = jax.tree.map(lambda a: a[i], {n: p[n] for n in
                                          ("w_gate", "w_up", "w_down")})
        out = out + g[..., e0 + i, None] * swiglu(w, y, quant)
    f = picked.mean(1) * (e / k)                          # (B, E)
    prob = (s_ / s_.sum(-1, keepdims=True)).mean(1)
    aux = cfg["aux_loss_alpha"] * jnp.sum(f * prob)
    return out, aux


def layer(p, x, cfg, quant=None):
    """One layer; returns (x, balance loss summed over the sequences)."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["attn"], rms(p["ln1"], x, eps), cfg, quant)
    y = rms(p["ln2"], x, eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], y, quant), jnp.zeros((), jnp.float32)
    out, aux = experts(p["moe"], y, cfg, quant)
    return x + out, aux


# ---------------------------------------------------------------------------
# training: three AdamW steps, as the program's optimizer states them
# ---------------------------------------------------------------------------

def _row_objective(params, tokens, labels, cfg, quant):
    """(nll summed over the rows, the same plus S x their balance losses):
    summed over all rows and divided by the tokens, the second is the
    mean loss plus the batch's mean balance loss."""
    x = jnp.take(params["embed"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    step = jax.checkpoint(functools.partial(layer, cfg=cfg, quant=quant))
    for name in ("lead_layers", "layers"):
        n = jax.tree.leaves(params[name])[0].shape[0]
        for i in range(n):
            x, a = step(jax.tree.map(lambda t: t[i], params[name]), x)
            aux = aux + a
    lg = mm("bsd,dv->bsv", rms(params["final_norm"], x, cfg["rms_norm_eps"]),
            params["lm_head"], quant)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    nll = jnp.sum(logz - gold)
    return nll + tokens.shape[1] * aux, nll


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(1,))
def _accumulate(params, acc, tokens, labels, cfg_items, quant):
    cfg = dict(cfg_items)
    (_, nll), g = jax.value_and_grad(_row_objective, has_aux=True)(
        params, tokens, labels, cfg, quant)
    return jax.tree.map(jnp.add, acc, g), nll


@jax.jit
def _change_norms(params, start):
    return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(start))]


def train_steps(cfg, seed, batches, *, lr, warmup, total, quant=None):
    """``len(batches)`` AdamW steps from the seed's weights (weight decay
    0, clip 1, betas 0.9/0.95, eps 1e-8, cosine to a tenth after
    ``warmup`` linear steps over ``total``), one sequence at a time.

    Returns per-step losses (the mean next-token loss, without the
    balance loss), the per-leaf norms of the first clipped gradient and
    per-leaf norms of the parameters' change over all steps, each as a
    flat {leaf path: float}. The starting weights are drawn again at the
    end rather than kept, to leave the chip room."""
    items = _items(cfg)
    params = init_params(cfg, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, params)
        tokens, labels = batch["tokens"], batch["labels"]
        total_nll = 0.0
        for r in range(tokens.shape[0]):
            acc, nll = _accumulate(params, acc, jnp.asarray(tokens[r:r + 1]),
                                   jnp.asarray(labels[r:r + 1]), items,
                                   quant)
            total_nll += float(nll)
        grads = jax.tree.map(lambda a: a / tokens.size, acc)
        del acc
        losses.append(total_nll / tokens.size)
        step = i + 1
        params, mu, nu, g = _adamw(
            params, grads, mu, nu, jnp.float32(step),
            jnp.float32(schedule(step, lr, warmup, total)))
        if first_grad is None:
            first_grad = leaf_norms(g)
        del g, grads
    del mu, nu
    start = init_params(cfg, seed)
    change = dict(zip(leaf_paths(params),
                      (float(v) for v in _change_norms(params, start))))
    return losses, first_grad, change
