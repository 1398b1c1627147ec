"""Logical-axis sharding rules (GSPMD).

Model code annotates tensors with *logical* axis names; the launcher installs
an ``AxisRules`` mapping logical names -> mesh axes for the active mesh.
Outside any rules context (unit tests, single device) annotations are no-ops.

Logical axes:
  batch   : data-parallel batch           -> ("pod", "data") / ("data",)
  tp      : tensor-parallel (heads, d_ff, experts, vocab)   -> ("model",)
  kvseq   : KV-cache / long-context sequence sharding       -> ("model",)
  longseq : 500k decode KV sequence        -> ("data", "model") combined
  zero    : optimizer-state / FSDP weight sharding          -> ("data",)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class AxisRules:
    mesh: Optional[Mesh]
    table: dict[str, tuple[str, ...]]

    @classmethod
    def for_mesh(cls, mesh: Mesh) -> "AxisRules":
        axes = mesh.axis_names
        batch = tuple(a for a in ("pod", "data") if a in axes)
        model = ("model",) if "model" in axes else ()
        return cls(mesh=mesh, table={
            "batch": batch,
            "tp": model,
            "kvseq": model,
            "longseq": batch + model,
            "zero": tuple(a for a in ("data",) if a in axes),
        })


_ACTIVE: Optional[AxisRules] = None


def set_rules(rules: Optional[AxisRules]) -> None:
    global _ACTIVE
    _ACTIVE = rules


def current_rules() -> Optional[AxisRules]:
    return _ACTIVE


def logical_to_spec(logical: Sequence[Optional[str]],
                    rules: Optional[AxisRules] = None) -> P:
    rules = rules or _ACTIVE
    if rules is None:
        return P()
    out = []
    for name in logical:
        if name is None:
            out.append(None)
        else:
            mapped = rules.table.get(name, ())
            out.append(mapped if len(mapped) != 1 else mapped[0])
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint by logical axis names; no-op without rules."""
    rules = _ACTIVE
    if rules is None or rules.mesh is None:
        return x
    spec = logical_to_spec(logical, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# parameter sharding specs (path-walk over the real param tree)
# ---------------------------------------------------------------------------

_COL_TP = {"wq", "wk", "wv", "wg", "wr", "w_up", "w_gate", "cm_wk",
           "cm_wr", "z_proj", "x_proj", "conv_x", "lm_head", "wkv_b"}
# replicated over tp: MLA's down projection "wkv_a" (the latent and the
# one shared rope key head) and its latent norm, the router and its
# correction bias
_ROW_TP = {"wo", "out_proj", "cm_wv", "w_down"}
_VEC_TP = {"conv_b_x", "gate_norm", "ln_x"}


def _leaf_spec(path: tuple[str, ...], ndim: int, cfg, tp) -> P:
    """Core PartitionSpec for one param leaf; leading stack dims padded."""
    key = path[-1]
    in_moe = "moe" in path and "shared" not in path

    if key == "embed":
        if cfg.n_codebooks:
            return P(None, None, tp)
        # tied tables serve take() AND logits: vocab-sharded keeps logits
        # tp-sharded (no giant psum); untied tables shard d_model instead
        return P(tp, None) if cfg.tie_embeddings else P(None, tp)
    if in_moe and key in ("w_gate", "w_up", "w_down"):
        core = (tp, None, None)               # experts over tp (EP)
    elif key in _COL_TP:
        core = (None, tp)
    elif key in _ROW_TP:
        core = (tp, None)
    elif key in _VEC_TP:
        core = (tp,)
    else:
        core = ()
    pad = (None,) * (ndim - len(core))
    return P(*(pad + core))


def _path_keys(path) -> tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def param_specs(cfg, rules: Optional[AxisRules] = None,
                fsdp: bool = True, param_shapes=None):
    """PartitionSpec pytree exactly matching ``init_params(cfg)``.

    Specs are assigned by walking the real (eval_shape'd) param tree and
    pattern-matching leaf paths — the spec tree always matches the param
    tree structure. With ``fsdp``, one extra dimension per leaf (never the
    leading stacked-layer dim) shards over the data axis: FSDP/ZeRO-3-style
    weight sharding whose gathers GSPMD overlaps inside the layer scan.
    """
    rules = rules or _ACTIVE
    tp = None
    if rules is not None:
        mapped = rules.table.get("tp", ())
        tp = mapped[0] if len(mapped) == 1 else (mapped or None)
    if param_shapes is None:
        from repro.models import model as _M
        param_shapes = jax.eval_shape(
            functools.partial(_M.init_params, cfg), jax.random.PRNGKey(0))

    data_axes = rules.table.get("zero", ()) if rules else ()
    data = data_axes[0] if data_axes else None
    n_data = int(rules.mesh.shape[data]) if data else 1

    flat, treedef = jax.tree_util.tree_flatten_with_path(param_shapes)
    specs = []
    for path, leaf in flat:
        keys = _path_keys(path)
        spec = _leaf_spec(keys, len(leaf.shape), cfg, tp)
        # (expert weights are stored FSDP-sharded too; shard_map reshards
        # to its P("model",...) in_specs = the FSDP gather, overlappable)
        if fsdp and data and n_data > 1 and keys[-1] != "embed" \
                and len(leaf.shape) >= 2:
            parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
            for i in range(len(leaf.shape) - 1, 0, -1):
                if parts[i] is None and leaf.shape[i] % n_data == 0 \
                        and leaf.shape[i] >= n_data:
                    parts[i] = data
                    break
            spec = P(*parts)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# decode-state / batch specs
# ---------------------------------------------------------------------------

def decode_state_specs(cfg, global_batch: int,
                       rules: Optional[AxisRules] = None,
                       layout: str = "fsdp"):
    """PartitionSpec tree matching transformer.init_decode_state.

    layout="fsdp" (baseline): batch over data when divisible; kv-heads over
    model when divisible, else the sequence dim shards over model; batch-1
    long-context decode shards the sequence over data AND model.
    layout="resident" (serving-optimized, §Perf C): batch replicated —
    weights stay 2D-resident (no per-token FSDP gather) and the KV sequence
    shards over data x model.
    """
    from repro.models.transformer import build_layout
    rules = rules or _ACTIVE
    if rules is None:
        return None
    tbl = rules.table
    tp = tbl.get("tp", (None,))[0] if tbl.get("tp") else None
    batch_axes = tbl.get("batch", ())
    mesh = rules.mesh
    bsz = 1
    for a in batch_axes:
        bsz *= int(mesh.shape[a])
    b_ax = batch_axes if (batch_axes and global_batch % bsz == 0
                          and global_batch >= bsz) else None
    if layout == "resident":
        b_ax = None
    if b_ax is not None and len(b_ax) == 1:
        b_ax = b_ax[0]
    tp_size = int(mesh.shape[tp]) if tp else 1

    def attn_spec():
        # (stack..., B, KV, S, D)
        if layout == "resident" and batch_axes and tp is not None:
            return (None, None, tuple(batch_axes) + (tp,), None)
        seq_ax = None
        if b_ax is None and batch_axes:
            # batch too small to shard -> the sequence takes the data axis
            seq_ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        if cfg.n_kv_heads % tp_size == 0 and tp_size > 1:
            return (b_ax, tp, seq_ax, None)
        if seq_ax is not None and tp is not None:
            return (b_ax, None, tuple(batch_axes) + (tp,), None)
        return (b_ax, None, tp, None)       # seq over model

    def stackP(nstack, core):
        return P(*((None,) * nstack + tuple(core)))

    stack = build_layout(cfg)
    if stack["kind"] == "uniform":
        if stack["block"] == "rwkv":
            st = (stackP(1, (b_ax, tp, None, None)),      # wkv (B,H,K,V)
                  stackP(1, (b_ax, None, None)),          # tm last token
                  stackP(1, (b_ax, None, None)))          # cm last token
            return {"layers": st}
        core = attn_spec()
        return {"layers": (stackP(1, core), stackP(1, core))}

    # periodic
    if stack["inner_block"] == "mamba":
        inner = (stackP(2, (b_ax, tp, None, None)),       # ssm (B,H,N,P)
                 stackP(2, (b_ax, None, tp)))             # conv (B,W-1,C)
        trailing = (stackP(1, (b_ax, tp, None, None)),
                    stackP(1, (b_ax, None, tp)))
    else:
        core = attn_spec()
        inner = (stackP(2, core), stackP(2, core))
        trailing = (stackP(1, core), stackP(1, core))
    core = attn_spec()
    if stack["single_block"] == "cross_attn":
        single = (stackP(1, (b_ax, None, None, None)),
                  stackP(1, (b_ax, None, None, None)))
    else:
        single = (stackP(1, core), stackP(1, core))
    return {"inner": inner, "single": single, "trailing": trailing}


def batch_specs(cfg, shape_kind: str, global_batch: int,
                rules: Optional[AxisRules] = None, layout: str = "fsdp"):
    """Input-batch PartitionSpecs per shape kind."""
    rules = rules or _ACTIVE
    b = None
    if rules is not None and layout != "resident":
        axes = rules.table.get("batch", ())
        size = 1
        for a in axes:
            size *= int(rules.mesh.shape[a])
        if axes and global_batch % size == 0 and global_batch >= size:
            b = axes if len(axes) > 1 else axes[0]
    out = {"tokens": P(b, None) if not cfg.n_codebooks else P(b, None, None)}
    if shape_kind == "train":
        out["labels"] = out["tokens"]
    if shape_kind == "decode":
        out["cache_len"] = P(b)
    if cfg.family == "vlm":
        out["vision"] = P(b, None, None)
    return out
