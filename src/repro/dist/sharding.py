"""Sharding plans: mesh axes -> PartitionSpecs for every tree we move.

The placement rules (DESIGN.md §5):

* FSDP over the ``data``-like axes (all mesh axes except ``model``): matmul
  weights shard their *input* dim, the embedding shards its vocab dim.
* TP over ``model``: column-parallel up-projections (``wq``/``w_gate``/...)
  shard the output dim, row-parallel down-projections (``wo``/``w_down``/...)
  shard the input dim; their biases follow the sharded output dim.
* Scan-stacked layer blocks (everything under ``unit`` or encoder ``blocks``)
  carry a leading layer axis that must stay unsharded -> leading ``None``.
* Norm scales/biases and the (small, fp32) MoE router stay replicated.
* MoE experts (``models/moe.py``): expert dim over ``model`` when the expert
  count divides TP (true expert parallelism, Llama-4); otherwise experts are
  replicated and each expert's ``d_ff`` shards over ``model`` (tensor-parallel
  experts, Mixtral).
* Decode caches shard their sequence dim over ``model`` (works for any head
  count; softmax stats reduce across shards — ``models/decode.py``).

Every rule drops mesh axes that do not divide the concrete dim (same policy
as ``RunCtx.constrain``), so one rule table serves the whole config zoo.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.transformer import RunCtx

# rule symbols
F = "fsdp"   # shard over the fsdp (data-like) axes
T = "tp"     # shard over the tensor axis
N = None     # replicate this dim


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Which mesh axes play which role; the one object the rules consume."""
    mesh: Any
    fsdp: Tuple[str, ...]
    tp: Optional[str]

    def axis_size(self, axis: Optional[str]) -> int:
        if axis is None:
            return 1
        return int(self.mesh.shape[axis])

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.fsdp:
            n *= self.axis_size(a)
        return n

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp)


def make_plan(mesh) -> MeshPlan:
    """FSDP over every non-``model`` axis; TP over ``model`` when present."""
    axes = tuple(mesh.axis_names)
    tp = "model" if "model" in axes else None
    return MeshPlan(mesh=mesh, fsdp=tuple(a for a in axes if a != tp), tp=tp)


# ---------------------------------------------------------------------------
# spec resolution


def _fsdp_entry(plan: MeshPlan):
    if not plan.fsdp:
        return None
    return plan.fsdp[0] if len(plan.fsdp) == 1 else plan.fsdp


def _resolve(plan: MeshPlan, shape: Tuple[int, ...], template) -> P:
    """Rule template -> PartitionSpec, dropping non-dividing axes."""
    if template is None or len(template) != len(shape):
        return P(*([None] * len(shape)))
    out = []
    for dim, sym in zip(shape, template):
        if sym == F:
            axes, size = _fsdp_entry(plan), plan.dp_size
        elif sym == T:
            axes, size = plan.tp, plan.tp_size
        else:
            axes, size = None, 1
        out.append(axes if axes is not None and size > 1 and dim % size == 0
                   else None)
    return P(*out)


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for p in path:
        if hasattr(p, "key"):
            keys.append(str(p.key))
        elif hasattr(p, "name"):
            keys.append(str(p.name))
    return tuple(keys)


# ---------------------------------------------------------------------------
# parameter specs

# column-parallel (in, out) weights: input over FSDP, output over TP
_COL2D = {"wq", "wk", "wv", "w_gate", "w_up", "w_rec_in", "w_gate_in",
          "w_a", "w_i", "wi", "wf", "w_ogate", "w_in"}
# row-parallel (in, out) weights: input over TP, output over FSDP
_ROW2D = {"wo", "w_down", "w_out", "lm_head"}
# 1-d vectors following a TP-sharded output dim
_TPVEC = {"bq", "bk", "bv", "b_in", "bi", "bf", "b_a", "b_i", "conv_b",
          "lam"}
# always replicated
_REPLICATED = {"scale", "bias", "router"}


def _param_template(name: str, ndim: int, cfg: ModelConfig,
                    plan: MeshPlan):
    if name in _REPLICATED:
        return None
    if name == "embed":
        return (F, T)
    if name in _COL2D and ndim == 2:
        return (F, T)
    if name in _ROW2D and ndim == 2:
        return (T, F)
    if name in _TPVEC and ndim == 1:
        return (T,)
    if name == "conv_w" and ndim == 2:       # (taps, r)
        return (N, T)
    if name == "r" and ndim == 3:            # sLSTM block-diag recurrence
        return (T, N, N)
    if name in ("we_gate", "we_up", "we_down") and ndim == 3:
        moe = cfg.moe
        expert_parallel = (moe is not None and plan.tp_size > 1
                           and moe.num_experts % plan.tp_size == 0)
        if name == "we_down":                # (E, ff, d)
            return (T, N, F) if expert_parallel else (N, T, F)
        return (T, F, N) if expert_parallel else (N, F, T)  # (E, d, ff)
    return None


def param_specs(params, cfg: ModelConfig, plan: MeshPlan):
    """PartitionSpec tree mirroring ``params`` (also fits the optimizer's
    momentum tree, which copies the parameter structure)."""
    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else ""
        stacked = "unit" in keys or "blocks" in keys
        shape = tuple(leaf.shape)
        base = shape[1:] if stacked else shape
        tmpl = _param_template(name, len(base), cfg, plan)
        spec = _resolve(plan, base, tmpl)
        return P(None, *spec) if stacked else spec

    return jax.tree_util.tree_map_with_path(rule, params)


# ---------------------------------------------------------------------------
# batch / cache specs


def batch_specs(cfg: ModelConfig, plan: MeshPlan, batch,
                seq_sharded: bool = False):
    """Batch leaves: global batch over FSDP; the sequence dim additionally
    shards over TP in context-parallel mode (``seq_sharded``)."""
    s_sym = T if seq_sharded else N

    def rule(path, leaf):
        name = _path_keys(path)[-1]
        shape = tuple(leaf.shape)
        if name == "mrope_positions":            # (3, b, s)
            tmpl = (N, F, s_sym)
        elif name in ("audio_feats", "patch_embeds"):  # (b, s', d)
            tmpl = (F, N, N)
        elif len(shape) == 1:                    # sample_weights (b,)
            tmpl = (F,)
        elif len(shape) == 2:                    # tokens/labels/mask (b, s)
            tmpl = (F, s_sym)
        else:
            tmpl = (F,) + (N,) * (len(shape) - 1)
        return _resolve(plan, shape, tmpl)

    return jax.tree_util.tree_map_with_path(rule, batch)


# cache leaf name + base ndim -> template (see models/decode.py layouts)
_CACHE_RULES = {
    ("k", 4): (F, T, N, N), ("v", 4): (F, T, N, N),
    ("ck", 4): (F, T, N, N), ("cv", 4): (F, T, N, N),
    ("h", 2): (F, T),                       # RG-LRU hidden (b, r)
    ("conv", 3): (F, N, T),                 # conv taps (b, taps, r)
    ("c", 4): (F, T, N, N),                 # mLSTM matrix memory
    ("c", 3): (F, T, N), ("n", 3): (F, T, N), ("h", 3): (F, T, N),
    ("m", 3): (F, T, N),                    # sLSTM states (b, nh, hd)
    ("n", 2): (F, T), ("m", 2): (F, T),     # mLSTM norms (b, nh)
}


def cache_specs(cfg: ModelConfig, plan: MeshPlan, cache):
    """Decode-cache specs: batch over FSDP, sequence/head state over TP."""
    def rule(path, leaf):
        keys = _path_keys(path)
        name = keys[-1] if keys else ""
        stacked = "unit" in keys
        shape = tuple(leaf.shape)
        base = shape[1:] if stacked else shape
        spec = _resolve(plan, base, _CACHE_RULES.get((name, len(base))))
        return P(None, *spec) if stacked else spec

    return jax.tree_util.tree_map_with_path(rule, cache)


# ---------------------------------------------------------------------------
# run context / placement helpers


def attn_mode_for(cfg: ModelConfig, plan: MeshPlan) -> str:
    """Attention execution mode (models/attention.py):

    * ``local``    — no tensor axis: per-shard attention, nothing to gather;
    * ``megatron`` — heads divide TP: gather sequence, shard heads;
    * ``context``  — heads do NOT divide TP: keep queries sequence-sharded
      and ring the K/V (context parallelism).
    """
    if plan.tp is None or plan.tp_size == 1:
        return "local"
    if cfg.num_heads % plan.tp_size == 0:
        return "megatron"
    return "context"


def make_run_ctx(cfg: ModelConfig, plan: MeshPlan, *,
                 compute_dtype=None, param_dtype=None, remat: bool = True,
                 chunk_q: int = 512, chunk_k: int = 512,
                 loss_chunk: int = 512) -> RunCtx:
    """RunCtx wired to the plan's mesh/axes with the right attention mode."""
    import jax.numpy as jnp

    mode = attn_mode_for(cfg, plan)
    return RunCtx(
        mesh=plan.mesh,
        tp_axis=plan.tp if plan.tp is not None else "model",
        dp_axes=tuple(plan.fsdp),
        attn_mode=mode,
        chunk_q=chunk_q, chunk_k=chunk_k, remat=remat, loss_chunk=loss_chunk,
        param_dtype=param_dtype if param_dtype is not None else jnp.bfloat16,
        compute_dtype=compute_dtype if compute_dtype is not None else jnp.bfloat16,
        seq_sharded=(mode == "context"),
    )


def named(tree, specs, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh`` (jit/device_put
    ready).  ``specs`` must mirror ``tree``'s structure."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
