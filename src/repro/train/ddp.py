"""DDP-mode ScaDLES: wire-accurate adaptive compression via shard_map.

The paper's setting is DDP (params replicated per device, gradients
all-reduced).  The adaptive rule changes the *collective shape* — dense
all-reduce vs all-gather of packed (values, indices) — which cannot vary
inside one jitted program, so we compile TWO programs and let the host-level
EWMA controller (core.compression.AdaptiveCompressor) pick per iteration:

  dense_step      — grads -> psum(r_i * g_i)                 (Eqn 4b on wire)
  compressed_step — grads -> top-k -> all_gather(r_i*vals, idx) -> scatter-add

The top-k is ``core.compression.global_topk``: exact, with no sort — a
bisection over the magnitudes' bits finds the k-th largest, and the kept
entries are packed with their indices in ascending order (on the TPU by the
Pallas kernel ``kernels/topk_compact.py``, elsewhere by ``jnp.nonzero``; the
platform picks).  The energy gap is taken from the packed values.

The compressed program's collectives move 2k*(D-1)/D * D ~ 2kD words instead
of 2G(D-1)/D — the reduction is directly visible in the HLO collective bytes
(benchmarks/compression_wire.py).  Meshes here are data-parallel only, like
the paper's edge clusters.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import compression as comp_lib
from repro.models.transformer import RunCtx
from repro.train.step import make_loss_fn


def make_ddp_steps(cfg: ModelConfig, ctx: RunCtx, mesh, opt_update: Callable,
                   lr_schedule: Callable, cr: float,
                   param_template) -> Tuple[Callable, Callable, int, int]:
    """Returns (dense_step, compressed_step, k, n_floats): the two jitted
    programs share the signature (params, opt_state, batch, rates, step) with
    params replicated and batch sharded over the mesh's data axes; ``k`` is
    the per-device top-k kept by the compressed program and ``n_floats`` the
    flattened gradient length."""
    dp = tuple(mesh.axis_names)
    loss_fn = make_loss_fn(cfg, ctx)
    flat0, unflatten = comp_lib.flatten_grads(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype)
                     if hasattr(s, "shape") else s, param_template))
    n_floats = int(flat0.shape[0])
    k = max(1, int(cr * n_floats))

    def local_loss_and_grads(params, batch):
        def f(p):
            total, m = loss_fn(p, batch)
            return total, m
        (total, m), grads = jax.value_and_grad(f, has_aux=True)(params)
        return grads, m

    def _weights(rate):
        total = rate
        for ax in dp:
            total = jax.lax.psum(total, ax)
        return rate / jnp.maximum(total, 1e-9), total

    def _update(params, opt_state, g_flat, step, metrics):
        grads = unflatten(g_flat)
        with jax.named_scope("optimizer"):
            lr = lr_schedule(step)
            params, opt_state = opt_update(grads, opt_state, params, lr)
        return params, opt_state, metrics

    # ---------------- dense program ----------------
    def dense_body(params, opt_state, batch, rate, step):
        grads, m = local_loss_and_grads(params, batch)
        w, _ = _weights(rate[0])
        flat, _ = comp_lib.flatten_grads(grads)
        g = flat * w
        for ax in dp:
            g = jax.lax.psum(g, ax)
        loss = m["loss"] * w
        for ax in dp:
            loss = jax.lax.psum(loss, ax)
        return _update(params, opt_state, g, step,
                       {"loss": loss, "gap": jnp.zeros(())})

    # ---------------- compressed program ----------------
    def comp_body(params, opt_state, batch, rate, step):
        grads, m = local_loss_and_grads(params, batch)
        w, _ = _weights(rate[0])
        flat, _ = comp_lib.flatten_grads(grads)
        with jax.named_scope("topk"):
            vals, idx = comp_lib.global_topk(flat, k)
        gap = comp_lib.energy_gap(flat, vals)
        # pack (r_i * values, indices) and all-gather across devices
        vals = vals * w
        for ax in dp:
            vals = jax.lax.all_gather(vals, ax, axis=0, tiled=False)
            idx = jax.lax.all_gather(idx, ax, axis=0, tiled=False)
        with jax.named_scope("scatter_add"):
            g = (jnp.zeros((n_floats,), flat.dtype)
                 .at[idx.reshape(-1)].add(vals.reshape(-1)))
        loss = m["loss"] * w
        gap_m = gap
        for ax in dp:
            loss = jax.lax.psum(loss, ax)
            gap_m = jax.lax.pmean(gap_m, ax)
        return _update(params, opt_state, g, step,
                       {"loss": loss, "gap": gap_m})

    rep = P()  # params/opt replicated
    bspec = P(dp, None)

    def wrap(body):
        def batch_specs(batch):
            return {kk: (P(dp, None, None) if batch[kk].ndim == 3
                         else P(dp) if batch[kk].ndim == 1
                         else bspec) for kk in batch}

        def step_fn(params, opt_state, batch, rates, step):
            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(rep, rep, batch_specs(batch), P(dp), rep),
                out_specs=(rep, rep, {"loss": rep, "gap": rep}),
                check_vma=False)
            return fn(params, opt_state, batch, rates, step)

        return step_fn

    return wrap(dense_body), wrap(comp_body), k, n_floats
