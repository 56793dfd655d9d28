"""Train-step factory: loss + grads + ScaDLES aggregation + optimizer update.

Weighted aggregation (Eqn 4) on the mesh is expressed as per-sample loss
weights: every sample carries w_s = r_{dev(s)} / b_{dev(s)} (precomputed by
the data pipeline, sums to 1 globally), so the batch-sharded gradient that
GSPMD all-reduces IS the paper's weighted aggregate — zero extra collectives
vs conventional DDL.  Conventional-DDL mode uses uniform weights.

The adaptive-compression wire path lives in ``repro.train.ddp`` (two-program
strategy); this module is the FSDPxTP path used by the dry-run/roofline.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import RunCtx, forward_hidden, lm_loss


MOE_AUX_WEIGHT = 0.01


def make_loss_fn(cfg: ModelConfig, ctx: RunCtx, sum_form: bool = False):
    """``sum_form``: return the weighted SUM of per-token nll (weights are
    globally normalised by the data pipeline), so microbatch gradients
    accumulate by addition without renormalisation."""
    def loss_fn(params, batch: Dict[str, Any]):
        extras = {}
        for k in ("audio_feats", "patch_embeds", "mrope_positions"):
            if k in batch:
                extras[k] = batch[k]
        h, aux = forward_hidden(params, batch["tokens"], cfg, ctx, **extras)
        mask = batch.get("loss_mask")
        w = batch.get("sample_weights")   # (b,) ScaDLES rate weights, sum=1
        if w is not None:
            base = (jnp.ones_like(batch["labels"], jnp.float32)
                    if mask is None else mask)
            if sum_form:
                # per-token weight w_i / (#valid tokens of i): the weighted
                # SUM over any microbatch partition equals the full-batch
                # weighted mean (sum over all tokens is exactly 1)
                per_tok = base / jnp.maximum(
                    jnp.sum(base, axis=1, keepdims=True), 1.0)
                mask = per_tok * w[:, None]
            else:
                mask = base * w[:, None]
        loss = lm_loss(params, h, batch["labels"], cfg, ctx, loss_mask=mask,
                       normalize=not sum_form)
        return loss + MOE_AUX_WEIGHT * aux, {"loss": loss, "aux": aux}

    return loss_fn


def make_train_step(cfg: ModelConfig, ctx: RunCtx, opt_update: Callable,
                    lr_schedule: Callable, n_micro: int = 1,
                    grad_shardings=None, grad_wire_bf16: bool = False):
    """Returns train_step(params, opt_state, batch, step) -> (p, s, metrics).

    ``n_micro > 1``: gradient accumulation over microbatches (lax.scan), the
    standard memory lever for 100B-scale configs — live activation carries
    shrink by n_micro while the wire/global batch semantics are unchanged.
    Requires ``sample_weights`` in the batch (ScaDLES weighted mode supplies
    them; uniform weights reproduce conventional DDL).
    """
    grad_fn_mean = jax.value_and_grad(make_loss_fn(cfg, ctx, sum_form=False),
                                      has_aux=True)
    grad_fn_sum = jax.value_and_grad(make_loss_fn(cfg, ctx, sum_form=True),
                                     has_aux=True)

    def finish(params, opt_state, grads, total, metrics, step):
        with jax.named_scope("optimizer"):
            lr = lr_schedule(step)
            params, opt_state = opt_update(grads, opt_state, params, lr)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
        metrics = dict(metrics, total=total, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    def train_step(params, opt_state, batch, step):
        if n_micro == 1:
            (total, metrics), grads = grad_fn_mean(params, batch)
            return finish(params, opt_state, grads, total, metrics, step)

        assert "sample_weights" in batch, "microbatching needs sample weights"

        def split(x):
            b = x.shape[0]
            if x.ndim >= 2 and x.shape[0] == 3:      # mrope (3, b, s)
                return x.reshape(3, n_micro, x.shape[1] // n_micro,
                                 *x.shape[2:]).swapaxes(0, 1)
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        micro = {k: split(v) for k, v in batch.items()}
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def pin(g):
            """Keep the accumulator sharded like the params (ZeRO-2): the
            per-microbatch partial grads then reduce-scatter instead of
            all-reducing full tensors inside the accumulation loop."""
            if grad_shardings is None:
                return g
            return jax.tree.map(jax.lax.with_sharding_constraint, g,
                                grad_shardings)

        g0 = pin(g0)

        def mb_body(carry, mb):
            grads, tot = carry
            (t, m), g = grad_fn_sum(params, mb)
            if grad_wire_bf16:
                # force the per-microbatch reduce-scatter onto the wire in
                # bf16 (the barrier stops XLA fusing the fp32 accumulate
                # upcast into the reduction); accumulator stays fp32
                g = jax.tree.map(
                    lambda x: jax.lax.optimization_barrier(
                        x.astype(jnp.bfloat16)), g)
            grads = pin(jax.tree.map(
                lambda a, b_: a + b_.astype(jnp.float32), grads, g))
            return (grads, tot + t), m["aux"]

        (grads, total), _ = jax.lax.scan(
            mb_body, (g0, jnp.zeros((), jnp.float32)), micro)
        return finish(params, opt_state, grads, total,
                      {"loss": total, "aux": jnp.zeros(())}, step)

    return train_step


def make_eval_step(cfg: ModelConfig, ctx: RunCtx):
    loss_fn = make_loss_fn(cfg, ctx)

    def eval_step(params, batch):
        _, m = loss_fn(params, batch)
        return m

    return eval_step
