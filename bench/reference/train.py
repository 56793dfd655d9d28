"""Plain reference of the training steps that the cells time: Adam with
decoupled weight decay behind a warmup-cosine schedule, and rate-weighted
data-parallel SGD with momentum whose per-device gradients may pass through
an exact top-k.  Imports nothing of the program.  The model is the
configuration's plain reference, ``bench/reference/<model_type>.py``
(``harness.model_module``): its ``weighted_loss(params, tokens, labels,
row_weights, c)`` is all that is differentiated here.

Each function follows the first steps of a run from the seed's weights and
returns the readings that ``bench/compare.py`` sets beside the program's:
the loss of each step, the per-slice norms of the first gradient, and the
per-slice norms of the parameters' change after the last step.

``dtype`` float32 with ``jax.default_matmul_precision("highest")`` is the
reference; bfloat16 is the control, the nearest precision below the cells'
float32 parameters and activations: weights kept and updated in bfloat16,
activations in bfloat16, optimizer state in float32.  ``fault`` plants one
of the faults the correctness check must catch, for the calibration runs of
``bench/calibrate.py``.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W


def warmup_cosine(step: int, base_lr: float, warmup: int, total: int,
                  min_frac: float = 0.1) -> float:
    if step < warmup:
        return base_lr * step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return base_lr * (min_frac + (1 - min_frac) * 0.5
                      * (1 + math.cos(math.pi * prog)))


def lr_at(step: int, sched: dict) -> float:
    if sched["name"] == "constant":
        return sched["lr"]
    return warmup_cosine(step, sched["base_lr"], sched["warmup"],
                         sched["total"], sched.get("min_frac", 0.1))


@jax.jit
def _adam(p, g, m, v, t, lr, b1, b2, eps, wd):
    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        step = mhat / (jnp.sqrt(vhat) + eps) + wd * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v
    out = jax.tree.map(upd, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda _, o: o[i], p, out)
    return pick(0), pick(1), pick(2)


@jax.jit
def _sgdm(p, g, m, lr, mu):
    m = jax.tree.map(lambda m, g: mu * m + g.astype(jnp.float32), m, g)
    p = jax.tree.map(lambda p, m: (p.astype(jnp.float32) - lr * m
                                   ).astype(p.dtype), p, m)
    return p, m


_slice_norms = jax.jit(W.slice_norms)


def _stored(p, dtype):
    """The weights as the step keeps them: in ``dtype``, so the control
    rounds every update to bfloat16 as a bfloat16 program would."""
    return jax.tree.map(lambda x: x.astype(dtype), p)


def _zeros(p):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)


@jax.jit
def _change_norms(p, p0):
    return W.slice_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0))


_VG: Dict[tuple, object] = {}


def _loss_and_grad(model, c, dtype):
    """Jitted value-and-grad of ``model``'s weighted loss, one per model,
    config and dtype, so that following several seeds traces it once."""
    key = (model.__file__, json.dumps(c, sort_keys=True),
           jnp.dtype(dtype).name)
    if key not in _VG:
        def f(params, tokens, labels, w):
            params = jax.tree.map(lambda x: x.astype(dtype), params)
            return model.weighted_loss(params, tokens, labels, w, c)
        _VG[key] = jax.jit(jax.value_and_grad(f))
    return _VG[key]


def _precision(dtype):
    return jax.default_matmul_precision(
        "highest" if dtype == jnp.float32 else "default")


def follow_adam(model, c: dict, init, key,
                batches: List[Dict[str, np.ndarray]], opt: dict, sched: dict,
                dtype=jnp.float32, fault: str = "") -> dict:
    """The single-program ScaDLES step: loss weighted per row by
    ``sample_weights``, then Adam.  ``fault="half_batch"`` leaves out the
    second half of the rows and takes the weighted mean over the rest."""
    vg = _loss_and_grad(model, c, dtype)
    with _precision(dtype):
        p = _stored(init(key), dtype)
        m, v = _zeros(p), _zeros(p)
        losses, grad0 = [], None
        for step, b in enumerate(batches):
            w = np.asarray(b["sample_weights"], np.float64)
            if fault == "half_batch":
                w[len(w) // 2:] = 0.0
                w = w / w.sum()
            loss, g = vg(p, b["tokens"], b["labels"],
                         jnp.asarray(w, jnp.float32))
            losses.append(float(loss))
            if step == 0:
                grad0 = np.asarray(_slice_norms(g))
            p, m, v = _adam(p, g, m, v, jnp.float32(step + 1),
                            jnp.float32(lr_at(step, sched)), opt["b1"],
                            opt["b2"], opt["eps"], opt["weight_decay"])
            del g
        del m, v
        change = np.asarray(_change_norms(p, init(key)))
    return {"losses": losses, "grad0": grad0, "change": change}


# ---------------------------------------------------------------------------
# data-parallel step with exact top-k


def _flat_leaves(tree):
    return [x.reshape(-1) for x in jax.tree.leaves(tree)]


@jax.jit
def _threshold(g, k):
    """Bit pattern t of the k-th largest |g| over all leaves (|g| >= 0, so
    its float32 bits order as unsigned integers), by bisection on the bits."""
    bits = [jax.lax.bitcast_convert_type(jnp.abs(x.astype(jnp.float32)),
                                         jnp.uint32) for x in _flat_leaves(g)]

    def count_ge(t):
        return sum(jnp.sum(b >= t, dtype=jnp.int32) for b in bits)

    def body(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), jnp.uint32(31 - i))
        return jnp.where(count_ge(cand) >= k, cand, t)

    return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))


@functools.partial(jax.jit, donate_argnums=0)
def _topk_accumulate(acc, g, t, k, w):
    """acc + w * topk(g): every entry with |g| above the threshold, and of
    those equal to it the first ones in flat order until k are taken.
    Returns (acc, energy gap of this device's top-k)."""
    leaves, tdef = jax.tree.flatten(g)
    bits = [jax.lax.bitcast_convert_type(jnp.abs(x.astype(jnp.float32)),
                                         jnp.uint32) for x in leaves]
    above = sum(jnp.sum(b > t, dtype=jnp.int32) for b in bits)
    room = k - above
    seen = jnp.int32(0)
    kept, e_all, e_kept = [], 0.0, 0.0
    for x, b in zip(leaves, bits):
        eq = (b == t).reshape(-1)
        rank = seen + jnp.cumsum(eq, dtype=jnp.int32)
        seen = seen + jnp.sum(eq, dtype=jnp.int32)
        keep = (b > t) | (eq & (rank <= room)).reshape(b.shape)
        xf = x.astype(jnp.float32)
        kx = jnp.where(keep, xf, 0.0)
        kept.append(kx)
        e_all = e_all + jnp.sum(xf * xf)
        e_kept = e_kept + jnp.sum(kx * kx)
    kept = jax.tree.unflatten(tdef, kept)
    acc = jax.tree.map(lambda a, kx: a + w * kx, acc, kept)
    return acc, jnp.abs(e_all - e_kept) / jnp.maximum(e_all, 1e-30)


@functools.partial(jax.jit, donate_argnums=0)
def _dense_accumulate(acc, g, w):
    return jax.tree.map(lambda a, x: a + w * x.astype(jnp.float32), acc, g)


def follow_ddp(model, c: dict, init, key,
               batches: List[Dict[str, np.ndarray]], n_dev: int, opt: dict,
               comp: dict, dtype=jnp.float32, fault: str = "") -> dict:
    """Rate-weighted DDP with SGD-momentum.  Device i's gradient is that of
    the mean token loss over its own rows; the step applies
    sum_i r_i / sum(r) * g_i, with each g_i cut to its exact top-k
    (k = cr * n) on the compressed steps.  The paper's controller picks
    them: the first step is compressed, so that there is an energy gap to
    judge, and each later one is compressed while the EWMA (``alpha``) of
    the last measured gap, updated once a step, is at most ``delta``.  The
    loss read is sum_i r_i / sum(r) * loss_i.

    Faults: ``half_batch`` leaves out the second half of each device's
    rows (or of its tokens, with one row); ``no_exchange`` keeps device 0's
    own gradient with weight 1, as a step whose collectives were dropped
    leaves the replicated copy.  Returns the readings, the picks and the
    energy gap of each compressed step (mean over devices)."""
    vg = _loss_and_grad(model, c, dtype)
    with _precision(dtype):
        p = _stored(init(key), dtype)
        n = sum(x.size for x in jax.tree.leaves(p))
        k = max(1, int(comp["cr"] * n))
        m = _zeros(p)
        losses, gaps, picks, grad0, ewma = [], [], [], None, None
        for step, b in enumerate(batches):
            if step:
                g_last = gaps[-1]
                ewma = g_last if ewma is None else (
                    comp["alpha"] * g_last + (1 - comp["alpha"]) * ewma)
            pick = "compressed" if ewma is None or ewma <= comp["delta"] \
                else "dense"
            picks.append(pick)
            rates = np.asarray(b["rates"], np.float64)
            wdev = rates / rates.sum()
            rows = b["tokens"].shape[0] // n_dev
            acc = _zeros(p)
            loss, gap = 0.0, 0.0
            devs = [0] if fault == "no_exchange" else range(n_dev)
            for i in devs:
                sl = slice(i * rows, (i + 1) * rows)
                toks, labs = b["tokens"][sl], b["labels"][sl]
                rw = np.full((rows,), 1.0 / rows)
                if fault == "half_batch":
                    if rows > 1:
                        rw[rows // 2:] = 0.0
                        rw /= rw.sum()
                    else:
                        half = toks.shape[1] // 2
                        toks, labs = toks[:, :half], labs[:, :half]
                wi = 1.0 if fault == "no_exchange" else wdev[i]
                li, g = vg(p, toks, labs, jnp.asarray(rw, jnp.float32))
                loss += wi * float(li)
                if pick == "compressed":
                    t = _threshold(g, k)
                    acc, gi = _topk_accumulate(acc, g, t, k,
                                               jnp.float32(wi))
                    gap += float(gi) / len(devs)
                else:
                    acc = _dense_accumulate(acc, g, jnp.float32(wi))
                del g
            losses.append(loss)
            if pick == "compressed":
                gaps.append(gap)
            if step == 0:
                grad0 = np.asarray(_slice_norms(acc))
            p, m = _sgdm(p, acc, m, jnp.float32(opt["lr"]),
                         jnp.float32(opt["momentum"]))
            del acc
        del m
        change = np.asarray(_change_norms(p, init(key)))
    return {"losses": losses, "grad0": grad0, "change": change,
            "gaps": gaps, "picks": picks}

