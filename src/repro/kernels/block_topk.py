"""Pallas TPU kernel: block-local top-k gradient sparsification.

TPU adaptation of the paper's Top-k compression (DESIGN.md §6): a global sort
is MXU/VPU-hostile, so the flat gradient is tiled into lane-aligned blocks of
``block_size`` (multiple of 128); each block keeps its proportional share
``k_b`` of survivors by magnitude.  The per-block threshold is found with a
fixed-depth bisection (pure VPU compares/reductions, no sort, fully in VMEM):

    lo, hi = 0, max|g|;  repeat 20x: mid=(lo+hi)/2;
    count(|g|>=mid) > k_b ? lo=mid : hi=mid;  tau = hi

The kernel emits the masked dense block and the per-block survivor count
(for CSR-style packing by the comm layer).  ``ref.py`` implements the *same*
bisection in pure jnp — kernel-vs-oracle equality is exact, and tests also
measure retention vs exact global top-k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

N_BISECT = 20
DEFAULT_BLOCK = 1024     # lanes-aligned (8 sublanes x 128 lanes)
TILE_BLOCKS = 8          # blocks per pallas program (VMEM tile rows)


def _bisect_threshold(mag, k: int):
    """Per-row threshold: mag (rows, block). Returns tau (rows, 1)."""
    hi = jnp.max(mag, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)
    for _ in range(N_BISECT):
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        gt = cnt > k
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
    return hi


def _block_topk_kernel(g_ref, out_ref, cnt_ref, *, k: int):
    g = g_ref[...]
    mag = jnp.abs(g.astype(jnp.float32))
    tau = _bisect_threshold(mag, k)
    # tau == 0 iff the block is all-zero (bisection can't raise hi above 0);
    # without the mag > 0 guard such blocks would report block_size survivors.
    keep = (mag >= tau) & (mag > 0)
    out_ref[...] = jnp.where(keep, g, jnp.zeros_like(g))
    cnt_ref[...] = jnp.sum(keep.astype(jnp.int32), axis=-1, keepdims=True)


def _block_topk_call(g2d: jnp.ndarray, k: int, interpret: bool):
    n_blocks, block = g2d.shape
    tile = min(TILE_BLOCKS, n_blocks)
    assert n_blocks % tile == 0, (n_blocks, tile)
    grid = (n_blocks // tile,)
    return pl.pallas_call(
        functools.partial(_block_topk_kernel, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_blocks, block), g2d.dtype),
                   jax.ShapeDtypeStruct((n_blocks, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(g2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _block_topk_vjp(g2d, k: int, interpret: bool):
    return _block_topk_call(g2d, k, interpret)


def _block_topk_fwd(g2d, k: int, interpret: bool):
    out, cnt = _block_topk_call(g2d, k, interpret)
    # survivors never carry value 0 (the mag > 0 guard), so out != 0 IS the
    # keep mask — no need to re-run the bisection in the backward pass.
    return (out, cnt), out != 0


def _block_topk_bwd(k: int, interpret: bool, keep, cts):
    d_out, _ = cts       # count cotangent is float0 (int output) — dropped
    return (jnp.where(keep, d_out, jnp.zeros_like(d_out)),)


_block_topk_vjp.defvjp(_block_topk_fwd, _block_topk_bwd)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def block_topk(g2d: jnp.ndarray, k: int, interpret: bool = None):
    """g2d (n_blocks, block_size) -> (sparsified g2d, counts (n_blocks, 1)).

    ``k`` survivors per block.  ``interpret=None`` compiles on a TPU and
    runs the interpreter elsewhere (``repro.kernels.resolve_interpret``).
    Differentiable: the VJP is a straight-through mask over survivors, so the
    compressed DDP program stays differentiable end-to-end.
    """
    return _block_topk_vjp(g2d, k, interpret)


# ---------------------------------------------------------------------------
# fused momentum-SGD update (single HBM pass over params/momentum/grads)


def _fused_sgdm_kernel(p_ref, m_ref, g_ref, lr_ref, out_p_ref, out_m_ref, *,
                       momentum: float, weight_decay: float):
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...]
    g = g_ref[...].astype(jnp.float32) + weight_decay * p
    lr = lr_ref[0]
    m2 = momentum * m + g
    out_m_ref[...] = m2
    out_p_ref[...] = (p - lr * m2).astype(p_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("momentum", "weight_decay", "interpret"))
def fused_sgdm(p2d, m2d, g2d, lr, momentum: float = 0.9,
               weight_decay: float = 0.0, interpret: bool = None):
    """Fused SGD-momentum over (rows, block) tiles; one pass over HBM."""
    n_blocks, block = p2d.shape
    tile = min(TILE_BLOCKS, n_blocks)
    assert n_blocks % tile == 0
    grid = (n_blocks // tile,)
    lr_arr = jnp.asarray([lr], jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_sgdm_kernel, momentum=momentum,
                          weight_decay=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                  pl.BlockSpec((tile, block), lambda i: (i, 0)),
                  pl.BlockSpec((tile, block), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                   pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
                   jax.ShapeDtypeStruct(m2d.shape, jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(p2d, m2d, g2d, lr_arr)
