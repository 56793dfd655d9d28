"""Jit'd public wrappers around the Pallas kernels.

Handles flat-vector padding/reshaping to lane-aligned (blocks, block_size)
tiles, leaves ``interpret=None`` to the platform (compiled on TPU, the
interpreter elsewhere), and exposes the API the compression layer consumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_topk as bt


def _to_blocks(flat: jnp.ndarray, block_size: int):
    n = flat.shape[0]
    pad = (-n) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.shape[0] // block_size
    # pad rows to a TILE_BLOCKS multiple so the pallas grid stays uniform
    rpad = (-rows) % bt.TILE_BLOCKS
    if rpad:
        flat = jnp.pad(flat, (0, rpad * block_size))
        rows += rpad
    return flat.reshape(rows, block_size), n


@functools.partial(jax.jit, static_argnames=("cr", "block_size", "interpret"))
def block_topk_sparsify(flat: jnp.ndarray, cr: float,
                        block_size: int = bt.DEFAULT_BLOCK,
                        interpret: bool = None):
    """Keep ~cr fraction per block; returns densified sparse vector (n,)."""
    g2d, n = _to_blocks(flat, block_size)
    k = max(1, int(cr * block_size))
    out, _ = bt.block_topk(g2d, k, interpret=interpret)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("cr", "block_size", "interpret"))
def block_topk_counts(flat: jnp.ndarray, cr: float,
                      block_size: int = bt.DEFAULT_BLOCK,
                      interpret: bool = None):
    g2d, n = _to_blocks(flat, block_size)
    k = max(1, int(cr * block_size))
    out, cnt = bt.block_topk(g2d, k, interpret=interpret)
    # _to_blocks pads with zero rows (element pad + TILE_BLOCKS row pad);
    # only the first ceil(n / block_size) rows are real data, so trim the
    # counts to keep CSR wire-cost accounting honest.
    rows = -(-n // block_size)
    return out.reshape(-1)[:n], cnt.reshape(-1)[:rows]


@functools.partial(jax.jit, static_argnames=("momentum", "weight_decay",
                                             "block_size", "interpret"))
def fused_sgdm_flat(p, m, g, lr, momentum: float = 0.9,
                    weight_decay: float = 0.0,
                    block_size: int = bt.DEFAULT_BLOCK, interpret: bool = None):
    """Fused momentum-SGD on flat vectors (one HBM pass)."""
    p2, n = _to_blocks(p, block_size)
    m2, _ = _to_blocks(m, block_size)
    g2, _ = _to_blocks(g, block_size)
    new_p, new_m = bt.fused_sgdm(p2, m2, g2, lr, momentum=momentum,
                                 weight_decay=weight_decay,
                                 interpret=interpret)
    return new_p.reshape(-1)[:n], new_m.reshape(-1)[:n]
