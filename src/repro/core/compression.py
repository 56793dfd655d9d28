"""Adaptive Top-k gradient compression (paper §IV "High communication cost").

The rule: send Topk(g) iff the *energy gap*

    gap(g) = ( ||g||^2 - ||Topk(g)||^2 ) / ||g||^2        in [0, 1]

(tracked with an EWMA over iterations to follow critical learning regions
[Accordion/critical-periods]) is <= delta; otherwise send dense g.  CNC ratio
= fraction of iterations that used the compressed path.

Top-k comes in two flavours:
* ``global_topk`` — exact top-k over the flat gradient (paper semantics; the
  DDP program and the convergence experiments).  No sort: a bisection over
  the magnitudes' float32 bits (``topk_threshold``) finds the k-th largest
  magnitude t, and the selection is every entry above t plus the first ones
  equal to t, in flat order, until k.  The values come back with their
  indices in ascending order.  The platform picks how they are packed: on
  the TPU the Pallas kernel ``kernels/topk_compact.py`` in one pass, elsewhere
  ``jnp.nonzero`` over the mask (the kernel's oracle); ``path_counts`` counts
  the traced calls on each;
* ``block_topk`` — TPU-native block-local top-k (``repro.kernels``): the flat
  gradient is tiled into lane-aligned blocks, each keeping its proportional
  share of survivors.  This is the deployable kernel path (DESIGN.md §6).

The mesh trainer uses a *two-program* strategy: compressed-collective and
dense-collective step functions are compiled once each, and the (host-level)
EWMA decision picks which to run next iteration — so the wire bytes really
change, visible in the HLO collective roofline term.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def flatten_grads(grads) -> Tuple[jnp.ndarray, Callable]:
    leaves, treedef = jax.tree.flatten(grads)
    shapes = [l.shape for l in leaves]
    sizes = [int(l.size) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unflatten(v):
        out, off = [], 0
        for sh, sz in zip(shapes, sizes):
            out.append(v[off:off + sz].reshape(sh))
            off += sz
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten


def flatten_stacked_grads(grads) -> Tuple[jnp.ndarray, Callable]:
    """Grads with a leading device axis -> (D, n) flat matrix + unflatten
    that maps a single (n,) vector back to one device's gradient pytree."""
    leaves, treedef = jax.tree.flatten(grads)
    shapes = [l.shape[1:] for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves], axis=1)

    def unflatten_one(v):
        out, off = [], 0
        for sh, sz in zip(shapes, sizes):
            out.append(v[off:off + sz].reshape(sh))
            off += sz
        return jax.tree.unflatten(treedef, out)

    return flat, unflatten_one


# magnitude bits decided per read of the gradient in ``topk_threshold``:
# 2^m - 1 candidates a pass, ceil(31 / m) passes.  Over 494M floats on a
# v5e, m = 1, 2, 3 took 82, 43 and 30 ms; a pass is bound by HBM up to 3.
BITS_PER_PASS = 3


def _magnitude_bits(flat):
    """|flat|'s float32 bits as uint32; they order as the magnitudes do."""
    bits = jax.lax.bitcast_convert_type(flat.astype(jnp.float32), jnp.uint32)
    return bits & jnp.uint32(0x7FFFFFFF)


def topk_threshold(flat: jnp.ndarray, k: int):
    """(t, room): t the magnitude bits of the k-th largest |flat|, the
    largest pattern with count(bits >= t) >= k, found bit by bit from the
    top, ``BITS_PER_PASS`` bits a read; room = k - count(bits > t), how
    many entries equal to t the top-k keeps.  count(bits > t) is
    count(bits >= t + 1), the count of the last candidate refused."""
    mag = _magnitude_bits(flat)
    t, c_gt = jnp.uint32(0), jnp.int32(0)
    hi = 30
    while hi >= 0:
        low = max(hi - BITS_PER_PASS + 1, 0)
        n_cand = (1 << (hi - low + 1)) - 1
        counts = jnp.stack([jnp.sum(mag >= (t | jnp.uint32(j << low)),
                                    dtype=jnp.int32)
                            for j in range(1, n_cand + 1)])
        take = jnp.sum(counts >= k, dtype=jnp.int32)
        t = t | jnp.left_shift(take.astype(jnp.uint32), jnp.uint32(low))
        c_gt = jnp.where(take < n_cand,
                         counts[jnp.minimum(take, n_cand - 1)], c_gt)
        hi = low - 1
    return t, k - c_gt


def _select(mag, t, room):
    """The top-k mask: bits above t, and the first ``room`` equal to it."""
    eq = mag == t
    rank = jnp.cumsum(eq, dtype=jnp.int32) - eq
    return (mag > t) | (eq & (rank < room))


# global_topk calls traced so far, by the path each took
_PATH_COUNTS = {"kernel": 0, "jnp": 0}


def path_counts() -> dict:
    """How many ``global_topk`` calls packed the selection with the Pallas
    kernel and how many with ``jnp.nonzero``, counted when traced."""
    return dict(_PATH_COUNTS)


def global_topk(flat: jnp.ndarray, k: int):
    """Exact top-k by magnitude -> (values, indices); k static.  Ties at
    the k-th magnitude go to the first entries in flat order; the indices
    ascend.  On the TPU the Pallas kernel packs them, elsewhere
    ``jnp.nonzero``."""
    t, room = topk_threshold(flat, k)
    if jax.default_backend() == "tpu":
        from repro.kernels.topk_compact import topk_compact
        _PATH_COUNTS["kernel"] += 1
        return topk_compact(flat, t.astype(jnp.int32), room, k=k)
    _PATH_COUNTS["jnp"] += 1
    idx = jnp.nonzero(_select(_magnitude_bits(flat), t, room), size=k)[0]
    return flat[idx], idx


def sparsify_mask(flat: jnp.ndarray, k: int):
    """Dense tensor with all but the top-k entries zeroed."""
    t, room = topk_threshold(flat, k)
    return jnp.where(_select(_magnitude_bits(flat), t, room), flat, 0)


def energy_gap(flat: jnp.ndarray, compressed: jnp.ndarray):
    """( |g|^2 - |Topk(g)|^2 ) / |g|^2; ``compressed`` holds the kept
    entries, dense with zeros elsewhere or packed (the top-k's values)."""
    e_full = jnp.sum(jnp.square(flat))
    e_comp = jnp.sum(jnp.square(compressed))
    return jnp.abs(e_full - e_comp) / jnp.maximum(e_full, 1e-30)


@dataclasses.dataclass
class EWMA:
    """Exponentially weighted moving average of the energy gap."""
    alpha: float = 0.1
    value: float = 1.0     # start pessimistic: first iters send dense
    initialized: bool = False

    def update(self, x: float) -> float:
        x = float(x)
        if not self.initialized:
            self.value, self.initialized = x, True
        else:
            self.value = self.alpha * x + (1 - self.alpha) * self.value
        return self.value


@dataclasses.dataclass
class AdaptiveCompressor:
    """Host-side controller implementing the paper's communication rule."""
    cr: float = 0.1          # compression ratio (k = cr * n)
    delta: float = 0.3       # gap threshold
    alpha: float = 0.1       # EWMA smoothing
    use_block_topk: bool = False
    block_size: int = 1024

    def __post_init__(self):
        self.ewma = EWMA(alpha=self.alpha)
        self.t_compressed = 0
        self.t_uncompressed = 0
        self.floats_sent = 0.0

    def k_for(self, n: int) -> int:
        return max(1, int(self.cr * n))

    def compress(self, flat: jnp.ndarray):
        n = flat.shape[0]
        k = self.k_for(n)
        if self.use_block_topk:
            from repro.kernels import ops as kops
            comp = kops.block_topk_sparsify(flat, self.cr,
                                            block_size=self.block_size)
        else:
            comp = sparsify_mask(flat, k)
        return comp

    def decide(self, gap: float) -> bool:
        """EWMA-update the gap and return True if compression is allowed."""
        return self.ewma.update(gap) <= self.delta

    def account(self, used_compressed: bool, n: int) -> None:
        k = self.k_for(n)
        if used_compressed:
            self.t_compressed += 1
            # k values + k int32 indices on the wire
            self.floats_sent += 2 * k
        else:
            self.t_uncompressed += 1
            self.floats_sent += n

    @property
    def cnc_ratio(self) -> float:
        tot = self.t_compressed + self.t_uncompressed
        return self.t_compressed / tot if tot else 0.0

    def step(self, flat: jnp.ndarray):
        """Full per-iteration rule: returns (tensor-to-send, used_compressed)."""
        comp = self.compress(flat)
        gap = float(energy_gap(flat, comp))
        use = self.decide(gap)
        self.account(use, flat.shape[0])
        return (comp if use else flat), use
