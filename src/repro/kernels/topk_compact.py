"""Pallas TPU kernel: pack an exact top-k selection into a list, in flat
order, with no sort.

``topk_compact(flat, t, room, k)`` takes the float32 vector, the bit
pattern ``t`` of its k-th largest magnitude (non-negative float32 bits
order as integers) and ``room``, how many entries whose magnitude equals
``t`` are kept: the first ones in flat order.  It returns the k kept
values and their indices, ascending.  ``core.compression`` finds ``t`` and
``room``; its jnp path, ``jnp.nonzero`` over the same mask, is the oracle.

One sequential pass over ``flat`` in blocks of ``rows`` x 128 entries:

1. *select*: magnitude above ``t``, or equal to it and among the first
   ``room`` such entries; a running count in SMEM carries the ties seen
   across blocks, and only a block that takes some but not all of its ties
   ranks them.
2. *rank*: the exclusive prefix count of the selection in row-major order,
   by two matmuls with 0/1 triangular matrices (exact: partial row counts
   up to 128 in bfloat16, sums up to ``rows`` x 128 in float32).
3. *shift compaction*: a kept entry at block position f goes to
   ``phase + rank``, where ``phase`` is the block's output offset mod 128,
   in a buffer with ``PAD_ROWS`` empty rows in front; so it moves left by
   d = f + 128 * PAD_ROWS - phase - rank > 0.  The bits of d are applied
   from the least significant up, each one static shift by 2^b (a lane
   roll with a one-row carry below 128, a row roll above) and a select.
   d does not decrease along the kept entries, so no two ever meet.  The
   index is not moved: it is f = position + d - 128 * PAD_ROWS at the end.
4. *write*: the buffer's first row takes the previous block's partial last
   row in its first ``phase`` lanes, and the used rows go to row
   offset // 128 of the output by DMA, ``CHUNK`` rows at a time.  A block
   waits for the previous block's copies before it starts its own, so the
   later write of a shared row wins; the rows past the end are padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128
PAD_ROWS = 8          # empty rows in front of a block, so that d > 0
CHUNK = 32            # output rows per DMA
DEFAULT_ROWS = 512    # rows of 128 entries per grid step


def _excl_prefix(m, lower):
    """Exclusive prefix count of the 0/1 matrix ``m`` (rows, 128) in
    row-major order, int32.  ``lower`` is the strictly lower triangular
    (rows, rows) 0/1 matrix in bfloat16."""
    i = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    mb = m.astype(jnp.bfloat16)
    in_row = jnp.dot(mb, (i < j).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    row_tot = jnp.dot(mb, jnp.ones((LANES, LANES), jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    before = jnp.dot(lower, row_tot.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return (in_row + before).astype(jnp.int32)


def _shift_left(x, s: int, lane):
    """y[p] = x[p + s] in row-major order (wrapping at the end)."""
    rows = x.shape[0]
    if s % LANES == 0:
        return pltpu.roll(x, rows - s // LANES, 0)
    a = pltpu.roll(x, LANES - s, 1)
    return jnp.where(lane < LANES - s, a, pltpu.roll(a, rows - 1, 0))


def _kernel(scal_ref, x_ref, vals_hbm, idx_hbm, buf_v, buf_i, carry_v,
            carry_i, lower_ref, state, sems, *, n: int, rows: int,
            nbits: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    slot = b % 2
    t, room = scal_ref[0], scal_ref[1]
    wrows = rows + PAD_ROWS

    @pl.when(b == 0)
    def _():
        state[0] = 0          # entries written before this block
        state[1] = 0          # ties seen before this block
        state[2] = 0          # DMAs of the previous block still in flight
        ri = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
        rj = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        lower_ref[...] = (rj < ri).astype(jnp.bfloat16)

    xi = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)
    mag = xi & 0x7FFFFFFF
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    f = row * LANES + lane
    valid = f < n - b * (rows * LANES)      # the last block runs past n
    gt = valid & (mag > t)
    eq = valid & (mag == t)
    n_eq = jnp.sum(eq.astype(jnp.int32))
    allow = jnp.clip(room - state[1], 0, n_eq)
    state[1] = state[1] + n_eq

    def some_ties():
        rank = _excl_prefix(eq.astype(jnp.float32), lower_ref[...])
        return (gt | (eq & (rank < allow))).astype(jnp.int32)

    def all_or_none():
        return (gt | (eq & (allow > 0))).astype(jnp.int32)

    sel = jax.lax.cond((allow > 0) & (allow < n_eq), some_ties, all_or_none)
    cnt = jnp.sum(sel)
    off = state[0]
    phase = off % LANES
    rank = _excl_prefix(sel.astype(jnp.float32), lower_ref[...])
    keep = sel > 0
    d = jnp.where(keep, f + PAD_ROWS * LANES - phase - rank, 0)
    v = jnp.where(keep, xi, 0)
    zeros = jnp.zeros((PAD_ROWS, LANES), jnp.int32)
    d = jnp.concatenate([zeros, d], axis=0)
    v = jnp.concatenate([zeros, v], axis=0)
    wlane = jax.lax.broadcasted_iota(jnp.int32, (wrows, LANES), 1)
    for bit in range(nbits):
        s = 1 << bit
        dn, vn = _shift_left(d, s, wlane), _shift_left(v, s, wlane)
        come = (dn & s) != 0
        go = (d & s) != 0
        d = jnp.where(come, dn, jnp.where(go, 0, d))
        v = jnp.where(come, vn, jnp.where(go, 0, v))
    wrow = jax.lax.broadcasted_iota(jnp.int32, (wrows, LANES), 0)
    pos = wrow * LANES + wlane
    idx = b * (rows * LANES) + pos + d - PAD_ROWS * LANES
    buf_v[slot, :wrows] = jax.lax.bitcast_convert_type(v, jnp.float32)
    buf_i[slot, :wrows] = idx
    # the first row's leading lanes belong to the blocks before
    head = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) < phase
    buf_v[slot, 0:1] = jnp.where(head, carry_v[...], buf_v[slot, 0:1])
    buf_i[slot, 0:1] = jnp.where(head, carry_i[...], buf_i[slot, 0:1])

    def copies(j, src_slot, dst_row):
        rs = pl.ds(pl.multiple_of(j * CHUNK, CHUNK), CHUNK)
        rd = pl.ds(dst_row + j * CHUNK, CHUNK)
        return (pltpu.make_async_copy(buf_v.at[src_slot, rs],
                                      vals_hbm.at[rd], sems.at[0]),
                pltpu.make_async_copy(buf_i.at[src_slot, rs],
                                      idx_hbm.at[rd], sems.at[1]))

    def wait_all(n_chunks):
        def body(j, c):
            for cp in copies(0, 0, 0):
                cp.wait()
            return c
        jax.lax.fori_loop(0, n_chunks, body, 0)

    # the previous block's copies land before this block's, which may
    # rewrite its last row
    wait_all(state[2])
    used = jnp.where(cnt > 0, (phase + cnt + LANES - 1) // LANES, 0)
    n_chunks = (used + CHUNK - 1) // CHUNK
    dst = off // LANES

    def start(j, c):
        for cp in copies(j, slot, dst):
            cp.start()
        return c
    jax.lax.fori_loop(0, n_chunks, start, 0)
    state[2] = n_chunks

    @pl.when(cnt > 0)
    def _():
        last = (phase + cnt) // LANES
        carry_v[...] = buf_v[slot, pl.ds(last, 1)]
        carry_i[...] = buf_i[slot, pl.ds(last, 1)]

    state[0] = off + cnt

    @pl.when(b == nb - 1)
    def _():
        wait_all(n_chunks)


@functools.partial(jax.jit, static_argnames=("k", "rows", "interpret"))
def topk_compact(flat, t, room, *, k: int, rows: int = DEFAULT_ROWS,
                 interpret=None):
    """(values f32[k], indices s32[k] ascending) of the entries of ``flat``
    whose magnitude bits exceed ``t``, and of the first ``room`` whose bits
    equal it; the caller makes that exactly k entries."""
    n = flat.shape[0]
    x = flat.astype(jnp.float32)
    if n % LANES:
        x = jnp.pad(x, (0, (-n) % LANES))
    x = x.reshape(-1, LANES)
    rows = min(rows, -(-x.shape[0] // 8) * 8)
    nblocks = -(-x.shape[0] // rows)
    nbits = ((rows + PAD_ROWS) * LANES - 1).bit_length()   # of the largest d
    brows = -(-(rows + PAD_ROWS) // CHUNK) * CHUNK
    # the last block's last DMA may run a chunk past the k entries
    out_rows = -(-k // LANES) + CHUNK + 8
    scal = jnp.stack([jnp.asarray(t, jnp.int32), jnp.asarray(room, jnp.int32)])
    vals, idx = pl.pallas_call(
        functools.partial(_kernel, n=n, rows=rows, nbits=nbits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[pl.BlockSpec((rows, LANES), lambda b, s: (b, 0))],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((2, brows, LANES), jnp.float32),
                            pltpu.VMEM((2, brows, LANES), jnp.int32),
                            pltpu.VMEM((1, LANES), jnp.float32),
                            pltpu.VMEM((1, LANES), jnp.int32),
                            pltpu.VMEM((rows, rows), jnp.bfloat16),
                            pltpu.SMEM((3,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((out_rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name="topk_compact",
    )(scal, x)
    return vals.reshape(-1)[:k], idx.reshape(-1)[:k]
