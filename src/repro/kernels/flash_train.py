"""Pallas TPU kernels: flash attention forward and backward for training.

``flash_fwd`` writes ``o`` and the log-sum-exp ``lse``; ``flash_bwd`` is a
dQ kernel (which also writes ``D = rowsum(dO * o)``) and a dK/dV kernel.
Together they implement the forward and backward rules of
``models/attention.py``'s custom-VJP flash attention on the chip; its JAX
``_flash_fwd``/``_flash_bwd`` are their oracle and the path taken everywhere
else (``attention.kernel_route``).

Layout.  The kernels take every array sequence-minor, (b, heads * head_dim,
s): the layout XLA gives the rotary embedding's output and wants for its
gradient, so no relayout sits between the projections and the kernels.  A
head is a block of ``head_dim`` rows (a multiple of 8 sublanes); a grid step
takes ``heads_per_step`` of them.  GQA is by index: query head ``h`` reads
K/V head ``h // g``, never a copy.  The forward and dK/dV kernels hold
scores as (k, q), so the softmax statistics reduce over sublanes and stay
rows along the sequence's lanes (a reduction over lanes costs the forward
more than its value matmul does); the dQ kernel, which reduces nothing over
keys, holds them as (q, k).  q and dO (dQ) and k and v (dK/dV) are
transposed to (tile, head_dim) once per tile, in VMEM.

Grid.  Forward and dQ run over (batch, query heads, q tile, k tile), dK/dV
over (batch, KV heads, k tile, query heads of the group, q tile); the last
axes reduce.  The score tile stays in VMEM.  Causal and SWA tiles that are
wholly masked issue no DMA (their index maps clamp to a live tile, which is
already resident) and no matmul (``pl.when``); only tiles that cross the
mask's edge are masked.

Precision, as XLA's DEFAULT precision gives the JAX path on a TPU: each
``dot`` takes bfloat16 operands, cast from the float32 tiles in VMEM, and
accumulates in float32; the softmax statistics, ``exp``, ``D`` and every
accumulator are float32, and nothing narrower than the inputs reaches HBM.

Validated against the JAX path in interpret mode on the CPU
(tests/test_flash_train.py) and compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_F32 = jnp.float32


def tile_size(n: int) -> int:
    """The sequence tile: the largest of 512/256/128 that divides ``n``.

    Larger tiles take fewer grid steps and fewer passes over K/V.  On a
    v5e, at 2 x 2048 tokens, 16 heads of 64: forward 0.43 ms and backward
    1.10 ms with 512 tiles, 0.75 and 1.28 ms with 256; 1024 needs 18.3 MB
    of VMEM, over the 16 MB scoped limit."""
    for t in (512, 256, 128):
        if n % t == 0:
            return t
    raise ValueError(f"length {n} is not a multiple of 128")


def heads_per_step(h: int, kvh: int, hd: int) -> int:
    """Query heads one grid step takes: the most, up to 256 rows, that
    divide the heads (MHA) or the query heads of one KV head (GQA), so a
    step reads one KV head under GQA and the matching ones under MHA.
    More heads a step halve the steps' fixed cost: on a v5e, at 2 x 2048
    tokens, 16 heads of 64, forward 0.43 / 0.47 / 0.55 ms and backward
    1.10 / 1.17 / 1.32 ms at 4 / 2 / 1 heads a step."""
    g = h // kvh
    group = h if g == 1 else g
    return max(p for p in (1, 2, 4) if group % p == 0 and p * hd <= 256)


@dataclasses.dataclass(frozen=True)
class _Geo:
    """Static shape of one attention call, and its tiling."""
    sq: int
    sk: int
    h: int
    kvh: int
    hd: int
    kind: str
    window: int
    q_offset: int
    bq: int
    bk: int

    @property
    def p(self):            # query heads per grid step
        return heads_per_step(self.h, self.kvh, self.hd)

    @property
    def g(self):            # query heads per KV head
        return self.h // self.kvh

    @property
    def pk(self):           # KV heads per grid step
        return self.p if self.g == 1 else 1

    @property
    def nc(self):           # query head blocks
        return self.h // self.p

    @property
    def nqb(self):          # query head blocks that read one KV head block
        return 1 if self.g == 1 else self.g // self.p

    @property
    def nq(self):
        return self.sq // self.bq

    @property
    def nk(self):
        return self.sk // self.bk

    @property
    def swa(self):
        return self.kind == "swa" and self.window > 0

    def kv_row(self, jl):
        """The KV head's slot, in its block, for query head ``jl``."""
        return jl if self.g == 1 else 0

    # -- tile liveness, on traced grid indices ------------------------------

    def k_range(self, i):
        """Live k tiles [lo, hi] of q tile ``i``."""
        if self.kind == "bidir":
            return 0, self.nk - 1
        qa = self.q_offset + i * self.bq
        hi = jnp.minimum((qa + self.bq - 1) // self.bk, self.nk - 1)
        lo = 0
        if self.swa:
            lo = jnp.maximum(qa - self.window + 1, 0) // self.bk
        return lo, hi

    def q_range(self, j):
        """Live q tiles [lo, hi] of k tile ``j``."""
        if self.kind == "bidir":
            return 0, self.nq - 1
        ka = j * self.bk
        lo = jnp.maximum(ka - self.q_offset, 0) // self.bq
        hi = self.nq - 1
        if self.swa:
            hi = jnp.minimum(
                (ka + self.bk + self.window - 2 - self.q_offset) // self.bq,
                hi)
        return lo, hi

    def edge(self, i, j):
        """Whether live tile (i, j) crosses the mask's edge."""
        qa = self.q_offset + i * self.bq
        ka = j * self.bk
        crosses = ka + self.bk - 1 > qa
        if self.swa:
            crosses = crosses | (ka <= qa + self.bq - 1 - self.window)
        return crosses

    def visible(self, i, j, transposed=False):
        """(bq, bk) mask of the keys each query sees; (bk, bq) transposed."""
        shape = (self.bk, self.bq) if transposed else (self.bq, self.bk)
        qax, kax = (1, 0) if transposed else (0, 1)
        qpos = (self.q_offset + i * self.bq
                + jax.lax.broadcasted_iota(jnp.int32, shape, qax))
        kpos = j * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, kax)
        m = kpos <= qpos
        if self.swa:
            m &= kpos > qpos - self.window
        return m


def _rows(ref, j, hd):
    """Head ``j``'s rows of a (heads * hd, s) tile."""
    return ref[j * hd:(j + 1) * hd, :]


def _bf16_t(x):
    """A (hd, n) tile as the (n, hd) bfloat16 operand of a matmul."""
    return x.astype(_F32).T.astype(jnp.bfloat16)


def _dot(a, b, dims):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               dims, preferred_element_type=_F32)


def _col(row):
    """(1, n) -> (n, 1)."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, :1]


def _on_live_tiles(geo: _Geo, i, j, x, lo, hi, tile):
    """Run ``tile(masked)`` if tile (i, j) is live, that is if its reduced
    index ``x`` lies in [lo, hi]; mask only on the mask's edge."""
    if geo.kind == "bidir":
        tile(False)
        return
    live = (lo <= x) & (x <= hi)
    edge = geo.edge(i, j)
    pl.when(live & jnp.logical_not(edge))(lambda: tile(False))
    pl.when(live & edge)(lambda: tile(True))


def _mask(geo: _Geo, s, i, j, masked, transposed=False):
    if not masked:
        return s
    return jnp.where(geo.visible(i, j, transposed), s, NEG_INF)


def _clamp(x, lo, hi, n):
    return jnp.clip(jnp.clip(x, lo, hi), 0, n - 1)


def _params(n_axes, n_reduced):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_axes - n_reduced)
        + ("arbitrary",) * n_reduced)


# ---------------------------------------------------------------------------
# forward
#
# Scores are (k, q): the softmax statistics reduce over sublanes and stay
# rows, as o, lse and the sequence-minor tiles are.


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, geo: _Geo):
    i, j = pl.program_id(2), pl.program_id(3)
    hd, scale = geo.hd, geo.hd ** -0.5

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, _F32)
        l_scr[...] = jnp.zeros(l_scr.shape, _F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)

    def tile(masked):
        for jl in range(geo.p):
            r = geo.kv_row(jl)
            st = _dot(_rows(k_ref, r, hd), _rows(q_ref, jl, hd), _TN) * scale
            st = _mask(geo, st, i, j, masked, transposed=True)
            m_prev = m_scr[jl]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            c1 = jnp.exp(m_prev - m_new)
            l_scr[jl] = l_scr[jl] * c1 + jnp.sum(pt, axis=0, keepdims=True)
            acc_scr[jl] = acc_scr[jl] * c1 + _dot(_rows(v_ref, r, hd), pt,
                                                  _NN)
            m_scr[jl] = m_new

    _on_live_tiles(geo, i, j, j, *geo.k_range(i), tile)

    @pl.when(j == geo.nk - 1)
    def _finish():
        for jl in range(geo.p):
            l = jnp.maximum(l_scr[jl], 1e-30)
            o_ref[jl * hd:(jl + 1) * hd, :] = (acc_scr[jl] / l
                                               ).astype(o_ref.dtype)
            lse_ref[jl:jl + 1, :] = m_scr[jl] + jnp.log(l)


def _fwd_specs(geo: _Geo):
    """Block specs of the (batch, head block, q tile, k tile) grid."""
    def q_map(b, c, i, j):
        return b, c, i

    def kv_map(b, c, i, j):
        return b, c // geo.nqb, _clamp(j, *geo.k_range(i), geo.nk)

    def stat_map(b, c, i, j):
        return b, c, 0, i

    return (pl.BlockSpec((None, geo.p * geo.hd, geo.bq), q_map),
            pl.BlockSpec((None, geo.pk * geo.hd, geo.bk), kv_map),
            pl.BlockSpec((None, None, geo.p, geo.bq), stat_map))


def _fwd_call(geo: _Geo, q, k, v, interpret):
    batch = q.shape[0]
    q_spec, kv_spec, stat_spec = _fwd_specs(geo)
    row = pltpu.VMEM((geo.p, 1, geo.bq), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, geo=geo),
        grid=(batch, geo.nc, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((batch, geo.nc, geo.p, geo.sq),
                                        _F32)],
        scratch_shapes=[row, row,
                        pltpu.VMEM((geo.p, geo.hd, geo.bq), _F32)],
        compiler_params=_params(4, 1),
        interpret=resolve_interpret(interpret),
        name="flash_train_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: dQ (and D) over k tiles with scores (q, k), then dK/dV over q
# tiles with scores (k, q), so the probability and gradient tiles are always
# the left operand of the matmuls that accumulate


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, d_ref,
               q_scr, do_scr, lse_scr, d_scr, acc_scr, *, geo: _Geo):
    i, j = pl.program_id(2), pl.program_id(3)
    hd, scale = geo.hd, geo.hd ** -0.5

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)
        for jl in range(geo.p):
            do = _rows(do_ref, jl, hd)
            d = jnp.sum(do.astype(_F32) * _rows(o_ref, jl, hd).astype(_F32),
                        axis=0, keepdims=True)
            d_ref[jl:jl + 1, :] = d
            d_scr[jl] = _col(d)
            lse_scr[jl] = _col(lse_ref[jl:jl + 1, :])
            q_scr[jl] = _bf16_t(_rows(q_ref, jl, hd))
            do_scr[jl] = _bf16_t(do)

    def tile(masked):
        for jl in range(geo.p):
            r = geo.kv_row(jl)
            k = _rows(k_ref, r, hd)
            s = _mask(geo, _dot(q_scr[jl], k, _NN) * scale, i, j, masked)
            p = jnp.exp(s - lse_scr[jl])
            dp = _dot(do_scr[jl], _rows(v_ref, r, hd), _NN)
            ds = p * (dp - d_scr[jl]) * scale
            acc_scr[jl] = acc_scr[jl] + _dot(ds, k, _NT)

    _on_live_tiles(geo, i, j, j, *geo.k_range(i), tile)

    @pl.when(j == geo.nk - 1)
    def _finish():
        for jl in range(geo.p):
            dq_ref[jl * hd:(jl + 1) * hd, :] = acc_scr[jl].T.astype(
                dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                k_scr, v_scr, dk_scr, dv_scr, *, geo: _Geo):
    j, t, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    hd, scale = geo.hd, geo.hd ** -0.5

    @pl.when((t == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, _F32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, _F32)
        for r in range(geo.pk):
            k_scr[r] = _bf16_t(_rows(k_ref, r, hd))
            v_scr[r] = _bf16_t(_rows(v_ref, r, hd))

    def tile(masked):
        for jl in range(geo.p):
            r = geo.kv_row(jl)
            q, do = _rows(q_ref, jl, hd), _rows(do_ref, jl, hd)
            st = _mask(geo, _dot(k_scr[r], q, _NN) * scale, i, j, masked,
                       transposed=True)
            pt = jnp.exp(st - lse_ref[jl:jl + 1, :])
            dv_scr[r] = dv_scr[r] + _dot(pt, do, _NT)
            dpt = _dot(v_scr[r], do, _NN)
            dst = pt * (dpt - d_ref[jl:jl + 1, :]) * scale
            dk_scr[r] = dk_scr[r] + _dot(dst, q, _NT)

    _on_live_tiles(geo, i, j, i, *geo.q_range(j), tile)

    @pl.when((t == geo.nqb - 1) & (i == geo.nq - 1))
    def _finish():
        for r in range(geo.pk):
            dk_ref[r * hd:(r + 1) * hd, :] = dk_scr[r].T.astype(dk_ref.dtype)
            dv_ref[r * hd:(r + 1) * hd, :] = dv_scr[r].T.astype(dv_ref.dtype)


def _bwd_calls(geo: _Geo, q, k, v, o, do, lse, interpret):
    batch = q.shape[0]
    interpret = resolve_interpret(interpret)
    q_spec, kv_spec, stat_spec = _fwd_specs(geo)
    q_t = pltpu.VMEM((geo.p, geo.bq, geo.hd), jnp.bfloat16)
    col = pltpu.VMEM((geo.p, geo.bq, 1), _F32)
    dq, d = pl.pallas_call(
        functools.partial(_dq_kernel, geo=geo),
        grid=(batch, geo.nc, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, stat_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, _F32)],
        scratch_shapes=[q_t, q_t, col, col,
                        pltpu.VMEM((geo.p, geo.bq, geo.hd), _F32)],
        compiler_params=_params(4, 1),
        interpret=interpret,
        name="flash_train_dq",
    )(q, k, v, o, do, lse)

    # (batch, KV head block, k tile, query head block of the group, q tile)
    def q_map(b, kc, j, t, i):
        return b, kc * geo.nqb + t, _clamp(i, *geo.q_range(j), geo.nq)

    def kv_map(b, kc, j, t, i):
        return b, kc, j

    def stat_map(b, kc, j, t, i):
        return b, kc * geo.nqb + t, 0, _clamp(i, *geo.q_range(j), geo.nq)

    q_spec = pl.BlockSpec((None, geo.p * geo.hd, geo.bq), q_map)
    kv_spec = pl.BlockSpec((None, geo.pk * geo.hd, geo.bk), kv_map)
    stat_spec = pl.BlockSpec((None, None, geo.p, geo.bq), stat_map)
    k_t = pltpu.VMEM((geo.pk, geo.bk, geo.hd), jnp.bfloat16)
    acc = pltpu.VMEM((geo.pk, geo.bk, geo.hd), _F32)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, geo=geo),
        grid=(batch, geo.kvh // geo.pk, geo.nk, geo.nqb, geo.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[k_t, k_t, acc, acc],
        compiler_params=_params(5, 2),
        interpret=interpret,
        name="flash_train_dkv",
    )(q, k, v, do, lse, d)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry points: q (b, sq, h, hd), k/v (b, sk, kv, hd)


def _geo(q, k, kind, window, q_offset, bq, bk) -> _Geo:
    _, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    return _Geo(sq=sq, sk=sk, h=h, kvh=kvh, hd=hd, kind=kind, window=window,
                q_offset=q_offset, bq=bq or tile_size(sq),
                bk=bk or tile_size(sk))


def _seq_minor(x):
    """(b, s, n, hd) -> (b, n * hd, s)."""
    b, s, n, hd = x.shape
    return x.reshape(b, s, n * hd).transpose(0, 2, 1)


def _seq_major(x, shape):
    return x.transpose(0, 2, 1).reshape(shape)


def flash_fwd(q, k, v, *, kind: str, window: int = 0, q_offset: int = 0,
              bq: int = 0, bk: int = 0, interpret=None):
    """Returns o (b, sq, h, hd) in q's dtype and lse (b, h / p, p, sq)
    float32, p = ``heads_per_step``.  ``bq``/``bk`` 0 take ``tile_size``
    (the tests take smaller tiles, to cross several at small lengths)."""
    geo = _geo(q, k, kind, window, q_offset, bq, bk)
    o, lse = _fwd_call(geo, _seq_minor(q), _seq_minor(k), _seq_minor(v),
                       interpret)
    return _seq_major(o, q.shape), lse


def flash_bwd(q, k, v, o, lse, do, *, kind: str, window: int = 0,
              q_offset: int = 0, bq: int = 0, bk: int = 0, interpret=None):
    """Gradients (dq, dk, dv) of the forward above, in the inputs' dtypes."""
    geo = _geo(q, k, kind, window, q_offset, bq, bk)
    dq, dk, dv = _bwd_calls(geo, _seq_minor(q), _seq_minor(k), _seq_minor(v),
                            _seq_minor(o), _seq_minor(do), lse, interpret)
    return (_seq_major(dq, q.shape), _seq_major(dk, k.shape),
            _seq_major(dv, v.shape))
