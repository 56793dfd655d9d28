"""Kernel microbenchmarks: block-top-k sparsification vs exact global top-k.

Wall-times here are CPU (interpret-mode pallas is a correctness path, not a
perf path), so the perf-relevant derived numbers are algorithmic: energy
retention vs exact top-k and the achieved density.
"""
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit, write_json_artifact
from repro.core.compression import sparsify_mask
from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.ref import block_topk_ref
from repro.models.attention import decode_attention


def _flash_decode_rows():
    """Flash-decode over a slots x seq-len grid: contiguous + paged cells,
    oracle max-err and wall times (interpret on CPU — correctness numbers;
    the jax oracle wall time is the XLA baseline the kernel replaces)."""
    rows = []
    h, kvh, hd, pg = 8, 4, 64, 128
    for b in (4, 16):
        for S in (128, 512):
            key = jax.random.PRNGKey(b * 1000 + S)
            kq, kk, kv, kl = jax.random.split(key, 4)
            q = jax.random.normal(kq, (b, 1, h, hd))
            k = jax.random.normal(kk, (b, S, kvh, hd))
            v = jax.random.normal(kv, (b, S, kvh, hd))
            kvl = jax.random.randint(kl, (b,), 1, S + 1)
            kern = jax.jit(lambda q, k, v, l: flash_decode(q, k, v, l))
            orac = jax.jit(lambda q, k, v, l: decode_attention(q, k, v, l))
            err = float(jnp.max(jnp.abs(kern(q, k, v, kvl)
                                        - orac(q, k, v, kvl))))
            us_k = timeit(lambda: jax.block_until_ready(kern(q, k, v, kvl)),
                          n=3)
            us_j = timeit(lambda: jax.block_until_ready(orac(q, k, v, kvl)),
                          n=3)
            emit(f"kernel_flash_decode_b{b}_s{S}", us_k,
                 f"max_err={err:.2e};jax_us={us_j:.0f}")
            rows.append({"kernel": "flash_decode", "slots": b, "seq": S,
                         "kernel_us": us_k, "jax_us": us_j, "max_err": err})
            # paged cell: same logical cache behind a scrambled block table
            ncols = S // pg
            pool_rows = b * ncols + b          # data pages + scratch pages
            perm = jax.random.permutation(kl, b * ncols)
            bt = perm.reshape(b, ncols).astype(jnp.int32)
            kp = jnp.zeros((pool_rows, pg, kvh, hd)).at[bt.reshape(-1)].set(
                k.reshape(b * ncols, pg, kvh, hd))
            vp = jnp.zeros((pool_rows, pg, kvh, hd)).at[bt.reshape(-1)].set(
                v.reshape(b * ncols, pg, kvh, hd))
            pkern = jax.jit(lambda q, kp, vp, bt, l: flash_decode_paged(
                q, kp, vp, bt, l))
            perr = float(jnp.max(jnp.abs(pkern(q, kp, vp, bt, kvl)
                                         - orac(q, k, v, kvl))))
            us_p = timeit(lambda: jax.block_until_ready(
                pkern(q, kp, vp, bt, kvl)), n=3)
            rows.append({"kernel": "flash_decode_paged", "slots": b, "seq": S,
                         "kernel_us": us_p, "jax_us": us_j, "max_err": perr})
    return rows


def main():
    n = 1 << 20  # ~1M grads (ResNet-scale slice)
    flat = jax.random.normal(jax.random.PRNGKey(0), (n,))
    rows = []
    for cr in (0.1, 0.01):
        k = int(cr * n)
        block_fn = jax.jit(lambda f: ops.block_topk_sparsify(f, cr))
        glob_fn = jax.jit(lambda f: sparsify_mask(f, k))
        us_b = timeit(lambda: jax.block_until_ready(block_fn(flat)), n=3)
        us_g = timeit(lambda: jax.block_until_ready(glob_fn(flat)), n=3)
        sp = block_fn(flat)
        gl = glob_fn(flat)
        ret = float(jnp.sum(sp * sp) / jnp.sum(gl * gl))
        emit(f"kernel_block_topk_cr{cr}", us_b,
             f"retention_vs_global={ret:.4f};global_topk_us={us_g:.0f}")
        rows.append({"kernel": "block_topk", "cr": cr, "n": n,
                     "block_us": us_b, "global_us": us_g,
                     "retention_vs_global": ret})

    # fused sgdm: one-pass update vs three-pass jnp
    p = jax.random.normal(jax.random.PRNGKey(1), (n,))
    m = jnp.zeros(n)
    g = jax.random.normal(jax.random.PRNGKey(2), (n,))
    fused = jax.jit(lambda p, m, g: ops.fused_sgdm_flat(p, m, g, 0.1))
    us = timeit(lambda: jax.block_until_ready(fused(p, m, g)), n=3)
    emit("kernel_fused_sgdm_1m", us, "mode=interpret(cpu-correctness)")
    rows.append({"kernel": "fused_sgdm", "n": n, "us": us,
                 "mode": "interpret(cpu-correctness)"})
    rows.extend(_flash_decode_rows())
    write_json_artifact("artifacts/perf/kernels.json", {"rows": rows})


if __name__ == "__main__":
    main()
