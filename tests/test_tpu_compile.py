"""v5e AOT compiles of the Pallas kernels on the main path, at qwen2-0.5b
shapes (14 query heads, 2 KV heads, head_dim 64), and the training
attention kernels at the benchmark's qwen1.5-0.5b shape as well.

Nothing runs here: the TPU compiler is installed without a chip and accepts
or refuses each kernel as the chip would — block tiling, memory spaces,
scalar prefetch, VMEM budget.  The topology is described inside a fixture,
so collecting this file never loads the TPU library.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import compression  # noqa: E402
from repro.kernels.block_topk import block_topk, fused_sgdm  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.flash_decode import (flash_decode,  # noqa: E402
                                        flash_decode_paged)
from repro.kernels.flash_train import flash_bwd, flash_fwd  # noqa: E402
from repro.kernels.topk_compact import topk_compact  # noqa: E402

CFG = get_config("qwen2-0.5b")
H, KVH, HD = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
SLOTS, PAGE = 8, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; it must hold a Mosaic kernel."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("cache_len,dtype", [(144, jnp.float32),
                                             (512, jnp.float32),
                                             (512, jnp.bfloat16)])
def test_flash_decode_compiles_for_v5e(one_chip, cache_len, dtype):
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                     sharding=one_chip)
    _compile(lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
             s((SLOTS, 1, H, HD)), s((SLOTS, cache_len, KVH, HD)),
             s((SLOTS, cache_len, KVH, HD)), s((SLOTS,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_compiles_for_v5e(one_chip, dtype):
    ncols = -(-144 // PAGE)
    rows = SLOTS * ncols + SLOTS            # + one scratch page per slot
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                     sharding=one_chip)
    _compile(lambda q, k, v, bt, n: flash_decode_paged(q, k, v, bt, n,
                                                       interpret=False),
             s((SLOTS, 1, H, HD)), s((rows, PAGE, KVH, HD)),
             s((rows, PAGE, KVH, HD)), s((SLOTS, ncols), jnp.int32),
             s((SLOTS,), jnp.int32))


@pytest.mark.parametrize("kind", ["causal", "swa"])
def test_flash_attention_fwd_compiles_for_v5e(one_chip, kind):
    x = jax.ShapeDtypeStruct((H, 512, HD), jnp.float32, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, kind=kind,
                                                 window=256,
                                                 interpret=False), x, x, x)


@pytest.mark.parametrize("b,s,h,kvh", [(2, 2048, 16, 16),   # qwen1.5-0.5b
                                       (4, 512, H, KVH)])    # qwen2-0.5b
def test_flash_train_fwd_bwd_compile_for_v5e(one_chip, b, s, h, kvh):
    """The training kernels (forward, dQ, dK/dV), causal at head_dim 64,
    at the benchmark cell's shape and at qwen2-0.5b's bring-up shape."""
    def fwd_bwd(q, k, v, do):
        o, lse = flash_fwd(q, k, v, kind="causal", interpret=False)
        return flash_bwd(q, k, v, o, lse, do, kind="causal", interpret=False)

    qs = jax.ShapeDtypeStruct((b, s, h, HD), jnp.float32, sharding=one_chip)
    ks = jax.ShapeDtypeStruct((b, s, kvh, HD), jnp.float32, sharding=one_chip)
    text = _compile(fwd_bwd, qs, ks, ks, qs).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_block_topk_compiles_for_v5e(one_chip):
    """The whole flattened qwen2-0.5b gradient as (blocks, 1024) tiles."""
    n_params = 494_032_768
    g2d = jax.ShapeDtypeStruct((-(-n_params // 8192) * 8, 1024), jnp.float32,
                               sharding=one_chip)
    _compile(lambda g: block_topk(g, 102, interpret=False), g2d)


def test_topk_compact_compiles_for_v5e(one_chip):
    """The packing kernel over 4M floats (not a multiple of its block),
    keeping a tenth, as the compressed DDP program does."""
    n = 4_000_000
    f = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    s = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(lambda f, t, r: topk_compact(f, t, r, k=n // 10,
                                          interpret=False), f, s, s)


def test_global_topk_for_v5e_has_no_sort_gather_or_scatter(one_chip,
                                                            monkeypatch):
    """On the TPU the exact top-k is reductions and the packing kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 3_000_000
    f = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    hlo = _compile(lambda f: compression.global_topk(f, n // 10),
                   f).as_text()
    for op in (" sort(", " gather(", " scatter(", "TopK", "top_k"):
        assert op not in hlo, op


def test_fused_sgdm_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    _compile(lambda p, m, g: fused_sgdm(p, m, g, 0.1, interpret=False),
             x, x, x)
