"""Pallas hot-path kernels: flash-decode over slot/ring/paged caches and
the block_topk VJP.

Oracle discipline (DESIGN.md §15): every kernel is validated in interpret
mode against the pure-JAX path it replaces — float tolerance for the
attention kernels (fp32 online softmax vs fp32 full softmax).
"""
import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.block_topk import block_topk  # noqa: E402
from repro.kernels.flash_decode import (flash_decode,  # noqa: E402
                                        flash_decode_paged)
from repro.kernels.ops import block_topk_counts  # noqa: E402
from repro.kernels.ref import block_topk_ref  # noqa: E402
from repro.models import RunCtx, init_params  # noqa: E402
from repro.models.attention import (chunked_attention,  # noqa: E402
                                    decode_attention)
from repro.models.decode import (ChunkedPrefill, PagePool,  # noqa: E402
                                 decode_step, init_cache, init_paged_cache,
                                 init_slot_cache, pages_needed, prefill_cache,
                                 slot_evict, slot_insert)

CTX = RunCtx(remat=False, chunk_q=8, chunk_k=8, loss_chunk=8)
PALLAS_DECODE = dataclasses.replace(CTX, decode_backend="pallas",
                                    kernel_interpret=True)
PALLAS_PREFILL = dataclasses.replace(CTX, prefill_backend="pallas",
                                     kernel_interpret=True)

# one representative per cache family: dense KV, SWA ring, RG-LRU, xLSTM
FAMILIES = ["qwen2-0.5b", "mixtral-8x22b", "recurrentgemma-2b", "xlstm-125m"]


def _cfg(arch):
    cfg = get_config(arch).reduced()
    if arch == "mixtral-8x22b":
        cfg = dataclasses.replace(cfg, window_size=8)  # exercise ring wrap
    return cfg


# ---------------------------------------------------------------------------
# flash-decode unit level: kernel vs decode_attention oracle


def _rand(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_flash_decode_contiguous_mixed_age():
    """Per-slot kv_len masking on a fixed-slot cache of mixed-age rows."""
    b, S, h, kvh, hd = 4, 24, 4, 2, 8
    q = _rand((b, 1, h, hd), 0)
    k = _rand((b, S, kvh, hd), 1)
    v = _rand((b, S, kvh, hd), 2)
    kvl = jnp.array([1, 24, 13, 7], jnp.int32)   # incl. minimum and full
    ref = decode_attention(q, k, v, kvl)
    out = flash_decode(q, k, v, kvl, bk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_flash_decode_scalar_len_and_block_snap():
    """Scalar kv_len (lockstep / cross-attn) + bk > S snaps to a divisor."""
    b, S, h, kvh, hd = 2, 24, 4, 4, 8
    q, k, v = _rand((b, 1, h, hd), 3), _rand((b, S, kvh, hd), 4), _rand(
        (b, S, kvh, hd), 5)
    ref = decode_attention(q, k, v, S)
    out = flash_decode(q, k, v, S, interpret=True)   # default bk=128 > S=24
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_flash_decode_ring_storage_order_irrelevant():
    """A wrapped SWA ring stores tokens rotated; attention is storage-order
    invariant, so rotating K/V rows must not change the output."""
    b, S, h, kvh, hd = 2, 16, 2, 2, 8
    q, k, v = _rand((b, 1, h, hd), 6), _rand((b, S, kvh, hd), 7), _rand(
        (b, S, kvh, hd), 8)
    out = flash_decode(q, k, v, S, bk=8, interpret=True)
    rot = 5                                           # ring write pointer
    k_r = jnp.roll(k, rot, axis=1)
    v_r = jnp.roll(v, rot, axis=1)
    out_r = flash_decode(q, k_r, v_r, S, bk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r), atol=2e-6)


def test_flash_decode_paged_indirection():
    """Paged pools behind a scrambled block table == contiguous gather."""
    b, h, kvh, hd, pg, ncols, rows = 3, 4, 2, 8, 8, 3, 12
    q = _rand((b, 1, h, hd), 9)
    kp = _rand((rows, pg, kvh, hd), 10)
    vp = _rand((rows, pg, kvh, hd), 11)
    bt = jnp.asarray(np.random.default_rng(0).permutation(rows)[:b * ncols]
                     .reshape(b, ncols), jnp.int32)
    kvl = jnp.array([5, 24, 17], jnp.int32)
    kview = kp[bt].reshape(b, ncols * pg, kvh, hd)
    vview = vp[bt].reshape(b, ncols * pg, kvh, hd)
    ref = decode_attention(q, kview, vview, kvl)
    out = flash_decode_paged(q, kp, vp, bt, kvl, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_decode_attention_backend_dispatch():
    """backend="pallas" on decode_attention routes through the kernel."""
    b, S, h, kvh, hd = 2, 16, 4, 2, 8
    q, k, v = _rand((b, 1, h, hd), 12), _rand((b, S, kvh, hd), 13), _rand(
        (b, S, kvh, hd), 14)
    kvl = jnp.array([9, 16], jnp.int32)
    ref = decode_attention(q, k, v, kvl)
    out = decode_attention(q, k, v, kvl, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


# ---------------------------------------------------------------------------
# flash-decode end to end: decode_step with ctx.decode_backend="pallas"


@pytest.mark.parametrize("arch", FAMILIES)
def test_decode_backend_matches_jax(arch):
    """Pallas decode == jax decode through the full model step for all four
    cache families, mixed-age slots, generating past the SWA window so the
    mixtral rings wrap (pos > S)."""
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    max_batch, cache_len = 4, 32
    prompts = [5, 11, 3]
    caches = {}
    for name, ctx in (("jax", CTX), ("pallas", PALLAS_DECODE)):
        c = init_slot_cache(cfg, max_batch, cache_len, ctx)
        for slot, plen in enumerate(prompts):
            toks = jax.random.randint(jax.random.PRNGKey(10 + slot),
                                      (1, plen), 0, cfg.vocab_size)
            fresh = init_cache(cfg, 1, cache_len, CTX)
            _, src = prefill_cache(params, toks, fresh, cfg, CTX)
            c = slot_insert(c, slot, src)
        caches[name] = c
    tok = jnp.array([[3], [7], [1], [0]], jnp.int32)
    sj = jax.jit(lambda c, t: decode_step(params, c, t, cfg, CTX))
    sp = jax.jit(lambda c, t: decode_step(params, c, t, cfg, PALLAS_DECODE))
    gen = 12 if arch == "mixtral-8x22b" else 4   # 12 > window=8: ring wraps
    for _ in range(gen):
        lj, caches["jax"] = sj(caches["jax"], tok)
        lp, caches["pallas"] = sp(caches["pallas"], tok)
        np.testing.assert_allclose(np.asarray(lj[:3]), np.asarray(lp[:3]),
                                   atol=1e-4)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b"])
def test_decode_backend_paged_evict_readmit(arch):
    """Paged pallas decode (block-table indirection in-kernel) == paged jax
    decode through mid-flight eviction and page recycling into a new
    request — the freed pages are re-admitted under a different slot."""
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    max_batch, cache_len, page = 4, 32, 8
    prompts, gen = [5, 11, 3], 6

    def admit(cache, pool, slot, plen, seed):
        toks = jax.random.randint(jax.random.PRNGKey(seed), (1, plen),
                                  0, cfg.vocab_size)
        fresh = init_cache(cfg, 1, cache_len, CTX)
        _, src = prefill_cache(params, toks, fresh, cfg, CTX)
        pages = pool.alloc(pages_needed(cfg, cache_len, page, plen + gen))
        return slot_insert(cache, slot, src, pages=pages), pages

    states = {}
    for name in ("jax", "pallas"):
        cache = init_paged_cache(cfg, max_batch, cache_len, CTX,
                                 page_size=page, num_pages=32)
        pool = PagePool(32)
        page_lists = []
        for slot, plen in enumerate(prompts):
            cache, pages = admit(cache, pool, slot, plen, 10 + slot)
            page_lists.append(pages)
        states[name] = [cache, pool, page_lists]

    tok = jnp.array([[3], [7], [1], [0]], jnp.int32)
    steps = {"jax": jax.jit(lambda c, t: decode_step(params, c, t, cfg, CTX)),
             "pallas": jax.jit(
                 lambda c, t: decode_step(params, c, t, cfg, PALLAS_DECODE))}
    for i in range(gen):
        logits = {}
        for name, st in states.items():
            l, st[0] = steps[name](st[0], tok)
            logits[name] = np.asarray(l)
        np.testing.assert_allclose(logits["jax"][:3], logits["pallas"][:3],
                                   atol=1e-4)
        if i == 2:      # evict slot 1, recycle its pages into a new request
            for name, st in states.items():
                st[0] = slot_evict(st[0], 1)
                st[1].free(st[2][1])
                st[0], st[2][1] = admit(st[0], st[1], 1, 7, 99)


# ---------------------------------------------------------------------------
# pallas prefill (flash_attention forward) behind the dispatch flag


@pytest.mark.parametrize("kind,window", [("causal", 0), ("swa", 8)])
def test_chunked_attention_pallas_backend(kind, window):
    b, sq, sk, h, kvh, hd = 2, 16, 16, 4, 2, 8
    q = _rand((b, sq, h, hd), 20)
    k = _rand((b, sk, kvh, hd), 21)
    v = _rand((b, sk, kvh, hd), 22)
    ref = chunked_attention(q, k, v, kind=kind, window=window,
                            chunk_q=8, chunk_k=8)
    out = chunked_attention(q, k, v, kind=kind, window=window,
                            backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("kind,window", [("causal", 0), ("swa", 8)])
def test_chunked_attention_pallas_q_offset(kind, window):
    """Chunked prefill: the second half of the queries attends against the
    full key range with a static q_offset — kernel == jax path."""
    b, sk, h, kvh, hd = 2, 16, 4, 2, 8
    sq, off = 8, 8
    q = _rand((b, sq, h, hd), 23)
    k = _rand((b, sk, kvh, hd), 24)
    v = _rand((b, sk, kvh, hd), 25)
    ref = chunked_attention(q, k, v, kind=kind, window=window, q_offset=off,
                            chunk_q=8, chunk_k=8)
    out = chunked_attention(q, k, v, kind=kind, window=window, q_offset=off,
                            backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b"])
def test_prefill_backend_matches_jax(arch):
    """ctx.prefill_backend="pallas" through ChunkedPrefill == the jax path
    (forward-only; serving prefill takes no gradients)."""
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0,
                              cfg.vocab_size)
    outs = {}
    for name, ctx in (("jax", CTX), ("pallas", PALLAS_PREFILL)):
        fresh = init_cache(cfg, 1, 32, CTX)
        job = ChunkedPrefill(params, toks, fresh, cfg, ctx)
        while not job.done:
            job.step(8)
        logits, cache = job.finish()
        outs[name] = (np.asarray(logits), np.asarray(cache["pos"]))
    np.testing.assert_allclose(outs["jax"][0], outs["pallas"][0], atol=1e-4)
    np.testing.assert_array_equal(outs["jax"][1], outs["pallas"][1])


# ---------------------------------------------------------------------------
# block_topk: custom VJP + zero-block / padded-row accounting


def test_block_topk_vjp_matches_masked_reference():
    """jax.grad through block_topk == jax.grad of the explicitly masked
    reference (straight-through over survivors, zero elsewhere)."""
    g2d = jnp.asarray(np.random.default_rng(3).normal(size=(8, 64)),
                      jnp.float32)

    def via_kernel(g):
        out, _ = block_topk(g, 4, interpret=True)
        return jnp.sum(jnp.sin(out))

    def via_ref(g):
        keep = block_topk_ref(g, 4)[0] != 0
        return jnp.sum(jnp.sin(jnp.where(keep, g, 0.0)))

    gk = jax.grad(via_kernel)(g2d)
    gr = jax.grad(via_ref)(g2d)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(gr))
    # non-survivors get exactly zero gradient
    keep = np.asarray(block_topk(g2d, 4, interpret=True)[0]) != 0
    assert np.all(np.asarray(gk)[~keep] == 0)


def test_block_topk_zero_blocks_report_zero():
    """An all-zero block must report 0 survivors (tau bisects to 0)."""
    g2d = jnp.zeros((8, 64), jnp.float32).at[0, :3].set(
        jnp.array([1.0, -2.0, 0.5]))
    out, cnt = block_topk(g2d, 4, interpret=True)
    ro, rc = block_topk_ref(g2d, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rc))
    assert int(cnt[0, 0]) == 3            # only the 3 nonzeros survive
    assert np.all(np.asarray(cnt[1:]) == 0)


def test_block_topk_counts_trims_padding():
    """flat n=100 with block 64 -> 2 real rows; the TILE_BLOCKS row pad must
    not leak phantom survivor counts into CSR wire accounting."""
    flat = jnp.asarray(np.random.default_rng(4).normal(size=(100,)),
                       jnp.float32)
    out, cnt = block_topk_counts(flat, 0.1, block_size=64, interpret=True)
    assert out.shape == (100,)
    assert cnt.shape == (2,)              # ceil(100/64), not the padded 8
    k = max(1, int(0.1 * 64))
    assert np.all(np.asarray(cnt) <= k)
    assert int(cnt.sum()) == int(jnp.sum(out != 0))
