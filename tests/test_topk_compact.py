"""Exact top-k by bit threshold (``core.compression``) and the Pallas packing
kernel (``kernels/topk_compact.py``), the kernel in interpret mode against
the jnp path it must equal bit for bit."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import compression as C  # noqa: E402
from repro.kernels.topk_compact import LANES, topk_compact  # noqa: E402

ROWS = 8                 # 1,024 entries a block: many blocks at small n
BLOCK = ROWS * LANES


def _kernel(flat, k, rows=ROWS):
    t, room = C.topk_threshold(flat, k)
    return topk_compact(flat, t.astype(jnp.int32), room, k=k, rows=rows,
                        interpret=True)


def _jnp(flat, k):
    return jax.jit(C.global_topk, static_argnums=1)(flat, k)


def _stable_topk(f, k):
    """Indices of the k largest |f|, ties to the first in flat order."""
    return np.sort(np.argsort(-np.abs(f), kind="stable")[:k])


def _case(name, rng):
    if name == "ragged_n":                    # n neither a block nor a lane multiple
        return rng.standard_normal(5 * BLOCK + 77).astype(np.float32), 700
    if name == "k1":
        return rng.standard_normal(3 * BLOCK).astype(np.float32), 1
    if name == "k_n":
        f = rng.standard_normal(2 * BLOCK + 5).astype(np.float32)
        return f, f.size
    if name == "all_zero":                    # t = 0: ties decide everything
        return np.zeros(4 * BLOCK + 3, np.float32), 1500
    if name == "ties_over_room":              # the k-th value repeats past k
        f = rng.choice(np.float32([0.5, -0.5, 0.25, 1.0, -1.0]),
                       6 * BLOCK, p=[.3, .3, .3, .05, .05])
        return f, 2000
    if name == "signed_zeros":
        f = rng.choice(np.float32([0.0, -0.0, 3.0, -2.0]), 4 * BLOCK + 9,
                       p=[.45, .45, .05, .05])
        return f, 900
    raise ValueError(name)


CASES = ["ragged_n", "k1", "k_n", "all_zero", "ties_over_room",
         "signed_zeros"]


@pytest.mark.parametrize("name", CASES)
def test_kernel_equals_jnp_path(name):
    f, k = _case(name, np.random.default_rng(CASES.index(name)))
    kv, ki = _kernel(jnp.asarray(f), k)
    jv, ji = _jnp(jnp.asarray(f), k)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ji))
    # bit for bit, so -0.0 stays -0.0
    np.testing.assert_array_equal(np.asarray(kv).view(np.int32),
                                  np.asarray(jv).view(np.int32))
    np.testing.assert_array_equal(np.asarray(ji), _stable_topk(f, k))


def test_kernel_output_starts_at_every_lane_phase():
    """Blocks keep 129 entries each (one more than a row), so their outputs
    start at all 128 offsets mod 128, then none, one, a full block and a
    few rows."""
    rng = np.random.default_rng(5)
    counts = np.array([129] * LANES + [0, 1, BLOCK, 300, 0, 257, 5])
    nblocks = counts.size
    f = rng.uniform(-0.9, 0.9, nblocks * BLOCK).astype(np.float32)
    for b, c in enumerate(counts):
        pos = b * BLOCK + rng.choice(BLOCK, c, replace=False)
        f[pos] = rng.uniform(1.0, 2.0, c).astype(np.float32)
    k = int(counts.sum())
    phases = set(np.concatenate([[0], np.cumsum(counts)[:-1]]) % LANES)
    assert phases == set(range(LANES))
    kv, ki = _kernel(jnp.asarray(f), k)
    idx = _stable_topk(f, k)
    np.testing.assert_array_equal(np.asarray(ki), idx)
    np.testing.assert_array_equal(np.asarray(kv), f[idx])


@pytest.mark.parametrize("bits_per_pass", [1, 2, 3])
@pytest.mark.parametrize("name", ["ragged_n", "all_zero", "ties_over_room"])
def test_threshold_at_any_bits_per_pass(monkeypatch, name, bits_per_pass):
    """t is the k-th largest magnitude's bits, room the ties it keeps,
    however many bits a read of the gradient decides."""
    monkeypatch.setattr(C, "BITS_PER_PASS", bits_per_pass)
    f, k = _case(name, np.random.default_rng(11))
    t, room = C.topk_threshold(jnp.asarray(f), k)
    mag = np.abs(f).view(np.uint32)
    kth = np.sort(mag)[::-1][k - 1]
    assert int(t) == int(kth)
    assert int(room) == k - int(np.sum(mag > kth))


def test_selection_equals_reference_topk_accumulate():
    """The set the program keeps is the plain reference's, the one the
    benchmark's ``correct`` check holds the DDP cell to."""
    from bench.reference import train as ref
    rng = np.random.default_rng(3)
    g = {"a": rng.standard_normal((37, 50)).astype(np.float32),
         "b": rng.choice(np.float32([0.5, -0.5, 0.125]), (900,))}
    flat = np.concatenate([g["a"].reshape(-1), g["b"]])
    k = 1000
    t = ref._threshold(g, k)
    acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), g)
    acc, _ = ref._topk_accumulate(acc, g, t, k, jnp.float32(1.0))
    want = np.nonzero(np.concatenate([np.asarray(acc["a"]).reshape(-1),
                                      np.asarray(acc["b"])]))[0]
    _, idx = _jnp(jnp.asarray(flat), k)
    np.testing.assert_array_equal(np.asarray(idx), want)
    _, kidx = _kernel(jnp.asarray(flat), k)
    np.testing.assert_array_equal(np.asarray(kidx), want)


@pytest.mark.parametrize("n,k", [(4096, 409), (10_000, 1), (3001, 3001)])
def test_tie_free_set_equals_lax_top_k(n, k):
    f = np.random.default_rng(n).permutation(n).astype(np.float32) - n / 2
    f = f + 0.25                              # no two magnitudes equal
    vals, idx = _jnp(jnp.asarray(f), k)
    _, want = jax.lax.top_k(jnp.abs(jnp.asarray(f)), k)
    want = np.sort(np.asarray(want))
    np.testing.assert_array_equal(np.asarray(idx), want)
    np.testing.assert_array_equal(np.asarray(vals), f[want])


def test_energy_gap_from_values_equals_densified():
    f = jnp.asarray(np.random.default_rng(2).standard_normal(20_000),
                    jnp.float32)
    k = 2_000
    vals, _ = _jnp(f, k)
    _, idx = jax.lax.top_k(jnp.abs(f), k)
    dense = jnp.zeros_like(f).at[idx].set(f[idx])
    np.testing.assert_allclose(float(C.energy_gap(f, vals)),
                               float(C.energy_gap(f, dense)), rtol=1e-5)


def test_path_counts_follow_the_platform(monkeypatch):
    f = jax.ShapeDtypeStruct((4096,), jnp.float32)
    before = C.path_counts()
    jax.eval_shape(lambda x: C.global_topk(x, 100), f)
    mid = C.path_counts()
    assert mid == {"kernel": before["kernel"], "jnp": before["jnp"] + 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    vals, idx = jax.eval_shape(lambda x: C.global_topk(x, 100), f)
    assert C.path_counts() == {"kernel": mid["kernel"] + 1,
                               "jnp": mid["jnp"]}
    assert (vals.shape, vals.dtype) == ((100,), jnp.float32)
    assert (idx.shape, idx.dtype) == ((100,), jnp.int32)


def test_sparsify_mask_keeps_the_top_k():
    f = np.random.default_rng(9).standard_normal(3000).astype(np.float32)
    out = np.asarray(C.sparsify_mask(jnp.asarray(f), 300))
    idx = _stable_topk(f, 300)
    assert np.count_nonzero(out) == 300
    np.testing.assert_array_equal(out[idx], f[idx])
