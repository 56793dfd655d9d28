"""repro.streamdata: partitioners, generators, sharded loader, trainer wiring.

Invariants under test (ISSUE / DESIGN.md §13):

* every sample is assigned to exactly one device, for every skew family;
* the divergence metric is 0 for the stratified IID split and maximal
  ((K-1)/K) for one-class shard devices; Dirichlet α→∞ recovers IID;
* ``SampleBuffer`` conservation: streamed == buffered + taken + dropped,
  under both drop-oldest (paper §IV) and drop-newest eviction;
* ``StreamSimulator`` arrival traces are deterministic given an explicit
  ``np.random.Generator``;
* the streamdata IID source is **bit-exact** with the legacy
  ``DeviceDataSource(iid=True)`` path through a full trainer run;
* skew flows end-to-end: trainer records, engine telemetry, controller bias.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.buffer import (DROP_NEWEST, DROP_OLDEST, PERSISTENCE,
                               SampleBuffer)
from repro.core.streams import TABLE_I, StreamSimulator
from repro.data import ClassClusterData, DeviceDataSource
from repro.streamdata import (DiurnalCurve, DriftSpec, Partition,
                              ShardedStreamLoader, StreamingDataSource,
                              contiguous_placement, label_coverage,
                              label_divergence, label_entropy,
                              make_label_shards, make_partition,
                              make_sharded_loader, make_stream_source,
                              max_divergence, round_robin_placement)


@pytest.fixture(scope="module")
def data():
    return ClassClusterData(num_classes=10, train_per_class=48,
                            test_per_class=8, noise=0.8, seed=0)


@pytest.fixture(scope="module")
def labels(data):
    return np.asarray(data.train_y)


# ---------------------------------------------------------------------------
# dataset label balance


def test_class_cluster_label_balance(labels):
    counts = np.bincount(labels, minlength=10)
    assert counts.shape == (10,)
    assert (counts == 48).all()          # exactly train_per_class per class


# ---------------------------------------------------------------------------
# partitioner invariants


@pytest.mark.parametrize("skew,kw", [
    ("iid", {}),
    ("dirichlet", {"alpha": 0.1}),
    ("dirichlet", {"alpha": 100.0}),
    ("shard", {"shards_per_device": 1}),
    ("shard", {"shards_per_device": 4}),
    ("quantity", {"alpha": 0.5}),
])
def test_every_sample_assigned_exactly_once(labels, skew, kw):
    p = make_partition(labels, 8, skew=skew, seed=3, **kw)
    allocated = np.concatenate(p.assignments)
    assert len(allocated) == len(labels)
    assert np.array_equal(np.sort(allocated), np.arange(len(labels)))
    assert all(len(a) >= 1 for a in p.assignments)   # no starved device


def test_iid_partition_divergence_exactly_zero(labels):
    # 48 per class / 4 devices divides evenly: the stratified deal makes
    # every device's mix *identical* to the global mix
    p = make_partition(labels, 4, skew="iid", seed=0)
    assert p.divergence().max() == 0.0
    assert np.allclose(p.entropy(), np.log2(10))


def test_dirichlet_alpha_inf_recovers_iid(labels):
    p = make_partition(labels, 4, skew="dirichlet", alpha=np.inf, seed=0)
    assert p.divergence().max() < 0.05   # exact uniform cuts, ±1 rounding


def test_dirichlet_alpha_orders_skew(labels):
    lo = make_partition(labels, 8, skew="dirichlet", alpha=0.05, seed=1)
    hi = make_partition(labels, 8, skew="dirichlet", alpha=100.0, seed=1)
    assert lo.divergence().mean() > hi.divergence().mean() + 0.1


def test_one_class_shards_hit_max_divergence(labels):
    # 10 devices x 1 shard over 10 balanced classes: one class per device
    p = make_partition(labels, 10, skew="shard", shards_per_device=1, seed=0)
    assert np.allclose(p.divergence(), max_divergence(10))
    assert np.allclose(p.entropy(), 0.0)  # one-class => zero label entropy


def test_quantity_skew_counts_unbalanced(labels):
    p = make_partition(labels, 8, skew="quantity", alpha=0.3, seed=2)
    c = p.counts()
    assert c.sum() == len(labels)
    assert c.max() > 2 * c.min()          # the point of quantity skew


def test_partition_determinism(labels):
    a = make_partition(labels, 8, skew="dirichlet", alpha=0.2, seed=7)
    b = make_partition(labels, 8, skew="dirichlet", alpha=0.2, seed=7)
    for x, y in zip(a.assignments, b.assignments):
        assert np.array_equal(x, y)


def test_metric_helpers():
    assert label_coverage(np.array([0.0]))[0] == 1.0
    assert label_coverage(np.array([1.0]), floor=0.05)[0] == 0.05
    one_hot = np.zeros((1, 10))
    one_hot[0, 3] = 1.0
    g = np.full(10, 0.1)
    assert label_divergence(one_hot, g)[0] == pytest.approx(0.9)
    assert label_entropy(one_hot)[0] == 0.0
    assert make_partition(np.zeros(8, np.int64), 2).kind == "iid"
    with pytest.raises(ValueError):
        make_partition(np.zeros(8, np.int64), 2, skew="nope")


# ---------------------------------------------------------------------------
# SampleBuffer eviction + conservation


def _conserved(b: SampleBuffer) -> bool:
    return b.total_streamed == len(b) + b.total_taken + b.total_dropped


def test_sample_buffer_drop_oldest():
    b = SampleBuffer(policy=PERSISTENCE, max_size=3, evict=DROP_OLDEST)
    b.extend([0, 1, 2, 3, 4])
    # paper §IV: stale frames are sacrificed — the head is evicted
    assert b.take(3) == [2, 3, 4]
    assert b.total_dropped == 2 and _conserved(b)


def test_sample_buffer_drop_newest():
    b = SampleBuffer(policy=PERSISTENCE, max_size=3, evict=DROP_NEWEST)
    b.extend([0, 1, 2, 3, 4])
    # arrivals refused once full — the oldest survive
    assert b.take(3) == [0, 1, 2]
    assert b.total_dropped == 2 and _conserved(b)


def test_sample_buffer_conservation_random_traffic():
    rng = np.random.default_rng(0)
    for evict in (DROP_OLDEST, DROP_NEWEST):
        b = SampleBuffer(max_size=16, evict=evict)
        for _ in range(200):
            b.extend(rng.integers(0, 1000, size=rng.integers(0, 9)).tolist())
            b.take(int(rng.integers(0, 12)))
        assert _conserved(b)
        assert len(b) <= 16


def test_sample_buffer_validation():
    with pytest.raises(ValueError):
        SampleBuffer(evict="sideways")
    with pytest.raises(ValueError):
        SampleBuffer(max_size=0)


# ---------------------------------------------------------------------------
# StreamSimulator: explicit rng + rate curves


def test_stream_simulator_explicit_rng_deterministic():
    mk = lambda: StreamSimulator(TABLE_I["S1"], 4,
                                 rng=np.random.default_rng(42))
    a, b = mk(), mk()
    ta = np.stack([a.rates_at(t) for t in range(10)])
    tb = np.stack([b.rates_at(t) for t in range(10)])
    assert np.array_equal(ta, tb)


def test_stream_simulator_rate_curve_applies_only_with_t_sim():
    curve = lambda t: np.full(4, 2.0)
    sim = StreamSimulator(TABLE_I["S1"], 4, seed=0, rate_curve=curve)
    ref = StreamSimulator(TABLE_I["S1"], 4, seed=0)
    assert np.array_equal(sim.rates_at(0), ref.rates_at(0))        # no t_sim
    assert np.allclose(sim.rates_at(1, t_sim=5.0),
                       2.0 * ref.rates_at(1))


def test_diurnal_curve_floor_and_phase():
    c = DiurnalCurve(day_s=100.0, amplitude=2.0, floor=0.1,
                     phase=np.array([0.0, 0.5]))
    v = c(75.0)                     # sin trough for phase 0
    assert v[0] == pytest.approx(0.1)      # clipped at the floor
    assert v[1] == pytest.approx(3.0)      # antiphase device is at its peak


# ---------------------------------------------------------------------------
# generators: drift + divergence over sim time


def test_drift_toward_uniform_decays_divergence(data):
    src = make_stream_source(data, 4, skew="shard", shards_per_device=1,
                             drift=DriftSpec("toward-uniform", t_scale=100.0),
                             seed=0)
    rng = np.random.default_rng(0)
    src.batches(rng, np.full(4, 8), 8, t_sim=0.0)
    early = src.label_divergence().mean()
    src.batches(rng, np.full(4, 8), 8, t_sim=100.0)
    late = src.label_divergence().mean()
    assert early > 0.5 and late < 1e-9      # fully faded into the global mix


def test_drift_rotate_conserves_total_skew(data):
    src = make_stream_source(data, 4, skew="shard", shards_per_device=1,
                             drift=DriftSpec("rotate", t_scale=100.0),
                             seed=0)
    rng = np.random.default_rng(0)
    src.batches(rng, np.full(4, 8), 8, t_sim=0.0)
    d0 = src.label_divergence()
    src.batches(rng, np.full(4, 8), 8, t_sim=100.0)
    d1 = src.label_divergence()
    assert d1.mean() == pytest.approx(d0.mean(), rel=0.2)   # skew migrates,
    assert d1.mean() > 0.5                                  # not vanishes


def test_noniid_source_draws_from_own_pool(data):
    part = make_partition(np.asarray(data.train_y), 10, skew="shard",
                          shards_per_device=1, seed=0)
    src = StreamingDataSource(data, 10, partition=part, augment=False)
    rng = np.random.default_rng(1)
    _, ys, masks = src.batches(rng, np.full(10, 16), 16)
    for dev in range(10):
        own = set(np.asarray(data.train_y)[part.assignments[dev]].tolist())
        got = set(ys[dev][masks[dev] > 0].tolist())
        assert got <= own                  # never samples outside its pool


# ---------------------------------------------------------------------------
# sharded loader


def test_loader_placement_controls_skew(data):
    rr = make_sharded_loader(data, 4, shards_per_device=4, skewed=False)
    sk = make_sharded_loader(data, 4, shards_per_device=4, skewed=True)
    assert sk.label_divergence().mean() > rr.label_divergence().mean() + 0.2


def test_loader_conservation_and_short_batches(data):
    ld = ShardedStreamLoader(data, 3, make_label_shards(data.train_y, 6),
                             placement=round_robin_placement,
                             max_buffer=40, evict=DROP_OLDEST, seed=0)
    rng = np.random.default_rng(0)
    for t in range(20):
        ld.on_arrivals(np.array([3.7, 60.0, 0.4]))   # overflow device 1
        _, _, masks = ld.batches(rng, np.full(3, 8), 8)
        assert masks[2].sum() <= 8                   # slow device runs short
    c = ld.conservation()
    assert c["balanced"]
    assert c["dropped"] > 0                          # device 1 overflowed
    # fractional arrivals accumulate: device 2 streamed ~0.4*20 samples
    assert ld.buffers[2].total_streamed == int(0.4 * 20)


def test_loader_rejects_bad_placement(data):
    shards = make_label_shards(data.train_y, 4)
    with pytest.raises(ValueError):
        ShardedStreamLoader(data, 2, shards, placement=lambda s, n: 99)


def test_contiguous_placement_covers_all_devices():
    place = contiguous_placement(8)
    owners = {place(s, 4) for s in range(8)}
    assert owners == {0, 1, 2, 3}


# ---------------------------------------------------------------------------
# trainer integration: IID bit-exactness + skew signal flow


def _make_model(d_in=32 * 32 * 3, hidden=16, classes=10):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (d_in, hidden)) * 0.02,
                "b1": jnp.zeros(hidden),
                "w2": jax.random.normal(k2, (hidden, classes)) * 0.02,
                "b2": jnp.zeros(classes)}

    def per_sample_loss(p, x, y):
        h = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return lse - gold

    return {"init": init, "per_sample_loss": per_sample_loss}


def test_streamdata_iid_bit_exact_with_legacy(data):
    from repro.core import ScaDLESConfig, ScaDLESTrainer
    model = _make_model()
    kw = dict(n_devices=4, dist="S1", b_max=32, seed=0)
    legacy = ScaDLESTrainer(model, DeviceDataSource(data, 4, iid=True),
                            ScaDLESConfig(**kw))
    stream = ScaDLESTrainer(model, make_stream_source(data, 4, skew="iid"),
                            ScaDLESConfig(**kw))
    h_l, h_s = legacy.run(5), stream.run(5)
    assert [r["loss"] for r in h_l] == [r["loss"] for r in h_s]
    for a, b in zip(jax.tree.leaves(legacy.params),
                    jax.tree.leaves(stream.params)):
        assert (np.asarray(a) == np.asarray(b)).all()      # bit-exact


def test_trainer_records_divergence_and_skew_weighting_runs(data):
    from repro.core import ScaDLESConfig, ScaDLESTrainer
    from repro.fleet import FleetConfig
    model = _make_model()
    src = make_stream_source(data, 4, skew="dirichlet", alpha=0.1, seed=0)
    tr = ScaDLESTrainer(model, src, ScaDLESConfig(
        n_devices=4, dist="S1", b_max=32, seed=0,
        fleet=FleetConfig(profile="jetson-mixed", policy="semi-sync",
                          semi_sync_k=2),
        skew_weighting=True, noniid_damping=1.0))
    hist = tr.run(6)
    assert all(np.isfinite(r["loss"]) for r in hist)
    assert hist[-1]["label_div_mean"] > 0.1
    assert hist[-1]["label_div_max"] >= hist[-1]["label_div_mean"]
    # skew reaches the engine's control-plane telemetry
    assert tr.fleet.telemetry_summary()["mean_label_divergence"] > 0.1


def test_semi_sync_beats_async_under_heavy_label_skew():
    """Dirichlet(0.05) label skew on a churning jetson-mixed fleet: semi-sync
    k=8 reaches the global test-loss target, while async's one-device commits
    reach it later or never (the heavy-skew regime of
    ``benchmarks/noniid_sweep.py``, on the same data, model and seed)."""
    from repro.core import TRUNCATION, ScaDLESConfig, ScaDLESTrainer
    from repro.fleet import FleetConfig
    data = ClassClusterData(num_classes=10, train_per_class=192,
                            test_per_class=32, noise=0.8, seed=0)
    model = _make_model(hidden=64)
    test_x, test_y = jnp.asarray(data.test_x), jnp.asarray(data.test_y)
    test_loss = jax.jit(
        lambda p: jnp.mean(model["per_sample_loss"](p, test_x, test_y)))
    target = 0.1                                 # global test loss

    def cell(policy, steps, eval_every, **over):
        cfg = ScaDLESConfig(
            n_devices=16, dist="S1", weighted=True, policy=TRUNCATION,
            b_max=128, base_lr=0.15, grad_floats=60.2e6, seed=0,
            fleet=FleetConfig(profile="jetson-mixed", policy=policy,
                              churn=True, **over),
            skew_weighting=True)
        src = make_stream_source(data, 16, skew="dirichlet", alpha=0.05,
                                 seed=0)
        hist = ScaDLESTrainer(model, src, cfg).run(
            steps, eval_every=eval_every,
            eval_fn=lambda p: {"eval_loss": float(test_loss(p))})
        t = next((h["sim_time_s"] for h in hist
                  if h.get("eval_loss", np.inf) < target), np.inf)
        div = float(np.mean([h.get("label_div_mean", 0.0) for h in hist]))
        return t, div

    # steps scale inversely with gradients per commit (8 vs 1), and so does
    # eval_every: both cells are evaluated every 32 committed gradients
    t_semi, div = cell("semi-sync", 100, 4, semi_sync_k=8)
    t_async, _ = cell("async", 400, 32)
    assert np.isfinite(t_semi)
    assert t_async > t_semi                      # advantage > 1; inf if never
    assert div > 0.5


def test_controller_skew_bias_flips_probe_direction():
    from repro.fleet.control import HillClimbController
    from repro.fleet.engine import RoundTelemetry

    def tel(div):
        return RoundTelemetry(
            round_index=0, policy="async", knobs={}, dt=1.0, commit_time=1.0,
            n_started=4, n_participants=4, n_carried=0, n_dropped=0,
            n_crashed=0, committed_samples=64.0, committed_wait=0.0,
            mean_staleness=0.0, max_staleness=0, label_divergence=div)

    iid = HillClimbController(8, skew_threshold=0.35)
    for _ in range(10):
        iid.update(tel(0.0), 1.0)
    assert not iid._skewed()

    skewed = HillClimbController(8, skew_threshold=0.35)
    for _ in range(10):
        skewed.update(tel(0.9), 1.0)
    assert skewed._skewed()
    # under skew the first probe proposes a *tighter* barrier (k: 1 -> 2)
    act = skewed._propose_probe()
    assert act is not None and skewed.cand_k > skewed.ref_k
