"""repro.obs: tracker ledgers, callback wiring, flops counting, and the
zero-perturbation invariant (a tracked run is bit-exact with an untracked
one and never adds jitted work)."""
import dataclasses
import json

import numpy as np
import pytest

from repro.obs import (FLEET_ROUND, NOOP, SERVE_EVENT, SERVE_SUMMARY,
                       TRAIN_ROUND, TRAIN_SUMMARY, CompositeTracker,
                       JsonTracker, MemoryTracker, NoopTracker,
                       RoundObserver, config_hash, ledger_metrics,
                       lowered_flops, read_ledger,
                       ring_wire_bytes_per_device)


# ---------------------------------------------------------------------------
# trackers


def test_json_tracker_ledger_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    cfg = {"lr": 0.05, "n_devices": 8}
    with JsonTracker(path, seed=7, config=cfg, meta={"entry": "test"}) as t:
        t.log_metrics({"loss": 1.5, "samples_per_s": 0.1}, step=0, kind=TRAIN_ROUND)
        t.log_metrics({"loss": np.float32(1.2), "samples_per_s": 0.2}, step=1,
                      kind=TRAIN_ROUND)
        t.log_summary({"final_loss": float("inf")}, kind=TRAIN_SUMMARY)

    recs = read_ledger(path)
    assert recs[0]["kind"] == "run_start"
    assert recs[0]["seed"] == 7
    assert recs[0]["schema_version"] >= 1
    assert recs[0]["entry"] == "test"
    assert len(recs[0]["git_sha"]) >= 7          # sha or "unknown"
    assert recs[0]["config_hash"] == config_hash(cfg)
    assert recs[-1]["kind"] == "run_end"
    assert ledger_metrics(recs, TRAIN_ROUND, "loss") == [1.5, pytest.approx(1.2)]
    # non-finite floats land as null, numpy scalars unwrap
    summ = read_ledger(path, kind=TRAIN_SUMMARY)
    assert summ[0]["data"]["final_loss"] is None
    # a finished ledger refuses further writes
    with pytest.raises(ValueError):
        t.log_metrics({"x": 1})


def test_composite_tracker_fans_out(tmp_path):
    a, b = MemoryTracker(), MemoryTracker()
    comp = CompositeTracker([a, NoopTracker(), b])
    assert comp.active
    comp.log_metrics({"v": 1}, step=3, kind="k")
    comp.log_summary({"s": 2})
    comp.finish()
    for t in (a, b):
        assert t.records[0] == {"kind": "k", "step": 3, "data": {"v": 1}}
        assert t.records[1]["data"] == {"s": 2}
        assert t.finished
    assert not CompositeTracker([NoopTracker()]).active


def test_noop_tracker_is_inert():
    assert not NOOP.active
    NOOP.log_metrics({"x": 1})          # no-op, no error
    NOOP.finish()


def test_config_hash_ignores_tracker_field():
    @dataclasses.dataclass
    class Cfg:
        lr: float = 0.1
        tracker: object = None

    assert config_hash(Cfg()) == config_hash(Cfg(tracker=MemoryTracker()))
    assert config_hash(Cfg(lr=0.2)) != config_hash(Cfg())


def test_write_artifact_stamps_run(tmp_path):
    path = str(tmp_path / "art.json")
    JsonTracker.write_artifact(path, {"x": float("nan"), "y": [1, 2]},
                               seed=3)
    doc = json.load(open(path))
    assert doc["x"] is None and doc["y"] == [1, 2]
    assert doc["run"]["seed"] == 3 and doc["run"]["schema_version"] >= 1


# ---------------------------------------------------------------------------
# flops / wire bytes


def test_lowered_flops_matches_hlo_cost_walker():
    import jax
    import jax.numpy as jnp

    from repro.dist.hlo_cost import analyze_hlo

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 32), jnp.float32)
    got = lowered_flops(f, a, b)
    want = analyze_hlo(f.lower(a, b).compile().as_text())["flops"]
    assert got == pytest.approx(want)
    assert got >= 2 * 64 * 128 * 32 * 0.9        # a matmul's worth of flops


def test_ring_wire_bytes_formula():
    # 2(N-1)/N * 4 bytes * floats — the EdgeClock charge
    assert ring_wire_bytes_per_device(8, 1e6) == \
        pytest.approx(2 * 7 / 8 * 4e6)
    assert ring_wire_bytes_per_device(1, 1e6) == 0.0


# ---------------------------------------------------------------------------
# producer wiring (trainer / fleet / serve)


@pytest.fixture(scope="module")
def tiny_setup():
    from repro.data import ClassClusterData, DeviceDataSource

    def make_model(d_in=32 * 32 * 3, hidden=32, classes=10):
        import jax
        import jax.numpy as jnp

        def init(key):
            k1, k2 = jax.random.split(key)
            return {"w1": jax.random.normal(k1, (d_in, hidden)) * 0.02,
                    "b1": jnp.zeros(hidden),
                    "w2": jax.random.normal(k2, (hidden, classes)) * 0.02,
                    "b2": jnp.zeros(classes)}

        def per_sample_loss(p, x, y):
            import jax.numpy as jnp
            h = jax.nn.relu(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return lse - gold

        return {"init": init, "per_sample_loss": per_sample_loss}

    data = ClassClusterData(num_classes=10, train_per_class=48,
                            test_per_class=8, noise=0.8, seed=0)
    src = DeviceDataSource(data, 8, iid=True)
    return make_model(), src


def _fleet_cfg(tracker=None):
    from repro.core import ScaDLESConfig
    from repro.fleet import FleetConfig
    return ScaDLESConfig(n_devices=8, dist="S1", weighted=True, b_max=64,
                         grad_floats=60.2e6, tracker=tracker,
                         fleet=FleetConfig(profile="k80-uniform"))


def test_tracked_fleet_run_emits_rounds_and_stays_bit_exact(tiny_setup):
    from repro.core import ScaDLESTrainer
    model, src = tiny_setup
    mt = MemoryTracker()
    tracked = ScaDLESTrainer(model, src, _fleet_cfg(tracker=mt))
    plain = ScaDLESTrainer(model, src, _fleet_cfg())
    tracked.run(5)
    plain.run(5)

    rounds = [r["data"] for r in mt.of_kind(TRAIN_ROUND)]
    assert len(rounds) == 5
    assert len(mt.of_kind(FLEET_ROUND)) == 5
    assert len(mt.of_kind(TRAIN_SUMMARY)) == 1
    r0 = rounds[0]
    assert r0["step_flops"] > 0
    assert {"dt_s", "step_flops", "samples_per_s", "wire_bytes_device",
            "wire_bytes_round"} <= r0.keys()
    assert "mfu" not in r0
    assert r0["wire_bytes_device"] == \
        pytest.approx(ring_wire_bytes_per_device(8, 60.2e6))
    assert r0["samples_per_s"] > 0
    fr0 = mt.of_kind(FLEET_ROUND)[0]["data"]
    assert fr0["policy"] == "full-sync" and fr0["n_participants"] == 8

    # zero-perturbation: bit-identical trajectories and params
    for h_t, h_p in zip(tracked.history, plain.history):
        assert h_t["loss"] == h_p["loss"]
    for k in tracked.params:
        assert np.array_equal(np.asarray(tracked.params[k]),
                              np.asarray(plain.params[k])), k
    # and an untracked run must never lower/compile for flops counting
    assert plain._obs._flops_cache == {}
    assert not plain._obs.active


def test_tracked_legacy_run_emits_rounds(tiny_setup):
    from repro.core import ScaDLESConfig, ScaDLESTrainer
    model, src = tiny_setup
    mt = MemoryTracker()
    tr = ScaDLESTrainer(model, src, ScaDLESConfig(
        n_devices=8, dist="S1", weighted=True, b_max=64,
        grad_floats=60.2e6, tracker=mt))
    tr.run(3)
    assert len(mt.of_kind(TRAIN_ROUND)) == 3
    assert all(r["data"]["step_flops"] > 0 and "mfu" not in r["data"]
               for r in mt.of_kind(TRAIN_ROUND))


def test_serve_tracker_events_and_zero_perturbation():
    from repro.serve import (ContinuousBatchingServer, RequestStream,
                             StaticBatchingServer, StepCostModel)
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4)
    reqs = RequestStream(dist="S2", n_clients=8, prompt_len=32,
                         max_new_tokens=8, slo_ttft_s=0.2, slo_tpot_s=0.05,
                         seed=0).generate(4.0)
    mt = MemoryTracker()
    recs_t, summ_t = ContinuousBatchingServer(4, cost, tracker=mt).run(reqs)
    recs_p, summ_p = ContinuousBatchingServer(4, cost).run(reqs)
    assert summ_t == summ_p                     # tracker changed nothing
    events = {e["data"]["event"] for e in mt.of_kind(SERVE_EVENT)}
    assert "admit" in events and "finish" in events
    admits = [e["data"] for e in mt.of_kind(SERVE_EVENT)
              if e["data"]["event"] == "admit"]
    assert len(admits) == sum(r.admit_s is not None for r in recs_t)
    assert len(mt.of_kind(SERVE_SUMMARY)) == 1
    assert "ttft_p95_s" in summ_t and "tpot_p95_s" in summ_t

    mt2 = MemoryTracker()
    StaticBatchingServer(4, cost, tracker=mt2).run(reqs)
    assert len(mt2.of_kind(SERVE_SUMMARY)) == 1


def test_round_observer_noop_never_assembles():
    obs = RoundObserver(NOOP, n_devices=8)
    assert not obs.active
    assert obs._flops_cache == {}
