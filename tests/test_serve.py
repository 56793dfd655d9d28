"""Serving runtime: sim core, slot-cache equivalence, fused prefill,
continuous-vs-static scheduling, and the real-model SlotRunner path."""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import RunCtx, init_params  # noqa: E402
from repro.models.decode import (decode_step, init_cache, init_slot_cache,  # noqa: E402
                                 prefill_cache, slot_evict, slot_insert)
from repro.serve import (ContinuousBatchingServer, Request, RequestStream,  # noqa: E402
                         SlotRunner, StaticBatchingServer, StepCostModel)
from repro.serve.metrics import summarize  # noqa: E402
from repro.sim import EventQueue, SimClock  # noqa: E402

CTX = RunCtx(remat=False, chunk_q=8, chunk_k=8, loss_chunk=8)

# one representative per cache family: dense KV, SWA ring, RG-LRU, xLSTM
FAMILIES = ["qwen2-0.5b", "mixtral-8x22b", "recurrentgemma-2b", "xlstm-125m"]


def _cfg(arch):
    cfg = get_config(arch).reduced()
    if arch == "mixtral-8x22b":
        cfg = dataclasses.replace(cfg, window_size=8)  # exercise ring wrap
    return cfg


# ---------------------------------------------------------------------------
# shared sim core


def test_fleet_events_rebased_on_sim_core():
    from repro.fleet import events as fev
    assert fev.EventQueue is EventQueue
    assert fev.Event.__module__ == "repro.sim.core"


def test_event_queue_fifo_tie_break():
    q = EventQueue()
    q.push(1.0, "a", 1)
    q.push(1.0, "b", 2)
    q.push(0.5, "c", 3)
    kinds = [e.kind for e in q.drain()]
    assert kinds == ["c", "a", "b"]


def test_event_actor_device_alias():
    q = EventQueue()
    e = q.push(0.0, "k", 7)
    assert e.actor == 7 and e.device == 7


def test_simclock_monotone():
    clk = SimClock()
    clk.advance_to(2.0)
    clk.advance_to(2.0 - 1e-12)  # float jitter tolerated
    assert clk.now == 2.0
    with pytest.raises(ValueError):
        clk.advance_to(1.0)
    with pytest.raises(ValueError):
        clk.advance_by(-1.0)


# ---------------------------------------------------------------------------
# slot-cache decode equivalence


@pytest.mark.parametrize("arch", FAMILIES)
def test_mixed_age_slot_decode_bit_exact(arch):
    """A request decoded inside a mixed-age continuous batch is bit-exact
    with the same request decoded with the rest of the batch empty: slots
    are perfectly isolated (every step op is row-independent)."""
    cfg = _cfg(arch)
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    CLEN = 32
    ks = jax.random.split(key, 4)
    prompts = [jax.random.randint(k, (1, n), 0, cfg.vocab_size)
               for k, n in zip(ks, (8, 5, 12))]
    pre = jax.jit(lambda p, c, t: prefill_cache(p, t, c, cfg, CTX))
    srcs = [pre(params, init_slot_cache(cfg, 1, CLEN, CTX), t)[1]
            for t in prompts]
    feed = jax.random.randint(ks[3], (5,), 0, cfg.vocab_size)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, CTX))
    # run A: the target request alone in slot 2 of a 4-slot cache
    ca = slot_insert(init_slot_cache(cfg, 4, CLEN, CTX), 2, srcs[1])
    # run B: same slot, but 0/1 occupied by other requests of other ages
    cb = slot_insert(slot_insert(ca, 0, srcs[0]), 1, srcs[2])
    for i in range(5):
        ta = jnp.stack([jnp.asarray(1), jnp.asarray(2), feed[i],
                        jnp.asarray(3)])[:, None]
        tb = jnp.stack([feed[(i + 1) % 5], feed[(i + 3) % 5], feed[i],
                        jnp.asarray(9)])[:, None]
        la, ca = step(params, ca, ta)
        lb, cb = step(params, cb, tb)
        np.testing.assert_array_equal(np.asarray(la[2]), np.asarray(lb[2]))


@pytest.mark.parametrize("arch", FAMILIES)
def test_slot_decode_matches_single_request(arch):
    """Slot-batched decode matches a true batch-1 decode of the same request
    to float tolerance (CPU gemms re-tile across batch shapes, so this is
    allclose, not bit-equal; bit-exactness at fixed shape is the test above)."""
    cfg = _cfg(arch)
    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    CLEN = 24
    k1, k2 = jax.random.split(key)
    prompt = jax.random.randint(k1, (1, 6), 0, cfg.vocab_size)
    feed = jax.random.randint(k2, (4,), 0, cfg.vocab_size)
    pre = jax.jit(lambda p, c, t: prefill_cache(p, t, c, cfg, CTX))
    _, src = pre(params, init_slot_cache(cfg, 1, CLEN, CTX), prompt)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, CTX))
    solo = src
    batched = slot_insert(init_slot_cache(cfg, 3, CLEN, CTX), 1, src)
    for i in range(4):
        ls, solo = step(params, solo, feed[i][None, None])
        lb, batched = step(params, batched,
                           jnp.stack([jnp.asarray(0), feed[i],
                                      jnp.asarray(5)])[:, None])
        assert float(jnp.max(jnp.abs(ls[0] - lb[1]))) < 2e-4


@pytest.mark.parametrize("arch", FAMILIES)
def test_fused_prefill_matches_token_loop(arch):
    """One-pass chunked prefill leaves the same cache (and last logits) as
    stepping the prompt token by token."""
    cfg = _cfg(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    s, b = 16, 2
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, CTX))
    cache = init_cache(cfg, b, s + 4, CTX)
    lg_ref = None
    for t in range(s):
        lg_ref, cache = step(params, cache, toks[:, t:t + 1])
    lg_f, cache_f = jax.jit(
        lambda p, c, t: prefill_cache(p, t, c, cfg, CTX))(
            params, init_cache(cfg, b, s + 4, CTX), toks)
    assert float(jnp.max(jnp.abs(lg_f - lg_ref))) < 2e-4
    errs = jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b_.astype(jnp.float32)))),
        cache, cache_f)
    assert max(jax.tree.leaves(errs)) < 2e-4


def test_fused_prefill_ring_wrap():
    """Prompt longer than the SWA window: the fused prefill leaves the same
    ring contents as the token loop (last W keys at their wrapped slots)."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b").reduced(),
                              window_size=8)
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg)
    s = 20  # > window: the ring wraps during prefill
    toks = jax.random.randint(key, (1, s), 0, cfg.vocab_size)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, CTX))
    cache = init_cache(cfg, 1, s + 4, CTX)
    for t in range(s):
        _, cache = step(params, cache, toks[:, t:t + 1])
    _, cache_f = prefill_cache(params, toks, init_cache(cfg, 1, s + 4, CTX),
                               cfg, CTX)
    errs = jax.tree.map(
        lambda a, b_: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b_.astype(jnp.float32)))),
        cache, cache_f)
    assert max(jax.tree.leaves(errs)) < 2e-4


def test_slot_insert_evict_bookkeeping():
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.ones((1, 5), jnp.int32)
    _, src = prefill_cache(params, prompt, init_slot_cache(cfg, 1, 16, CTX),
                           cfg, CTX)
    cache = init_slot_cache(cfg, 3, 16, CTX)
    cache = slot_insert(cache, 1, src)
    assert cache["pos"].tolist() == [0, 5, 0]
    k = cache["unit"]["p0"]["k"]
    assert float(jnp.abs(k[:, 1]).max()) > 0      # slot 1 populated
    assert float(jnp.abs(k[:, 0]).max()) == 0     # others untouched
    cache = slot_evict(cache, 1)
    assert cache["pos"].tolist() == [0, 0, 0]
    assert float(jnp.abs(cache["unit"]["p0"]["k"][:, 1]).max()) == 0


# ---------------------------------------------------------------------------
# schedulers (synthetic cost model: deterministic, model-free)

COST = StepCostModel(decode_step_s=0.01, prefill_token_s=0.001)


def _req(rid, t, deadline, prompt_len=10, gen=4, slo_ttft=1e9):
    return Request(rid=rid, arrival_s=t, prompt_len=prompt_len,
                   max_new_tokens=gen, deadline_s=deadline,
                   slo_ttft_s=slo_ttft)


def test_continuous_admits_on_free_slot():
    reqs = [_req(0, 0.0, 100.0), _req(1, 0.0, 100.0), _req(2, 0.0, 100.0)]
    recs, s = ContinuousBatchingServer(2, COST).run(reqs)
    by = {r.rid: r for r in recs}
    # 0 and 1 admitted immediately; 2 waits for the first free slot
    assert by[0].admit_s == 0.0 and by[1].admit_s == pytest.approx(0.01)
    assert by[2].admit_s > by[1].admit_s
    assert s["completed"] == 3 and s["dropped"] == 0
    assert all(r.tokens_out == 4 for r in recs)


def test_continuous_deadline_eviction_frees_slot():
    # request 0 can never finish by its deadline; 1 arrives later and can
    reqs = [_req(0, 0.0, 0.025, gen=50), _req(1, 0.05, 10.0)]
    recs, s = ContinuousBatchingServer(1, COST).run(reqs)
    by = {r.rid: r for r in recs}
    assert by[0].dropped == "slo_miss" and by[0].tokens_out < 50
    assert by[1].completed and by[1].met_deadline


def test_continuous_drops_expired_in_queue():
    # slot busy until t=0.51; request 1's TTFT budget expires at t=0.1
    reqs = [_req(0, 0.0, 100.0, gen=50), _req(1, 0.0, 100.0, slo_ttft=0.1)]
    recs, _ = ContinuousBatchingServer(1, COST).run(reqs)
    by = {r.rid: r for r in recs}
    assert by[0].completed
    assert by[1].dropped == "expired_in_queue" and by[1].tokens_out == 0


def test_static_waits_to_fill_and_blocks():
    reqs = [_req(0, 0.0, 100.0), _req(1, 1.0, 100.0)]
    recs, s = StaticBatchingServer(2, COST).run(reqs)
    by = {r.rid: r for r in recs}
    # request 0 sat in the queue until request 1 arrived (batch must fill)
    assert by[0].admit_s == pytest.approx(1.0)
    assert s["completed"] == 2 and s["dropped"] == 0


@pytest.mark.parametrize("stream_kw,horizon_s,cost", [
    (dict(dist="S1", n_clients=8, prompt_len=16, max_new_tokens=8,
          slo_ttft_s=0.15, seed=0), 5.0, COST),
    # S2 near overload: the static batcher's fill waits blow the TTFT SLO
    (dict(dist="S2", n_clients=12, prompt_len=64, max_new_tokens=16,
          slo_ttft_s=0.25, slo_tpot_s=0.05, seed=0), 8.0,
     StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4)),
], ids=["S1", "S2-near-overload"])
def test_continuous_beats_static_on_ttft_and_goodput(stream_kw, horizon_s,
                                                     cost):
    reqs = RequestStream(**stream_kw).generate(horizon_s=horizon_s)
    cr, _ = ContinuousBatchingServer(4, cost).run(reqs)
    sr, _ = StaticBatchingServer(4, cost).run(reqs)
    h = max(max((r.finish_s or r.arrival_s) for r in cr),
            max((r.finish_s or r.arrival_s) for r in sr))
    cs, ss = summarize(cr, h), summarize(sr, h)
    assert cs["ttft_p99_s"] < ss["ttft_p99_s"]
    assert cs["goodput_tok_s"] > ss["goodput_tok_s"]
    assert cs["slo_attainment"] > ss["slo_attainment"]


def test_request_stream_reproducible_and_deadlined():
    a = RequestStream(dist="S2", n_clients=4, seed=3).generate(2.0)
    b = RequestStream(dist="S2", n_clients=4, seed=3).generate(2.0)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(r.deadline_s > r.arrival_s for r in a)
    assert all(a[i].arrival_s <= a[i + 1].arrival_s
               for i in range(len(a) - 1))


# ---------------------------------------------------------------------------
# real-model end to end


def test_slot_runner_generation_isolated_from_cotenants():
    """Tokens a request generates inside the continuous batch are identical
    to replaying that request alone (same slot shape) — scheduler decisions
    don't leak into generation."""
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=0.001)
    mk_runner = lambda: SlotRunner(params, cfg, CTX, max_batch=2,
                                   cache_len=16, seed=0)
    reqs = [_req(0, 0.0, 100.0, prompt_len=6, gen=5),
            _req(1, 0.02, 100.0, prompt_len=6, gen=5),
            _req(2, 0.04, 100.0, prompt_len=6, gen=5)]
    runner = mk_runner()
    recs, s = ContinuousBatchingServer(2, cost, runner=runner).run(reqs)
    assert s["completed"] == 3
    assert all(len(runner.generated[r.rid]) == 5 for r in recs)
    # replay request 1 alone in the same-shape runner and the same slot
    # (the server admits rid 0 -> slot 0, rid 1 -> slot 1)
    solo = mk_runner()
    solo.admit(1, reqs[1])
    for _ in range(4):
        solo.step([1])
    assert solo.generated[1] == runner.generated[1]
