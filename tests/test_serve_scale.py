"""Serving at scale: paged KV caches, chunked-interleaved prefill, the
multi-runner scheduler, and the hill-climb serving controller."""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.fleet.control import ClimbCore  # noqa: E402
from repro.models import RunCtx, init_params  # noqa: E402
from repro.models.decode import (ChunkedPrefill, PagePool, decode_step,  # noqa: E402
                                 init_cache, init_paged_cache,
                                 init_slot_cache, pages_needed,
                                 prefill_cache, slot_evict, slot_insert)
from repro.models.paging import PrefixIndex, page_keys  # noqa: E402
from repro.obs import SERVE_EVENT, MemoryTracker  # noqa: E402
from repro.serve import (BurstyRequestStream, ContinuousBatchingServer,  # noqa: E402
                         PRIORITIES, PrefixSimRunner, Request, RequestStream,
                         Scheduler, ServeController, SlotRunner,
                         StepCostModel, resolve_decode_backend)
from repro.serve.metrics import RollingWindow  # noqa: E402

CTX = RunCtx(remat=False, chunk_q=8, chunk_k=8, loss_chunk=8)

# one representative per cache family: dense KV, SWA ring, RG-LRU, xLSTM
FAMILIES = ["qwen2-0.5b", "mixtral-8x22b", "recurrentgemma-2b", "xlstm-125m"]

# the stress cost model of benchmarks/serving_scale.py (decode 10ms,
# 0.5ms/token prefill + 2ms dispatch base so chunk granularity has a real
# cost side)
COST = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                     prefill_base_s=2e-3)


def _cfg(arch):
    cfg = get_config(arch).reduced()
    if arch == "mixtral-8x22b":
        cfg = dataclasses.replace(cfg, window_size=8)  # exercise ring wrap
    return cfg


def _s2_requests(horizon=8.0):
    return RequestStream(dist="S2", n_clients=12, prompt_lens=(16, 64, 256),
                         max_new_tokens=16, slo_ttft_s=0.25, slo_tpot_s=0.05,
                         seed=0).generate(horizon)


# ---------------------------------------------------------------------------
# paged KV cache: bit-exactness against the fixed-slot layout


@pytest.mark.parametrize("arch", FAMILIES)
def test_paged_cache_bit_exact(arch):
    """Fixed-slot and paged caches at identical occupancy decode the same
    logits bit-for-bit, through inserts, decode steps, and a mid-flight
    evict whose pages get recycled."""
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    max_batch, cache_len, page = 4, 32, 8
    prompts, gen = [5, 11, 3], 6

    fixed = init_slot_cache(cfg, max_batch, cache_len, CTX)
    paged = init_paged_cache(cfg, max_batch, cache_len, CTX,
                             page_size=page, num_pages=32)
    pool = PagePool(32)
    page_lists = []
    for slot, plen in enumerate(prompts):
        toks = jax.random.randint(jax.random.PRNGKey(10 + slot), (1, plen),
                                  0, cfg.vocab_size)
        fresh = init_cache(cfg, 1, cache_len, CTX)
        _, src = prefill_cache(params, toks, fresh, cfg, CTX)
        fixed = slot_insert(fixed, slot, src)
        pages = pool.alloc(pages_needed(cfg, cache_len, page, plen + gen))
        page_lists.append(pages)
        paged = slot_insert(paged, slot, src, pages=pages)
    np.testing.assert_array_equal(np.asarray(fixed["pos"]),
                                  np.asarray(paged["pos"]))

    tok = jnp.array([[3], [7], [1], [0]], jnp.int32)
    step = jax.jit(lambda c, t: decode_step(params, c, t, cfg, CTX))
    for i in range(gen):
        lf, fixed = step(fixed, tok)
        lp, paged = step(paged, tok)
        np.testing.assert_array_equal(np.asarray(lf[:3]), np.asarray(lp[:3]))
        if i == 2:      # evict slot 1 mid-flight; survivors must stay exact
            fixed = slot_evict(fixed, 1)
            paged = slot_evict(paged, 1)
            pool.free(page_lists[1])


def test_page_pool_semantics():
    pool = PagePool(4)
    got = pool.alloc(3)
    assert len(got) == 3 and pool.available == 1
    assert pool.alloc(2) is None        # insufficient: no partial grant
    assert pool.available == 1
    pool.free(got)
    assert pool.available == 4
    with pytest.raises(ValueError):
        pool.free(got)                  # double free


def test_pages_needed_respects_swa_window():
    """A sliding-window layer caps its cache at the window, so a long
    request needs no more pages than the window covers."""
    dense = _cfg("qwen2-0.5b")          # full attention: needs the lot
    swa = _cfg("mixtral-8x22b")         # window_size=8 caps every layer
    assert pages_needed(dense, 32, 8, 32) == 32 // 8
    assert pages_needed(dense, 32, 8, 8) == 1   # short prompt, few pages
    assert pages_needed(swa, 32, 8, 32) < pages_needed(dense, 32, 8, 32)


# ---------------------------------------------------------------------------
# chunked prefill: equivalence with the fused one-pass prefill


@pytest.mark.parametrize("arch", FAMILIES)
def test_chunked_prefill_matches_whole(arch):
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    cache_len, plen = 32, 13
    toks = jax.random.randint(jax.random.PRNGKey(99), (1, plen), 0,
                              cfg.vocab_size)
    lg_whole, cache_whole = prefill_cache(
        params, toks, init_cache(cfg, 1, cache_len, CTX), cfg, CTX)
    cp = ChunkedPrefill(params, toks, init_cache(cfg, 1, cache_len, CTX),
                        cfg, CTX)
    while not cp.done:
        cp.step(4)                      # uneven final chunk (13 = 4+4+4+1)
    lg_chunk, cache_chunk = cp.finish()
    np.testing.assert_allclose(np.asarray(lg_whole), np.asarray(lg_chunk),
                               atol=4e-6, rtol=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(cache_whole),
            jax.tree_util.tree_leaves(cache_chunk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=4e-6,
                                   rtol=1e-5, err_msg=str(path))


def test_chunked_prefill_guards():
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(1), cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    cp = ChunkedPrefill(params, toks, init_cache(cfg, 1, 32, CTX), cfg, CTX)
    with pytest.raises(ValueError):
        cp.finish()                     # not done yet
    cp.step(8)
    assert cp.done and cp.remaining == 0


# ---------------------------------------------------------------------------
# real runner: paged generation identity + insufficient-pages shedding


def test_paged_runner_generation_identity():
    """The same trace through a fixed-slot and a paged SlotRunner (behind
    the scheduler, chunked prefill) yields identical token streams."""
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = RequestStream(dist="S1", n_clients=4, prompt_lens=(8, 24),
                         max_new_tokens=6, slo_ttft_s=2.0, slo_tpot_s=0.5,
                         seed=0).generate(3.0)
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                         prefill_base_s=1e-3)

    def run(**kw):
        runner = SlotRunner(params, cfg, CTX, 2, 48, **kw)
        _, s = Scheduler(2, cost, runners=[runner],
                         chunk_tokens=8).run(reqs, horizon_s=3.0)
        assert s["conservation_ok"]
        return runner.generated

    fixed = run()
    paged = run(page_size=16, num_pages=8)
    assert fixed.keys() == paged.keys() and len(fixed) > 0
    for rid in fixed:
        assert fixed[rid] == paged[rid], f"rid {rid} diverged"


def test_insufficient_pages_sheds_oversized_request():
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SlotRunner(params, cfg, CTX, 2, 32, page_size=8, num_pages=2)
    big = Request(rid=0, arrival_s=0.0, prompt_len=16, max_new_tokens=8,
                  deadline_s=10.0, slo_ttft_s=10.0)
    assert not runner.can_admit(big)
    recs, s = Scheduler(2, COST, runners=[runner]).run([big], horizon_s=1.0)
    assert s["conservation_ok"]
    assert recs[0].dropped == "insufficient_pages"


# ---------------------------------------------------------------------------
# scheduler: conservation, the chunked win, multi-runner fan-out


def test_scheduler_conservation_across_grid():
    reqs = _s2_requests()
    for chunk in (None, 16, 64):
        for prio in PRIORITIES:
            recs, s = Scheduler(4, COST, chunk_tokens=chunk,
                                priority=prio).run(reqs, horizon_s=8.0)
            assert s["conservation_ok"], (chunk, prio)
            done = sum(r.finish_s is not None for r in recs)
            dropped = sum(r.dropped is not None for r in recs)
            assert done + dropped == len(reqs)


def test_chunked_interleaved_beats_whole_prompt():
    """Near overload with mixed prompt lengths: chunked prefill must win on
    deadline-met goodput AND the TTFT tail (sim-clock figures: the claim is
    the ordering, not the values)."""
    reqs = _s2_requests()
    _, whole = ContinuousBatchingServer(4, COST).run(reqs, horizon_s=8.0)
    _, chunked = Scheduler(4, COST, chunk_tokens=64,
                           priority="decode_first").run(reqs, horizon_s=8.0)
    assert chunked["goodput_tok_s"] > whole["goodput_tok_s"]
    assert chunked["ttft_p95_s"] < whole["ttft_p95_s"]


def test_deadline_evicts_mid_prefill():
    """A prompt admitted with a feasible solo ETA but starved by a later
    arrival's round-robin share is evicted mid-prefill, not ground out."""
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=1e-3)
    a = Request(rid=0, arrival_s=0.0, prompt_len=200, max_new_tokens=4,
                deadline_s=0.3, slo_ttft_s=0.25)
    b = Request(rid=1, arrival_s=0.01, prompt_len=200, max_new_tokens=4,
                deadline_s=1.0, slo_ttft_s=0.6)
    recs, s = Scheduler(4, cost, chunk_tokens=16).run([a, b], horizon_s=2.0)
    assert s["conservation_ok"]
    assert recs[0].dropped == "slo_miss" and recs[0].first_token_s is None
    assert recs[1].finish_s is not None


def test_multi_runner_scaling():
    reqs = BurstyRequestStream(base_rate=30.0, burst_mult=4.0,
                               prompt_lens=(16, 64, 256), max_new_tokens=16,
                               slo_ttft_s=0.25, slo_tpot_s=0.05,
                               seed=1).generate(8.0)
    out = {}
    for n in (1, 4):
        _, s = Scheduler(4, COST, n_runners=n, chunk_tokens=32,
                         priority="prefill_first").run(reqs, horizon_s=8.0)
        assert s["conservation_ok"]
        out[n] = s["goodput_tok_s"]
    assert out[4] > 1.5 * out[1]


def test_shrinking_active_runners_requeues_work():
    """Deactivating lanes mid-run hands their queued requests back to the
    live lanes; nothing is lost."""
    reqs = _s2_requests(horizon=6.0)

    class Shrink:
        def tick(self, now, sched):
            if now >= 2.0 and sched.active_runners > 1:
                sched.set_active_runners(1)

    _, s = Scheduler(4, COST, n_runners=4, chunk_tokens=32).run(
        reqs, horizon_s=6.0, controller=Shrink(), control_every_s=1.0)
    assert s["conservation_ok"] and s["active_runners"] == 1


def test_queue_wait_percentiles_reported():
    _, s = Scheduler(4, COST, chunk_tokens=64).run(_s2_requests(),
                                                   horizon_s=8.0)
    assert 0.0 <= s["queue_wait_p50_s"] <= s["queue_wait_p95_s"]


def test_expired_in_queue_emits_drop_event():
    """Satellite fix: the continuous server's admission-expiry drop now
    lands in the ledger, so event counts reconcile with the summary."""
    mt = MemoryTracker()
    reqs = _s2_requests()
    recs, s = ContinuousBatchingServer(4, COST, tracker=mt).run(
        reqs, horizon_s=8.0)
    drops = [r["data"] for r in mt.of_kind(SERVE_EVENT)
             if r["data"]["event"] == "drop"]
    assert len(drops) == sum(r.dropped == "expired_in_queue" for r in recs)
    assert len(drops) > 0


# ---------------------------------------------------------------------------
# control: the reusable climb core + the serving controller


def test_climbcore_relax_tie_and_revert():
    core = ClimbCore(0, 10, 5, tol=0.05, probe_every=2, relax_dir=-1)
    assert core.observe(1.0) == (4, "probe")      # explores the relax end
    assert core.observe(1.0) == (5, "confirm")    # ambiguous: re-run the ref
    assert core.observe(1.0) == (4, "accept")     # tie rides to relaxed
    assert core.ref == 4 and core.step == 2
    # accept pre-charges the settle counter: one settle window re-anchors
    # the reference and immediately probes onward with the doubled step
    assert core.observe(1.0) == (2, "probe")
    assert core.observe(0.3) == (4, "confirm")
    assert core.observe(1.0) is None              # clear loss: revert in place
    assert core.ref == 4 and core.step == 1 and core.direction == 1


def test_climbcore_tighten_needs_proof():
    core = ClimbCore(0, 10, 0, tol=0.05, probe_every=2, relax_dir=-1)
    assert core.observe(1.0) == (1, "probe")      # at lo: must tighten
    assert core.observe(1.0) == (0, "confirm")    # tie while tightening
    assert core.observe(1.0) is None              # ...is a reject
    assert core.ref == 0


def test_serve_controller_tracks_best_static():
    reqs = BurstyRequestStream(base_rate=30.0, burst_mult=4.0,
                               prompt_lens=(16, 64, 256), max_new_tokens=16,
                               slo_ttft_s=0.25, slo_tpot_s=0.05,
                               seed=1).generate(8.0)
    best = 0.0
    for c in (None, 64):
        for p in PRIORITIES:
            for n in (1, 4):
                _, s = Scheduler(4, COST, n_runners=n, chunk_tokens=c,
                                 priority=p).run(reqs, horizon_s=8.0)
                best = max(best, s["goodput_tok_s"])
    ctrl = ServeController()
    _, cs = Scheduler(4, COST, n_runners=4).run(
        reqs, horizon_s=8.0, controller=ctrl,
        control_every_s=1.0, window_s=1.0)
    assert cs["conservation_ok"]
    assert cs["goodput_tok_s"] >= 0.95 * best
    assert len(ctrl.actions) > 0
    grid = set(ctrl.chunk_grid)
    for a in ctrl.actions:
        if a.axis == "chunk_tokens":
            assert a.value in grid
        elif a.axis == "priority":
            assert a.value in PRIORITIES
        else:
            assert 1 <= a.value <= 4


# ---------------------------------------------------------------------------
# metrics + streams


def test_rolling_window_goodput():
    w = RollingWindow(2.0)
    w.record(0.5, 10)
    w.record(1.0, 10)
    assert w.goodput(1.0) == pytest.approx(10.0)   # 20 tokens / 2 s
    assert w.goodput(3.4) == pytest.approx(0.0)    # both aged out
    w.record(4.0, 6)
    w.record(3.0, 4)                               # out of order: clamped
    assert w.n_events(4.0) == 2
    assert w.goodput(4.0) == pytest.approx(5.0)


def test_bursty_stream_shape():
    s = BurstyRequestStream(base_rate=10.0, burst_mult=5.0, burst_every_s=4.0,
                            burst_len_s=1.0, seed=3)
    assert s.rate_at(0.5) == 50.0 and s.rate_at(2.0) == 10.0
    reqs = s.generate(12.0)
    arr = [r.arrival_s for r in reqs]
    assert arr == sorted(arr) and len(reqs) > 0
    in_burst = sum(1 for t in arr if (t % 4.0) < 1.0)
    assert in_burst > len(arr) / 3      # bursts carry an outsized share
    for r in reqs[:5]:
        assert r.deadline_s > r.arrival_s + r.slo_ttft_s


def test_request_stream_mixed_lengths():
    reqs = RequestStream(dist="S2", n_clients=4, prompt_lens=(16, 256),
                         max_new_tokens=8, seed=0).generate(5.0)
    lens = {r.prompt_len for r in reqs}
    assert lens <= {16, 256} and len(lens) == 2
    again = RequestStream(dist="S2", n_clients=4, prompt_lens=(16, 256),
                          max_new_tokens=8, seed=0).generate(5.0)
    assert [r.prompt_len for r in reqs] == [r.prompt_len for r in again]


# ---------------------------------------------------------------------------
# prefix sharing: refcounted pool, CoW tails, prefix-aware admission


def _req(rid, prompt_len=16, max_new=8, template=None, prefix_len=0):
    return Request(rid=rid, arrival_s=0.0, prompt_len=prompt_len,
                   max_new_tokens=max_new, deadline_s=100.0, slo_ttft_s=100.0,
                   template=template, prefix_len=prefix_len)


def _shared_trace(horizon=3.0, seed=0):
    return RequestStream(dist="S1", n_clients=4, prompt_len=24,
                         max_new_tokens=6, slo_ttft_s=2.0, slo_tpot_s=0.5,
                         seed=seed, n_templates=2,
                         template_prefix_len=16).generate(horizon)


def test_page_pool_refcounts_shared_page():
    """A shared page survives its first free and recycles on the last; a
    third free is a double free."""
    pool = PagePool(4)
    pages = pool.alloc(2)
    pool.incref([pages[0]])                 # a second request maps page 0
    assert pool.refcount(pages[0]) == 2
    released = pool.free(pages)             # first mapper lets go of both
    assert released == [pages[1]]           # page 0 still shared
    assert pool.refcount(pages[0]) == 1 and pool.in_use() == 1
    assert pool.free([pages[0]]) == [pages[0]]
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    assert pool.conserved()


def test_page_pool_reservation_blocks_oversubscription():
    """Reservations draw down ``available`` so overlapping admissions can
    no longer both pass on the same free pages (the admit/alloc race)."""
    pool = PagePool(4)
    assert pool.reserve(3)
    assert not pool.reserve(2)              # only 1 unreserved page left
    assert pool.alloc(2) is None            # unreserved alloc sees 1 page
    with pytest.raises(ValueError, match="without reservation"):
        pool.alloc(4, reserved=True)
    got = pool.alloc(3, reserved=True)      # consumes the reservation
    assert len(got) == 3 and pool.reserved == 0
    pool.unreserve(0)
    assert pool.conserved()


def test_prefix_index_match_and_cow_tail():
    """Full pages match by hash chain; the partial tail matches by content
    until invalidated (the donor's first decode write)."""
    pool = PagePool(8)
    idx = PrefixIndex(4)
    toks = tuple(range(10))                 # 2 full pages + 2-token tail
    pages = pool.alloc(3)
    idx.insert(toks, pages, pool)
    assert [pool.refcount(p) for p in pages] == [2, 2, 1]   # tail: no ref
    m = idx.match(toks + (99, 98))          # same prefix, longer prompt
    assert m.pages == pages[:2] and m.tail_page == pages[2]
    assert m.tail_tokens == 2 and m.tokens == 10
    # diverging inside page 1 keeps only page 0
    m2 = idx.match((0, 1, 2, 3, 7, 7, 7, 7, 8, 9))
    assert m2.pages == pages[:1] and m2.tail_page is None
    # the limit clamp trims the tail first, then whole pages
    m3 = idx.match(toks, limit=9)
    assert m3.tokens == 9 and m3.tail_tokens == 1
    m4 = idx.match(toks, limit=6)
    assert m4.pages == pages[:1] and m4.tokens == 4
    idx.invalidate_tail(pages[2])
    m5 = idx.match(toks)
    assert m5.tail_page is None and m5.tokens == 8
    assert page_keys(toks, 4) == page_keys(toks + (99,), 4)


def test_prefix_index_reclaim_lru_leaf_first():
    """Under pool pressure the index releases cold leaves first, never a
    page a live request still maps."""
    pool = PagePool(8)
    idx = PrefixIndex(4)
    a = pool.alloc(2)
    idx.insert(tuple(range(8)), a, pool)
    b = pool.alloc(2)
    idx.insert(tuple(range(100, 108)), b, pool)
    pool.free(a), pool.free(b)              # donors gone: index-only pages
    idx.match(tuple(range(8)))              # chain A is warm
    assert idx.reclaimable(pool) == 4
    assert idx.reclaim(1, pool) == 1
    assert pool.refcount(b[1]) == 0         # cold leaf went first
    assert pool.refcount(a[1]) == 1
    # page a[0] pinned by a live mapper is never reclaimed
    pool.incref([a[0]])
    idx.reclaim(10, pool)
    assert pool.refcount(a[0]) == 2 and idx.n_pages == 1
    assert pool.conserved()


def test_admission_reserves_pages_regression():
    """Two overlapping admissions can no longer double-count the free
    list: the second ``can_admit`` sees the first one's reservation."""
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SlotRunner(params, cfg, CTX, 2, 32, page_size=8, num_pages=4)
    r1, r2 = _req(1, 16, 8), _req(2, 16, 8)     # 3 pages each, pool of 4
    assert runner.can_admit(r1)
    assert runner.pool.reserved == 3
    assert not runner.can_admit(r2)             # would have passed pre-fix
    job = runner.start_prefill(r1)
    while not job.done:
        job.step(8)
    runner.finish_prefill(0, r1, job)
    assert runner.pool.reserved == 0 and runner.pool.in_use() == 3
    assert not runner.can_admit(r2)
    runner.release(0)
    assert runner.can_admit(r2) and runner.pool.conserved()


def test_cancel_prefill_unwinds_shared_refs():
    """Evicting a job mid-prefill returns its reservation and drops its
    shared-page refs without freeing pages the index still holds."""
    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    runner = SlotRunner(params, cfg, CTX, 2, 48, page_size=8, num_pages=12,
                        prefix_sharing=True)
    assert runner.prefix_index is not None
    donor = _req(1, 24, 6, template=0, prefix_len=16)
    assert runner.can_admit(donor)
    job = runner.start_prefill(donor)
    while not job.done:
        job.step(8)
    runner.finish_prefill(0, donor, job)        # donates 2 full prefix pages
    held = sorted(runner.prefix_index.held_pages())
    base = [runner.pool.refcount(p) for p in held]
    consumer = _req(2, 24, 6, template=0, prefix_len=16)
    assert runner.can_admit(consumer)
    assert sum(runner.pool.refcount(p) for p in held) > sum(base)  # increfed
    job2 = runner.start_prefill(consumer)
    assert job2.done_tokens > 0                 # prefill skipped the match
    runner.cancel_prefill(job2)                 # mid-prefill eviction
    assert runner.pool.reserved == 0
    assert [runner.pool.refcount(p) for p in held] == base
    assert all(runner.pool.refcount(p) >= 1 for p in held)
    runner.release(0)
    assert runner.pool.conserved()
    assert sorted(runner.prefix_index.held_pages()) == held


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mixtral-8x22b"])
def test_prefix_sharing_generation_bit_exact(arch):
    """Sharing-on and sharing-off paged runners emit identical token
    streams on a Zipf template trace — through donation, CoW tail gathers,
    evict -> recycle -> re-admit.  The SWA-ring family must gate sharing
    off entirely (ring pages rewrap during decode) and still match."""
    cfg = _cfg(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = _shared_trace()
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                         prefill_base_s=1e-3)

    def run(sharing):
        runner = SlotRunner(params, cfg, CTX, 2, 48, page_size=8,
                            num_pages=12, prefix_sharing=sharing)
        _, s = Scheduler(2, cost, runners=[runner],
                         chunk_tokens=8).run(reqs, horizon_s=3.0)
        assert s["conservation_ok"] and runner.pool.conserved()
        return runner, s

    off_runner, _ = run(False)
    on_runner, s_on = run(True)
    assert off_runner.generated.keys() == on_runner.generated.keys()
    assert len(on_runner.generated) > 0
    for rid in off_runner.generated:
        assert off_runner.generated[rid] == on_runner.generated[rid], \
            f"rid {rid} diverged under prefix sharing"
    if arch == "qwen2-0.5b":                # dense: sharing active and used
        share = s_on["prefix_sharing"]
        assert share["hits"] > 0 and share["pages_saved"] > 0
        assert share["prefill_tokens_skipped"] > 0
    else:                                   # SWA ring: gated off, zero hits
        assert on_runner.prefix_index is None
        assert "prefix_sharing" not in s_on


def test_shared_prefix_sim_cell_wins():
    """The pure-sim Zipf cell: sharing-on admits and serves strictly more
    than sharing-off at equal ``num_pages``, with conserved pools."""
    reqs = RequestStream(dist="S2", n_clients=8, prompt_len=64,
                         max_new_tokens=8, slo_ttft_s=0.5, slo_tpot_s=0.05,
                         seed=0, n_templates=2,
                         template_prefix_len=48).generate(4.0)
    out = {}
    for mode in (False, True):
        runner = PrefixSimRunner(8, 80, 8, 24, prefix_sharing=mode)
        _, s = Scheduler(8, COST, runners=[runner],
                         chunk_tokens=16).run(reqs, horizon_s=4.0)
        assert s["conservation_ok"] and runner.pool.conserved()
        out[mode] = s
    assert out[True]["goodput_tok_s"] > out[False]["goodput_tok_s"]
    share = out[True]["prefix_sharing"]
    assert share["prefix_hit_rate"] > 0 and share["pages_saved_frac"] > 0
    assert "prefix_sharing" not in out[False]


def test_decode_backend_autoflip(monkeypatch):
    """Off-TPU (interpret autodetect) the serving path flips to pallas
    flash-decode; the env var and an explicit backend both override; and
    the flipped runner's tokens match a forced-jax runner bit-for-bit."""
    monkeypatch.delenv("REPRO_DECODE_BACKEND", raising=False)
    assert resolve_decode_backend(CTX) == "pallas"      # no TPU in CI
    explicit = dataclasses.replace(CTX, decode_backend="jax_paged")
    assert resolve_decode_backend(explicit) == "jax_paged"
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "jax")
    assert resolve_decode_backend(CTX) == "jax"

    cfg = _cfg("qwen2-0.5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    reqs = RequestStream(dist="S1", n_clients=3, prompt_lens=(8, 16),
                         max_new_tokens=6, slo_ttft_s=2.0, slo_tpot_s=0.5,
                         seed=0).generate(2.0)
    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                         prefill_base_s=1e-3)

    def run():
        runner = SlotRunner(params, cfg, CTX, 2, 32, page_size=8,
                            num_pages=8)
        _, s = Scheduler(2, cost, runners=[runner],
                         chunk_tokens=8).run(reqs, horizon_s=2.0)
        assert s["conservation_ok"]
        return runner.ctx.decode_backend, runner.generated

    backend_jax, gen_jax = run()
    monkeypatch.delenv("REPRO_DECODE_BACKEND")
    backend_pallas, gen_pallas = run()
    assert backend_jax == "jax" and backend_pallas == "pallas"
    assert gen_jax.keys() == gen_pallas.keys() and len(gen_jax) > 0
    for rid in gen_jax:
        assert gen_jax[rid] == gen_pallas[rid], f"rid {rid} diverged"
