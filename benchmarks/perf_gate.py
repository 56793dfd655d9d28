"""CI perf-regression gate: fresh fast-tier metrics vs ``BENCH_scadles.json``.

Regenerates the repo's headline performance numbers in a few minutes on a
CPU host, diffs them against the committed baseline with per-metric
tolerance bands (``repro.obs.regress``), writes a machine-readable report,
and exits nonzero on any regression — the CI job that keeps the speed
claims in DESIGN.md honest.

Four collectors, chosen so the gate is *deterministic* wherever possible:

* **training/fleet** — one full-sync ``k80-uniform`` fleet run (the
  ``fleet_policies.py`` baseline cell) with a ``MemoryTracker`` attached:
  sim-seconds to the loss target, per-round MFU / step flops / wire bytes
  from the ``train_round`` ledger records.  All sim-time or model-constant
  numbers: bit-stable across runs on one toolchain.
* **noniid** — the ``noniid_sweep.py`` headline cell pair (semi-sync vs
  async on Dirichlet(0.05) label-skewed streams): the capped
  strict-advantage ratio and realised label divergence.  Pure deterministic
  sim over a seeded partition.
* **serving** — continuous vs static batching on a *synthetic*
  ``StepCostModel`` under the S2 near-overload stream: deadline-met
  goodput, SLO attainment, TTFT p95.  Pure discrete-event sim:
  deterministic.
* **prefill** — fused one-pass prefill vs the token-by-token loop on the
  reduced arch: the only wall-clock metric, gated with a wide band that
  catches catastrophic regressions (losing the fusion) without tripping on
  CI noise.

Usage::

    python -m benchmarks.perf_gate                  # gate against baseline
    python -m benchmarks.perf_gate --bless          # re-bless the baseline
    python -m benchmarks.perf_gate --profile        # + profiler traces
    python -m benchmarks.perf_gate --report out.json --baseline other.json

Exit status: 0 = every metric within band, 1 = regression or a baseline
metric the fresh run failed to produce.  ``--bless`` rewrites the baseline
from the fresh values (stamped with git SHA + seed) and exits 0; commit the
result when a change is intentionally faster/slower.
"""
import argparse
import sys
import time

import numpy as np

from repro.obs import (FLEET_ROUND, TRAIN_ROUND, MemoryTracker, MetricSpec,
                       capture, compare, load_baseline,
                       save_baseline, write_report)

GATE_SEED = 0
BASELINE_PATH = "BENCH_scadles.json"
REPORT_PATH = "artifacts/perf_gate/report.json"
PROFILE_DIR = "artifacts/profiles"

# per-metric band: how each number is allowed to move before the gate trips.
# direction says which way is *worse*; two-sided metrics are model constants
# (drift either way means the cost model or the lowering changed — re-bless
# deliberately, e.g. on a jax upgrade, rather than letting it slide).
TOLERANCES = {
    "fleet_t_target_s": dict(
        tol_frac=0.15, direction="lower",
        note="sim s to loss target, full-sync k80-uniform S1 (deterministic)"),
    "fleet_sim_time_s": dict(
        tol_frac=0.05, direction="two-sided",
        note="sim s for the whole run: the clock/comm model constant"),
    "train_step_flops": dict(
        tol_frac=0.10, direction="two-sided",
        note="HLO-counted flops of the jitted step; moves only when the "
             "lowering changes"),
    "train_mfu_mean": dict(
        tol_frac=0.25, direction="two-sided",
        note="mean per-round MFU (sim dt): flops drift tolerance"),
    "train_samples_per_s_mean": dict(
        tol_frac=0.10, direction="higher",
        note="committed samples per sim second"),
    "train_wire_bytes_round": dict(
        tol_frac=0.01, direction="two-sided",
        note="analytic ring-allreduce bytes per round: a formula, not a "
             "measurement"),
    "serve_cont_goodput_tok_s": dict(
        tol_frac=0.05, direction="higher",
        note="continuous batching deadline-met tok/s, synthetic cost model "
             "(deterministic)"),
    "serve_static_goodput_tok_s": dict(
        tol_frac=0.05, direction="two-sided",
        note="static baseline goodput: drift means the scheduler changed"),
    "serve_cont_slo_attainment": dict(
        tol_frac=0.05, direction="higher",
        note="fraction of requests meeting both SLO clauses"),
    "serve_cont_ttft_p95_s": dict(
        tol_frac=0.10, direction="lower",
        note="continuous batching TTFT p95 (sim s)"),
    "serve_sched_chunked_goodput_tok_s": dict(
        tol_frac=0.05, direction="higher",
        note="chunked-interleaved scheduler (chunk=64, decode_first) "
             "deadline-met tok/s on the S2 mixed-length trace "
             "(deterministic sim)"),
    "serve_sched_chunk_win_x": dict(
        tol_frac=0.03, direction="higher",
        note="chunked goodput / PR-5 whole-prompt goodput on the same "
             "trace: > 1 pins the chunked-interleaved win"),
    "serve_sched_ttft_win_x": dict(
        tol_frac=0.02, direction="higher",
        note="whole-prompt TTFT p95 / chunked TTFT p95: > 1 pins the "
             "short-prompt overtaking win"),
    "serve_sched_scaleup_x": dict(
        tol_frac=0.05, direction="higher",
        note="4-runner / 1-runner goodput on the bursty aggregate trace: "
             "multi-runner fan-out must keep scaling"),
    "serve_ctrl_goodput_tok_s": dict(
        tol_frac=0.05, direction="higher",
        note="ServeController closed-loop goodput on the bursty trace, "
             "starting from whole-prompt defaults (deterministic sim)"),
    "serve_ctrl_vs_static_frac": dict(
        tol_frac=0.05, direction="higher",
        note="controller goodput / best static (chunk, priority, replicas) "
             "grid point: near 1 means the climb finds the grid optimum "
             "unprompted, > 1 means it beats every static setting"),
    "serve_prefix_hit_rate": dict(
        tol_frac=0.05, direction="higher",
        note="prefix-index hit rate on the Zipf shared-prefix trace "
             "(deterministic sim): fraction of admissions that matched at "
             "least one full cached page"),
    "serve_shared_goodput_win_x": dict(
        tol_frac=0.05, direction="higher",
        note="sharing-on / sharing-off deadline-met goodput at equal "
             "num_pages on the Zipf trace: the prefix-sharing headline win"),
    "serve_pages_saved_frac": dict(
        tol_frac=0.05, direction="higher",
        note="fraction of requested KV pages served from shared prefixes "
             "instead of fresh allocations (admission accounting pin)"),
    "noniid_strict_advantage_x": dict(
        tol_frac=0.05, direction="higher",
        note="capped async/semi-sync time-to-global-eval-target ratio at "
             "Dirichlet alpha=0.05 on jetson-mixed: > 1 means strict sync "
             "converges faster under heavy label skew (deterministic sim; "
             "the noniid_sweep.py headline regime)"),
    "noniid_mean_divergence": dict(
        tol_frac=0.02, direction="two-sided",
        note="realised mean per-round label divergence of the skewed cell: "
             "a partitioner/divergence-metric determinism pin"),
    "prefill_speedup_x": dict(
        tol_frac=0.85, direction="higher",
        note="fused vs loop prefill, real wall-clock: wide band, catches "
             "losing the fusion, not CI noise"),
    "prefill_max_cache_err": dict(
        tol_frac=0.0, abs_tol=1e-3, direction="lower",
        note="fused and loop prefill must fill identical caches"),
    "kernel_decode_max_err": dict(
        tol_frac=0.0, abs_tol=1e-3, direction="lower",
        note="pallas flash-decode vs jnp decode_attention, worst case over "
             "contiguous mixed-age and paged block-table cells (interpret)"),
    "kernel_prefill_flash_max_err": dict(
        tol_frac=0.0, abs_tol=1e-3, direction="lower",
        note="pallas flash-attention prefill vs the chunked jax path, worst "
             "case over causal and SWA kinds with a q_offset chunk"),
}


# ---------------------------------------------------------------------------
# collectors


def collect_training(profile_dir=None):
    """Full-sync fleet baseline cell with a tracker attached."""
    from benchmarks.common import run_trainer
    from repro.core import TRUNCATION, ScaDLESConfig
    from repro.fleet import FleetConfig

    mt = MemoryTracker()
    cfg = ScaDLESConfig(
        n_devices=16, dist="S1", weighted=True, policy=TRUNCATION,
        b_max=128, base_lr=0.05, grad_floats=60.2e6, seed=GATE_SEED,
        fleet=FleetConfig(profile="k80-uniform"), tracker=mt)
    out = run_trainer(cfg, steps=40, loss_target=0.1)
    s = out["trainer"].summary()
    rounds = [r["data"] for r in mt.of_kind(TRAIN_ROUND)]
    mfus = [r["mfu"] for r in rounds if r.get("mfu")]
    flops = next((r["step_flops"] for r in rounds if r.get("step_flops")),
                 None)
    sps = [r["samples_per_s"] for r in rounds]
    assert mt.of_kind(FLEET_ROUND), "fleet engine emitted no round records"

    if profile_dir:
        # profiler window around the jitted train step: a short tracked
        # continuation run, traced
        with capture(f"{profile_dir}/train_step"):
            out["trainer"].run(2)
        print("# profile train_step: captured")

    return {
        "fleet_t_target_s": out["time_to_target"],
        "fleet_sim_time_s": s["sim_time_s"],
        "train_step_flops": flops,
        "train_mfu_mean": float(np.mean(mfus)) if mfus else None,
        "train_samples_per_s_mean": float(np.mean(sps)) if sps else None,
        "train_wire_bytes_round": next(
            (r["wire_bytes_round"] for r in rounds), None),
    }


def collect_noniid():
    """The non-IID headline cell pair (benchmarks/noniid_sweep.py):
    semi-sync k=8 vs async on Dirichlet(0.05) label-skewed streams,
    jetson-mixed, time to the *global test-loss* target.  Pure deterministic
    sim — at the crossover learning rate async's one-class commits plateau
    above the target while semi-sync converges, so the capped advantage
    ratio pins the regime the sweep demonstrates."""
    from benchmarks.common import run_noniid_trainer
    from benchmarks.noniid_sweep import (ADV_CAP, BASE_LR, DIST, EVAL_TARGET,
                                         N_DEVICES, PRESET)
    from repro.core import TRUNCATION, ScaDLESConfig
    from repro.fleet import FleetConfig

    def cell(policy, steps, eval_every, **over):
        fleet = FleetConfig(profile=PRESET, policy=policy, churn=True, **over)
        cfg = ScaDLESConfig(n_devices=N_DEVICES, dist=DIST, weighted=True,
                            policy=TRUNCATION, b_max=128, base_lr=BASE_LR,
                            grad_floats=60.2e6, seed=GATE_SEED, fleet=fleet,
                            skew_weighting=True)
        return run_noniid_trainer(cfg, steps, skew="dirichlet", alpha=0.05,
                                  eval_every=eval_every,
                                  eval_target=EVAL_TARGET)
    semi = cell("semi-sync", 100, 4, semi_sync_k=8)
    asyn = cell("async", 400, 32)
    t_semi = semi["time_to_eval_target"]
    t_async = asyn["time_to_eval_target"]
    adv = (ADV_CAP if not np.isfinite(t_async)
           else min(t_async / t_semi, ADV_CAP)) if np.isfinite(t_semi) \
        else 0.0
    return {
        "noniid_strict_advantage_x": adv,
        "noniid_mean_divergence": semi["mean_divergence"],
    }


def collect_serving():
    """Continuous vs static on a synthetic cost model: pure sim."""
    from repro.serve import (ContinuousBatchingServer, RequestStream,
                             StaticBatchingServer, StepCostModel)

    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4)
    reqs = RequestStream(dist="S2", n_clients=12, prompt_len=64,
                         max_new_tokens=16, slo_ttft_s=0.25,
                         slo_tpot_s=0.05, seed=GATE_SEED).generate(8.0)
    _, cont = ContinuousBatchingServer(4, cost).run(reqs)
    _, stat = StaticBatchingServer(4, cost).run(reqs)
    return {
        "serve_cont_goodput_tok_s": cont["goodput_tok_s"],
        "serve_static_goodput_tok_s": stat["goodput_tok_s"],
        "serve_cont_slo_attainment": cont["slo_attainment"],
        "serve_cont_ttft_p95_s": cont["ttft_p95_s"],
    }


def collect_serving_scale():
    """Chunked-interleaved vs whole-prompt, multi-runner scaling, and the
    controller closed loop (all pure sim on the synthetic cost model)."""
    from repro.serve import (BurstyRequestStream, ContinuousBatchingServer,
                             PRIORITIES, RequestStream, Scheduler,
                             ServeController, StepCostModel)

    cost = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                         prefill_base_s=2e-3)
    # S2 near-overload with mixed prompt lengths: the regime where chunked
    # prefill lets short prompts overtake long ones mid-prefill
    reqs = RequestStream(dist="S2", n_clients=12, prompt_lens=(16, 64, 256),
                         max_new_tokens=16, slo_ttft_s=0.25, slo_tpot_s=0.05,
                         seed=GATE_SEED).generate(8.0)
    _, whole = ContinuousBatchingServer(4, cost).run(reqs, horizon_s=8.0)
    _, chunked = Scheduler(4, cost, chunk_tokens=64,
                           priority="decode_first").run(reqs, horizon_s=8.0)
    assert chunked["conservation_ok"], "scheduler lost a request"

    # bursty aggregate trace: multi-runner scaling + the closed loop vs the
    # best static (chunk, priority, replicas) grid point
    breqs = BurstyRequestStream(base_rate=30.0, burst_mult=4.0,
                                prompt_lens=(16, 64, 256), max_new_tokens=16,
                                slo_ttft_s=0.25, slo_tpot_s=0.05,
                                seed=1).generate(8.0)
    grid = {}
    for c in (None, 32, 64, 128):
        for p in PRIORITIES:
            for n in (1, 2, 4):
                _, s = Scheduler(4, cost, n_runners=n, chunk_tokens=c,
                                 priority=p).run(breqs, horizon_s=8.0)
                grid[(c, p, n)] = s["goodput_tok_s"]
    best_static = max(grid.values())
    ctrl = ServeController()
    _, cs = Scheduler(4, cost, n_runners=4).run(
        breqs, horizon_s=8.0, controller=ctrl,
        control_every_s=1.0, window_s=1.0)
    assert cs["conservation_ok"], "controller run lost a request"

    # prefix-sharing cell: Zipf shared-template trace, sharing on vs off at
    # equal pool size (pure sim through PrefixSimRunner's refcounted pool)
    from benchmarks.serving_scale import run_shared_prefix_cell
    _, _, win = run_shared_prefix_cell()
    return {
        "serve_sched_chunked_goodput_tok_s": chunked["goodput_tok_s"],
        "serve_sched_chunk_win_x": (chunked["goodput_tok_s"]
                                    / whole["goodput_tok_s"]),
        "serve_sched_ttft_win_x": (whole["ttft_p95_s"]
                                   / chunked["ttft_p95_s"]),
        "serve_sched_scaleup_x": (grid[(32, "prefill_first", 4)]
                                  / grid[(32, "prefill_first", 1)]),
        "serve_ctrl_goodput_tok_s": cs["goodput_tok_s"],
        "serve_ctrl_vs_static_frac": cs["goodput_tok_s"] / best_static,
        "serve_prefix_hit_rate": win["prefix_hit_rate"],
        "serve_shared_goodput_win_x": win["shared_goodput_win_x"],
        "serve_pages_saved_frac": win["pages_saved_frac"],
    }


def collect_prefill(profile_dir=None, prompt_len=64, reps=3):
    """Fused vs loop prefill on the reduced arch (real wall-clock)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.decode import decode_step, init_cache, prefill_cache
    from repro.models.transformer import RunCtx, init_params

    cfg = get_config("qwen2-0.5b").reduced()
    ctx = RunCtx(remat=False, chunk_q=64, chunk_k=64)
    params = init_params(jax.random.PRNGKey(GATE_SEED), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, prompt_len), 0,
                              cfg.vocab_size)
    mk = lambda: init_cache(cfg, 1, prompt_len + 8, ctx)
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, ctx))
    fused = jax.jit(lambda p, c, t: prefill_cache(p, t, c, cfg, ctx))

    def run_loop():
        cache, lg = mk(), None
        for i in range(prompt_len):
            lg, cache = step(params, cache, toks[:, i:i + 1])
        return lg, cache

    def run_fused():
        return fused(params, mk(), toks)

    def best_of(fn):
        jax.block_until_ready(fn())             # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return min(ts), out

    t_loop, (lg_l, cache_l) = best_of(run_loop)
    t_fused, (lg_f, cache_f) = best_of(run_fused)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        cache_l, cache_f)
    max_err = max(max(jax.tree.leaves(errs)),
                  float(jnp.max(jnp.abs(lg_l - lg_f))))

    if profile_dir:
        # slot-decode capture window: the same jitted step the serving
        # schedulers drive, traced one step at a time
        jax.block_until_ready(step(params, mk(), toks[:, :1]))  # compile
        with capture(f"{profile_dir}/slot_decode"):
            jax.block_until_ready(step(params, mk(), toks[:, :1]))
        print("# profile slot_decode: captured")

    return {
        "prefill_speedup_x": t_loop / t_fused,
        "prefill_max_cache_err": max_err,
    }


def collect_kernels():
    """Pallas hot-path kernels vs their jnp oracles (interpret mode on CPU:
    deterministic correctness numbers, not wall-clock)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_decode import flash_decode, flash_decode_paged
    from repro.models.attention import chunked_attention, decode_attention

    key = jax.random.PRNGKey(GATE_SEED)
    ks = jax.random.split(key, 8)
    b, S, h, kvh, hd = 4, 32, 4, 2, 16
    q = jax.random.normal(ks[0], (b, 1, h, hd))
    k = jax.random.normal(ks[1], (b, S, kvh, hd))
    v = jax.random.normal(ks[2], (b, S, kvh, hd))
    kvl = jnp.array([1, 32, 13, 7], jnp.int32)
    ref = decode_attention(q, k, v, kvl)
    err_c = float(jnp.max(jnp.abs(
        flash_decode(q, k, v, kvl, bk=8, interpret=True) - ref)))
    pg, ncols = 8, 4
    bt = jax.random.permutation(ks[3], b * ncols).reshape(b, ncols)
    bt = bt.astype(jnp.int32)
    kp = jnp.zeros((b * ncols, pg, kvh, hd)).at[bt.reshape(-1)].set(
        k.reshape(b * ncols, pg, kvh, hd))
    vp = jnp.zeros((b * ncols, pg, kvh, hd)).at[bt.reshape(-1)].set(
        v.reshape(b * ncols, pg, kvh, hd))
    err_p = float(jnp.max(jnp.abs(
        flash_decode_paged(q, kp, vp, bt, kvl, interpret=True) - ref)))

    sq = 16
    qq = jax.random.normal(ks[4], (b, sq, h, hd))
    err_f = 0.0
    for kind, window, off in (("causal", 0, 0), ("swa", 8, 0),
                              ("causal", 0, 16)):
        ref_a = chunked_attention(qq, k, v, kind=kind, window=window,
                                  q_offset=off, chunk_q=8, chunk_k=8)
        out_a = chunked_attention(qq, k, v, kind=kind, window=window,
                                  q_offset=off, backend="pallas",
                                  interpret=True)
        err_f = max(err_f, float(jnp.max(jnp.abs(out_a - ref_a))))

    return {
        "kernel_decode_max_err": max(err_c, err_p),
        "kernel_prefill_flash_max_err": err_f,
    }


def collect(profile_dir=None):
    metrics = {}
    for name, fn in (("training", lambda: collect_training(profile_dir)),
                     ("noniid", collect_noniid),
                     ("serving", collect_serving),
                     ("serving_scale", collect_serving_scale),
                     ("prefill", lambda: collect_prefill(profile_dir)),
                     ("kernels", collect_kernels)):
        t0 = time.perf_counter()
        metrics.update(fn())
        print(f"# collected {name} in {time.perf_counter() - t0:.1f}s")
    return metrics


# ---------------------------------------------------------------------------
# gate


def bless(metrics, path):
    specs = {}
    for name, value in metrics.items():
        if value is None:
            raise SystemExit(f"cannot bless: metric {name!r} came back None")
        specs[name] = MetricSpec(value=float(value),
                                 **TOLERANCES.get(name, {}))
    save_baseline(path, specs, seed=GATE_SEED,
                  meta={"gate": "benchmarks.perf_gate"})
    print(f"# blessed {len(specs)} metrics -> {path}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", default=BASELINE_PATH,
                    help="blessed baseline to gate against")
    ap.add_argument("--report", default=REPORT_PATH,
                    help="machine-readable gate report (CI artifact)")
    ap.add_argument("--bless", action="store_true",
                    help="rewrite the baseline from fresh metrics and exit 0")
    ap.add_argument("--profile", action="store_true",
                    help="capture JAX profiler traces of the train step and "
                         f"slot decode under {PROFILE_DIR}/ (fails when "
                         "the profiler cannot start)")
    args = ap.parse_args(argv)

    metrics = collect(PROFILE_DIR if args.profile else None)
    if args.bless:
        bless(metrics, args.baseline)
        return 0

    _, specs = load_baseline(args.baseline)
    report = compare(specs, metrics)
    write_report(args.report, report, baseline_path=args.baseline,
                 meta={"gate": "benchmarks.perf_gate"})
    print(report.format_table())
    print(f"# report -> {args.report}")
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
