"""Planet-scale serving benchmark: chunked prefill, runner fan-out, control.

Five cells, one artifact (``artifacts/serve/serving_scale.json``):

1. **Chunked-interleaved vs whole-prompt** — on the S2 near-overload stream
   with *mixed* prompt lengths (16/64/256), sweep the scheduler's
   ``chunk_tokens`` x ``priority`` grid against the PR-5 whole-prompt
   server.  Chunking lets short prompts overtake a long prompt mid-prefill;
   the grid shows the interior optimum (too-small chunks repay the dispatch
   base too often, whole-prompt blocks the lane).
2. **Multi-runner fan-out** — the bursty aggregate trace (flash-crowd
   Poisson) across 1/2/4 runner lanes on one sim clock: deadline-met
   goodput must scale with replicas.
3. **Closed-loop control** — ``ServeController`` (the fleet hill-climb core
   re-pointed at serving knobs) starts from whole-prompt defaults and tunes
   ``chunk_tokens`` / ``priority`` / ``active_runners`` online against the
   rolling goodput window; compared against *every* static grid point.
4. **Real paged runner** — a small trace driven end-to-end through a real
   jitted ``SlotRunner`` with a paged KV cache and real ``ChunkedPrefill``
   jobs: the integration cell proving the sim scheduler and the model-level
   paging agree (conservation + all terminals real).
5. **Prefix sharing** — a Zipf shared-template trace (few hot system
   prompts) through ``PrefixSimRunner`` lanes at equal ``num_pages``,
   sharing on vs off: refcounted prefix pages + prefill-skip must buy
   deadline-met goodput and TTFT p95 where the page pool is the binding
   constraint (``prefix_hit_rate``, ``shared_goodput_win_x``,
   ``pages_saved_frac``).

Cells 1-3 and 5 run on the synthetic stress cost model (the constants
``tests/test_serve_scale.py`` uses) so the regime is the interesting one on
any host; their goodputs and latencies are sim-seconds.  The real-runner
cell also reports this host's measured base+token prefill fit.
"""
import argparse

from benchmarks.common import emit, write_json_artifact
from repro.serve import (BurstyRequestStream, ContinuousBatchingServer,
                         PRIORITIES, PrefixSimRunner, RequestStream,
                         Scheduler, ServeController, SlotRunner,
                         StepCostModel, measured_cost_model)

MAX_BATCH = 4
HORIZON = 8.0
CHUNKS = (None, 16, 32, 64, 128)
RUNNERS = (1, 2, 4)
# the stress regime: decode 10ms, prefill 0.5ms/token
# + 2ms dispatch base (the chunking tradeoff needs a real base cost)
COST = StepCostModel(decode_step_s=0.01, prefill_token_s=5e-4,
                     prefill_base_s=2e-3)


def _row(summary, **extra):
    keep = ("goodput_tok_s", "throughput_tok_s", "ttft_p95_s", "ttft_p99_s",
            "slo_attainment", "deadline_met", "dropped", "queue_wait_p50_s",
            "queue_wait_p95_s", "conservation_ok")
    return {**{k: summary[k] for k in keep if k in summary}, **extra}


def bench_chunk_grid():
    """S2 mixed-length near-overload: whole-prompt vs the chunk grid."""
    reqs = RequestStream(dist="S2", n_clients=12, prompt_lens=(16, 64, 256),
                         max_new_tokens=16, slo_ttft_s=0.25, slo_tpot_s=0.05,
                         seed=0).generate(HORIZON)
    _, whole = ContinuousBatchingServer(MAX_BATCH, COST).run(
        reqs, horizon_s=HORIZON)
    emit("serve_scale_whole_S2", HORIZON * 1e6,
         f"goodput={whole['goodput_tok_s']:.1f};"
         f"ttft_p95={whole['ttft_p95_s']:.3f}")
    rows = [_row(whole, mode="whole_prompt", chunk_tokens=None,
                 priority=None)]
    for c in CHUNKS:
        for p in PRIORITIES:
            _, s = Scheduler(MAX_BATCH, COST, chunk_tokens=c,
                             priority=p).run(reqs, horizon_s=HORIZON)
            emit(f"serve_scale_c{'whole' if c is None else c}_{p}_S2",
                 HORIZON * 1e6,
                 f"goodput={s['goodput_tok_s']:.1f};"
                 f"ttft_p95={s['ttft_p95_s']:.3f};"
                 f"cons={s['conservation_ok']}")
            rows.append(_row(s, mode="scheduler", chunk_tokens=c,
                             priority=p))
    best = max((r for r in rows if r["mode"] == "scheduler"),
               key=lambda r: r["goodput_tok_s"])
    flag = ("OK" if best["goodput_tok_s"] > whole["goodput_tok_s"]
            and best["ttft_p95_s"] < whole["ttft_p95_s"] else "REGRESSION")
    print(f"# chunked c={best['chunk_tokens']} {best['priority']}: "
          f"{best['goodput_tok_s']:.1f} tok/s / p95 {best['ttft_p95_s']:.3f} "
          f"vs whole {whole['goodput_tok_s']:.1f} / "
          f"{whole['ttft_p95_s']:.3f} -> {flag}")
    return {"n_requests": len(reqs), "rows": rows}


def bench_fanout_and_control():
    """Bursty trace: runner scaling grid + the controller closed loop."""
    reqs = BurstyRequestStream(base_rate=30.0, burst_mult=4.0,
                               prompt_lens=(16, 64, 256), max_new_tokens=16,
                               slo_ttft_s=0.25, slo_tpot_s=0.05,
                               seed=1).generate(HORIZON)
    rows, best = [], None
    for n in RUNNERS:
        for c in CHUNKS:
            for p in PRIORITIES:
                _, s = Scheduler(MAX_BATCH, COST, n_runners=n,
                                 chunk_tokens=c, priority=p).run(
                    reqs, horizon_s=HORIZON)
                r = _row(s, n_runners=n, chunk_tokens=c, priority=p)
                rows.append(r)
                if best is None or r["goodput_tok_s"] > best["goodput_tok_s"]:
                    best = r
        g = max(r["goodput_tok_s"] for r in rows if r["n_runners"] == n)
        emit(f"serve_scale_runners{n}_bursty", HORIZON * 1e6,
             f"best_goodput={g:.1f}")

    ctrl = ServeController()
    _, cs = Scheduler(MAX_BATCH, COST, n_runners=max(RUNNERS)).run(
        reqs, horizon_s=HORIZON, controller=ctrl,
        control_every_s=1.0, window_s=1.0)
    frac = cs["goodput_tok_s"] / best["goodput_tok_s"]
    emit("serve_scale_ctrl_bursty", HORIZON * 1e6,
         f"goodput={cs['goodput_tok_s']:.1f};vs_best_static={frac:.3f};"
         f"final_chunk={cs['chunk_tokens']};final_prio={cs['priority']};"
         f"final_runners={cs['active_runners']}")
    flag = "OK" if frac >= 0.95 else "REGRESSION"
    print(f"# controller {cs['goodput_tok_s']:.1f} tok/s vs best static "
          f"{best['goodput_tok_s']:.1f} (c={best['chunk_tokens']} "
          f"{best['priority']} n={best['n_runners']}): {frac:.3f}x -> {flag}")
    return {"n_requests": len(reqs), "grid": rows, "best_static": best,
            "controller": _row(cs, chunk_tokens=cs["chunk_tokens"],
                               priority=cs["priority"],
                               active_runners=cs["active_runners"],
                               vs_best_static=frac),
            "actions": [{"t": a.t, "axis": a.axis, "value": a.value,
                         "reason": a.reason} for a in ctrl.actions]}


def bench_real_paged_runner():
    """A real jitted SlotRunner with a paged cache behind the scheduler."""
    import jax

    from repro.configs import get_config
    from repro.models.transformer import RunCtx, init_params

    cfg = get_config("qwen2-0.5b").reduced()
    ctx = RunCtx(remat=False, chunk_q=64, chunk_k=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache_len, prompt_len = 64, 32
    cost = measured_cost_model(params, cfg, ctx, MAX_BATCH, cache_len,
                               prompt_len)
    runner = SlotRunner(params, cfg, ctx, MAX_BATCH, cache_len,
                        page_size=16, num_pages=4 * MAX_BATCH)
    reqs = RequestStream(dist="S1", n_clients=6, prompt_lens=(8, 32),
                         max_new_tokens=8, slo_ttft_s=2.0, slo_tpot_s=0.5,
                         seed=0).generate(4.0)
    _, s = Scheduler(MAX_BATCH, cost, runners=[runner], chunk_tokens=16,
                     priority="decode_first").run(reqs, horizon_s=4.0)
    emit("serve_scale_real_paged", HORIZON * 1e6,
         f"goodput={s['goodput_tok_s']:.1f};n_reqs={len(reqs)};"
         f"cons={s['conservation_ok']};"
         f"prefill_base_s={cost.prefill_base_s:.2e}")
    print(f"# real paged runner: {len(reqs)} requests, "
          f"goodput {s['goodput_tok_s']:.1f} tok/s, "
          f"conservation_ok={s['conservation_ok']}")
    return {"n_requests": len(reqs),
            "cost_model": {"decode_step_s": cost.decode_step_s,
                           "prefill_token_s": cost.prefill_token_s,
                           "prefill_base_s": cost.prefill_base_s},
            "summary": _row(s)}


def shared_prefix_trace(horizon=HORIZON):
    """The Zipf shared-template near-overload trace: long prompts whose
    first 192 tokens are one of 4 templates."""
    return RequestStream(dist="S2", n_clients=16, prompt_len=256,
                         max_new_tokens=16, slo_ttft_s=0.5, slo_tpot_s=0.05,
                         seed=0, n_templates=4, template_prefix_len=192,
                         template_zipf=1.2).generate(horizon)


def run_shared_prefix_cell(horizon=HORIZON):
    """Sharing on vs off at equal pool size; returns (rows, win metrics).

    Geometry: 256-token prompts + 16 generated = 17 pages of 16 at
    cache_len 288; the 192-token template prefix is 12 full shareable
    pages, so a hit admits on 5 new pages instead of 17.  The pool (64
    pages) binds: sharing-off fits 3 requests, sharing-on ~10 plus the
    resident template prefixes — admission capacity is the whole game.
    """
    cache_len, page, num_pages, mb = 288, 16, 64, 16
    reqs = shared_prefix_trace(horizon)
    rows = {}
    for mode in ("off", "on"):
        runner = PrefixSimRunner(mb, cache_len, page, num_pages,
                                 prefix_sharing=(mode == "on"))
        _, s = Scheduler(mb, COST, runners=[runner], chunk_tokens=32).run(
            reqs, horizon_s=horizon)
        rows[mode] = _row(s, mode=mode, completed=s["completed"],
                          prefix_sharing=s.get("prefix_sharing"))
    on, off = rows["on"], rows["off"]
    share = on["prefix_sharing"]
    win = {"shared_goodput_win_x": (on["goodput_tok_s"]
                                    / max(off["goodput_tok_s"], 1e-9)),
           "admitted_win_x": on["completed"] / max(off["completed"], 1),
           "prefix_hit_rate": share["prefix_hit_rate"],
           "pages_saved_frac": share["pages_saved_frac"],
           "prefill_tokens_skipped": share["prefill_tokens_skipped"]}
    return reqs, rows, win


def bench_shared_prefix():
    """Zipf shared-prefix trace: sharing on vs off at equal ``num_pages``."""
    reqs, rows, win = run_shared_prefix_cell()
    on, off = rows["on"], rows["off"]
    emit("serve_scale_shared_prefix", HORIZON * 1e6,
         f"goodput_on={on['goodput_tok_s']:.1f};"
         f"goodput_off={off['goodput_tok_s']:.1f};"
         f"win={win['shared_goodput_win_x']:.2f}x;"
         f"hit_rate={win['prefix_hit_rate']:.3f};"
         f"pages_saved={win['pages_saved_frac']:.3f};"
         f"cons={on['conservation_ok']}")
    flag = ("OK" if win["shared_goodput_win_x"] >= 1.2
            or win["admitted_win_x"] >= 1.3 else "REGRESSION")
    print(f"# prefix sharing: {on['goodput_tok_s']:.1f} vs "
          f"{off['goodput_tok_s']:.1f} tok/s "
          f"({win['shared_goodput_win_x']:.2f}x), ttft_p95 "
          f"{on['ttft_p95_s']:.3f} vs {off['ttft_p95_s']:.3f}, "
          f"hit_rate {win['prefix_hit_rate']:.3f} -> {flag}")
    return {"n_requests": len(reqs), "rows": list(rows.values()), **win}


def main():
    argparse.ArgumentParser(description=__doc__).parse_args()
    chunk = bench_chunk_grid()
    fanout = bench_fanout_and_control()
    real = bench_real_paged_runner()
    shared = bench_shared_prefix()
    write_json_artifact("artifacts/serve/serving_scale.json", {
        "max_batch": MAX_BATCH, "horizon_s": HORIZON,
        "cost_model": {"decode_step_s": COST.decode_step_s,
                       "prefill_token_s": COST.prefill_token_s,
                       "prefill_base_s": COST.prefill_base_s},
        "chunk_grid": chunk, "fanout": fanout, "real_runner": real,
        "shared_prefix": shared,
    })


if __name__ == "__main__":
    main()
