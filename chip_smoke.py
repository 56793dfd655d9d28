#!/usr/bin/env python3
"""Chip smoke test: the ScaDLES trainer, its two DDP programs, the paged
server and the Pallas kernels, each run once on a TPU at the full published
width of qwen2-0.5b (24 layers, d_model 896, 14/2 heads, vocab 151,936,
random weights from a seed).

    python chip_smoke.py             # one chip: device, train, ddp, serve,
                                     # kernels
    python chip_smoke.py --chips 4   # four chips: the rate-weighted DDP step,
                                     # dense and compressed, against a
                                     # reference computed on one device

Every phase prints one line, ``PHASE {...}``: its name, wall seconds,
``compile_s`` (XLA compile time, persistent-cache reads included), the checks
it made with their values, and ``peak_bytes_in_use`` per device (the
process's peak so far).  The last line, printed only when every phase
passed, is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU, or when a phase fails, the script exits nonzero and prints no
such line.  It runs in one process and starts no children.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import sys
import time
from pathlib import Path

ARCH = "qwen2-0.5b"
SEED = 0

# train phase: the ScaDLES launcher's own step (Adam, per-sample S1 rates)
TRAIN_ARGV = ["--arch", ARCH, "--steps", "5", "--batch", "8", "--seq", "512",
              "--scadles", "--dist", "S1", "--seed", str(SEED)]
# DDP programs: SGD-momentum, top-k ratio 0.1
DDP_BATCH, DDP_SEQ, DDP_STEPS = 4, 512, 4
DDP4_LOCAL_BATCH = 1
DDP_CR, DDP_LR = 0.1, 1e-2
# serving: paged SlotRunner behind the Scheduler
SERVE_MAX_BATCH, SERVE_REQUESTS, SERVE_NEW_TOKENS = 8, 8, 16
SERVE_PROMPT_LENS = (32, 64, 96, 128)
SERVE_PAGE = 16
PREFILL_LEN = 512
KERNEL_INTERPRET = False        # the kernels phase compiles every kernel

# Tolerances.  Loss: relative, one program against another at the same
# params and batch (the same forward pass in a different program).
LOSS_RTOL = 1e-3
# Kernels: max abs error against the jax path, both in f32 at "highest".
KERNEL_ATOL = 2e-3
# Four chips: relative L2 error of the parameter update against the
# one-device reference, both at "highest".  The compressed bound allows a
# few top-k picks to flip where two gradient magnitudes tie.
DDP4_DENSE_RTOL = 1e-4
DDP4_COMP_RTOL = 1e-3
# Served and offline greedy tokens may part only at a near-tie.
SERVE_TIE_GAP = 1e-3


class NoChip(RuntimeError):
    pass


def model_config():
    from repro.configs import get_config
    return get_config(ARCH)


# ---------------------------------------------------------------------------
# reporting


class CompileMeter:
    """Sums XLA compile seconds and counts persistent-cache hits, from
    JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compile_s, self.hits = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.compile_s, self.hits


def peak_bytes(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def check(checks, name, value, ok):
    checks[name] = {"value": value, "ok": bool(ok)}


def finite(xs):
    return all(math.isfinite(x) for x in xs)


def rel_close(a, b, rtol):
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-30)


def run_phase(name, fn, meter, devices, *args):
    """Run one phase; print its line; raise if it failed."""
    checks = {}
    c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    rec = {"phase": name}
    err = None
    try:
        # the entry points' own progress lines go to stderr: stdout holds
        # one line per phase and the result line
        with contextlib.redirect_stdout(sys.stderr):
            rec.update(fn(checks, *args) or {})
    except Exception as e:            # reported below, then re-raised
        err = e
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
    gc.collect()
    c1, h1 = meter.snapshot()
    rec["wall_s"] = time.perf_counter() - t0
    rec["compile_s"] = c1 - c0
    rec["cache_hits"] = h1 - h0
    rec["checks"] = checks
    rec["peak_bytes_in_use"] = peak_bytes(devices)
    failed = [k for k, v in checks.items() if not v["ok"]]
    rec["ok"] = err is None and not failed
    print("PHASE " + json.dumps(rec, default=float), flush=True)
    if err is not None:
        raise err
    if failed:
        raise RuntimeError(f"phase {name}: failed checks {failed}")


# ---------------------------------------------------------------------------
# phases


def phase_device(checks, chips, cache_dir):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found platform {d.platform!r} "
                     f"({d.device_kind}), not a TPU")
    check(checks, "platform", d.platform, True)
    check(checks, "device_count", len(devs), len(devs) >= chips)
    return {"device_kind": d.device_kind, "compile_cache_dir": cache_dir}


def phase_train(checks):
    """The single-program ScaDLES step, through ``repro.launch.train.run``."""
    from repro.launch import train
    out = train.run(train.parse_args(TRAIN_ARGV))
    hist = out.pop("history")
    losses = [h["loss"] for h in hist]
    gnorms = [h["grad_norm"] for h in hist]
    check(checks, "loss_finite", losses, finite(losses))
    check(checks, "grad_norm_finite", gnorms, finite(gnorms))
    check(checks, "params_changed", [out["param_norm0"], out["param_norm"]],
          out["param_norm"] != out["param_norm0"])
    return {"steps": len(hist), "step_compile_s": out["compile_s"],
            "run_s": out["run_s"]}


def _ddp_setup(n_dev, batch_size, seq):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import TABLE_I, StreamSimulator
    from repro.data import TokenData
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import train_ctx
    from repro.models.transformer import init_params
    from repro.optim.optimizers import sgdm_update
    from repro.train.ddp import make_ddp_steps

    cfg = model_config()
    ctx = train_ctx(seq)
    mesh = make_test_mesh((n_dev,), ("data",))
    init = jax.jit(lambda k: init_params(k, cfg))
    key = jax.random.PRNGKey(SEED)
    template = jax.eval_shape(init, key)
    opt_update = (lambda g, s, p, lr:
                  sgdm_update(g, s, p, lr=lr, momentum=0.9))
    dense, comp, k, n = make_ddp_steps(cfg, ctx, mesh, opt_update,
                                       lambda t: DDP_LR, cr=DDP_CR,
                                       param_template=template)
    data = TokenData(vocab_size=cfg.vocab_size, seq_len=seq, seed=SEED)
    rng = np.random.default_rng(SEED)

    def batch_at():
        toks, labels = data.sample(rng, batch_size)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

    rates = StreamSimulator(TABLE_I["S1"], n_dev, seed=SEED).rates_at(0)
    return dict(cfg=cfg, ctx=ctx, mesh=mesh, init=lambda: init(key),
                dense=jax.jit(dense, donate_argnums=(0, 1)),
                comp=jax.jit(comp, donate_argnums=(0, 1)), k=k, n=n,
                batch_at=batch_at, rates=np.asarray(rates, np.float32))


def phase_ddp(checks):
    """Both DDP programs on a one-chip mesh, chosen per step by the paper's
    adaptive-compression controller."""
    import jax
    import jax.numpy as jnp

    from repro.core.compression import AdaptiveCompressor
    from repro.optim.optimizers import sgdm_init
    from repro.train.step import make_eval_step

    s = _ddp_setup(1, DDP_BATCH, DDP_SEQ)
    batch = s["batch_at"]()
    rates = jnp.asarray(s["rates"])
    step0 = jnp.asarray(0)
    params = s["init"]()
    opt = sgdm_init(params)
    dense = s["dense"].lower(params, opt, batch, rates, step0).compile()
    comp = s["comp"].lower(params, opt, batch, rates, step0).compile()
    # peak_bytes_in_use counts live arrays only; a program's own need is
    # arguments + outputs + temporaries - donated (aliased) bytes
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    for name, c in (("dense", dense), ("compressed", comp)):
        m = c.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        check(checks, f"{name}_program_fits",
              {"program_bytes": total, "temps": m.temp_size_in_bytes,
               "aliased": m.alias_size_in_bytes, "bytes_limit": limit},
              limit is None or total <= limit)

    # the train step's own loss function, uniform weights, same params/batch
    b = batch["tokens"].shape[0]
    ref_loss = float(jax.jit(make_eval_step(s["cfg"], s["ctx"]))(
        params, dict(batch, sample_weights=jnp.full((b,), 1.0 / b)))["loss"])
    t0 = time.perf_counter()
    params, opt, m = dense(params, opt, batch, rates, step0)
    dense_loss = float(m["loss"])
    step_s = {"dense": [time.perf_counter() - t0], "compressed": []}
    del params, opt, m
    params = s["init"]()
    opt = sgdm_init(params)
    t0 = time.perf_counter()
    params, opt, m = comp(params, opt, batch, rates, step0)
    comp_loss, gap = float(m["loss"]), float(m["gap"])
    step_s["compressed"].append(time.perf_counter() - t0)
    check(checks, "dense_loss_vs_train_loss", [dense_loss, ref_loss],
          rel_close(dense_loss, ref_loss, LOSS_RTOL))
    check(checks, "compressed_loss_vs_dense_loss", [comp_loss, dense_loss],
          rel_close(comp_loss, dense_loss, LOSS_RTOL))

    # the controller picks dense or compressed from the EWMA energy gap
    ctrl = AdaptiveCompressor(cr=DDP_CR)
    ctrl.account(True, s["n"])
    gaps, losses, picks = [gap], [comp_loss], ["compressed"]
    for step in range(1, DDP_STEPS):
        use = ctrl.decide(gaps[-1])
        pick = "compressed" if use else "dense"
        batch = s["batch_at"]()
        t0 = time.perf_counter()
        params, opt, m = (comp if use else dense)(
            params, opt, batch, rates, jnp.asarray(step))
        losses.append(float(m["loss"]))
        step_s[pick].append(time.perf_counter() - t0)
        ctrl.account(use, s["n"])
        picks.append(pick)
        if use:
            gaps.append(float(m["gap"]))
    check(checks, "losses_finite", losses, finite(losses))
    check(checks, "energy_gap_in_0_1", gaps,
          all(0.0 <= g <= 1.0 for g in gaps))
    return {"k": s["k"], "n_floats": s["n"], "picks": picks,
            "cnc_ratio": ctrl.cnc_ratio, "step_s": step_s}


def _flat_grad_fn(cfg, ctx):
    import jax

    from repro.core import compression as comp_lib
    from repro.train.step import make_loss_fn
    loss_fn = make_loss_fn(cfg, ctx)

    @jax.jit
    def flat_grad(params, batch):
        grads = jax.grad(lambda p: loss_fn(p, batch)[0])(params)
        return comp_lib.flatten_grads(grads)[0]
    return flat_grad


def _rel_l2(new_host, new_ref, old_ref):
    """||new - new_ref|| / ||new_ref - old_ref|| over a list of leaves."""
    import numpy as np
    num = den = 0.0
    for a, b, o in zip(new_host, new_ref, old_ref):
        b, o = np.asarray(b), np.asarray(o)
        num += float(np.sum(np.square((a - b).astype(np.float64))))
        den += float(np.sum(np.square((b - o).astype(np.float64))))
    return math.sqrt(num) / max(math.sqrt(den), 1e-30)


def phase_ddp4(checks):
    """One dense and one compressed DDP step over a four-chip data mesh with
    unequal S1 rates, against the same aggregation done on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import compression as comp_lib
    from repro.optim.optimizers import sgdm_init, sgdm_update

    n_dev = 4
    s = _ddp_setup(n_dev, n_dev * DDP4_LOCAL_BATCH, DDP_SEQ)
    mesh = s["mesh"]
    rates = s["rates"]
    check(checks, "rates_unequal", rates.tolist(), len(set(rates)) > 1)
    batch = s["batch_at"]()
    rep = NamedSharding(mesh, P())
    batch_d = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    rates_d = jax.device_put(jnp.asarray(rates), NamedSharding(mesh,
                                                               P("data")))
    devs = {sh.device for sh in batch_d["tokens"].addressable_shards}
    check(checks, "batch_devices", sorted(d.id for d in devs),
          len(devs) == n_dev)
    step0 = jnp.asarray(0)
    out, step_s = {}, {}
    with jax.default_matmul_precision("highest"):
        for name in ("dense", "comp"):
            params = jax.device_put(s["init"](), rep)
            opt = jax.device_put(sgdm_init(params), rep)
            fn = s[name].lower(params, opt, batch_d, rates_d,
                               step0).compile()
            t0 = time.perf_counter()
            params, opt, m = fn(params, opt, batch_d, rates_d, step0)
            loss = float(m["loss"])
            step_s[name] = time.perf_counter() - t0
            out[name] = ([np.asarray(x) for x in jax.tree.leaves(params)],
                         loss, float(m["gap"]))
            del params, opt, m, fn
            gc.collect()

        # reference on one device: per-shard grads weighted r_i / sum(r),
        # summed (or top-k'd and scatter-added), then the same SGD-momentum
        # update; accumulators are donated so device 0 holds one at a time
        flat_grad = _flat_grad_fn(s["cfg"], s["ctx"])
        topk = jax.jit(comp_lib.global_topk, static_argnums=1)
        add = jax.jit(lambda acc, g, w: acc + w * g, donate_argnums=0)
        scatter_add = jax.jit(lambda acc, v, i, w: acc.at[i].add(v * w),
                              donate_argnums=0)

        @jax.jit
        def update(p, g):
            unflatten = comp_lib.flatten_grads(p)[1]
            return sgdm_update(unflatten(g), sgdm_init(p), p, lr=DDP_LR,
                               momentum=0.9)[0]

        params0 = s["init"]()
        shards = [{k_: v[i * DDP4_LOCAL_BATCH:(i + 1) * DDP4_LOCAL_BATCH]
                   for k_, v in batch.items()} for i in range(n_dev)]
        w = rates.astype(np.float64) / float(np.sum(rates.astype(np.float64)))
        for name in ("dense", "comp"):
            acc = jnp.zeros((s["n"],), jnp.float32)
            for i, sh in enumerate(shards):
                g = flat_grad(params0, sh)
                wi = np.float32(w[i])
                if name == "dense":
                    acc = add(acc, g, wi)
                else:
                    vals, idx = topk(g, s["k"])
                    acc = scatter_add(acc, vals, idx, wi)
                    del vals, idx
                del g
            new = update(params0, acc)
            del acc
            err = _rel_l2(out[name][0], jax.tree.leaves(new),
                          jax.tree.leaves(params0))
            del new
            gc.collect()
            tol = DDP4_DENSE_RTOL if name == "dense" else DDP4_COMP_RTOL
            check(checks, f"{name}_update_rel_l2_vs_reference", err,
                  err <= tol)
    losses = [out["dense"][1], out["comp"][1]]
    check(checks, "losses_finite", losses, finite(losses))
    check(checks, "compressed_loss_vs_dense_loss", losses,
          rel_close(losses[1], losses[0], LOSS_RTOL))
    check(checks, "energy_gap_in_0_1", out["comp"][2],
          0.0 <= out["comp"][2] <= 1.0)
    return {"k": s["k"], "n_floats": s["n"], "step_s": step_s}


def phase_serve(checks, state):
    """The paged SlotRunner behind the Scheduler; the first request's tokens
    against the offline fused-prefill + decode path."""
    import jax
    import numpy as np

    from repro.launch.serve import offline_generate
    from repro.models.decode import init_cache
    from repro.models.transformer import RunCtx, init_params
    from repro.serve import (RequestStream, Scheduler, SlotRunner,
                             StepCostModel)

    cfg = model_config()
    ctx = RunCtx(remat=False, chunk_q=128, chunk_k=128)
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(SEED))
    # generous SLOs: every request must complete, none may be dropped
    reqs = RequestStream(dist="S1", n_clients=SERVE_MAX_BATCH,
                         prompt_lens=SERVE_PROMPT_LENS,
                         max_new_tokens=SERVE_NEW_TOKENS, slo_ttft_s=1e9,
                         slo_tpot_s=1e9, seed=SEED).generate(60.0)
    reqs = reqs[:SERVE_REQUESTS]
    cache_len = max(SERVE_PROMPT_LENS) + SERVE_NEW_TOKENS
    pages = -(-cache_len // SERVE_PAGE)
    runner = SlotRunner(params, cfg, ctx, SERVE_MAX_BATCH, cache_len,
                        temperature=0.0, seed=SEED, page_size=SERVE_PAGE,
                        num_pages=pages * SERVE_MAX_BATCH)
    # keep the cache of the busiest decode step for the kernels phase
    busiest = {"active": -1}
    step = runner.step

    def step_and_snapshot(active_slots):
        step(active_slots)
        if len(active_slots) > busiest["active"]:
            busiest.update(active=len(active_slots), cache=runner.cache)
    runner.step = step_and_snapshot

    # sim-clock costs only order the events; slow enough sim steps that the
    # requests overlap and the batch fills
    cost = StepCostModel(decode_step_s=0.1, prefill_token_s=1e-3)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        recs, summary = Scheduler(SERVE_MAX_BATCH, cost,
                                  runners=[runner]).run(reqs)
        serve_s = time.perf_counter() - t0
        first = reqs[0]
        prompt = runner.prompt_tokens(first)
        ref = offline_generate(
            params, cfg, ctx, prompt, first.max_new_tokens,
            cache=init_cache(cfg, 1, first.prompt_len + first.max_new_tokens,
                             ctx))
    served = np.asarray(runner.generated[first.rid])
    offline = ref["tokens"][0]
    n_tokens = [len(runner.generated.get(r.rid, [])) for r in reqs]
    check(checks, "conservation_ok", summary["conservation_ok"],
          summary["conservation_ok"])
    check(checks, "completed", [summary["completed"], len(reqs)],
          summary["completed"] == len(reqs))
    check(checks, "tokens_per_request", n_tokens,
          all(n == SERVE_NEW_TOKENS for n in n_tokens))
    agree = bool(np.array_equal(served, offline))
    detail = {"agree": agree, "served": served.tolist(),
              "offline": offline.tolist()}
    ok = agree
    if not agree:
        i = int(np.argmax(served != offline)) if len(served) == len(
            offline) else min(len(served), len(offline))
        gap = float(ref["top2_gap"][0, min(i, len(offline) - 1)])
        detail.update(first_diff_step=i, top2_gap=gap)
        ok = gap < SERVE_TIE_GAP
    check(checks, "first_request_vs_offline", detail, ok)
    state["cache"] = busiest["cache"]
    state["cfg"] = cfg
    return {"decode_backend": runner.ctx.decode_backend,
            "requests": len(reqs), "prompt_lens": [r.prompt_len
                                                   for r in reqs],
            "busiest_step_active_slots": busiest["active"],
            "scheduler_wall_s": serve_s}


def phase_kernels(checks, state):
    """Compiled Pallas kernels against the jax reference: both decode
    kernels on the serve phase's live paged cache, flash attention on a
    512-token causal prefill."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_decode import flash_decode, flash_decode_paged
    from repro.models.attention import chunked_attention, decode_attention

    cfg, cache = state.pop("cfg"), state.pop("cache")
    cl = next(iter(cache["unit"].values()))
    layer = cl["k"].shape[0] // 2
    kp, vp, bt = cl["k"][layer], cl["v"][layer], cl["bt"][layer]
    b, ncols = bt.shape
    _, pg, kvh, hd = kp.shape
    S = ncols * pg
    kv_len = jnp.clip(cache["pos"], 1, S)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    h = cfg.num_heads
    q = jax.random.normal(keys[0], (b, 1, h, hd), jnp.float32)
    k_view = kp[bt].reshape(b, S, kvh, hd)
    v_view = vp[bt].reshape(b, S, kvh, hd)
    qp = jax.random.normal(keys[1], (1, PREFILL_LEN, h, hd), jnp.float32)
    kpf = jax.random.normal(keys[2], (1, PREFILL_LEN, kvh, hd), jnp.float32)
    vpf = jax.random.normal(keys[3], (1, PREFILL_LEN, kvh, hd), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref_dec = decode_attention(q, k_view, v_view, kv_len)
        ref_pre = chunked_attention(qp, kpf, vpf, kind="causal",
                                    chunk_q=128, chunk_k=128)
    outs = {
        "flash_decode": (flash_decode(q, k_view, v_view, kv_len,
                                      interpret=KERNEL_INTERPRET), ref_dec),
        "flash_decode_paged": (flash_decode_paged(
            q, kp, vp, bt, kv_len, interpret=KERNEL_INTERPRET), ref_dec),
        "flash_attention_fwd": (flash_attention(
            qp, kpf, vpf, kind="causal", interpret=KERNEL_INTERPRET),
            ref_pre),
    }
    for name, (out, ref) in outs.items():
        err = float(jnp.max(jnp.abs(out - ref)))
        check(checks, f"{name}_max_abs_err", err,
              math.isfinite(err) and err <= KERNEL_ATOL)
    return {"layer": int(layer), "kv_len": [int(x) for x in kv_len],
            "atol": KERNEL_ATOL}


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip DDP check")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    meter = CompileMeter()
    devices = jax.devices()[:args.chips]
    try:
        run_phase("device", phase_device, meter, devices, args.chips,
                  cache_dir)
        if args.chips == 4:
            run_phase("ddp4", phase_ddp4, meter, devices)
        else:
            state = {}
            run_phase("train", phase_train, meter, devices)
            run_phase("ddp", phase_ddp, meter, devices)
            run_phase("serve", phase_serve, meter, devices, state)
            run_phase("kernels", phase_kernels, meter, devices, state)
    except NoChip as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    except Exception as e:
        print(f"chip_smoke: failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
