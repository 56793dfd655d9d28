"""Serving launcher: offline batched decoding or streaming continuous batching.

Offline (the classic static batch, now on the fused chunked prefill):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 8 --prompt-len 32 --gen 64 [--long-context]

Streaming (continuous batching under Table-I arrival distributions, with
per-request deadlines — the ``repro.serve`` runtime driving the real model):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --streaming --dist S1 --horizon 8 --max-batch 8

The heavy lifting lives in ``repro.models.decode`` (slot caches, fused
prefill) and ``repro.serve`` (schedulers, metrics); this is a thin CLI.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.decode import (decode_step, init_cache, prefill_cache,
                                 prefill_cross_kv)
from repro.models.transformer import RunCtx, init_params


def _setup(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ctx = RunCtx(remat=False, chunk_q=min(128, args.prompt_len),
                 chunk_k=min(128, args.prompt_len))
    # one key per use: init / prompts / audio / sampling must not share a
    # PRNG stream (a shared key correlates the sampling chain with init)
    k_init, k_prompt, k_audio, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 4)
    params = init_params(k_init, cfg)
    return cfg, ctx, params, k_prompt, k_audio, k_sample


def offline_generate(params, cfg, ctx, tokens, gen: int, *, cache,
                     pattern=None, temperature: float = 0.0, key=None):
    """Static-batch generation: fused ``prefill_cache`` over the prompt
    ``tokens`` (b, s), then ``gen`` lockstep ``decode_step`` calls.

    ``cache`` is a fresh ``init_cache`` of at least ``s + gen`` entries.
    Returns host arrays ``tokens`` (b, gen) and ``top2_gap`` (b, gen), the
    gap between the two largest logits each token was picked from, plus
    the ``prefill_s`` and ``decode_s`` wall times.
    """
    step_jit = jax.jit(
        lambda p, c, t: decode_step(p, c, t, cfg, ctx, pattern=pattern))
    prefill_jit = jax.jit(
        lambda p, c, t: prefill_cache(p, t, c, cfg, ctx, pattern=pattern))

    t0 = time.time()
    logits, cache = jax.block_until_ready(prefill_jit(params, cache, tokens))
    prefill_s = time.time() - t0

    out, gaps = [], []
    t0 = time.time()
    for i in range(gen):
        if temperature > 0:
            key, sk = jax.random.split(key)
            nxt = jax.random.categorical(sk, logits / temperature, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        top2 = jax.lax.top_k(logits, 2)[0]
        out.append(np.asarray(nxt))
        gaps.append(np.asarray(top2[:, 0] - top2[:, 1]))
        if i + 1 < gen:
            logits, cache = step_jit(params, cache, nxt[:, None])
    return {"tokens": np.stack(out, 1), "top2_gap": np.stack(gaps, 1),
            "prefill_s": prefill_s, "decode_s": time.time() - t0}


def run_offline(args):
    cfg, ctx, params, k_prompt, k_audio, k_sample = _setup(args)
    pattern = cfg.pattern_for_long_context() if args.long_context else None

    cache_len = args.prompt_len + args.gen
    cache = init_cache(cfg, args.batch, cache_len, ctx, pattern=pattern)
    if cfg.family == "audio":
        feats = jax.random.normal(
            k_audio, (args.batch, cfg.encoder_seq_len, cfg.d_model))
        cache = prefill_cross_kv(params, feats, cfg, ctx, cache)

    toks = jax.random.randint(k_prompt, (args.batch, args.prompt_len), 0,
                              cfg.vocab_size)
    out = offline_generate(params, cfg, ctx, toks, args.gen, cache=cache,
                           pattern=pattern, temperature=args.temperature,
                           key=k_sample)
    dt = out["decode_s"]
    toks_s = args.batch * args.gen / dt
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill={out['prefill_s']:.2f}s "
          f"decode={dt:.2f}s ({toks_s:.1f} tok/s) cache_len={cache_len}")
    print("sample:", out["tokens"][0][:16])


def run_streaming(args):
    from repro.serve import (ContinuousBatchingServer, RequestStream,
                             SlotRunner, measured_cost_model)
    cfg, ctx, params, _, _, _ = _setup(args)
    pattern = cfg.pattern_for_long_context() if args.long_context else None
    cache_len = args.prompt_len + args.gen
    cost = measured_cost_model(params, cfg, ctx, args.max_batch, cache_len,
                               args.prompt_len, pattern=pattern)
    runner = SlotRunner(params, cfg, ctx, args.max_batch, cache_len,
                        pattern=pattern, temperature=args.temperature,
                        seed=args.seed)
    stream = RequestStream(dist=args.dist, n_clients=args.clients,
                           prompt_len=args.prompt_len,
                           max_new_tokens=args.gen,
                           slo_ttft_s=args.slo_ttft, seed=args.seed)
    requests = stream.generate(args.horizon)
    tracker = None
    if args.track:
        from repro.obs import JsonTracker
        tracker = JsonTracker(
            args.track, seed=args.seed,
            meta={"entry": "launch.serve --streaming", "arch": cfg.name,
                  "dist": args.dist, "clients": args.clients,
                  "max_batch": args.max_batch})
    recs, summary = ContinuousBatchingServer(
        args.max_batch, cost, runner=runner, tracker=tracker).run(requests)
    if tracker is not None:
        tracker.finish()
        print(f"# run ledger -> {args.track}")
    print(f"arch={cfg.name} dist={args.dist} clients={args.clients} "
          f"requests={summary['n_requests']} "
          f"decode_step={cost.decode_step_s * 1e3:.1f}ms "
          f"prefill={cost.prefill_s(args.prompt_len) * 1e3:.1f}ms")
    for k in ("completed", "deadline_met", "dropped", "slo_attainment",
              "ttft_p50_s", "ttft_p95_s", "ttft_p99_s", "tpot_p50_s",
              "throughput_tok_s", "goodput_tok_s"):
        v = summary[k]
        print(f"  {k} = {v:.4f}" if isinstance(v, float) else
              f"  {k} = {v}")
    done = [r for r in recs if r.completed]
    if done:
        toks = runner.generated[done[0].rid]
        print("sample:", np.asarray(toks[:16]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--offline", action="store_true",
                      help="static batch, fused prefill + lockstep decode "
                           "(default)")
    mode.add_argument("--streaming", action="store_true",
                      help="continuous batching under Table-I arrivals")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--long-context", action="store_true")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    # streaming knobs
    ap.add_argument("--dist", default="S1", help="Table-I distribution")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--horizon", type=float, default=8.0,
                    help="arrival window (sim seconds)")
    ap.add_argument("--slo-ttft", type=float, default=0.75)
    ap.add_argument("--track", metavar="LEDGER",
                    help="write a JSONL run ledger (request lifecycle events "
                         "+ scorecard) to this path, stamped with git SHA "
                         "and seed")
    args = ap.parse_args()
    enable_compile_cache()
    if args.streaming:
        run_streaming(args)
    else:
        run_offline(args)


if __name__ == "__main__":
    main()
