"""Training launcher: real (small-scale) runs on the available devices.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --steps 50 --batch 16 --seq 128 [--scadles] [--dist S1] \
        [--trace-dir DIR]

Uses the same config/model/sharding stack as the dry-run, but actually
allocates and steps on whatever jax.devices() offers.  With ``--scadles``
the ScaDLES mechanisms are active: per-device streaming rates drive sample
weights (Eqn 4) and the linear LR scaling rule.  ``run`` is the whole
launch minus argument parsing; ``chip_smoke.py`` calls it directly.

Each step runs under ``jax.profiler.StepTraceAnnotation("train")`` with the
host spans the benchmark names (``bench/harness.SPANS``): ``input`` (the
batch), ``dispatch`` (the step's launch) and ``metrics_read`` (the one
device-to-host read of its metrics).  ``--trace-dir`` captures steps 1 to
N-1 in a profiler trace, beside the device's ops.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import TABLE_I, StreamSimulator
from repro.data import TokenData
from repro.launch.compile_cache import enable_compile_cache
from repro.models import attention
from repro.models.transformer import RunCtx, init_params
from repro.obs.profile import capture
from repro.optim import make_optimizer, warmup_cosine
from repro.train import make_train_step
from repro.checkpoint import save_pytree


def train_ctx(seq: int) -> RunCtx:
    """The execution context every training entry point uses at ``seq``."""
    return RunCtx(remat=True, loss_chunk=min(128, seq),
                  chunk_q=min(128, seq), chunk_k=min(128, seq))


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--scadles", action="store_true")
    ap.add_argument("--dist", default="S1")
    ap.add_argument("--n-virtual-devices", type=int, default=8)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default="",
                    help="write a profiler trace of steps 1..N-1 here")
    return ap.parse_args(argv)


@jax.jit
def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def run(args: argparse.Namespace) -> Dict[str, Any]:
    """Train for ``args.steps`` steps; returns the config name ``arch``,
    the final ``params``, the per-step ``history`` (host floats: loss,
    grad_norm, lr, ...), the step's ``compile_s`` and ``run_s``, and the
    global parameter norm before (``param_norm0``) and after
    (``param_norm``), and ``attention_paths``: the attention calls the
    step's trace sent to the Pallas kernels and to the JAX path (logged
    after the compile; a layer scan counts once)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ctx = train_ctx(args.seq)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")

    opt_init, opt_update = make_optimizer("adam", weight_decay=0.01)
    opt_state = opt_init(params)
    schedule = warmup_cosine(args.lr, max(args.steps // 10, 1), args.steps)
    # params and optimizer state are updated in place
    step_fn = jax.jit(make_train_step(cfg, ctx, opt_update, schedule),
                      donate_argnums=(0, 1))

    data = TokenData(vocab_size=cfg.vocab_size, seq_len=args.seq,
                     seed=args.seed)
    rng = np.random.default_rng(args.seed)
    sim = StreamSimulator(TABLE_I[args.dist], args.n_virtual_devices,
                          seed=args.seed) if args.scadles else None

    def batch_at(step: int):
        toks, labels = data.sample(rng, args.batch)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        if sim is not None:
            # map each sample to a virtual streaming device; weight = Eqn 4a
            rates = sim.rates_at(step)
            dev = rng.integers(0, args.n_virtual_devices, size=args.batch)
            w = rates[dev].astype(np.float64)
            batch["sample_weights"] = jnp.asarray(
                (w / w.sum()).astype(np.float32))
        return batch

    param_norm0 = float(_global_norm(params))
    batch = batch_at(0)
    paths0 = attention.path_counts()
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, batch,
                             jnp.asarray(0)).compile()
    compile_s = time.perf_counter() - t0
    paths = {k: n - paths0[k] for k, n in attention.path_counts().items()}
    print(f"attention calls traced: {paths['kernel']} on the Pallas kernels, "
          f"{paths['jax']} on the JAX path")

    history: List[Dict[str, float]] = []
    span = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with contextlib.ExitStack() as traced:
        for step in range(args.steps):
            if step == 1 and args.trace_dir:
                traced.enter_context(capture(args.trace_dir))
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with span("input"):
                    if step:
                        batch = batch_at(step)
                with span("dispatch"):
                    params, opt_state, metrics = compiled(
                        params, opt_state, batch, jnp.asarray(step))
                with span("metrics_read"):
                    metrics = jax.device_get(metrics)
            history.append({k: float(v) for k, v in metrics.items()})
            if step % 10 == 0 or step == args.steps - 1:
                m = history[-1]
                print(f"step {step:4d} loss={m['loss']:.4f} "
                      f"lr={m['lr']:.2e} gnorm={m['grad_norm']:.3f} "
                      f"({(time.perf_counter()-t0)/(step+1):.2f}s/it)")
        run_s = time.perf_counter() - t0
    return {"arch": cfg.name, "params": params, "history": history,
            "compile_s": compile_s, "run_s": run_s,
            "attention_paths": paths,
            "param_norm0": param_norm0,
            "param_norm": float(_global_norm(params))}


def main(argv: Optional[Sequence[str]] = None):
    args = parse_args(argv)
    enable_compile_cache()
    out = run(args)
    if args.ckpt:
        path = save_pytree({"params": out["params"]}, args.ckpt,
                           name=out["arch"])
        print("saved", path)


if __name__ == "__main__":
    main()
