"""Driver: the single-program ScaDLES training step on one chip.

The program's own step, built as ``repro.launch.train.run`` builds it:
``make_train_step`` with ``train_ctx(seq)`` (remat on), Adam with the
workload's weight decay behind ``warmup_cosine``, params and optimizer state
donated.  Each step's batch carries Eqn 4a ``sample_weights`` from the
traffic's streams, and the host reads the step's metrics once per step, as
the launcher does.

Set-up builds the weights on the device from the seed in one jitted call,
and then drives the step through the workload's ``check_steps`` first steps
(the first call compiles, or reads the cache).  The same step and state
then run the window.  From those first steps it keeps the readings the
reference is compared on: each loss, the first gradient's per-slice norms
as Adam received it (its first moment after one step is (1 - b1) g), and
the per-slice norms of the parameters' change.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import compare, program
from bench import weights as W
from bench.reference import train as ref_train
from bench.traffic import Traffic


class Session:
    def __init__(self, cell):
        from repro.launch.train import train_ctx
        from repro.models.transformer import init_params
        from repro.optim import make_optimizer, warmup_cosine
        from repro.train import make_train_step

        self.cell, spec, c = cell, cell.spec, cell.config
        self.spans = cell.spans
        self.device = cell.devices[0]
        cfg = cell.arch.model_config(c)
        seq = cell.traffic["seq_len"]
        opt, sched = spec["optimizer"], spec["schedule"]
        self.init = cell.arch.make_init(c)
        template = jax.eval_shape(lambda k: init_params(k, cfg),
                                  jax.random.PRNGKey(0))
        W.check_layout(self.init, template)
        self.names = W.leaf_slices(template)
        opt_init, opt_update = make_optimizer(
            "adam", b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"])
        opt_update = program.opt_update(cell.plant, opt_update)
        schedule = warmup_cosine(sched["base_lr"], sched["warmup"],
                                 sched["total"], sched.get("min_frac", 0.1))
        self.fn = jax.jit(make_train_step(cfg, train_ctx(seq), opt_update,
                                          schedule), donate_argnums=(0, 1))
        self.key = W.jax_key(cell.seed)
        self.traffic = Traffic(cell.traffic, c["vocab_size"], cell.seed)
        self.tokens_per_step = self.traffic.tokens_per_step
        b1 = opt["b1"]
        first_grad = jax.jit(lambda m: W.slice_norms(m) / (1.0 - b1))
        change = jax.jit(lambda p, key: W.slice_norms(jax.tree.map(
            jnp.subtract, p, self.init(key))))

        # every argument committed to the chip from the start, so the step
        # compiles once
        on_chip = SingleDeviceSharding(self.device)
        self.params = jax.jit(self.init, out_shardings=on_chip)(self.key)
        self.opt = jax.jit(opt_init, out_shardings=on_chip)(self.params)
        self.i = 0
        losses, grad0 = [], None
        for _ in range(spec["check_steps"]):
            m = self._run(self._batch())
            losses.append(float(m["loss"]))
            if grad0 is None:
                grad0 = np.asarray(first_grad(self.opt["m"]))
        self.readings = {"losses": losses, "grad0": grad0,
                         "change": np.asarray(change(self.params,
                                                     self.key))}

    def _batch(self):
        b = self.traffic.batch(self.i)
        b = program.half_batch(self.cell.plant, b)
        return jax.device_put(b, self.device)

    def _run(self, batch):
        self.params, self.opt, m = self.fn(self.params, self.opt, batch,
                                           np.int32(self.i))
        self.i += 1
        return m

    def step(self) -> bool:
        sp = self.spans
        with sp.span("input"):
            batch = self._batch()
        with sp.span("dispatch"):
            m = self._run(batch)
        with sp.span("metrics_read"):
            host = jax.device_get(m)
        return bool(np.isfinite(host["loss"]))

    def free(self) -> None:
        del self.params, self.opt
        gc.collect()

    def check(self) -> dict:
        cell, spec = self.cell, self.cell.spec
        fresh = Traffic(cell.traffic, cell.config["vocab_size"], cell.seed)
        batches = [fresh.batch(i) for i in range(spec["check_steps"])]
        with jax.default_device(self.device):
            ref = ref_train.follow_adam(cell.reference, cell.config,
                                        self.init, self.key, batches,
                                        spec["optimizer"], spec["schedule"])
        return compare.numbers(self.readings, ref, self.names)


def setup(cell) -> Session:
    return Session(cell)
