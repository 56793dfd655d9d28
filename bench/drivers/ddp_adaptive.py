"""Driver: rate-weighted DDP with adaptive top-k compression over a data mesh
of the cell's chips.

The program's two programs from ``repro.train.ddp.make_ddp_steps`` (dense:
psum of r_i/sum(r) * g_i; compressed: exact top-k of each device's gradient,
all-gather of the weighted values and indices, scatter-add), both jitted with
params and optimizer state donated, SGD-momentum behind a constant learning
rate.  The program's ``AdaptiveCompressor`` picks one for each step, as
``chip_smoke.phase_ddp`` drives it: the first step is compressed, so that
there is an energy gap to judge, and each later step asks the controller
with the last gap measured.  The rates come from the traffic's streams, one
stream per chip.

Set-up builds the weights replicated on every chip from the seed in one
jitted call, warms the dense program with one call (then builds the weights
again, so that the run starts from the seed), and drives the workload's
``check_steps`` first steps, keeping the readings the reference is compared
on: each loss, the per-slice norms of the first aggregated gradient (the
momentum after one step), of the parameters' change, and the picks.
"""
from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import compare, program
from bench import weights as W
from bench.reference import train as ref_train
from bench.traffic import Traffic


class Session:
    def __init__(self, cell):
        from repro.core.compression import AdaptiveCompressor
        from repro.launch.train import train_ctx
        from repro.models.transformer import init_params
        from repro.optim.optimizers import sgdm_init, sgdm_update
        from repro.train.ddp import make_ddp_steps

        self.cell, spec, c = cell, cell.spec, cell.config
        self.spans = cell.spans
        cfg = cell.arch.model_config(c)
        opt, comp = spec["optimizer"], spec["compression"]
        self.n_dev = len(cell.devices)
        self.mesh = Mesh(np.array(cell.devices), ("data",))
        rep = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("data", None))
        self.init = cell.arch.make_init(c)
        template = jax.eval_shape(lambda k: init_params(k, cfg),
                                  jax.random.PRNGKey(0))
        W.check_layout(self.init, template)
        self.names = W.leaf_slices(template)

        def sgdm(g, s, p, lr):
            return sgdm_update(g, s, p, lr=lr, momentum=opt["momentum"])

        with program.collectives(cell.plant):
            dense, compressed, _, self.n = make_ddp_steps(
                cfg, train_ctx(cell.traffic["seq_len"]), self.mesh,
                program.opt_update(cell.plant, sgdm),
                lambda t: opt["lr"], cr=comp["cr"], param_template=template)
            self.fns = {
                "dense": jax.jit(dense, donate_argnums=(0, 1)),
                "compressed": jax.jit(compressed, donate_argnums=(0, 1))}
            self.ctrl = AdaptiveCompressor(cr=comp["cr"],
                                           delta=comp["delta"],
                                           alpha=comp["alpha"])
            self.key = W.jax_key(cell.seed)
            self.init_rep = jax.jit(self.init, out_shardings=rep)
            self.opt_init = jax.jit(sgdm_init, out_shardings=rep)
            self.traffic = Traffic(cell.traffic, c["vocab_size"], cell.seed)
            self.tokens_per_step = self.traffic.tokens_per_step
            first = self.traffic.batch(0)
            self.rates = jax.device_put(
                jnp.asarray(first.pop("rates")),
                NamedSharding(self.mesh, P("data")))
            batch0 = self._place(first)
            change = jax.jit(lambda p, key: W.slice_norms(jax.tree.map(
                jnp.subtract, p, self.init(key))))
            grad = jax.jit(W.slice_norms)

            # one warm call of the dense program, then the seed's weights
            self.params = self.init_rep(self.key)
            self.opt = self.opt_init(self.params)
            self.i = 0
            jax.block_until_ready(self._run("dense", batch0))
            self.params = self.init_rep(self.key)
            self.opt = self.opt_init(self.params)
            self.i, self.gap = 0, None
            losses, picks, grad0 = [], [], None
            for step in range(spec["check_steps"]):
                batch = batch0 if step == 0 else self._next_batch()
                pick = self._pick()
                m = jax.device_get(self._run(pick, batch))
                self._account(pick, m)
                losses.append(float(m["loss"]))
                picks.append(pick)
                if grad0 is None:
                    grad0 = np.asarray(grad(self.opt["mom"]))
            self.readings = {"losses": losses, "grad0": grad0,
                             "picks": picks,
                             "change": np.asarray(change(self.params,
                                                         self.key))}

    def _place(self, b):
        b = program.half_batch(self.cell.plant, b, groups=self.n_dev)
        return jax.device_put(b, self.rows)

    def _next_batch(self):
        b = self.traffic.batch(self.i)
        b.pop("rates")
        return self._place(b)

    def _pick(self) -> str:
        if self.gap is None:
            return "compressed"
        return "compressed" if self.ctrl.decide(self.gap) else "dense"

    def _account(self, pick, m) -> None:
        self.ctrl.account(pick == "compressed", self.n)
        if pick == "compressed":
            self.gap = float(m["gap"])

    def _run(self, pick, batch):
        self.params, self.opt, m = self.fns[pick](
            self.params, self.opt, batch, self.rates, np.int32(self.i))
        self.i += 1
        return m

    def step(self) -> bool:
        sp = self.spans
        with sp.span("controller"):
            pick = self._pick()
        sp.count(f"step.{pick}")
        with sp.span("input"):
            batch = self._next_batch()
        with sp.span(f"step.{pick}"):
            with sp.span("dispatch"):
                m = self._run(pick, batch)
            with sp.span("metrics_read"):
                host = jax.device_get(m)
        self._account(pick, host)
        return bool(np.isfinite(host["loss"]))

    def free(self) -> None:
        del self.params, self.opt
        gc.collect()

    def check(self) -> dict:
        cell, spec = self.cell, self.cell.spec
        fresh = Traffic(cell.traffic, cell.config["vocab_size"], cell.seed)
        batches = [fresh.batch(i) for i in range(spec["check_steps"])]
        with jax.default_device(cell.devices[0]):
            ref = ref_train.follow_ddp(
                cell.reference, cell.config, self.init, self.key, batches,
                self.n_dev, spec["optimizer"], spec["compression"])
        return compare.numbers(self.readings, ref, self.names)


def setup(cell) -> Session:
    return Session(cell)
