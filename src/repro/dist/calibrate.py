"""Calibrate the fleet engine's comm model against compiled DDP programs.

PR 1's fleet engine charges communication from the analytic ring formula
``2(N-1)/N * 4 * floats_on_wire``.  This module replaces that estimate with
*measured* collective wire bytes from the two compiled DDP programs in
``repro.train.ddp`` (dense weighted all-reduce vs all-gather of packed
top-k): the programs are lowered for the fleet's device count, the optimized
HLO is walked (``hlo_cost.analyze_hlo``), and the per-device collective wire
bytes become a :class:`CommCalibration` that plugs into
``FleetConfig.comm_model``.  The legacy analytic model stays the default —
``comm_model=None`` keeps the homogeneous full-sync case bit-exact with
``EdgeClock`` — so calibration is strictly opt-in.

Lowering needs one XLA process per device count (the host-device flag is
locked at jax init), so :func:`calibrate` shells out exactly like
``benchmarks/compression_wire.py`` and caches the result as a JSON artifact
under ``artifacts/perf/``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from typing import Optional


def ring_wire_bytes(n_devices: int, floats_on_wire: float) -> float:
    """The legacy analytic model: per-device ring all-reduce bytes."""
    if n_devices <= 1:
        return 0.0
    return 2.0 * (n_devices - 1) / n_devices * 4.0 * floats_on_wire


@dataclasses.dataclass(frozen=True)
class CommCalibration:
    """Per-round, per-device collective wire bytes of the two DDP programs.

    ``bytes_for`` is the fleet engine's comm-bytes source: the trainer
    announces ``floats_on_wire`` (``n_floats`` dense, ``2k`` compressed) and
    the calibration returns the measured bytes of the matching program.
    Float counts near the dense size scale the dense program, counts near
    ``2k`` scale the compressed one (other cr values) — a calibration is
    per-model, so simulate a different model with its own calibration, not
    by scaling this one.
    """
    n_devices: int
    n_floats: int
    k: int
    dense_wire_bytes: float
    compressed_wire_bytes: float
    arch: str = ""
    source: str = "hlo"

    def bytes_for(self, floats_on_wire: float) -> float:
        comp_floats = 2.0 * self.k
        if 2.0 * floats_on_wire >= self.n_floats + comp_floats:
            return self.dense_wire_bytes * floats_on_wire / self.n_floats
        return self.compressed_wire_bytes * floats_on_wire / comp_floats

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CommCalibration":
        return cls(**{f.name: d[f.name] for f in dataclasses.fields(cls)
                      if f.name in d})


@dataclasses.dataclass(frozen=True)
class AnalyticRingModel:
    """Calibration-shaped wrapper around the legacy formula (useful for A/B
    runs: an engine given this model matches the default engine exactly)."""
    n_devices: int

    def bytes_for(self, floats_on_wire: float) -> float:
        return ring_wire_bytes(self.n_devices, floats_on_wire)


# ---------------------------------------------------------------------------
# lowering + extraction

_CALIB_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%(n)d "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.dist.hlo_cost import analyze_hlo
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import RunCtx, init_params
from repro.optim.optimizers import sgdm_init, sgdm_update
from repro.train.ddp import make_ddp_steps

cfg = get_config(%(arch)r)
if %(reduced)r:
    cfg = cfg.reduced()
ctx = RunCtx(remat=%(remat)r, chunk_q=%(chunk)d, chunk_k=%(chunk)d,
             loss_chunk=%(chunk)d)
params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
mesh = make_test_mesh((%(n)d,), ("data",))
opt_update = lambda g, s, p, lr: sgdm_update(g, s, p, lr=lr, momentum=0.9)
dense_step, comp_step, k, n_floats = make_ddp_steps(
    cfg, ctx, mesh, opt_update, lambda t: 1e-3, cr=%(cr)r,
    param_template=params)
batch = {"tokens": jax.ShapeDtypeStruct((%(batch)d, %(seq)d), jnp.int32),
         "labels": jax.ShapeDtypeStruct((%(batch)d, %(seq)d), jnp.int32)}
opt = jax.eval_shape(sgdm_init, params)
rates = jax.ShapeDtypeStruct((%(n)d,), jnp.float32)
step_s = jax.ShapeDtypeStruct((), jnp.int32)
out = {"n_devices": %(n)d, "k": k, "n_floats": n_floats, "arch": %(arch)r}
with jax.set_mesh(mesh):
    for name, fn in (("dense", dense_step), ("compressed", comp_step)):
        txt = jax.jit(fn).lower(params, opt, batch, rates,
                                step_s).compile().as_text()
        out[name + "_wire_bytes"] = analyze_hlo(txt)["collective_bytes"]
print(json.dumps(out))
"""


def _cache_path(arch: str, n_devices: int, cr: float, reduced: bool,
                cache_dir: str) -> str:
    tag = f"comm_calibration__{arch.replace('/', '_')}__d{n_devices}__cr{cr}"
    if reduced:
        tag += "__reduced"
    return os.path.join(cache_dir, tag + ".json")


def calibrate(arch: str = "qwen1.5-0.5b", n_devices: int = 8,
              cr: float = 0.1, *, reduced: bool = True,
              batch_per_device: int = 2, seq_len: int = 64,
              remat: bool = False, cache_dir: str = "artifacts/perf",
              timeout: int = 1800,
              repo_root: Optional[str] = None) -> CommCalibration:
    """Lower the two DDP programs for ``n_devices`` and return the parsed
    per-device collective wire bytes as a :class:`CommCalibration`.

    Runs in a subprocess (the host-device count must be set before jax
    initialises) and caches the JSON artifact, so repeat calls are free.
    ``reduced=True`` (the default) lowers the smoke-scale config — the wire
    *ratio* is size-independent, and calibrating the full model is a
    dry-run-scale job, not a test-scale one.
    """
    path = _cache_path(arch, n_devices, cr, reduced, cache_dir)
    if os.path.exists(path):
        with open(path) as f:
            return CommCalibration.from_dict(json.load(f))
    script = _CALIB_SCRIPT % {
        "n": n_devices, "arch": arch, "reduced": reduced, "cr": cr,
        "batch": batch_per_device * n_devices, "seq": seq_len,
        "remat": remat, "chunk": min(seq_len, 512),
    }
    env = dict(os.environ)
    root = repo_root or os.getcwd()
    src = os.path.join(root, "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=root)
    if r.returncode != 0:
        tail = (r.stderr or r.stdout).strip().splitlines()[-5:]
        raise RuntimeError("calibration lowering failed:\n" + "\n".join(tail))
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    cal = CommCalibration(
        n_devices=rec["n_devices"], n_floats=rec["n_floats"], k=rec["k"],
        dense_wire_bytes=rec["dense_wire_bytes"],
        compressed_wire_bytes=rec["compressed_wire_bytes"], arch=rec["arch"])
    os.makedirs(cache_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(cal.to_dict(), f, indent=1)
    return cal


def calibrated_fleet_config(fleet_cfg, arch: str = "qwen1.5-0.5b",
                            cr: float = 0.1, n_devices: Optional[int] = None,
                            **kwargs):
    """Return a copy of ``FleetConfig`` with ``comm_model`` set from a
    (cached) HLO calibration for the fleet's device count."""
    import dataclasses as _dc
    n = n_devices if n_devices is not None else 8
    cal = calibrate(arch, n, cr, **kwargs)
    return _dc.replace(fleet_cfg, comm_model=cal)
