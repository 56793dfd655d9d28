"""The control of ``correct``: the reference computed in bfloat16, put in
the program's place, must come out not correct against each cell's limits,
on three seeds; so must each fault planted in the reference put in the
program's place.  Tiny cells on the CPU (the DDP cell on four virtual
devices, in a child process); the same readings at the cells' own sizes on
the chip are in PERF.md."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEEDS = [3, 4, 5]
TRAIN_FAULTS = ["half_batch"]
DDP_FAULTS = ["half_batch", "no_exchange"]


@pytest.fixture(scope="module")
def train_controls():
    return tiny.run_controls("tiny.train", SEEDS, TRAIN_FAULTS)


@pytest.fixture(scope="module")
def ddp_controls():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.tiny", "control",
                        "tiny.ddp", ",".join(DDP_FAULTS)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["control"] + TRAIN_FAULTS)
@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails(train_controls, kind, seed):
    r = train_controls[f"{kind}.{seed}"]
    assert not r["correct"], json.dumps(r["checks"])


@pytest.mark.parametrize("kind", ["control"] + DDP_FAULTS)
@pytest.mark.parametrize("seed", SEEDS)
def test_ddp_control_fails(ddp_controls, kind, seed):
    r = ddp_controls[f"{kind}.{seed}"]
    assert not r["correct"], json.dumps(r["checks"])


def test_reference_in_place_of_itself_is_correct(tmp_path):
    import jax
    import jax.numpy as jnp

    from bench import calibrate, compare, harness
    root = tiny.make_root(tmp_path)
    bench, entry, spec, config, traffic = harness.load_cell(root,
                                                            "tiny.train")
    cell = harness.Cell(root, "tiny.train", entry, spec, config, traffic,
                        SEEDS[0], jax.devices()[:1], harness.Spans())
    nums, _ = calibrate.reference_in_place(cell, jnp.float32)
    correct, checks = compare.judge(nums, spec["limits"])
    assert correct and all(c["value"] == 0.0 for c in checks)


@pytest.mark.parametrize("limits", [
    {"loss_gap": 1.0},
    {"loss_gap": 1.0, "grad_norm_gap": 1.0, "change_median_gap": 1.0}])
def test_judge_wants_one_limit_for_each_number(limits):
    from bench import compare
    nums = {"loss_gap": {"value": 0.0}, "grad_norm_gap": {"value": 0.0}}
    with pytest.raises(KeyError):
        compare.judge(nums, limits)
