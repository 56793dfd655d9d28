"""Whole runs of the harness at a tiny size on the CPU, with the look for a
chip skipped: a sound run is correct, and each fault planted under the timed
path makes ``correct`` come out false.  The tiny cells, their configuration
and traffic live only in a temporary copy of the benchmark, so the harness
finds a cell it has never seen with no code edit."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
TRAIN_PLANTS = ["", "state_unchanged", "half_batch"]
DDP_PLANTS = ["", "state_unchanged", "half_batch", "no_exchange"]


@pytest.fixture(scope="module")
def train_runs():
    return tiny.run_plants("tiny.train", TRAIN_PLANTS)


@pytest.fixture(scope="module")
def ddp_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.tiny", "tiny.ddp",
                        ",".join(DDP_PLANTS)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("plant", TRAIN_PLANTS)
def test_train_cell(train_runs, plant):
    r = train_runs[plant]
    assert r["attempted"] >= 1
    assert r["correct"] == (plant == ""), r["checks"]
    assert r["metrics"] == ["setup_s", "step_ms_p90", "train_tokens_per_s"] \
        or r["metrics"] == ["setup_s", "train_tokens_per_s"]


@pytest.mark.parametrize("plant", DDP_PLANTS)
def test_ddp_cell(ddp_runs, plant):
    r = ddp_runs[plant]
    assert r["attempted"] >= 1
    assert r["correct"] == (plant == ""), r["checks"]
    assert r["checks"]["picks_differ"]["value"] == 0.0


def _run_py(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train.qwen1.5-0.5b.seq2048", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_no_chip_exits_nonzero_without_a_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
