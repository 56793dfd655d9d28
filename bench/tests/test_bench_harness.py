"""Whole runs of the harness at a tiny size on the CPU, with the look for a
chip skipped: a sound run is correct, and each fault planted under the timed
path makes ``correct`` come out false.  The tiny cells, their configuration
and traffic live only in a temporary copy of the benchmark, so the harness
finds a cell it has never seen with no code edit; so does an architecture
that only that copy holds (``tiny_swa``), through both drivers."""
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
SWA_CELLS = ["tiny.swa.train", "tiny.swa.ddp"]


@pytest.fixture(scope="module")
def train_runs():
    return tiny.run_plants({"tiny.train": TRAIN_PLANTS,
                            "tiny.swa.train": [""]})


@pytest.fixture(scope="module")
def ddp_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    runs = {"tiny.ddp": DDP_PLANTS, "tiny.swa.ddp": [""]}
    p = subprocess.run([sys.executable, "-m", "bench.tests.tiny",
                        json.dumps(runs)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("plant", TRAIN_PLANTS)
def test_train_cell(train_runs, plant):
    r = train_runs["tiny.train"][plant]
    assert r["attempted"] >= 1
    assert r["correct"] == (plant == ""), r["checks"]
    assert r["metrics"] == ["setup_s", "step_ms_p90", "train_tokens_per_s"] \
        or r["metrics"] == ["setup_s", "train_tokens_per_s"]


@pytest.mark.parametrize("plant", DDP_PLANTS)
def test_ddp_cell(ddp_runs, plant):
    r = ddp_runs["tiny.ddp"][plant]
    assert r["attempted"] >= 1
    assert r["correct"] == (plant == ""), r["checks"]
    assert r["checks"]["picks_differ"]["value"] == 0.0


@pytest.mark.parametrize("cell", SWA_CELLS)
def test_a_new_architecture_joins_by_new_files(train_runs, ddp_runs, cell):
    from bench import harness
    r = (train_runs if cell in train_runs else ddp_runs)[cell][""]
    assert r["attempted"] >= 1
    assert r["correct"], r["checks"]
    # the FLOPs per token the run took are the new architecture's count,
    # which its sliding window keeps under the Qwen2 count of its sizes
    arch = harness.load_module(ROOT / "bench" / "tests" / "tiny_swa" /
                               "arch.py")
    qwen2 = harness.load_module(ROOT / "bench" / "arch" / "qwen2.py")
    want = arch.train_flops_per_token(tiny.TINY_SWA, 32)
    assert r["values"]["flops_per_token"] == want
    assert want < qwen2.train_flops_per_token(tiny.TINY_CONFIG, 32)


def test_an_unknown_model_type_fails_before_compiling(tmp_path):
    import time

    import repro  # noqa: F401  imported before the count starts
    from jax import monitoring

    from bench import harness
    root = tiny.make_root(tmp_path)
    c = dict(tiny.TINY_CONFIG, model_type="no_such_arch")
    (root / "bench" / "configs" / "tiny-qwen2.json").write_text(json.dumps(c))
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    with pytest.raises(FileNotFoundError,
                       match="bench/arch/no_such_arch.py"):
        harness.run_cell(root, "tiny.train", 5, 0.2, False,
                         time.perf_counter(), require_chip=False)
    assert compiles == []


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
