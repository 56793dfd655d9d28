"""A copy of the benchmark at a size the CPU runs in seconds: the bench
tree and BENCHMARK.json copied to a temporary root, plus files for two tiny
cells (a 2-layer, 64-wide Qwen2-style model) that the harness has never
seen.  Nothing in the harness names them."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny-qwen2", "source": "a 2-layer stand-in for tests",
    "registry": "qwen2-0.5b", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "hidden_act": "silu", "qkv_bias": True,
    "reduced": [],
}


# each tiny cell mirrors a real one: its driver, optimizer and limits
MIRROR = {"tiny.train": ("train.qwen1.5-0.5b.seq2048",
                         {"rows": 4, "seq_len": 32, "determinism": 0.8,
                          "streams": {"dist": "S1", "devices": 8,
                                      "weights": "per_sample"}}),
          "tiny.ddp": ("ddp4.qwen2-0.5b.adaptive",
                       {"rows": 8, "seq_len": 32, "determinism": 0.8,
                        "streams": {"dist": "S1", "devices": 4,
                                    "weights": "per_device"}})}


def make_root(tmp: Path) -> Path:
    """A checkout-like root under ``tmp`` with the tiny cells added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-qwen2.json").write_text(
        json.dumps(TINY_CONFIG))
    for name, (real, traffic) in MIRROR.items():
        spec = json.loads((REPO / "bench" / "workloads" /
                           f"{real}.json").read_text())
        chips = spec["chips"]
        spec.update(config="tiny-qwen2", traffic=name)
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(spec))
        bench["workloads"].append({"name": name, "config": "tiny-qwen2",
                                   "traffic": name, "chips": chips,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_plants(name: str, plants, seconds: float = 0.2) -> dict:
    """``correct`` and the compared numbers of one tiny run per plant."""
    import sys
    import tempfile
    import time

    from bench import harness
    if str(REPO / "src") not in sys.path:
        sys.path.insert(0, str(REPO / "src"))
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = make_root(Path(tmp))
        for plant in plants:
            res = harness.run_cell(root, name, 20261016, seconds, False,
                                   time.perf_counter(), require_chip=False,
                                   plant=plant)
            out[plant] = {"correct": res["correct"],
                          "attempted": res["attempted"],
                          "metrics": sorted(res["metrics"]),
                          "checks": res["checks"]}
    return out


def run_controls(name: str, seeds, faults=()) -> dict:
    """``correct`` of the bfloat16 reference, and of the float32 reference
    with each fault, put in the program's place, for each seed."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from bench import calibrate, compare, harness
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = make_root(Path(tmp))
        bench, entry, spec, config, traffic = harness.load_cell(root, name)
        for seed in seeds:
            cell = harness.Cell(root, name, entry, spec, config, traffic,
                                seed, jax.devices()[:entry["chips"]],
                                harness.Spans())
            nums, ref = calibrate.reference_in_place(cell, jnp.bfloat16)
            runs = {"control": nums}
            for f in faults:
                runs[f] = calibrate.reference_in_place(cell, jnp.float32, f,
                                                       ref)[0]
            for kind, nums in runs.items():
                correct, checks = compare.judge(nums, spec["limits"])
                out[f"{kind}.{seed}"] = {"correct": correct,
                                         "checks": checks}
    return out


if __name__ == "__main__":
    # the DDP cell needs four devices, so its tests run this in a child
    # process started with XLA_FLAGS=--xla_force_host_platform_device_count=4
    import sys
    if sys.argv[1] == "control":
        print(json.dumps(run_controls(sys.argv[2], [3, 4, 5],
                                      sys.argv[3].split(","))))
    else:
        print(json.dumps(run_plants(sys.argv[1], sys.argv[2].split(","))))
