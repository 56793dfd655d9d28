"""A copy of the benchmark at a size the CPU runs in seconds: the bench
tree and BENCHMARK.json copied to a temporary root, plus files for tiny
cells that the harness has never seen: two of a 2-layer, 64-wide
Qwen2-style model, and two of an architecture that only this copy holds
(``tiny_swa/``: its ``bench/arch`` and ``bench/reference`` modules, and a
reader of the FLOPs per token the harness took from it).  Nothing in the
harness names them."""
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

# the same sizes under an architecture of its own: no q/k/v biases, every
# layer over a sliding window
TINY_SWA = dict({k: v for k, v in TINY_CONFIG.items()
                 if k not in ("registry", "qkv_bias")},
                name="tiny-swa", model_type="tiny_swa", sliding_window=8)
SWA_FILES = {"arch.py": "arch/tiny_swa.py",
             "reference.py": "reference/tiny_swa.py",
             "flops_per_token.py": "metrics/flops_per_token.py"}


TRAIN_TRAFFIC = {"rows": 4, "seq_len": 32, "determinism": 0.8,
                 "streams": {"dist": "S1", "devices": 8,
                             "weights": "per_sample"}}
DDP_TRAFFIC = {"rows": 8, "seq_len": 32, "determinism": 0.8,
               "streams": {"dist": "S1", "devices": 4,
                           "weights": "per_device"}}
# each tiny cell mirrors a real one: its driver, optimizer and limits
MIRROR = {"tiny.train": ("train.qwen1.5-0.5b.seq2048", TINY_CONFIG,
                         TRAIN_TRAFFIC),
          "tiny.ddp": ("ddp4.qwen2-0.5b.adaptive", TINY_CONFIG,
                       DDP_TRAFFIC),
          "tiny.swa.train": ("train.qwen1.5-0.5b.seq2048", TINY_SWA,
                             TRAIN_TRAFFIC),
          "tiny.swa.ddp": ("ddp4.qwen2-0.5b.adaptive", TINY_SWA,
                           DDP_TRAFFIC)}


def make_root(tmp: Path) -> Path:
    """A checkout-like root under ``tmp`` with the tiny cells added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in (TINY_CONFIG, TINY_SWA):
        (root / "bench" / "configs" / f"{c['name']}.json").write_text(
            json.dumps(c))
    for src, dst in SWA_FILES.items():
        shutil.copy(Path(__file__).parent / "tiny_swa" / src,
                    root / "bench" / dst)
    bench["end_to_end"].append({
        "name": "flops_per_token", "unit": "FLOP", "better": "lower",
        "bound": 0.01, "source": "host_clock", "workloads": []})
    for name, (real, config, traffic) in MIRROR.items():
        spec = json.loads((REPO / "bench" / "workloads" /
                           f"{real}.json").read_text())
        chips = spec["chips"]
        spec.update(config=config["name"], traffic=name)
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(spec))
        bench["workloads"].append({"name": name, "config": config["name"],
                                   "traffic": name, "chips": chips,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()) or (
                    m["name"] == "flops_per_token" and config is TINY_SWA):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_plants(runs: dict, seconds: float = 0.2) -> dict:
    """``correct``, the metrics and the compared numbers of one tiny run
    per plant: ``runs`` is ``{cell: [plant, ...]}``, the result
    ``{cell: {plant: ...}}``."""
    import sys
    import tempfile
    import time

    from bench import harness
    if str(REPO / "src") not in sys.path:
        sys.path.insert(0, str(REPO / "src"))
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = make_root(Path(tmp))
        for name, plants in runs.items():
            for plant in plants:
                res = harness.run_cell(root, name, 20261016, seconds, False,
                                       time.perf_counter(),
                                       require_chip=False, plant=plant)
                out.setdefault(name, {})[plant] = {
                    "correct": res["correct"],
                    "attempted": res["attempted"],
                    "metrics": sorted(res["metrics"]),
                    "values": {k: v["value"]
                               for k, v in res["metrics"].items()},
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
        print(json.dumps(run_plants(json.loads(sys.argv[1]))))
