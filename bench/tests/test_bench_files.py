"""Every file the benchmark finds by name is there and agrees with
``BENCHMARK.json``, and ``BENCHMARK.json`` keeps to its own format."""
import json
import re
from pathlib import Path

import pytest

from bench import harness
from bench.traffic import Traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_lines():
    items = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + \
        BENCH["per_layer"]
    for it in items:
        assert NAME.match(it["name"]), it["name"]
        for key in ("why", "layer", "source"):
            if key in it:
                assert 1 <= len(it[key]) <= 200 and "\n" not in it[key]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len({m["name"] for m in items}) == len(items)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    bench, entry, spec, config, traffic = harness.load_cell(ROOT, cell)
    assert (ROOT / "bench" / "drivers" / f"{spec['driver']}.py").is_file()
    assert config["name"] == entry["config"]
    assert config["reduced"] == next(
        c["reduced"] for c in BENCH["configs"] if c["name"] == entry["config"])
    assert set(spec["limits"]) >= {"loss_gap", "grad_norm_gap",
                                   "change_norm_gap"}
    assert spec["check_steps"] >= 2
    gen = Traffic(traffic, config["vocab_size"], 2**33 + 5)
    b = gen.batch(0)
    assert b["tokens"].shape == (traffic["rows"], traffic["seq_len"])
    assert (b["tokens"] < config["vocab_size"]).all()
    # every cell reports setup_s, another end-to-end metric and a per-layer
    e2e = [m["name"] for m in harness.metrics_for(bench, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(bench, cell, True)


def test_every_metric_has_a_reader_and_every_layer_moves_a_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = harness.load_module(ROOT / "bench" / "metrics" /
                                  f"{m['name']}.py")
        assert callable(mod.read)
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        # each cell that reports the layer metric reports what it moves
        for cell in m.get("workloads", CELLS):
            assert cell in moved.get("workloads", CELLS)


def test_configs_name_their_files():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["source"] == c["source"]


def test_traffic_repeats_for_a_seed_and_keeps_its_sizes():
    spec = {"rows": 3, "seq_len": 16,
            "streams": {"dist": "S1", "devices": 8, "weights": "per_sample"}}
    a = [Traffic(spec, 100, 7).batch(i) for i in range(1)]
    b = Traffic(spec, 100, 7)
    c = Traffic(spec, 100, 2**40 + 7)
    for x, y in zip(a, [b.batch(0)]):
        for k in x:
            assert (x[k] == y[k]).all()
    other = c.batch(0)
    assert {k: v.shape for k, v in other.items()} == \
        {k: v.shape for k, v in a[0].items()}
    assert not (other["tokens"] == a[0]["tokens"]).all()
    assert abs(float(a[0]["sample_weights"].sum()) - 1.0) < 1e-6
    with pytest.raises(ValueError):
        b.batch(5)


def test_ddp_cells_hold_picks_and_few_cells_take_four_chips():
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 2), four
    for cell in CELLS:
        spec = json.loads((ROOT / "bench" / "workloads" /
                           f"{cell}.json").read_text())
        if spec["driver"] == "ddp_adaptive":
            assert spec["limits"]["picks_differ"] == 0, cell


WORKLOAD_FILES = sorted(p.stem for p in (ROOT / "bench" / "workloads")
                        .glob("*.json"))


@pytest.mark.parametrize("cell", WORKLOAD_FILES)
def test_workload_files_name_their_steps_scopes(cell):
    spec = json.loads((ROOT / "bench" / "workloads" /
                       f"{cell}.json").read_text())
    assert isinstance(spec["scopes"], list) and spec["scopes"]
    assert len(set(spec["scopes"])) == len(spec["scopes"])
    assert all(NAME.match(s) for s in spec["scopes"])


def test_scope_metrics_name_only_cells_with_their_scope():
    readers = [m for m in BENCH["per_layer"]
               if m["name"].endswith("_ms_per_step")
               and (ROOT / "bench" / "metrics" / f"{m['name']}.py")
               .read_text().count("scopes.ms_per_step(")]
    assert {m["name"] for m in readers} >= {
        f"{s}_ms_per_step" for s in ("attention", "mlp", "vocab",
                                     "optimizer", "topk", "scatter_add")}
    for m in readers:
        scope = m["name"][:-len("_ms_per_step")]
        assert m["source"] == "device_trace" and m["unit"] == "ms"
        for cell in m["workloads"]:
            spec = json.loads((ROOT / "bench" / "workloads" /
                               f"{cell}.json").read_text())
            assert scope in spec["scopes"], (m["name"], cell)


# the sizes a Qwen2 configuration file states; only the qwen2 architecture
# and reference modules, the configuration files and tests read them
ARCH_KEYS = ("registry", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "qkv_bias", "rope_theta",
             "rms_norm_eps", "tie_word_embeddings")
ARCH_FILES = {"bench/arch/qwen2.py", "bench/reference/qwen2.py"}


def test_only_the_architecture_modules_read_its_shapes():
    for path in sorted((ROOT / "bench").rglob("*.py")):
        rel = path.relative_to(ROOT).as_posix()
        if rel in ARCH_FILES or rel.startswith("bench/tests/"):
            continue
        text = path.read_text()
        for key in ARCH_KEYS:
            assert f'"{key}"' not in text and f"'{key}'" not in text, \
                (rel, key)
