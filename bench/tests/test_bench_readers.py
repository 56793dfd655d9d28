"""The data-parallel cell's per-layer readers on made-up runs: collective
device time per step, and the controller's share of compressed steps."""
from collections import Counter
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def reader(name):
    return harness.load_module(METRICS / f"{name}.py")


@pytest.mark.parametrize("op, counted", [
    ("all-reduce.7", True), ("all-gather-start.2", True),
    ("reduce-scatter-fusion.3", True), ("psum.21", True), ("psum", True),
    ("pmax.4", True), ("psum_scatter.1", True), ("fusion.375", False),
    ("sort.1", False), ("psum_fusion.2", False), ("copy-start", False)])
def test_collective_instruction_names(op, counted):
    assert reader("collective_ms_per_step").is_collective(op) == counted


def test_collective_ms_per_step_means_over_chips_and_steps():
    tr = {"window": [0, 10_000_000], "host": [],
          "devices": {"a": [[0, 2_000_000, "all-reduce.7"],
                            [2_000_000, 1_000_000, "psum.21"],
                            [3_000_000, 5_000_000, "sort.1"]],
                      "b": [[0, 4_000_000, "all-reduce.7"]]}}
    run = SimpleNamespace(trace=tr, done=[1.0, 2.0])
    # chip a 3 ms, chip b 4 ms: 3.5 ms of collectives over 2 steps
    assert reader("collective_ms_per_step").read(run) == pytest.approx(1.75)
    tr["devices"] = {"a": [[0, 5, "fusion.1"]]}
    assert reader("collective_ms_per_step").read(run) is None
    assert reader("collective_ms_per_step").read(
        SimpleNamespace(trace=None, done=[1.0])) is None


@pytest.mark.parametrize("counts, share", [
    ({"step.compressed": 5}, 100.0),
    ({"step.compressed": 1, "step.dense": 3}, 25.0),
    ({"step.dense": 2}, 0.0),
    ({}, None)])
def test_compressed_step_share(counts, share):
    run = SimpleNamespace(counts=Counter(counts))
    assert reader("compressed_step_share").read(run) == share
