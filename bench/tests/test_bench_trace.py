"""The reduction from a profiler trace to the per-layer numbers, on a small
trace recorded on one v5e chip (``data/tpu_small.xplane.pb``): three rounds
of an ``input`` span (an add, and a 2 ms host sleep), a ``dispatch`` span
that launches two small programs, and a ``metrics_read`` span that reads
both results.  Expected values are worked out here another way: busy time on
a 1 us grid, op time by a plain sum."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "tpu_small.xplane.pb"
SPANS = ("input", "dispatch", "metrics_read")


@pytest.fixture(scope="module")
def tr():
    raw = trace.read(str(DATA), SPANS)
    # the recording has no "window" span: the three rounds are the window
    lo = min(h[0] for h in raw["host"])
    hi = max(h[0] + h[1] for h in raw["host"])
    return dict(raw, window=[lo, hi])


def test_reads_one_chip_and_the_host_spans(tr):
    assert list(tr["devices"]) == ["/device:TPU:0"]
    ops = tr["devices"]["/device:TPU:0"]
    assert len(ops) == 15
    assert {o[2] for o in ops} == {"broadcast_add_fusion", "copy-start",
                                   "copy-done", "fusion",
                                   "add_reduce_fusion"}
    names = [h[2] for h in sorted(tr["host"])]
    assert names == list(SPANS) * 3


def test_op_name_parses_the_instruction():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert trace.op_name("%all-reduce-start.3 = (f32[4]) all-reduce-"
                         "start(%x)") == "all-reduce-start.3"


def test_busy_and_idle_match_a_grid(tr):
    lo, hi = tr["window"]
    grid = np.zeros(int((hi - lo) // 1000) + 2, bool)
    for s, d, _ in tr["devices"]["/device:TPU:0"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    by_grid = grid.sum() * 1e-6
    busy = trace.busy(tr)["/device:TPU:0"]
    # each of the 15 ops may gain up to 2 us on the grid
    assert by_grid - 15 * 2e-6 <= busy <= by_grid
    assert trace.window_s(tr) == pytest.approx((hi - lo) / 1e9)
    assert trace.idle_share(tr) == pytest.approx(1 - busy / trace.window_s(tr))


def test_op_seconds_sum_each_name(tr):
    ops = trace.op_seconds(tr)
    want = {}
    for s, d, n in tr["devices"]["/device:TPU:0"]:
        want[n] = want.get(n, 0.0) + d / 1e9
    assert ops == pytest.approx(want)
    assert trace.top(ops, 2) == [[k, v] for k, v in sorted(
        want.items(), key=lambda kv: -kv[1])[:2]]
    assert trace.collective_seconds(tr) == 0.0


def test_idle_time_is_charged_to_host_spans(tr):
    idle = trace.idle_by_span(tr)
    assert set(idle) <= set(SPANS) | {"none"}
    total_idle = trace.window_s(tr) - trace.busy(tr)["/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx(total_idle)
    # the host sleeps and builds inputs while the chip waits: most idle time
    # falls in the input spans
    assert idle["input"] > 0.5 * total_idle


def test_collectives_are_matched_by_instruction_name():
    tr = {"window": [0, 100], "host": [],
          "devices": {"a": [[0, 10, "all-reduce.1"], [10, 5, "fusion.2"],
                            [20, 4, "all-gather-start"],
                            [30, 6, "reduce-scatter-fusion.3"],
                            [40, 2, "copy-start"]],
                      "b": [[0, 20, "all-reduce.1"]]}}
    # mean over the two chips: (10 + 4 + 6) and 20, in seconds
    assert trace.collective_seconds(tr) == pytest.approx(20e-9)
    assert trace.busy(tr) == {"a": pytest.approx(27e-9),
                              "b": pytest.approx(20e-9)}


def test_nested_ops_count_their_self_time():
    # a while loop (0..100) holding two body ops, then an op after it
    tr = {"window": [0, 200], "host": [],
          "devices": {"a": [[0, 100, "while.1"], [10, 30, "fusion.1"],
                            [50, 20, "fusion.2"], [120, 10, "fusion.3"]]}}
    assert trace.op_seconds(tr) == {"while.1": pytest.approx(50e-9),
                                    "fusion.1": pytest.approx(30e-9),
                                    "fusion.2": pytest.approx(20e-9),
                                    "fusion.3": pytest.approx(10e-9)}
    assert trace.busy(tr)["a"] == pytest.approx(110e-9)
