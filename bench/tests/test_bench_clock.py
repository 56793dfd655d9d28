"""The device clock against the host's, and the reductions of
``bench/trace.py`` beside ``bench/scopes.py``'s additions, on the small
trace recorded on one v5e chip (``data/tpu_small.xplane.pb``, described in
``test_bench_trace.py``)."""
from pathlib import Path

import pytest

from bench import scopes, trace

DATA = Path(__file__).parent / "data" / "tpu_small.xplane.pb"
SPANS = ("input", "dispatch", "metrics_read")


@pytest.fixture(scope="module")
def tr():
    raw = scopes.read(str(DATA), SPANS)
    lo = min(h[0] for h in raw["host"])
    hi = max(h[0] + h[1] for h in raw["host"])
    return dict(raw, window=[lo, hi])


def test_reductions_read_as_they_did_before_the_clock_and_scopes(tr):
    # ``scopes.read`` keeps ``trace.read``'s keys as they were, and the
    # reductions give the numbers they gave before it existed
    plain = trace.read(str(DATA), SPANS)
    assert {k: tr[k] for k in plain} == plain
    assert tr["window"] == [47613819.0, 149161638.0]
    assert trace.busy(tr) == {"/device:TPU:0": 0.000114484}
    assert trace.idle_share(tr) == 0.9988726099572852
    assert trace.op_seconds(tr) == {
        "broadcast_add_fusion": 3.7638999999999997e-05,
        "copy-start": 4e-08, "copy-done": 1.8159e-05,
        "fusion": 3.5453999999999996e-05,
        "add_reduce_fusion": 2.3192e-05}
    assert trace.idle_by_span(tr) == {"input": 0.09879197899999999,
                                      "dispatch": 0.002641356}


def test_each_run_bounds_the_device_clocks_offset(tr):
    assert [r[2] for r in tr["modules"]["/device:TPU:0"]] == \
        list(range(5, 14))
    clock = scopes.clock_offset_ns(tr)["/device:TPU:0"]
    lo, hi = clock["bracket"]
    # the device starts each program 1.61-1.72 ms before the host enqueued
    # it, and ends it 1.86-2.45 ms before the host's completion callback
    assert lo / 1e6 == pytest.approx(1.718, abs=1e-3)
    assert hi / 1e6 == pytest.approx(1.860, abs=1e-3)
    assert clock["offset"] == pytest.approx((lo + hi) / 2)
    assert clock["pairs"] == 9


def test_no_pairs_give_no_offset(tr):
    assert scopes.clock_offset_ns(dict(tr, launches=[])) == {
        "/device:TPU:0": {"bracket": None, "offset": 0.0, "pairs": 0}}
    # bounds that cross say the runs were not matched right: no offset
    late = [[s + 10e6 if n == "DoEnqueueProgram" else s, d, n, r, o]
            for s, d, n, r, o in tr["launches"]]
    assert scopes.clock_offset_ns(dict(tr, launches=late))[
        "/device:TPU:0"]["pairs"] == 0
    assert scopes.idle_by_span_aligned(dict(tr, launches=[])) == \
        trace.idle_by_span(tr)


def test_idle_time_on_the_aligned_clock(tr):
    off = scopes.clock_offset_ns(tr)["/device:TPU:0"]["offset"]
    idle = scopes.idle_by_span_aligned(tr)
    shifted = {"/device:TPU:0": [[s + off, d, n] for s, d, n in
                                 tr["devices"]["/device:TPU:0"]]}
    assert idle == trace.idle_by_span(dict(tr, devices=shifted))
    # the whole idle time is charged, and now the host's read of the
    # results waits on the chip too
    total = trace.window_s(tr) - trace.busy(dict(tr, devices=shifted))[
        "/device:TPU:0"]
    assert sum(idle.values()) == pytest.approx(total)
    assert idle["metrics_read"] > 0
    # busy time and idle share are not shifted
    assert trace.idle_share(tr) == 0.9988726099572852


def test_the_summary_sums_up_the_chips_clocks(tr):
    one = scopes.clock_summary(scopes.clock_offset_ns(tr))
    assert one["clock_pairs"] == 9
    assert one["clock_bracket_ms"] == pytest.approx([1.717871, 1.859771])
    assert one["clock_offset_ms"] == pytest.approx(1.788821)
    two = scopes.clock_summary({
        "a": {"bracket": [1e6, 2e6], "offset": 1.5e6, "pairs": 4},
        "b": {"bracket": [1.2e6, 2.4e6], "offset": 1.8e6, "pairs": 6}})
    assert two == {"clock_offset_ms": pytest.approx(1.65),
                   "clock_bracket_ms": [1.0, 2.4], "clock_pairs": 4}
    assert scopes.clock_summary({
        "a": {"bracket": None, "offset": 0.0, "pairs": 0}}) == {
        "clock_offset_ms": 0.0, "clock_bracket_ms": None, "clock_pairs": 0}
