"""The launcher's host loop under the profiler: ``--trace-dir`` writes one
trace holding each captured step's spans, named as the benchmark names them
(``bench/harness.SPANS``), and a capture that cannot start fails the run
instead of leaving it untraced."""
from collections import Counter

import pytest
from jax.profiler import ProfileData

from repro.launch import train
from repro.obs import capture

SPANS = ("train", "input", "dispatch", "metrics_read")


def test_trace_dir_captures_steps_one_to_the_last(tmp_path):
    out = train.run(train.parse_args(
        ["--arch", "qwen2-0.5b", "--reduced", "--steps", "4", "--batch", "2",
         "--seq", "32", "--scadles", "--trace-dir", str(tmp_path)]))
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    seen = Counter(e.name for p in ProfileData.from_file(str(files[0])).planes
                   if p.name.startswith("/host:") for line in p.lines
                   for e in line.events if e.name in SPANS)
    # step 0 runs before the capture; steps 1, 2 and 3 inside it
    assert seen == {name: 3 for name in SPANS}
    assert len(out["history"]) == 4
    assert all(isinstance(v, float)
               for h in out["history"] for v in h.values())


def test_capture_raises_when_the_profiler_cannot_start(tmp_path):
    with capture(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError):
            with capture(str(tmp_path / "inner")):
                pass
    assert list((tmp_path / "outer").rglob("*.xplane.pb"))
