"""The step's named scopes and the device clock, from a profiler trace:
what ``bench/trace.py``'s reductions do not read.  The harness reads a
traced run's trace with ``load``, and the scope metrics
(``bench/metrics/<scope>_ms_per_step.py``) read it with ``ms_per_step``.

``read`` gives ``bench/trace.read``'s dict with three more keys:

    {"op_paths": {"/device:TPU:0": [path, ...], ...},  # XLA's ``tf_op``
     "modules": {"/device:TPU:0": [[start_ns, dur_ns, run_id], ...], ...},
     "launches": [[start_ns, dur_ns, name, run_id, device_ordinal], ...]}

``op_paths`` gives the path of each event of ``devices``, in order: the
op's name stack in the program (``jax.named_scope``), from its own
instruction's metadata in the trace (``bench/xspace.py``), "" where it has
none.  An event keeps its own path because an op name is unique only
within one HLO module, and two programs may run in one window.
``modules`` are each plane's "XLA Modules" events, one per run of a
program; ``launches`` the host's ``DoEnqueueProgram`` and
``CompleteCallbacks`` events of the same runs.

- scope time: each op's self time charged to the innermost of the given
  scope names in its path ("unscoped" when none is), mean over chips.  A
  cell's workload file lists its step's scopes (``"scopes"``); a name it
  leaves out is no scope, and its ops go to the scope around it;
- clock offset: the device clock runs early against the host's by an
  amount no run can contradict: a program starts on the device after the
  host began to enqueue it, and ends before the host's completion callback
  starts.  Each run bounds the offset from below (enqueue start - device
  start) and from above (callback start - device end);
- idle by host span on the aligned clock: ``bench/trace.idle_by_span`` with
  each chip's ops shifted by the midpoint of its bracket.

Run on a trace file, it prints these reductions as one JSON line:

    python3 bench/scopes.py TRACE.xplane.pb

The window is the benchmark's "window" span where the trace has one, else
the launcher's captured steps (``python -m repro.launch.train --trace-dir
DIR``: one ``train`` span a step); a step is a ``dispatch`` span in it.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

if __package__ in (None, ""):       # run as a script from the checkout
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace, xspace  # noqa: E402

MODULES_LINE = "XLA Modules"
LAUNCH_EVENTS = ("DoEnqueueProgram", "CompleteCallbacks")
# the launcher's training step's named scopes (``src/repro``), which the
# command line reads; a benchmark cell names its own in its workload file
STEP_SCOPES = ("attention", "mlp", "vocab", "optimizer")
SPANS = ("window", "train", "input", "dispatch", "metrics_read")


def read(path: str, spans: Iterable[str]) -> dict:
    """``bench/trace.read``, with the ``op_paths``, ``modules`` and
    ``launches`` of the module's docstring."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)       # read twice below
    tr = trace.from_planes(planes, spans)
    with open(path, "rb") as f:
        paths = xspace.event_stats(f.read(), "tf_op", trace.DEVICE_PREFIX)
    paths = {plane: {text: p[:-1] if p.endswith(":") else p
                     for text, p in named.items()}
             for plane, named in paths.items()}
    modules: Dict[str, list] = {}
    op_paths: Dict[str, List[str]] = {}
    launches: List[list] = []
    for plane in planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            runs = [[e.start_ns, e.duration_ns, dict(e.stats).get("run_id")]
                    for line in plane.lines if line.name == MODULES_LINE
                    for e in line.events]
            modules[plane.name] = [r for r in runs if r[2] is not None]
            named = paths.get(plane.name, {})
            op_paths[plane.name] = [
                named.get(e.name, "") for line in plane.lines
                if line.name == trace.OPS_LINE for e in line.events]
            if len(op_paths[plane.name]) != len(tr["devices"][plane.name]):
                raise RuntimeError(f"{plane.name}: ops read twice differ")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in LAUNCH_EVENTS:
                        st = dict(e.stats)
                        if "run_id" in st:
                            launches.append([e.start_ns, e.duration_ns,
                                             e.name, st["run_id"],
                                             st.get("device_ordinal")])
    return dict(tr, op_paths=op_paths, modules=modules, launches=launches)


def load(path: str, spans: Iterable[str] = SPANS) -> dict:
    """``read`` of ``spans`` with a window: the "window" span, or from the
    first ``train`` span's start to the last one's end.  Neither stays
    among the host spans."""
    tr = read(path, set(spans) | {"window", "train"})
    windows = [h for h in tr["host"] if h[2] == "window"]
    if len(windows) > 1:
        raise RuntimeError(f"{len(windows)} 'window' spans in the trace")
    steps = [h for h in tr["host"] if h[2] == "train"]
    if windows:
        s, d, _ = windows[0]
        lo, hi = s, s + d
    elif steps:
        lo = min(h[0] for h in steps)
        hi = max(h[0] + h[1] for h in steps)
    else:
        raise RuntimeError("no 'window' or 'train' span in the trace")
    return dict(tr, window=[lo, hi],
                host=[h for h in tr["host"] if h[2] not in ("window", "train")])


def steps_in(tr: dict) -> int:
    lo, hi = tr["window"]
    return sum(1 for s, _, name in tr["host"]
               if name == "dispatch" and lo <= s < hi)


def scope_of(path: str, names: Sequence[str]) -> str:
    """The innermost of ``names`` in an op's ``path``, or "unscoped".  A
    name counts as a whole ``/``-separated segment, or wrapped in a
    transform's name (``jvp(vocab)``, ``transpose(jvp(attention))``)."""
    for seg in reversed(path.split("/")):
        m = _WRAPPED.match(seg)
        if m and m.group(1) in names:
            return m.group(1)
    return "unscoped"


_WRAPPED = re.compile(r"^(?:\w+\()*([^()]+)\)*$")


def scope_op_seconds(tr: dict, names: Sequence[str]
                     ) -> Dict[str, Dict[str, float]]:
    """``{scope: {op: self seconds}}`` inside the window, mean over chips,
    each op under ``scope_of`` its path."""
    lo, hi = tr["window"]
    out: Dict[str, Dict[str, float]] = {}
    n = max(len(tr["devices"]), 1)
    for dev, evs in tr["devices"].items():
        keyed = [[s, d, (op, path)] for (s, d, op), path
                 in zip(evs, tr["op_paths"][dev])]
        for (op, path), v in trace.self_seconds(keyed, lo, hi).items():
            ops = out.setdefault(scope_of(path, names), {})
            ops[op] = ops.get(op, 0.0) + v / n
    return out


def scope_seconds(tr: dict, names: Sequence[str]) -> Dict[str, float]:
    """Self seconds per scope (``scope_op_seconds``), mean over chips."""
    return {k: sum(v.values())
            for k, v in scope_op_seconds(tr, names).items()}


def step_scope_ms(tr: Optional[dict], names: Sequence[str], steps: int
                  ) -> Optional[Dict[str, float]]:
    """Device ms per step in each of ``names`` and "unscoped"; None
    without a trace, or where no op in the window is under any of them (a
    program that names no scope, or a trace without paths)."""
    if tr is None or not steps:
        return None
    secs = scope_seconds(tr, names)
    if not set(secs) - {"unscoped"}:
        return None
    return {k: secs.get(k, 0.0) / steps * 1e3
            for k in tuple(names) + ("unscoped",)}


def ms_per_step(run, name: str) -> Optional[float]:
    """A scope metric's reading of a run (``bench/harness.Run``): device
    self ms per window step in scope ``name``, mean over chips, each op
    charged to the innermost of the cell's ``"scopes"``.  None where the
    cell does not list ``name``, the run was not traced, or no op of the
    window lies in the scope."""
    names = tuple(run.cell.spec["scopes"])
    if name not in names or run.trace is None or not run.done:
        return None
    if _last[0] is not run.trace or _last[1] != names:
        _last[:] = [run.trace, names, scope_seconds(run.trace, names)]
    secs = _last[2]
    return secs[name] / len(run.done) * 1e3 if name in secs else None


# the last trace, scope names and ``scope_seconds`` that ``ms_per_step``
# read: a run's readers share one pass over its ops
_last: list = [None, None, None]


def clock_offset_ns(tr: dict) -> Dict[str, dict]:
    """Per chip, how early its clock runs against the host's:
    ``{"bracket": [lo, hi], "offset": (lo + hi) / 2, "pairs": n}`` in ns,
    ``n`` the runs that bound it from below.  Where no run bounds it on
    either side, or the bounds cross, ``offset`` is 0 and ``pairs`` 0."""
    enq: Dict[tuple, float] = {}
    done: Dict[tuple, float] = {}
    for s, _, name, run, ordinal in tr.get("launches", []):
        first = enq if name == "DoEnqueueProgram" else done
        key = (ordinal, run)
        first[key] = min(first.get(key, s), s)
    out = {}
    for dev, runs in tr.get("modules", {}).items():
        m = re.match(r"\d+", dev[len(trace.DEVICE_PREFIX):])
        ordinal = int(m.group()) if m else None
        lows, highs = [], []
        for s, d, run in runs:
            key = (ordinal, run)
            if key in enq:
                lows.append(enq[key] - s)
            if key in done:
                highs.append(done[key] - (s + d))
        if lows and highs and max(lows) <= min(highs):
            lo, hi = max(lows), min(highs)
            out[dev] = {"bracket": [lo, hi], "offset": (lo + hi) / 2,
                        "pairs": len(lows)}
        else:
            out[dev] = {"bracket": None, "offset": 0.0, "pairs": 0}
    return out


def clock_summary(clock: Dict[str, dict]) -> dict:
    """``clock_offset_ns`` over the chips, in ms: the mean offset applied,
    the envelope of the chips' brackets, and the fewest runs that bound
    one."""
    if not clock:
        return {"clock_offset_ms": 0.0, "clock_bracket_ms": None,
                "clock_pairs": 0}
    c = list(clock.values())
    brackets = [x["bracket"] for x in c if x["bracket"] is not None]
    return {"clock_offset_ms": sum(x["offset"] for x in c) / len(c) / 1e6,
            "clock_bracket_ms": [min(b[0] for b in brackets) / 1e6,
                                 max(b[1] for b in brackets) / 1e6]
            if len(brackets) == len(c) else None,
            "clock_pairs": min(x["pairs"] for x in c)}


def idle_by_span_aligned(tr: dict) -> Dict[str, float]:
    """``bench/trace.idle_by_span`` with each chip's ops moved onto the
    host's clock by its ``clock_offset_ns``."""
    off = clock_offset_ns(tr)
    shifted = {dev: [[s + off.get(dev, {"offset": 0.0})["offset"], d, n]
                     for s, d, n in evs]
               for dev, evs in tr["devices"].items()}
    return trace.idle_by_span(dict(tr, devices=shifted))


def summary(tr: dict, names: Sequence[str] = STEP_SCOPES) -> dict:
    """Everything above for one windowed trace, per window step."""
    steps = steps_in(tr)
    n = max(len(tr["devices"]), 1)
    busy_s = sum(trace.busy(tr).values()) / n
    out = {"steps": steps, "window_s": trace.window_s(tr), "busy_s": busy_s,
           "scope_ms_per_step": step_scope_ms(tr, names, steps),
           **clock_summary(clock_offset_ns(tr)),
           "idle_gaps": trace.top(trace.idle_by_span(tr)),
           "idle_gaps_aligned": trace.top(idle_by_span_aligned(tr))}
    if out["scope_ms_per_step"] is not None:
        rest = scope_op_seconds(tr, names).get("unscoped", {})
        dev = next(iter(tr["devices"]))
        paths = {op: p for (_, _, op), p
                 in zip(tr["devices"][dev], tr["op_paths"][dev])}
        out["unscoped_ops"] = [[op, v, paths.get(op, "")]
                               for op, v in trace.top(rest)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a profiler's .xplane.pb")
    args = ap.parse_args(argv)
    print(json.dumps(summary(load(args.trace))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
