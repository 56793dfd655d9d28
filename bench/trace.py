"""From a profiler trace to the numbers the per-layer metrics read.

``read`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
dict of plain lists (``bench/scopes.load`` adds the window), and the
reductions below work on that dict alone:

    {"window": [start_ns, end_ns],           # the harness's "window" span
     "devices": {"/device:TPU:0": [[start_ns, dur_ns, op], ...], ...},
     "host": [[start_ns, dur_ns, span], ...]}  # the benchmark's host spans

Device events are those of each TPU plane's "XLA Ops" line; ``op`` is the
HLO instruction's name (``%fusion.12 = f32[...] ...`` gives ``fusion.12``).
Host and device events share the trace's clock.

- busy time of a chip: the union of its op intervals inside the window;
- idle share: 1 - busy / window, averaged over the chips;
- op time: the summed self time of each op name (an op nested inside
  another on the line, as a loop's body ops inside the ``while``, is taken
  out of its parent), averaged over the chips;
- idle by host span: each gap between busy intervals is charged to the
  innermost of the benchmark's host spans that covers the gap's midpoint
  ("none" when no span does);
- collective time: the summed self time of ops whose instruction is an XLA
  collective (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all, their async -start/-done halves and fusions named after them).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def read(path: str, spans: Iterable[str]) -> dict:
    """Every "XLA Ops" event of each TPU plane, and every host event named
    in ``spans``: ``{"devices": {...}, "host": [...]}``."""
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(path).planes, spans)


def from_planes(planes, spans: Iterable[str]) -> dict:
    """``read`` of a trace's planes (``ProfileData.planes``)."""
    keep = set(spans)
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([e.start_ns, e.duration_ns, op_name(e.name)]
                               for e in line.events)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.duration_ns, e.name]
                            for e in line.events if e.name in keep)
    return {"devices": devices, "host": host}


def _union(intervals: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(tr: dict) -> Dict[str, float]:
    """Busy seconds of each chip inside the window."""
    lo, hi = tr["window"]
    return {dev: sum(e - s for s, e in _union(
        [(s, s + d) for s, d, _ in evs], lo, hi)) / 1e9
        for dev, evs in tr["devices"].items()}


def window_s(tr: dict) -> float:
    lo, hi = tr["window"]
    return (hi - lo) / 1e9


def idle_share(tr: dict) -> float:
    """1 - busy / window, mean over chips, as a fraction."""
    b = busy(tr)
    if not b:
        raise RuntimeError("the trace holds no device")
    w = window_s(tr)
    return sum(1.0 - v / w for v in b.values()) / len(b)


def self_seconds(evs: Sequence[list], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds per op name of one chip's ops starting in [lo, hi), each op
    counted by its self time: an op inside another on the line (the body of
    a ``while`` inside the loop) is taken out of its parent's time."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, child time] of open ops

    def close(top):
        end, name, child, dur = top
        out[name] = out.get(name, 0.0) + (dur - child) / 1e9

    for s, d, name in sorted(evs, key=lambda e: (e[0], -e[1])):
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][2] += d
        if lo <= s < hi:
            stack.append([s + d, name, 0.0, d])
        else:
            stack.append([s + d, None, 0.0, d])
    while stack:
        close(stack.pop())
    out.pop(None, None)
    return out


def op_seconds(tr: dict) -> Dict[str, float]:
    """Self seconds per op name inside the window, mean over chips."""
    lo, hi = tr["window"]
    out: Dict[str, float] = {}
    n = max(len(tr["devices"]), 1)
    for evs in tr["devices"].values():
        for name, v in self_seconds(evs, lo, hi).items():
            out[name] = out.get(name, 0.0) + v / n
    return out


def collective_seconds(tr: dict) -> float:
    return sum(v for k, v in op_seconds(tr).items() if COLLECTIVE.match(k))


def idle_by_span(tr: dict) -> Dict[str, float]:
    """Idle seconds charged to the innermost host span over each gap, mean
    over chips.  The spans nest or follow one another, so the innermost one
    over a point is the latest-starting one that still covers it."""
    lo, hi = tr["window"]
    host = sorted(tr["host"])
    starts = [h[0] for h in host]
    out: Dict[str, float] = {}
    n = max(len(tr["devices"]), 1)

    def span_at(t):
        j = bisect.bisect_right(starts, t) - 1
        for i in range(j, max(j - 16, -1), -1):   # spans nest a few deep
            s, d, name = host[i]
            if t < s + d:
                return name
        return "none"

    for evs in tr["devices"].values():
        merged = _union([(s, s + d) for s, d, _ in evs], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = span_at((a + b) / 2)
                out[name] = out.get(name, 0.0) + (b - a) / 1e9 / n
    return out


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
