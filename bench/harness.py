"""The benchmark's harness: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Everything that belongs to one cell is found by name, so a later cell,
configuration, traffic mix or metric needs new files and no edit here:

- ``BENCHMARK.json``: the cell's entry (configuration, traffic, chips) and
  the metrics it reports;
- ``bench/workloads/<cell>.json``: the driver, the optimizer and schedule,
  the number of steps the reference follows, the limits of ``correct``;
- ``bench/configs/<config>.json``: the model's published sizes, and its
  ``model_type``, by which the two files of its architecture are found:
- ``bench/arch/<model_type>.py``: what touches the program, ``model_config
  (c)`` (the program's configuration), ``make_init(c, dtype)`` (weights
  from the seed in the program's layout) and ``train_flops_per_token(c,
  seq_len)``;
- ``bench/reference/<model_type>.py``: the plain reference,
  ``weighted_loss(params, tokens, labels, row_weights, c)``, which imports
  nothing of the program;
- ``bench/traffic/<traffic>.json``: parameters of ``bench/traffic.py``;
- ``bench/drivers/<driver>.py``: ``setup(cell) -> session`` builds the
  program's step and its state, warms every shape and runs the first steps
  that the reference will follow; ``session.step()`` runs one step of the
  window; ``session.free()`` drops the program's state;
  ``session.check()`` runs the reference and returns the compared numbers;
- ``bench/metrics/<metric>.py``: ``read(run) -> float | None``.  A traced
  run carries ``bench/scopes.read``'s trace, with each op's named-scope
  path; the workload file's ``"scopes"`` names the step's scopes.

A run: the look for the chip, set-up (``setup_s`` counts from the process's
start to the first timed step), the window of ``--seconds`` (steps are
timed on the host clock; with ``--trace 1`` a shorter one, under the
profiler: ``window_over``), the peak
memory, then the program's state is freed and the reference decides
``correct``.  The last line of stdout is the result; the numbers compared,
each beside its limit, are the last lines of stderr and the result's last
key.  With no TPU, fewer chips than the cell asks for, or a device missing
from ``bench/peaks.json``, it exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
SPANS = ("window", "input", "dispatch", "metrics_read", "controller",
         "step.dense", "step.compressed")
EXIT_NO_CHIP = 3
TRACE_STEPS, TRACE_SECONDS = 3, 3.0


class NoChip(RuntimeError):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of ``bench/`` by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(root: Path, c: dict, part: str):
    """``bench/<part>/<model_type>.py`` of configuration ``c``: ``part``
    "arch" or "reference".  A ``model_type`` without its file is an error
    that names the file."""
    path = root / "bench" / part / f"{c['model_type']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {c['name']!r} has model_type "
            f"{c['model_type']!r}, and there is no {path.relative_to(root)}")
    return load_module(path)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, with
    sub-second compiles cached too.  Set before JAX is imported, so that
    the program, which takes ``JAX_COMPILATION_CACHE_DIR`` when it is set,
    uses the same directory."""
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileMeter:
    """XLA compile seconds and count, from JAX's monitoring events (after
    ``chip_smoke.CompileMeter``)."""

    def __init__(self):
        from jax import monitoring
        self.compile_s, self.compiles = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1


class Spans:
    """Host spans: each is a ``jax.profiler.TraceAnnotation`` (so a traced
    run sees it beside the device's ops) and, while ``on``, a host-clock
    duration; ``count`` keeps counters the same way."""

    def __init__(self):
        import jax.profiler
        self._annotation = jax.profiler.TraceAnnotation
        self.on = False
        self.seconds: Dict[str, List[float]] = defaultdict(list)
        self.counts: Counter = Counter()

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            yield
        if self.on:
            self.seconds[name].append(time.perf_counter() - t0)

    def count(self, name: str, n: int = 1) -> None:
        if self.on:
            self.counts[name] += n


class GcClock:
    """Seconds the garbage collector ran, and its full collections, while
    open (``gc.callbacks``)."""

    def __init__(self):
        self.seconds, self.full, self._t0 = 0.0, 0, None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.full += info.get("generation") == 2

    def close(self):
        gc.callbacks.remove(self._cb)


@dataclasses.dataclass
class Cell:
    """What a driver is given."""
    root: Path
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    spec: dict           # bench/workloads/<name>.json
    config: dict         # bench/configs/<config>.json
    traffic: dict        # bench/traffic/<traffic>.json
    seed: int
    devices: list
    spans: Spans
    plant: str = ""      # a fault planted under the timed path (tests only)

    @functools.cached_property
    def arch(self):
        """``bench/arch/<model_type>.py`` of the cell's configuration."""
        return model_module(self.root, self.config, "arch")

    @functools.cached_property
    def reference(self):
        """``bench/reference/<model_type>.py``: the plain reference."""
        return model_module(self.root, self.config, "reference")


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""
    cell: Cell
    setup_s: float
    compile_s: float                 # compile seconds during set-up
    t_start: float                   # window start, host clock
    done: List[float]                # each window step's completion
    tokens_per_step: int             # over all of the cell's chips
    flops_per_token: float
    peak: Optional[dict]             # bench/peaks.json row, None off-chip
    trace: Optional[dict] = None     # bench/scopes.read's dict, traced runs

    @property
    def chips(self) -> int:
        return len(self.cell.devices)

    @property
    def spans(self) -> Dict[str, List[float]]:
        return self.cell.spans.seconds

    @property
    def counts(self) -> Counter:
        return self.cell.spans.counts

    @property
    def tokens_per_s(self) -> Optional[float]:
        if not self.done:
            return None
        return len(self.done) * self.tokens_per_step / (
            self.done[-1] - self.t_start)


def load_cell(root: Path, name: str):
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = load_json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {spec[key]!r} in its file "
                             f"and {entry[key]!r} in BENCHMARK.json")
    config = load_json(root / "bench" / "configs" / f"{entry['config']}.json")
    traffic = load_json(root / "bench" / "traffic" /
                        f"{entry['traffic']}.json")
    return bench, entry, spec, config, traffic


def metrics_for(bench: dict, name: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def window_over(elapsed: float, steps: int, seconds: float,
                traced: bool) -> bool:
    """A window runs whole steps for ``seconds``.  Under the profiler it
    stops sooner, once it has run ``TRACE_STEPS`` steps and
    ``TRACE_SECONDS``: a trace of a step holds every op of every layer, and
    a whole window of them would take longer to read than a run may."""
    if elapsed >= seconds:
        return True
    return traced and steps >= TRACE_STEPS and elapsed >= TRACE_SECONDS


def find_chips(chips: int, root: Path):
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found {d.platform!r} ({d.device_kind}), no TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks = load_json(root / "bench" / "peaks.json")
    if d.device_kind not in peaks:
        raise NoChip(f"{d.device_kind!r} is not in bench/peaks.json")
    return devices[:chips], peaks[d.device_kind]


def run_cell(root: Path, name: str, seed: int, seconds: float, traced: bool,
             t_proc: float, require_chip: bool = True,
             plant: str = "") -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    ``require_chip=False`` skips the look for a chip and runs on JAX's
    default devices (the CPU tests); ``plant`` breaks the timed path
    underneath (``bench/program.py``)."""
    import jax

    from bench import compare, scopes
    from bench import trace as trace_lib

    bench, entry, spec, config, traffic = load_cell(root, name)
    chips = entry["chips"]
    if require_chip:
        devices, peak = find_chips(chips, root)
    else:
        devices, peak = jax.devices()[:chips], None
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} devices")
    try:
        import repro  # noqa: F401  the program under test
    except ImportError as e:
        raise RuntimeError(f"the program is not in {root / 'src'}: {e}")
    spans = Spans()
    cell = Cell(root, name, entry, spec, config, traffic, seed, devices,
                spans, plant)
    _ = cell.arch, cell.reference   # a model_type without them fails here
    meter = CompileMeter()
    driver = load_module(root / "bench" / "drivers" / f"{spec['driver']}.py")
    session = driver.setup(cell)

    # what set-up made stays for the run: out of the collector's way, so
    # that a full collection inside the window does not walk it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_proc
    compile_s, compiles = meter.compile_s, meter.compiles
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    done, failed = [], 0
    gc_clock = GcClock()
    spans.on = True
    t_start = time.perf_counter()
    with spans.span("window"):
        while not window_over(time.perf_counter() - t_start, len(done),
                              seconds, traced):
            failed += not session.step()
            done.append(time.perf_counter())
    spans.on = False
    gc_clock.close()
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    if meter.compiles != compiles:
        raise RuntimeError(f"{meter.compiles - compiles} compiles inside "
                           "the window")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devices]
    session.free()
    gc.collect()

    nums = session.check()
    correct, checks = compare.judge(nums, spec["limits"])

    tr = None
    if traced:
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"{len(files)} trace files in {trace_dir}")
        tr = scopes.load(str(files[0]), SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell, setup_s, compile_s, t_start, done,
              session.tokens_per_step,
              cell.arch.train_flops_per_token(config, traffic["seq_len"]),
              peak, tr)
    metrics = {}
    for m in metrics_for(bench, name, traced):
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max((b for b in mem if b is not None),
                                       default=None)}
    out = {"correct": bool(correct), "attempted": len(done),
           "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = (sum(trace_lib.busy(tr).values())
                            / max(len(tr["devices"]), 1))
        device["window_s"] = trace_lib.window_s(tr)
        out["breakdown"] = {
            "device_ops": trace_lib.top(trace_lib.op_seconds(tr)),
            "idle_gaps": trace_lib.top(trace_lib.idle_by_span(tr))}
    out["window"] = dict(window_summary(t_start, done, spans.seconds),
                         gc_s=gc_clock.seconds, gc_full=gc_clock.full)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    out["_check_detail"] = checks
    return out


def window_summary(t_start: float, done: List[float],
                   spans: Dict[str, List[float]]) -> dict:
    """The window's steps on the host clock, for a reader looking for a
    stall: the median and longest step, and the spans of the longest."""
    if not done:
        return {"steps": 0}
    edges = [t_start] + done
    steps = [b - a for a, b in zip(edges, edges[1:])]
    i = max(range(len(steps)), key=steps.__getitem__)
    return {"steps": len(steps),
            "median_ms": sorted(steps)[len(steps) // 2] * 1e3,
            "max_ms": steps[i] * 1e3, "max_at": i,
            "max_spans_ms": {k: v[i] * 1e3 for k, v in spans.items()
                             if len(v) == len(steps)}}


def main(argv=None, t_proc: Optional[float] = None) -> int:
    t_proc = time.perf_counter() if t_proc is None else t_proc
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    enable_compile_cache(ROOT)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_proc)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    detail = out.pop("_check_detail")
    print(f"window {json.dumps(out['window'])}", file=sys.stderr)
    for c in detail:
        where = {k: v for k, v in c.items()
                 if k not in ("name", "value", "limit", "ok")}
        print(f"check {c['name']} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'} {json.dumps(where)}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
