"""repro.obs — unified observability: trackers, telemetry, profiling, gating.

The subsystem every perf claim reports through (DESIGN.md §12):

* ``tracker``   — the sink layer: ``Tracker`` interface, append-only JSONL
  ``JsonTracker`` ledgers stamped with git SHA / seed / config hash,
  ``CompositeTracker`` fan-out, in-memory and noop sinks.
* ``callbacks`` — the producer layer: per-round trainer records (MFU,
  samples/s, wire bytes), fleet commit telemetry, serve request events.
* ``mfu``       — model-flops utilisation from the lowered step program via
  ``repro.dist.hlo_cost``'s trip-count-aware walker.
* ``profile``   — JAX profiler capture windows.
* ``regress``   — the perf-regression gate: tolerance-banded comparison of
  fresh metrics against the committed ``BENCH_scadles.json`` baseline
  (driven by ``benchmarks/perf_gate.py`` in CI).

Invariant: observability is zero-perturbation.  Producers gate all metric
assembly on ``tracker.active``, derive records only from host-side values
the workload already computed, and never add jitted work — a tracked run is
bit-exact with an untracked one, and ``NOOP`` costs nothing.
"""
from repro.obs.callbacks import (FLEET_ROUND, SERVE_EVENT,  # noqa: F401
                                 SERVE_SUMMARY, TRAIN_ROUND, TRAIN_SUMMARY,
                                 RoundObserver, fleet_round_record,
                                 ring_wire_bytes_per_device, serve_event)
from repro.obs.mfu import DEVICE_PEAK_FLOPS, lowered_flops, mfu  # noqa: F401
from repro.obs.profile import capture  # noqa: F401
from repro.obs.regress import (GateReport, MetricSpec, compare,  # noqa: F401
                               load_baseline, save_baseline, write_report)
from repro.obs.tracker import (NOOP, SCHEMA_VERSION, CompositeTracker,  # noqa: F401
                               JsonTracker, MemoryTracker, NoopTracker,
                               Tracker, config_hash, git_sha, json_clean,
                               ledger_metrics, read_ledger, run_stamp)
