"""repro.obs — unified observability: trackers, telemetry, profiling.

The run ledgers of the simulator, the trainer and the serving schedulers
(DESIGN.md §12).  Their times are sim-seconds: the record of speed on a chip
is the ``bench/`` benchmark, not these ledgers.

* ``tracker``   — the sink layer: ``Tracker`` interface, append-only JSONL
  ``JsonTracker`` ledgers stamped with git SHA / seed / config hash,
  ``CompositeTracker`` fan-out, in-memory and noop sinks.
* ``callbacks`` — the producer layer: per-round trainer records (step flops
  counted from the lowered program by ``repro.dist.hlo_cost``'s
  trip-count-aware walker, samples per sim-second, wire bytes), fleet commit
  telemetry, serve request events.
* ``profile``   — JAX profiler capture windows.

Invariant: observability is zero-perturbation.  Producers gate all metric
assembly on ``tracker.active``, derive records only from host-side values
the workload already computed, and never add jitted work — a tracked run is
bit-exact with an untracked one, and ``NOOP`` costs nothing.
"""
from repro.obs.callbacks import (FLEET_ROUND, SERVE_EVENT,  # noqa: F401
                                 SERVE_SUMMARY, TRAIN_ROUND, TRAIN_SUMMARY,
                                 RoundObserver, fleet_round_record,
                                 lowered_flops, ring_wire_bytes_per_device,
                                 serve_event)
from repro.obs.profile import capture  # noqa: F401
from repro.obs.tracker import (NOOP, SCHEMA_VERSION, CompositeTracker,  # noqa: F401
                               JsonTracker, MemoryTracker, NoopTracker,
                               Tracker, config_hash, git_sha, json_clean,
                               ledger_metrics, read_ledger, run_stamp)
