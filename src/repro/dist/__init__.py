"""repro.dist — distribution-analysis layer (DESIGN.md §7).

Four pieces:

* ``sharding``     — mesh plans and PartitionSpec rules for every parameter /
  batch / decode-cache tree in the model zoo (FSDP over ``data``, TP over
  ``model``, scan-stacked layers get a leading ``None``).
* ``hlo_cost``     — trip-count-aware flops/bytes walker over optimized HLO
  text (XLA's ``cost_analysis`` counts ``while`` bodies once; scans dominate
  our programs, so the walker multiplies body costs by the known trip count).
* ``hlo_analysis`` — collective parsing (ring wire factors), the three-term
  roofline, and MODEL_FLOPS references.
* ``calibrate``    — lowers the dense/compressed DDP programs and turns their
  parsed collective wire bytes into the fleet engine's comm-bytes model.
"""
from repro.dist import hlo_analysis, hlo_cost, sharding  # noqa: F401
from repro.dist.hlo_analysis import (CollectiveOp, collective_bytes,  # noqa: F401
                                     model_flops, roofline)
from repro.dist.hlo_cost import analyze_hlo  # noqa: F401
from repro.dist.sharding import (MeshPlan, attn_mode_for, batch_specs,  # noqa: F401
                                 cache_specs, make_plan, make_run_ctx, named,
                                 param_specs)
