"""Model-flops utilisation from the lowered step program.

MFU here is the paper-standard ratio: flops the model *needs* per step
(counted from the optimized HLO by ``repro.dist.hlo_cost``'s trip-count-aware
walker — scan-over-layers programs are counted correctly) over flops the
hardware *could have done* in the simulated round time.  The reference peak
is the paper's Table II hardware (one K80 GPU per edge device), so MFU reads
as "fraction of the fleet's K80-seconds the committed gradients used".

Counting is a one-time, host-side act per jitted function: ``lowered_flops``
traces + compiles the step (numerically inert — jit would have compiled the
same program anyway) and walks the HLO text.  Producers cache the result and
only call this when a tracker is active, keeping the noop path free.
"""
from __future__ import annotations

from typing import Optional

#: fp32 peak of one K80 GPU (the paper's per-device accelerator, Table II).
#: Absolute MFU values are relative to this; regression gating only needs
#: the number to be stable, not flattering.
DEVICE_PEAK_FLOPS = 4.37e12


def lowered_flops(fn, *args) -> float:
    """Flops of one call of jitted ``fn`` at ``args``, from optimized HLO.

    Primary source is ``repro.dist.hlo_cost.analyze_hlo`` (matches XLA's
    ``cost_analysis`` and multiplies ``while`` bodies by their trip count);
    where the walker counts nothing, ``Compiled.cost_analysis()`` answers.
    Lowering or compile errors propagate: a step that cannot be compiled is
    a fault, not a missing metric.
    """
    from repro.dist.hlo_cost import analyze_hlo
    compiled = fn.lower(*args).compile()
    flops = float(analyze_hlo(compiled.as_text())["flops"])
    if flops == 0.0:
        flops = float(compiled.cost_analysis().get("flops", 0.0))
    return flops


def mfu(step_flops: Optional[float], dt_s: float, *,
        n_devices: int = 1, peak_flops: float = DEVICE_PEAK_FLOPS) -> float:
    """Fleet MFU for one round: step flops over available device-flops.

    ``step_flops`` is the whole jitted step (all devices' gradients — the
    trainer vmaps over the device axis), so the denominator spans the full
    fleet: ``dt * peak * n_devices``.
    """
    if not step_flops or dt_s <= 0.0:
        return 0.0
    return float(step_flops) / (dt_s * peak_flops * max(int(n_devices), 1))
