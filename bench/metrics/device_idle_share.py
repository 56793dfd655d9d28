"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the "XLA Ops" intervals / window), mean over the cell's
chips (``bench/trace.py``)."""
from bench import trace


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return trace.idle_share(run.trace) * 100.0
