"""Device time of XLA collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, with their async halves)
per window step, mean over the cell's chips, in ms (``bench/trace.py``).
Nothing to read, and no value, where the trace holds no collective."""
from bench import trace


def read(run):
    if run.trace is None or not run.done:
        return None
    ops = trace.op_seconds(run.trace)
    coll = [v for k, v in ops.items() if trace.COLLECTIVE.match(k)]
    if not coll:
        return None
    return sum(coll) / len(run.done) * 1e3
