"""Device time of XLA collective ops (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, with their async halves)
per window step, mean over the cell's chips, in ms (``bench/trace.py``).
An instruction that XLA names after the JAX collective it came from
(``%psum.21 = f32[] all-reduce(...)``) counts too.  Nothing to read, and
no value, where the trace holds no collective."""
import re

from bench import trace

JAX_COLLECTIVE = re.compile(
    r"^(psum|pmax|pmin|psum_scatter|ppermute|all_gather|all_to_all)"
    r"(\.\d+)?$")


def is_collective(op: str) -> bool:
    return bool(trace.COLLECTIVE.match(op) or JAX_COLLECTIVE.match(op))


def read(run):
    if run.trace is None or not run.done:
        return None
    ops = trace.op_seconds(run.trace)
    coll = [v for k, v in ops.items() if is_collective(k)]
    if not coll:
        return None
    return sum(coll) / len(run.done) * 1e3
