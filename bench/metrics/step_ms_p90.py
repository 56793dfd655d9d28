"""90th percentile of the step time, in ms: the intervals between
consecutive step completions in the window, the first counted from the
window's start (host clock).  Input, dispatch, device and the host's read
of the step's metrics all fall inside an interval."""
import statistics


def read(run):
    if len(run.done) < 10:
        return None
    edges = [run.t_start] + run.done
    steps = [b - a for a, b in zip(edges, edges[1:])]
    return statistics.quantiles(steps, n=10, method="inclusive")[8] * 1e3
