"""Device self time per window step, in ms, mean over the cell's chips, of the
``topk`` scope of the compressed DDP program: the exact top-k of each
chip's gradient and the gather of its values.  Each op is charged to the
innermost of the scopes the cell's workload file lists
(``bench/scopes.ms_per_step``).  Nothing to read in a cell that does not
list the scope."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "topk")
