"""Device self time per window step, in ms, mean over the cell's chips, of the
``scatter_add`` scope of the compressed DDP program: the scatter-add of the
gathered (index, value) pairs, and the densify of the chip's own top-k for
the energy gap.  Each op is charged to the innermost of the scopes the
cell's workload file lists (``bench/scopes.ms_per_step``).  Nothing to read
in a cell that does not list the scope."""
from bench import scopes


def read(run):
    return scopes.ms_per_step(run, "scatter_add")
