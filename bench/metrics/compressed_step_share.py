"""Share of the window's steps for which the adaptive-compression
controller picked the compressed program, in %: the ``step.compressed``
count over all steps.  Nothing to read in a cell without the controller."""


def read(run):
    n = run.counts.get("step.compressed", 0) + run.counts.get("step.dense", 0)
    if not n:
        return None
    return run.counts.get("step.compressed", 0) / n * 100.0
