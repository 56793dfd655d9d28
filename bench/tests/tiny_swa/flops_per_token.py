"""The model FLOPs per token that the harness took from the configuration's
architecture module (``Run.flops_per_token``): a reader that only the
tests' copy of the benchmark holds, as ``bench/metrics/flops_per_token.py``."""


def read(run):
    return run.flops_per_token
