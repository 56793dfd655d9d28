import os

# The benchmark's tests run on the CPU; the chip is the benchmark's own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
