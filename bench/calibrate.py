#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from; not part of a run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange] \\
        [--fault-seeds 1,2,3] [--out calibrate.<cell>.json]

For each seed, in one process and at the cell's own size, without a
measured window:

- ``program``: the cell's driver builds the program's step, runs the first
  ``check_steps`` steps as a run's set-up does, frees its state, and the
  reference follows the same steps (the lower readings);
- ``control`` (``--control-seeds``): the reference computed in bfloat16,
  put in the program's place, against the float32 reference (the upper
  readings);
- each fault of ``--faults``, on each seed of ``--fault-seeds`` (by
  default the control's), planted in the program (``state_unchanged``,
  ``half_batch`` and ``no_exchange`` through ``bench/program.py``) or, with
  a ``ref:`` prefix, in the reference put in the program's place.

Prints one JSON line per reading, with ``correct`` as the cell's limits
judge it, and writes them all to ``--out``.
"""
import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import compare, harness  # noqa: E402


def reference_in_place(cell, dtype, fault="", ref=None):
    """Readings of the reference (``dtype``, ``fault``) against the float32
    reference ``ref`` (followed here when not given), on the cell's first
    steps.  Returns (numbers, ref)."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W
    from bench.reference import train as ref_train
    from bench.traffic import Traffic

    spec, c, model = cell.spec, cell.config, cell.reference
    init = cell.arch.make_init(c)
    key = W.jax_key(cell.seed)
    fresh = Traffic(cell.traffic, c["vocab_size"], cell.seed)
    batches = [fresh.batch(i) for i in range(spec["check_steps"])]
    names = W.leaf_slices(jax.eval_shape(init, jax.random.PRNGKey(0)))
    with jax.default_device(cell.devices[0]):
        if spec["driver"] == "train_step":
            run = lambda dt, f: ref_train.follow_adam(  # noqa: E731
                model, c, init, key, batches, spec["optimizer"],
                spec["schedule"], dtype=dt, fault=f)
        else:
            run = lambda dt, f: ref_train.follow_ddp(  # noqa: E731
                model, c, init, key, batches, len(cell.devices),
                spec["optimizer"], spec["compression"], dtype=dt, fault=f)
        ref = run(jnp.float32, "") if ref is None else ref
        other = run(dtype, fault)
    return compare.numbers(other, ref, names), ref


def program_readings(root, name, seed, plant, devices):
    bench, entry, spec, config, traffic = harness.load_cell(root, name)
    cell = harness.Cell(root, name, entry, spec, config, traffic, seed,
                        devices, harness.Spans(), plant)
    driver = harness.load_module(root / "bench" / "drivers" /
                                 f"{spec['driver']}.py")
    session = driver.setup(cell)
    session.free()
    gc.collect()
    return session.check(), cell


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    root = harness.ROOT
    sys.path.insert(0, str(root / "src"))
    harness.enable_compile_cache(root)
    import jax.numpy as jnp

    _, entry, spec, config, traffic = harness.load_cell(root, args.workload)
    devices, _ = harness.find_chips(entry["chips"], root)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]
    fseeds = cseeds if args.fault_seeds is None else [
        int(s) for s in args.fault_seeds.split(",") if s]
    rows = []

    def emit(kind, seed, nums, t0):
        row = {"kind": kind, "seed": seed, "s": time.perf_counter() - t0,
               "correct": compare.judge(nums, spec["limits"])[0],
               **{k: v["value"] for k, v in nums.items()},
               "at": {k: v.get("at") for k, v in nums.items()}}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        nums, _ = program_readings(root, args.workload, seed, "", devices)
        emit("program", seed, nums, t0)
    for seed in dict.fromkeys(cseeds + fseeds):
        cell = harness.Cell(root, args.workload, entry, spec, config,
                            traffic, seed, devices, harness.Spans())
        ref = None
        if seed in cseeds:
            t0 = time.perf_counter()
            nums, ref = reference_in_place(cell, jnp.bfloat16)
            emit("control", seed, nums, t0)
        for f in faults if seed in fseeds else []:
            t0 = time.perf_counter()
            if f.startswith("ref:"):
                nums, _ = reference_in_place(cell, jnp.float32, f[4:], ref)
            else:
                nums, _ = program_readings(root, args.workload, seed, f,
                                           devices)
            emit(f, seed, nums, t0)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    print(f"calibrate: {time.perf_counter() - T_PROC:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
