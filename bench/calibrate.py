#!/usr/bin/env python3
"""Readings for the limits of a cell's comparisons, many seeds in one
process (set-up is long, so each process pays it once per seed, not a
process start and a compile-cache load as well).

    python3 bench/calibrate.py --workload qwen4b-walk --seeds 101-112
    python3 bench/calibrate.py --workload qwen4b-walk --seeds 201-203 --control
    python3 bench/calibrate.py --workload qwen4b-walk --seeds 301-303 --fault half_batch

For each seed it runs the cell's set-up (for a walk: weights, masks and
the first finetune call, which the check follows), for a serving cell a
window of ``--seconds``, then the check, and prints one JSON line of the
numbers it reads. ``--control`` runs the program one precision step
below what the cell states: three bfloat16 passes (``high``) where the
traffic states float32 matmuls at ``highest``, else the program's own
bfloat16 path (bfloat16 weights and activations); ``--fault`` plants one
of ``harness/faults.py``'s faults. The benchmark's own runs do neither.
"""
import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CONTROL = {"dtype": "bfloat16", "param_dtype": "bfloat16"}
# the step below a stated matmul precision
LOWER = {"highest": "high", "high": "default"}


def control(cell):
    """(config override, precision) of the cell's precision control: three
    bf16 passes where the traffic states float32 at ``highest``, else the
    program's own bfloat16 path."""
    stated = cell.traffic.get("matmul_precision")
    return (None, LOWER[stated]) if stated else (CONTROL, None)


def seeds(text):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def reading(cell, seed, seconds, devices, control_run=False, fault=None):
    """Every number the cell's check reads, compared or not, for one seed."""
    import contextlib

    from harness import cli, faults

    override, precision = control(cell) if control_run else (None, None)
    ctx = cli.Context(cell, seed, devices, None, override, precision)
    plant = faults.FAULTS[cell.traffic["driver"]][fault](ctx) if fault \
        else contextlib.nullcontext()
    with ctx.computing(), plant:
        drv = cell.driver().Driver(ctx)
        drv.setup()
        if cell.traffic["driver"] != "walk":  # a walk is checked on its first call
            drv.window(seconds)
        drv.free()
    return drv.check(), getattr(drv, "notes", {})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)

    from harness import cli, device
    from harness import spec as S

    cell = S.cell(S.load_spec(), args.workload)
    sys.path.insert(0, os.path.join(S.ROOT, "src"))
    try:
        devices = device.look(cell.chips)
    except device.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    cli.use_compile_cache(S.ROOT)
    for seed in args.seeds:
        t = time.perf_counter()
        nums, notes = reading(cell, seed, args.seconds, devices, args.control, args.fault)
        gc.collect()
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "mode": "control" if args.control else args.fault or "program",
                          "numbers": nums, "notes": notes,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
