#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/limits.py --workload 2d5p.long --seconds 4 \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 21,22,23

One process sets the cell up once, then for each seed makes the input,
runs a window of ``--seconds`` at the cell's own load through the timed
path and checks a sample of its calls as ``run.py`` does: the program's
readings (the lower end of the limit).  Then it puts the control in the
program's place, the plain reference computed in bfloat16, the precision
next below the configuration's float32, and reads it the same way on
``--control-seeds`` (the upper end).  One JSON line per reading; the
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import random
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import cells, run, work  # noqa: E402

CONTROL_DTYPE = {"float32": "bfloat16", "float64": "float32",
                 "bfloat16": "float8_e4m3fn"}


def control_path(cell: cells.Cell, program: run.Setup) -> run.Setup:
    """The reference, computed one precision lower, in the program's
    place."""
    taps = work.taps_from_config(cell.config)
    steps = int(cell.traffic["steps_per_call"])
    low = CONTROL_DTYPE[cell.config["dtype"]]
    mesh = run.cell_mesh(cell)

    def call(x):
        if mesh is None:
            return work.reference_steps(x, taps, steps, low)
        return work.mesh_reference_steps(x, taps, steps, mesh, low)
    return run.Setup(call=call, plan=lambda: f"reference in {low}")


def readings(cell: cells.Cell, setup: run.Setup, seeds, seconds: float):
    import jax
    cfg, tr = cell.config, cell.traffic
    taps = work.taps_from_config(cfg)
    steps = int(tr["steps_per_call"])
    mesh = run.cell_mesh(cell)
    for seed in seeds:
        x = run.make_input(cfg["shape"], cfg["dtype"], seed, mesh)
        x = jax.block_until_ready(setup.call(x))
        calls, window_s, samples, x = run.window(
            setup.call, x, seconds, int(tr["check_calls"]),
            random.Random(seed))
        del x
        worst, _, errs = run.check(samples, taps, steps, float("inf"), mesh)
        del samples
        yield {"seed": seed, "calls": len(calls), "rel_err": worst,
               "samples": errs}


def main(argv=None, *, root: Path = ROOT, bench_dir: Path = BENCH,
         require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(root, args.workload, bench_dir)
    import jax
    if require_chip and (jax.devices()[0].platform != "tpu"
                         or jax.device_count() != cell.chips):
        print("limits: needs the cell's chips", file=sys.stderr)
        return 2
    run.use_caches(root)
    program = run.program_path(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",")]
    for side, setup, side_seeds in (("program", program, seeds),
                                    ("control", control_path(cell, program),
                                     control)):
        for row in readings(cell, setup, side_seeds, args.seconds):
            print(json.dumps({"workload": cell.name, "side": side, **row}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
