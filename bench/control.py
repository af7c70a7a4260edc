#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python bench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

For each seed, in one process: the cell's set-up (the program's first
steps), one unit of work, then the numbers that ``correct`` compares, read
from the program against the plain reference (the lower readings); for each
control seed also the same numbers read from the control, the reference put
in the program's place one precision below the configuration's (the upper
readings). One JSON line per seed. It takes the chips as ``run.py`` does and
is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as harness


def readings(cell_name: str, seeds: list[int], control_seeds: set[int],
             devices_for=harness.accelerators, root=harness.ROOT, log=print):
    cell = harness.load_cell(cell_name, root)
    for p in (str(harness.ROOT / "src"), str(harness.BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    harness.use_compile_cache()
    devices = devices_for(cell.chips)
    module = harness.load_module(cell.driver_path)
    out = []
    for seed in seeds:
        ctx = harness.RunContext(cell=cell, seed=seed, devices=devices,
                                 spans=harness.Spans())
        driver = module.Driver(ctx)
        driver.setup()
        ctx.units, ctx.work = 1, driver.unit()
        driver.release()
        checks, _ = driver.check()
        line = {"seed": seed, "program": {c.name: c.value for c in checks}}
        if seed in control_seeds:
            line["control"] = driver.control()
            if hasattr(driver, "faults"):
                line["faults"] = driver.faults()
        log(json.dumps(line), flush=True)
        out.append(line)
        del driver
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    try:
        readings(args.workload, seeds, ctl)
    except harness.SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
