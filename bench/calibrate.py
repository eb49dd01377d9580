"""Readings from which a cell's correctness limit is set, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 11,12,... --control-seeds 11,12,13

Runs the cell once per seed (set-up is paid once: later runs find the
plans compiled) and prints one JSON line per run with the number the
benchmark compares (``rel_err``: the program against the oracle) and,
on the control seeds, the control's (``control_rel_err``: the oracle in
bfloat16 put in the program's place) and that of a step that returns
its state unchanged (``unchanged_rel_err``).  The benchmark's own runs
never read the control.  Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rates", default="",
                    help="open-loop rates in place of the mix's, each run "
                         "on every seed: the sweep that finds the knee")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import benchspec, roofline
    from bench import run as bench_run
    from repro.core.perfmodel import hardware_for

    jax = bench_run.setup_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    env = bench_run.Env(devices, hardware_for(devices[0]),
                        roofline.peaks_for(devices[0].device_kind), T_START)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    seeds = [int(s) for s in args.seeds.split(",")]
    for rate, seed in itertools.product(rates, seeds):
        cell = benchspec.resolve(args.workload)
        if rate is not None:
            cell.traffic["rate_per_s"] = rate
        env.t_start = time.perf_counter()
        out = bench_run.run_cell(cell, seed, args.seconds, False, env,
                                 control=seed in control)
        print(json.dumps({"seed": seed, "rate": rate,
                          "correct": out["correct"],
                          "metrics": out["metrics"],
                          "notes": out["notes"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
