#!/usr/bin/env python3
"""Calibration runs of one cell, many seeds (and rates) in one process.

    python3 bench/calibrate.py --workload phi3.analog.chat \\
        --seeds 11,12,13 --seconds 20 [--rates 1.5,2,2.5]

Each run is ``bench/run.py``'s run of the cell, with the cell's own load,
sizes and check; only set-up is shared, so every seed after the first
finds its executables compiled. Each line reports every statistic of
the check, the fp8 control's gaps beside the program's on the same
sample of requests: the readings a limit in ``bench/limits/`` is set
from. ``--rates`` replaces the mix's arrival rate, one run per rate and
seed: the sweep that finds the knee. One JSON line per run on stdout;
the benchmark's own runs never use this tool.
"""

from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

sys.path[0] = str(pathlib.Path(__file__).resolve().parents[1])

from bench import model, run, traffic  # noqa: E402


def main(argv=None) -> int:
    """CLI entry point (see module docstring)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    try:
        bench = run.load_benchmark()
        cell = run.find_cell(bench, args.workload)
        conf = model.load_config(cell["config"])
        base = traffic.load_mix(cell["traffic"])
        limits = run.load_limits(cell["name"])
        run.require_program()
        run.enable_cache()
        peaks = json.loads((run.BENCH / "peaks.json").read_text())
        device = run.require_chip(cell["chips"], peaks)
    except (run.RunError, FileNotFoundError) as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        mix = copy.deepcopy(base)
        if rate is not None:
            mix["arrivals"]["rate_per_s"] = rate
        for seed in (int(s) for s in args.seeds.split(",")):
            result, notes = run.run_cell(
                bench, cell, conf, mix, limits, seed, args.seconds,
                bool(args.trace), device,
                peaks[device["kind"]])
            for line in notes:
                print(f"calibrate: seed {seed} rate {rate}: {line}",
                      file=sys.stderr, flush=True)
            print(json.dumps({"seed": seed, "rate": rate, **result}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
