"""Readings that set a cell's correctness limit and its open-loop rate.

    python bench/calibrate.py --workload smollm-chat --seeds 11,12,13 \\
        --seconds 15 [--rates 1.5,2.5]

Runs the cell once for every seed (with ``--rates``, once for every
rate and seed, the rate taking the place of the mix's), all in one
process, so that set-up is paid once.  Each run also scores the
control, the float8 reference put in the program's place, by the same
limits.  Every run prints one JSON line: the rate, the seed, the
program's ``correct``, metrics and compared numbers, and the control's
``correct`` and numbers.  The benchmark's own runs never run the
control.  Exits 2 without the chips the cell needs, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, chips_or_exit  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="",
                    help="comma-separated req/s for an open loop; "
                         "default the mix's own")
    args = ap.parse_args(argv)
    from harness.cell import run
    from harness.load import load_cell
    cell = load_cell(args.workload, ROOT)
    devices = chips_or_exit(cell.chips, ROOT)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    t_start = T_PROCESS
    for rate in rates:
        if rate is not None:
            cell.traffic = dict(cell.traffic, rate_rps=rate)
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run(cell, seed, args.seconds, False, t_process=t_start,
                      devices=devices, control=True)
            print(json.dumps({"rate_rps": cell.traffic.get("rate_rps"),
                              "seed": seed, "correct": out["correct"],
                              "metrics": out["metrics"],
                              "checks": out["checks"],
                              "control": out["control"],
                              "memory_peak_bytes":
                                  out["device"]["memory_peak_bytes"]}),
                  flush=True)
            gc.collect()
            t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
