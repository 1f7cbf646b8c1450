"""Run one cell of the chip benchmark once.

    python bench/run.py --workload smollm-chat --seed 7 --seconds 30 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, metric readers and reference are files under ``bench/``.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), and last the numbers the correctness check compared,
each beside its limit.  Set-up attribution, the window's counter
deltas and the generator's lateness go to standard error before it.

Exits 2, printing no result, when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_exit(n: int, root: str):
    """The devices, or exit 2 when they are not the chips the cell
    needs."""
    import jax
    from harness.load import peaks
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < n:
        print(f"bench: the cell needs {n} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        sys.exit(2)
    try:
        peaks(dev.device_kind, root)
    except KeyError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        sys.exit(2)
    return devices


def main(argv=None) -> int:
    args = parse(argv)
    from harness.cell import run
    from harness.load import load_cell
    cell = load_cell(args.workload, ROOT)
    devices = chips_or_exit(cell.chips, ROOT)
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              t_process=T_PROCESS, devices=devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
