"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only e1,e4] [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows and writes the same rows
as machine-readable JSON (default ``BENCH_serving.json``) so the perf
trajectory — steady-state decode tokens/s, host syncs per token,
batching/join/prefix-sharing wins — is tracked commit-over-commit.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: e1,e2,e3,e4,e5,e6,e7,e8,e9,"
                         "e10_quant,e11_chaos,roofline")
    ap.add_argument("--json", default=None,
                    help="write rows as machine-readable JSON here "
                         "(default: BENCH_serving.json on full runs; "
                         "--only runs skip the file unless one is given, "
                         "so a filtered run never clobbers the tracked "
                         "full report; '' disables)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    json_path = args.json if args.json is not None \
        else ("" if only else "BENCH_serving.json")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (e1_multimodel, e2_ars, e3_mtcnn, e4_overhead, e5_batching,
                   e6_decode_loop, e7_frontdoor, e8_sharded, e9_speculative,
                   e10_quant, e11_chaos, roofline)
    sections = [("e1", e1_multimodel), ("e2", e2_ars), ("e3", e3_mtcnn),
                ("e4", e4_overhead), ("e5", e5_batching),
                ("e6", e6_decode_loop), ("e7", e7_frontdoor),
                ("e8", e8_sharded), ("e9", e9_speculative),
                ("e10_quant", e10_quant), ("e11_chaos", e11_chaos),
                ("roofline", roofline)]
    print("name,us_per_call,derived")
    failed = False
    report = {"sections": {}, "rows": []}
    def emit(name, row):
        print(row, flush=True)
        bench, us, derived = row.split(",", 2)
        try:
            us_f = float(us)
        except ValueError:
            us_f = None
        report["rows"].append({"name": bench, "us_per_call": us_f,
                               "derived": derived, "section": name})

    for name, mod in sections:
        if only and name not in only:
            continue
        # stream rows as the section produces them: a mid-run failure
        # keeps everything measured up to that point (stdout AND json)
        try:
            for row in mod.run():
                emit(name, row)
            report["sections"][name] = "ok"
        except Exception:  # noqa: BLE001
            failed = True
            emit(name, f"{name}_ERROR,0.0,{traceback.format_exc(limit=3)!r}")
            report["sections"][name] = "error"
    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {json_path} ({len(report['rows'])} rows)",
              file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
