"""Readings that set a cell's correctness limit (not part of a benchmark
run): for each seed, one short run of the cell, then the widest gap of
the served tokens (the program) and of the control's picks (the
reference with float8 operands) on the same prompts and tokens.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 --seconds 20

All seeds run in one process, so compiled programs are made once. The
limit goes above the largest program reading and below the smallest
control reading; `limits/<cell>.json` keeps it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    try:
        devices, peaks = harness.check_devices(cell.chips)
    except harness.BenchError as e:
        harness.log(f"calibrate: FAIL: {e}")
        return 2
    rows = []
    t = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.measure(cell, seed, args.seconds, False,
                              devices=devices, peaks=peaks, t_process=t,
                              control=True)
        row = dict(seed=seed, **out["_check"],
                   compiles=out["_compiles"],
                   metrics={k: v["value"] for k, v in out["metrics"].items()})
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
        t = time.perf_counter()
    prog = [r["widest_gap"] for r in rows]
    ctl = [r["control_widest_gap"] for r in rows]
    print("SUMMARY " + json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": max(prog), "control_min": min(ctl),
        "separation": min(ctl) / max(prog) if max(prog) else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
