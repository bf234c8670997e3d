"""Find an open-loop mix's knee on the chip (not part of a benchmark
run): one run of the cell at each offered rate, lowest first, in one process,
with the throughput, the first-token tail and whether the backlog grew;
it stops at the first rate whose backlog grows.

    python3 benchmarks/chip/sweep.py --workload <cell> \
        --rates 1,2,3,4 --seconds 30 --seed 5

Each run starts its arrivals the mix's `warmup_s` before the window, as
a benchmark run does, so the window sees the rate's steady state. The
knee is the highest rate whose backlog does not grow: the queue of
requests due but without a first token grows over the window by no more
than a tenth of the requests that came due in it (and by at most 2).
The cell's mix file then states 0.8 x that rate.
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


def grows(b: dict) -> bool:
    return b["waiting_at_end"] - b["waiting_at_start"] > max(2, 0.1 * b["due"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        harness.log("sweep: FAIL: the cell's mix is not an open loop")
        return 2
    harness.enable_compile_cache()
    try:
        devices, peaks = harness.check_devices(cell.chips)
    except harness.BenchError as e:
        harness.log(f"sweep: FAIL: {e}")
        return 2
    knee = None
    t = T_PROCESS
    for rate in sorted(float(r) for r in args.rates.split(",")):
        cell.mix = dict(cell.mix, rate_per_s=rate)
        out = harness.measure(cell, args.seed, args.seconds, False,
                              devices=devices, peaks=peaks, t_process=t,
                              check_output=False)
        b = out["_backlog"]
        row = {"rate_per_s": rate, "grows": grows(b), **b,
               **{k: v["value"] for k, v in out["metrics"].items()}}
        print("RATE " + json.dumps(row), flush=True)
        if row["grows"]:
            break                 # the rates above it cannot do better
        knee = rate
        t = time.perf_counter()
    print("KNEE " + json.dumps({"workload": args.workload, "knee": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
