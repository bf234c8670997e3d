"""Benchmark harness — one module per paper table/figure plus the serving
and dry-run lanes.

    PYTHONPATH=src python -m benchmarks.run [--only fig11,serve]
    PYTHONPATH=src python -m benchmarks.run --smoke --out results/bench

Prints ``name,us_per_call,derived`` CSV lines (benchmarks.common.emit).
`--smoke` shrinks the configurable lanes (serving request counts, dry-run
cells) via BENCH_SMOKE=1 — the CI benchmark job's config. `--out DIR`
writes one ``BENCH_<tag>.json`` per module (each module's returned rows),
which CI uploads as artifacts next to the regenerated `results/dryrun/`
records.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback


MODULES = [
    ("table1", "benchmarks.bench_memory_cost"),
    ("fig5", "benchmarks.bench_roofline_scatter"),
    ("fig6", "benchmarks.bench_bwcap_curve"),
    ("fig8", "benchmarks.bench_prefetch"),
    ("bfs", "benchmarks.bench_bfs_case"),
    ("fig9", "benchmarks.bench_tier_ratios"),
    ("fig10", "benchmarks.bench_sensitivity"),
    ("fig11", "benchmarks.bench_lbench"),
    ("fig12", "benchmarks.bench_placement_case"),
    ("fig13", "benchmarks.bench_scheduler_case"),
    ("serve", "benchmarks.bench_serving"),
    ("pager", "benchmarks.bench_pager_churn"),
    ("fleet", "benchmarks.bench_fleet"),
    ("dryrun", "benchmarks.bench_dryrun_sweep"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated tags, e.g. fig11,serve")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest configs (sets BENCH_SMOKE=1)")
    ap.add_argument("--out", default=None,
                    help="write BENCH_<tag>.json row dumps to this dir")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only:
        # a typo'd lane name must fail loudly, not pass green doing no work
        known = {tag for tag, _ in MODULES}
        bad = sorted(only - known)
        if bad:
            ap.error(
                f"unknown --only lane(s) {', '.join(bad)}; "
                f"valid: {', '.join(tag for tag, _ in MODULES)}"
            )
    from repro.common import compile_cache

    compile_cache.enable()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
    if args.out:
        os.makedirs(args.out, exist_ok=True)

    print("name,us_per_call,derived")
    failures = []
    for tag, modname in MODULES:
        if only and tag not in only:
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run()
            if args.out:
                with open(os.path.join(args.out,
                                       f"BENCH_{tag}.json"), "w") as f:
                    json.dump({"tag": tag, "module": modname,
                               "rows": rows}, f, indent=1, default=str)
        except Exception as e:
            failures.append((tag, e))
            traceback.print_exc()
    if failures:
        print(f"FAILED: {[t for t, _ in failures]}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
