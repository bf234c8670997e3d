"""Entry point of the on-chip serving benchmark (see harness.py).

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout; the program is imported from its
`src/`. Exits non-zero, printing no result, without a TPU, with fewer
chips than the cell asks for, or without the program beside it.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    try:
        import repro.serving  # noqa: F401
    except ImportError as e:
        print(f"bench: FAIL: the program is not importable from "
              f"{os.path.join(ROOT, 'src')}: {e}", file=sys.stderr)
        sys.exit(2)
    import harness

    sys.exit(harness.main(t_process=T_PROCESS))
