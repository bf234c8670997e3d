"""Small-mesh dry-run sweep — keeps `results/dryrun/` records fresh.

Runs `repro.launch.dryrun` for a small arch x shape subset on a 4x4
emulated mesh (16 host-platform devices) in a subprocess (the dry-run must
set XLA_FLAGS before jax initializes, so it cannot run in-process), then
summarizes the regenerated records. Wired into `benchmarks/run.py` (tag
`dryrun`) and the CI benchmark job, which uploads the JSON records as
artifacts — closing the ROADMAP item about records going stale.

`BENCH_SMOKE=1` narrows the sweep to one cell.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

CELLS = [
    ("smollm-360m", "decode_32k"),
    ("smollm-360m", "prefill_32k"),
    ("mamba2-780m", "decode_32k"),
]
SMOKE_CELLS = CELLS[:1]
MESH = "4x4"
OUTDIR = "results/dryrun"


def run():
    smoke = os.environ.get("BENCH_SMOKE", "") not in ("", "0")
    cells = SMOKE_CELLS if smoke else CELLS
    rows = []
    for arch, shape in cells:
        # the child compiles on forced host devices; it must never reach
        # for the chip this process may hold
        env = dict(os.environ, REPRO_DEVICES="16", JAX_PLATFORMS="cpu")
        env.setdefault("PYTHONPATH", "src")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", arch, "--shape", shape, "--mesh", MESH,
             "--outdir", OUTDIR],
            env=env, capture_output=True, text=True,
        )
        name = arch.replace("-", "_")
        rec_path = os.path.join(OUTDIR, f"{name}_{shape}_{MESH}.json")
        rec = None
        if os.path.exists(rec_path):
            with open(rec_path) as f:
                rec = json.load(f)
        ok = (proc.returncode == 0 and rec is not None
              and rec.get("status") == "ok")
        derived = f"status={'ok' if ok else 'fail'}"
        if rec and rec.get("status") == "ok":
            ro = rec["roofline"]
            derived += (
                f" dominant={ro['dominant']}"
                f" t_compute={ro['t_compute_s']:.2e}"
                f" t_memory={ro['t_memory_s']:.2e}"
                f" compile_s={rec.get('compile_s')}"
            )
        elif not ok:
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()
            derived += f" err={tail[-1][:120] if tail else 'no-output'}"
        emit(f"dryrun_{name}_{shape}", 1e6 * (rec or {}).get("wall_s", 0.0),
             derived)
        rows.append({"arch": name, "shape": shape, "mesh": MESH, "ok": ok,
                     "record": rec_path})
        if not ok:
            raise RuntimeError(
                f"dry-run cell {arch} x {shape} failed: see {rec_path}"
            )
    return rows
