"""The benchmark's tensor-parallel cell, `mistral-nemo-12b-tp4.rag`, run
whole on the CPU: the harness's own loop, window and check, on the
reduced mistral_nemo_12b (`configs.reduced`: query width below the
hidden size, GQA 4:1, untied head, rope_theta 1e6) over a (data=1,
model=2) mesh of forced host devices, seeded random weights, interpret
kernels, against the float32 reference and the cell's own limit:

* a sound run is correct;
* a missing exchange is caught: every shard but the first of `wo` and
  `w_down` zeroed, so the row-parallel all-reduces sum one chip's part;
* the float8 control fails the limit.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mistral-nemo-12b-tp4.rag"

RUN = """
import json, sys
sys.path.insert(0, "benchmarks/chip/tests")
import jax, jax.numpy as jnp
import rehearsal

def missing_exchange(engine):
    tp = engine.ctx.mesh.shape["model"]

    def first_shard(path, leaf):
        if path[-1].key not in ("wo", "w_down"):
            return leaf
        n = leaf.shape[1]                 # the row-parallel axis
        keep = (jnp.arange(n) < n // tp).reshape((1, n) + (1,) * (leaf.ndim - 2))
        return jax.device_put(jnp.where(keep, leaf, 0).astype(leaf.dtype),
                              leaf.sharding)

    # a new tree: the reference keeps the weights as made
    engine.params = jax.tree_util.tree_map_with_path(first_shard,
                                                     engine.params)

fault = {fault}
out = rehearsal.run({cell!r}, seed={seed}, seconds={seconds}, fault=fault,
                    control={control}, limit=rehearsal.real_limit({cell!r}))
print("RESULT", json.dumps({{"correct": out["correct"], **out["_check"]}}))
"""


def _run(fault="None", control=False, seed=5, seconds=16.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    r = subprocess.run(
        [sys.executable, "-c", RUN.format(cell=CELL, fault=fault, seed=seed,
                                          control=control, seconds=seconds)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", ["sound", "missing_exchange", "control"])
def test_tp_cell_against_the_reference(case):
    if case == "sound":
        got = _run()
        assert got["correct"] is True, got
    elif case == "missing_exchange":
        got = _run(fault="missing_exchange")
        assert got["correct"] is False, got
    else:
        got = _run(control=True)
        assert got["correct"] is True, got          # the program itself
        assert got["control_widest_gap"] > got["limit"], got
    assert got["tokens"] > 500, got                 # enough near-ties
