"""The check must catch a broken timed path, and the control must fail it.

Each test drives a whole run on the CPU (reduced model, the harness's
own loop and check; only the look for a chip is skipped) with a fault
planted underneath the timed path, and sees `correct` come out false:

* a decode step that returns its state (the KV cache) unchanged;
* half of the batch left out (every odd slot gets no new token
  computed: it repeats its last one);
* a token altered where it is produced (every 5th decode step, every
  slot's token is bumped by one).

The control (`reference.py`, float8 operands) must come out not correct
against the same limit.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))

RUN = """
import json, sys
sys.path.insert(0, "benchmarks/chip/tests")
import jax, jax.numpy as jnp, numpy as np
import rehearsal, harness

def stale_state(engine):
    real = engine.cells.decode_fn
    def step(params, tok, caches, *rest):
        keep = jax.tree.map(jnp.copy, caches)
        nxt, fin, _ = real(params, tok, caches, *rest)
        return nxt, fin, keep
    engine.cells.decode_fn = step

def half_batch(engine):
    real = engine.cells.decode_fn
    def step(params, tok, caches, *rest):
        nxt, fin, caches = real(params, tok, caches, *rest)
        odd = jnp.arange(tok.shape[0]) % 2 == 1
        return jnp.where(odd, tok, nxt), fin, caches
    engine.cells.decode_fn = step

def altered_token(engine):
    real = engine.cells.decode_fn
    n = [0]
    def step(params, tok, caches, *rest):
        nxt, fin, caches = real(params, tok, caches, *rest)
        n[0] += 1
        if n[0] % 5 == 0:
            nxt = (nxt + 1) % engine.cfg.vocab_size
        return nxt, fin, caches
    engine.cells.decode_fn = step

fault = {fault}
out = rehearsal.run({cell!r}, seed={seed}, seconds={seconds}, fault=fault,
                    control={control}, limit=rehearsal.real_limit({cell!r}))
print("RESULT", json.dumps({{"correct": out["correct"], **out["_check"]}}))
"""


# ~900 served tokens a run: enough near-ties for a small error to flip a
# greedy pick, as the chip's runs of some thousand tokens have
def _run(cell, fault="None", control=False, seed=5, seconds=16.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    r = subprocess.run(
        [sys.executable, "-c", RUN.format(cell=cell, fault=fault, seed=seed,
                                          control=control, seconds=seconds)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    import json

    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sound_run_is_correct():
    got = _run("smollm-360m.chat")
    assert got["correct"] is True, got


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
def test_fault_is_caught(fault):
    got = _run("smollm-360m.chat", fault=fault)
    assert got["correct"] is False, got


@pytest.mark.parametrize("cell", ["smollm-360m.chat",
                                  "smollm-360m.fullctx"])
def test_control_fails_the_limit(cell):
    got = _run(cell, control=True)
    assert got["correct"] is True, got          # the program itself
    assert got["control_widest_gap"] > got["limit"], got
