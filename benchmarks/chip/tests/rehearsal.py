"""CPU rehearsal of a benchmark run: the harness's own loop, window and
check on a reduced model (`configs.reduced(arch)`), a small engine and a
scaled-down copy of the cell's traffic, with the device check skipped.
The tests drive it; the real command still fails without a TPU.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tests/rehearsal.py \
        smollm-360m.chat
"""

from __future__ import annotations

import copy
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
ENGINE = {"n_slots": 4, "max_seq": 256, "page_tokens": 16,
          "prefill_chunk": 32, "pool_dtype": "int8",
          "substrate": "emulated", "local_budget_frac": 0.25}


VOCAB = 4096         # wide enough that greedy picks have near ties


def reduced_model(conf: dict) -> dict:
    """The configuration's model block at the program's reduced sizes
    (with a vocabulary of VOCAB)."""
    from repro import configs

    cfg = configs.reduced(conf["program"]["arch"])
    cfg = dataclasses.replace(cfg, vocab_size=VOCAB)
    m = dict(conf["model"])
    m.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
             num_attention_heads=cfg.num_heads,
             num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
             intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
             tie_word_embeddings=cfg.tie_embeddings,
             rope_theta=cfg.rope_theta,
             embedding_multiplier=math.sqrt(cfg.d_model),
             max_position_embeddings=ENGINE["max_seq"])
    return m


def reduced_mix(mix: dict) -> dict:
    """The mix cut to prompts of 32..96 and outputs of 4..24 tokens, at
    a few requests a second after a warm-up of 2 s."""
    mix = copy.deepcopy(mix)
    mix["prompt"] = {"dist": "uniform", "min": 32, "max": 96, "step": 32}
    mix["output"] = {"dist": "uniform", "min": 4, "max": 24, "step": 1}
    if mix["loop"] == "open":
        mix["rate_per_s"] = 4.0
        mix["warmup_s"] = 2.0
    else:
        mix["clients"] = ENGINE["n_slots"]
        mix["pool"] = 64
    mix["check_requests"] = 64
    return mix


def real_limit(name: str) -> float:
    """The cell's own limit on the widest gap."""
    return harness.load_cell(name).limits["widest_gap"]


def cell(name: str, limit: float = 1.0) -> "harness.Cell":
    c = harness.load_cell(name)
    conf = copy.deepcopy(c.config)
    conf["program"] = dict(conf["program"], reduced=True)
    conf["program"]["overrides"] = dict(conf["program"]["overrides"],
                                        vocab_size=VOCAB)
    conf["model"] = reduced_model(conf)
    conf["engine"] = dict(ENGINE)
    conf["mesh"] = ({"data": 1, "model": 2} if conf["mesh"] else None)
    c.config = conf
    c.chips = 2 if conf["mesh"] else 1
    c.mix = reduced_mix(c.mix)
    c.limits = {"widest_gap": limit}
    return c


def run(name: str, seed: int = 3, seconds: float = 4.0, trace=False,
        fault=None, control=False, limit: float = 1.0) -> dict:
    import jax

    from repro import kernels

    c = cell(name, limit)
    kernels.force_backend("interpret")
    try:
        return harness.measure(
            c, seed, seconds, trace, devices=jax.devices("cpu")[:c.chips],
            peaks=CPU_PEAKS, t_process=time.perf_counter(), fault=fault,
            control=control)
    finally:
        kernels.force_backend(None)


if __name__ == "__main__":
    out = run(sys.argv[1], trace=len(sys.argv) > 2)
    harness.emit(out)
    print(json.dumps(out.get("_check")))
