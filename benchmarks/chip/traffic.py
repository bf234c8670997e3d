"""Traffic generator: one general reader of the mix files in `traffic/`.

A mix file fixes the shape of the load; `--seed` only picks the token
ids. The sizes (prompt and output lengths), their order and, for an open
loop, the arrival times are drawn once from the file's `shape_seed`, so
every seed serves the same requests at the same times. (With the order
left to the seed, runs of one seed agreed within 1 % and runs of
different seeds differed by 12-28 %: in a window shorter than a request's
life, the order decides which work falls inside it.)

Open loop (`"loop": "open"`): `rate_per_s` Poisson arrivals, from
`warmup_s` before the window (so that the window opens on an engine
holding its steady load, not an empty one) to its end: a run of `seconds`
gets round(rate * (warmup_s + seconds)) requests whose gaps sum to less
than warmup_s + seconds, so every one of them is due by the window's end.

Closed loop (`"loop": "closed"`): `clients` clients, each sending its next
request when its last one completes; requests come from a pool of `pool`
sizes, handed out in the mix's order.

Length distributions:
  {"dist": "lognormal", "median", "sigma", "min", "max"[, "round_up_to"]}
  {"dist": "uniform", "min", "max", "step"}   (multiples of step from min)
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Item:
    """One request as generated: sizes, token ids and (open loop) the
    offset from the window's start at which it is due."""
    prompt: np.ndarray            # (prompt_len,) int32 token ids
    max_new_tokens: int
    offset_s: Optional[float] = None


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def rng_for(seed: int) -> np.random.Generator:
    """numpy generator for any whole-number seed (also past 2**32)."""
    return np.random.default_rng(int(seed))


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
        x = np.clip(x, spec["min"], spec["max"])
        step = spec.get("round_up_to", 1)
        x = np.ceil(x / step) * step
        return np.clip(x, spec["min"], spec["max"]).astype(np.int64)
    if spec["dist"] == "uniform":
        vals = np.arange(spec["min"], spec["max"] + 1, spec["step"])
        return rng.choice(vals, size=n).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _sizes(mix: dict, n: int):
    base = rng_for(mix["shape_seed"])
    return draw_lengths(mix["prompt"], n, base), \
        draw_lengths(mix["output"], n, base), base


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests due in the warm-up and a window of `seconds`, in due
    order; `offset_s` counts from the warm-up's start."""
    span = mix["warmup_s"] + seconds
    n = max(1, int(round(mix["rate_per_s"] * span)))
    plen, olen, base = _sizes(mix, n)
    # n exponential gaps, scaled so the last arrival lands before the end
    gaps = base.exponential(1.0 / mix["rate_per_s"], size=n)
    gaps *= min(1.0, span * n / (n + 1) / gaps.sum())
    offsets = np.cumsum(gaps)
    rng = rng_for(seed)
    return [Item(rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32),
                 int(olen[i]), float(offsets[i]))
            for i in range(n)]


def closed_loop(mix: dict, seed: int, vocab: int) -> List[Item]:
    """The pool of requests the clients draw from, in order (the
    harness cycles through it)."""
    n = mix["pool"]
    plen, olen, _ = _sizes(mix, n)
    rng = rng_for(seed)
    return [Item(rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32),
                 int(olen[i]))
            for i in range(n)]

