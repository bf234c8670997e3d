"""Random weights from `--seed`, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so that the plain
reference can read them: it takes nothing the program has made. The tree
follows the program's parameter layout (`cells.abstract_params`: one
stacked leaf per role, layers on axis 0), in the dtype it is served in,
and lands straight in the program's parameter shardings.

Scales: every matrix is normal with std 1/sqrt(fan-in) (the contracted
dims); norm scales are 1 + 0.1 * normal, so a dropped norm scale shows.
Two choices keep the greedy picks sensitive to the whole model, since
the check compares served tokens:

* the embedding is EMBED times 1/sqrt(hidden), so the residual stream
  is carried by the layers. At unit scale a tied-embedding model repeats
  its input token with a margin of ~6.5 logits (CPU, 16 layers of
  smollm-360m: 512 of 512 picks), and no fault of the context, nor the
  float8 control, can change a pick;
* the query and key projections are SHARPEN times larger, so attention
  scores spread with a std of about SHARPEN**2 and a query attends to
  tens of keys rather than averaging all of them away, where a fault in
  the KV cache would go unseen. Sharper still (2.2) makes the random
  32-layer model chaotic: bfloat16 rounding alone then moves logits by
  units (measured on the chip).

With both (CPU, 16 layers of smollm-360m, 512 positions): the bf16
program's widest gap 0.015, the float8 control's 0.238.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

IMPL = "threefry2x32"          # see seed_key
SHARPEN = 1.4
EMBED = 0.1
_MULT = {"wq": SHARPEN, "wk": SHARPEN}
# fan-in: the leaf dims a matmul contracts (layer axis 0 excluded)
_FAN_IN = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
           "w_gate": (1,), "w_up": (1,), "w_down": (1,), "lm_head": (0,)}


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    # threefry is partitioned by XLA:TPU, so each chip draws only its
    # shard ("rbg" draws every leaf whole on each chip: 11.7 GB of
    # temporaries for mistral-nemo's feed-forward, compiled for v5e:2x2)
    key = jax.random.key(seed % (1 << 31), impl=IMPL)
    return jax.random.fold_in(key, seed >> 31)


def _leaf(key, path, shape, dtype):
    name = path[-1].key
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "embedding":
        std = EMBED / math.sqrt(shape[1])
    elif name in _FAN_IN:
        std = _MULT.get(name, 1.0) / math.sqrt(
            math.prod(shape[d] for d in _FAN_IN[name]))
    else:
        raise ValueError(f"no scale rule for weight {jax.tree_util.keystr(path)}")
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(abstract_params, shardings, seed: int):
    """The weight tree for `abstract_params` (shape structs), placed per
    `shardings` (a matching tree of shardings, or None for the default
    device). One compiled program for every seed: the key is an argument."""
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        abstract_params)[0]]
    treedef = jax.tree.structure(abstract_params)
    leaves = jax.tree.leaves(abstract_params)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _leaf(k, p, a.shape, a.dtype)
            for k, p, a in zip(keys, paths, leaves)])

    fn = jax.jit(init, out_shardings=shardings)
    return fn(seed_key(seed))
