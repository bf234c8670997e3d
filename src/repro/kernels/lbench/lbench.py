"""LBench as a Pallas TPU kernel — the paper's interference/roofline probe.

The FMA chain (`beta = beta * A[i] + alpha`, NFLOP//2 times) is unrolled at
trace time exactly like the paper's `#pragma GCC unroll 16`; NFLOP selects
the arithmetic intensity (NFLOP/8 flop/B for f32 read+write), which is how
LoI is dialed. BlockSpec tiles the array through VMEM in (block_rows, 128)
tiles — 128 matches the VPU lane width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(a_ref, o_ref, *, nflop: int, alpha: float):
    x = a_ref[...]
    beta = x + alpha if (nflop % 2 == 1) else x
    for _ in range(nflop // 2):
        beta = beta * x + alpha
    o_ref[...] = beta


@functools.partial(
    jax.jit, static_argnames=("nflop", "alpha", "interpret", "block_rows")
)
def lbench_pallas(a, nflop: int, alpha: float = 0.5, *,
                  interpret: bool = False, block_rows: int = 512):
    orig_shape = a.shape
    n = a.size
    assert n % LANES == 0, f"size {n} must be a multiple of {LANES}"
    rows = n // LANES
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    br = max(br, 1)
    grid = (rows // br,)
    a2 = a.reshape(rows, LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, nflop=nflop, alpha=alpha),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), a.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((br, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        name="lbench_pallas",
        interpret=interpret,
    )(a2)
    return out.reshape(orig_shape)
