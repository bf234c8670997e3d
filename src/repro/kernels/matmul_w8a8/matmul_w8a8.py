"""W8A8 int8 matmul Pallas kernel with megacore partitioning.

int8 x int8 tiles contract on the MXU into an int32 VMEM accumulator;
the dequant epilogue (rank-1 outer product of the per-row activation
scales and per-column weight scales) runs once on the final K step.
Grid (M/bm, N/bn, K/bk) with `dimension_semantics=("parallel",
"parallel", "arbitrary")`: the independent output tiles split across
the TPU's TensorCores (megacore), only the K reduction is sequential —
the matmul twin of the paged attention kernels' partitioning, and the
compute cell that matches the serving engine's int8 pool default
(per-token sub-scale pages quantize K/V rows the same symmetric way a
W8A8 activation row is quantized here).

Tile floors follow the int8 (32, 128) TPU tiling: the default 128x128
output blocks with K steps of 128 satisfy every operand's minimum tile.
The public wrapper (`ops.matmul_w8a8`) zero-pads ragged shapes up to
block multiples — zero rows/columns contract to zero, so padding never
changes the visible output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = (
            acc[...].astype(jnp.float32) * sa_ref[...] * sb_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def matmul_w8a8_pallas(a8, b8, sa, sb, *, block_m: int = 128,
                       block_n: int = 128, block_k: int = 128,
                       interpret: bool = False):
    """a8 (M, K) int8 @ b8 (K, N) int8 with per-row scales sa (M,) and
    per-column scales sb (N,) float32 -> (M, N) float32. M, N, K must be
    multiples of the block sizes (ops.py pads)."""
    from jax.experimental.pallas import tpu as pltpu

    M, K = a8.shape
    _, N = b8.shape
    nk = K // block_k
    grid = (M // block_m, N // block_n, nk)
    sa2 = jnp.asarray(sa, jnp.float32).reshape(M, 1)
    sb2 = jnp.asarray(sb, jnp.float32).reshape(1, N)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((block_k, block_n), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, ki: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        name="matmul_w8a8_pallas",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # MEGACORE: output tiles are independent -> parallel; only
            # the K reduction carries the accumulator sequentially
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
    )(a8, b8, sa2, sb2)
