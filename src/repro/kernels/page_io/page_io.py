"""Aliased page-writer Pallas kernel: land whole page tiles in a physical
page pool IN PLACE via `input_output_aliases` — the kernel-level
replacement for the standalone jnp page scatter (`pool.at[:, phys].set`)
the prefill-insert cell used to issue per K/V leaf, which costs one full
extra read+write of the pool through HBM on every admission.

The physical page ids ride the scalar-prefetch channel, so the output
BlockSpec index map chases `phys[j]` exactly like the paged attention
kernels chase the block table. Grid (nb, n_wp): one step per (stack
level, written page); each step copies its whole (*page_dims) tile into
the aliased pool block. The tile is whole in its minor dims, as Mosaic's
tiling needs, so the pool keeps its own layout and is never reshaped.
Every block the grid never names keeps the input pool's bytes —
aliasing turns "rewrite the whole pool" into "DMA just the chunk's
pages". Pages must be uniquely owned (the pager's free-list contract),
so no two grid steps target the same block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(phys_ref, t_ref, pool_ref, o_ref):
    del phys_ref, pool_ref          # phys is chased by the index maps;
    # the pool input exists only to alias the output buffer
    o_ref[...] = t_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_pages_pallas(pool, tiles, phys, *, interpret: bool = False):
    """pool (nb, P_phys, *page_dims), tiles (nb, n_wp, *page_dims) in the
    pool dtype with at least two page dims, phys (n_wp,) int32 unique
    physical page ids. Returns the pool with `pool[:, phys[j]] =
    tiles[:, j]` applied in place (aliased)."""
    from jax.experimental.pallas import tpu as pltpu

    nb = pool.shape[0]
    n_wp = tiles.shape[1]
    block = (1, 1) + tuple(pool.shape[2:])
    rest = (0,) * (len(block) - 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                   # the physical page ids
        grid=(nb, n_wp),
        in_specs=[
            pl.BlockSpec(block, lambda i, j, phys: (i, j) + rest),
            pl.BlockSpec(block, lambda i, j, phys: (i, phys[j]) + rest),
        ],
        out_specs=pl.BlockSpec(block,
                               lambda i, j, phys: (i, phys[j]) + rest),
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=grid_spec,
        # inputs count the scalar-prefetch operand: phys(0) tiles(1) pool(2)
        input_output_aliases={2: 0},
        name="write_pages_pallas",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
    )(jnp.asarray(phys, jnp.int32), tiles, pool)
