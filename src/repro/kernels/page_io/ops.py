"""Public page-writer op: land page tiles in the physical pool.

Reference backend scatters with jnp (`ref.write_pages`); pallas/interpret
run the aliased in-place kernel, so the serving prefill-insert cell
issues zero standalone page-scatter ops on the kernel backends. The
kernel copies whole (*page_dims) tiles in the pool's own layout."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import select_impl
from repro.kernels.page_io import ref


@functools.partial(jax.jit, static_argnames=("impl",))
def write_pages(pool, tiles, phys, *, impl: Optional[str] = None):
    """pool (nb, P_phys, *page_dims), tiles (nb, n_wp, *page_dims), phys
    (n_wp,) int32 unique physical page ids (live block-table entries).
    Returns the pool with the tiles landed at their physical pages."""
    tiles = tiles.astype(pool.dtype)
    kind, interpret = select_impl(impl)
    if kind == "reference":
        return ref.write_pages(pool, tiles, phys)
    from repro.kernels.page_io import page_io

    return page_io.write_pages_pallas(pool, tiles, phys,
                                      interpret=interpret)
