"""Public decode-attention op (the serving hot loop)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import select_impl
from repro.kernels.decode_attention import ref


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def decode_mha(
    q,
    k,
    v,
    length,
    *,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
):
    """q (B,H,D) vs cache k/v (B,S,KV,D) with valid `length`."""
    kind, interpret = select_impl(impl)
    if kind == "reference":
        return ref.decode_mha(q, k, v, length, scale=scale)
    from repro.kernels.decode_attention import decode_attention as da

    return da.flash_decode(q, k, v, length, scale=scale,
                           interpret=interpret)


def clamp_dead_entries(block_tables, n_pages, page, frontier):
    """Clamp block-table entries at/past the per-sequence `frontier`
    (valid token count for decode; the causal frontier c0+C for chunked
    prefill — `flash_attention.ops` shares this helper) to physical page
    0 so the gather stays in bounds on every backend; the kernels' masks
    keep them out of the math."""
    live = (
        jnp.arange(n_pages, dtype=jnp.int32)[None, :] * page
        < frontier[:, None]
    )
    return jnp.where(live, jnp.asarray(block_tables, jnp.int32), 0)


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def paged_decode_mha(
    q,
    k_pages,
    v_pages,
    block_tables,
    lengths,
    *,
    k_sz=None,
    v_sz=None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
):
    """q (B,H,D) vs a PAGED cache: k/v (P_phys, page, KV, D) physical page
    pool + (B, n_logical) block tables (`KVPager.block_table` layout) with
    per-sequence valid `lengths`.

    A length past the table (> n_logical * page) marks a PARKED row: the
    engine parks every slot that is not decoding at n_logical * page, so
    its `t + 1` lands there, and the cache insert drops writes at such
    positions. Such a row has no live page and returns zeros, on every
    backend. Block-table entries at or past a row's length are never
    read by the kernel; the reference clamps them to physical page 0 so
    its gather stays in bounds, and the length mask keeps them out of
    the math.

    `k_sz`/`v_sz` (P_phys, KV, 2) float32 switch the pool to int8 block
    quantization (`repro.kernels.quant`): the payload is int8 and the
    kernel (or oracle) dequantizes each gathered page with its per-page
    (scale, zero) pair."""
    n_pages = block_tables.shape[1]
    page = k_pages.shape[1]
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32), (q.shape[0],)
    )
    lengths = jnp.where(lengths > n_pages * page, 0, lengths)
    kind, interpret = select_impl(impl)
    if kind == "reference":
        block_tables = clamp_dead_entries(block_tables, n_pages, page,
                                          lengths)
        return ref.paged_decode_mha(q, k_pages, v_pages, block_tables,
                                    lengths, k_sz=k_sz, v_sz=v_sz,
                                    scale=scale)
    from repro.kernels.decode_attention import paged as pg

    return pg.paged_flash_decode(
        q, k_pages, v_pages, block_tables, lengths, k_sz=k_sz, v_sz=v_sz,
        scale=scale, interpret=interpret,
    )
