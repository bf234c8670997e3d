"""Pure-jnp oracle for single-token decode attention against a KV cache.

Written in grouped-GQA einsum form (no jnp.repeat): broadcasting the KV
heads to Q heads makes XLA SPMD replicate a sequence-sharded cache
(measured: 40 GB of all-gather per decoded token on the 16x16 mesh); the
grouped contraction partitions cleanly along the sharded sequence dim with
only an (B,H,1)-sized psum for the softmax statistics.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def gather_pages(pages: jnp.ndarray, block_tables) -> jnp.ndarray:
    """Assemble a dense (B, S, KV, D) cache from a physical page pool
    (P_phys, page, KV, D) through (B, n_logical) block tables — the
    oracle's view of the paged layout (and the parity test's bridge
    between `KVPager.block_table` and the dense reference)."""
    block_tables = jnp.asarray(block_tables, jnp.int32)
    g = pages[block_tables]                 # (B, n_logical, page, KV, D)
    B, n, page, KV, D = g.shape
    return g.reshape(B, n * page, KV, D)


def gather_pages_q8(pages: jnp.ndarray, sz: jnp.ndarray, block_tables,
                    dtype=jnp.float32) -> jnp.ndarray:
    """`gather_pages` for a block-quantized pool: int8 payload
    (P_phys, page, KV, D) plus (scale, zero) ``sz`` float32
    (`repro.kernels.quant` layout), dequantized to a dense (B, S, KV, D)
    cache. The sz grain is dispatched on rank: per-page
    (P_phys, KV, 2) or per-token (P_phys, page, KV, 2) — the
    speculative-decoding sub-scale layout."""
    from repro.kernels import quant

    block_tables = jnp.asarray(block_tables, jnp.int32)
    g = pages[block_tables]                 # (B, n_logical, page, KV, D)
    s = sz[block_tables]
    if sz.ndim == pages.ndim:               # per-token sub-scales
        d = quant.dequantize_tokens(g, s, dtype=dtype)
    else:                                   # per-page (B, n, KV, 2)
        d = quant.dequantize_pages(g, s, dtype=dtype)
    B, n, page, KV, D = d.shape
    return d.reshape(B, n * page, KV, D)


def paged_decode_mha(q, k_pages, v_pages, block_tables, lengths, *,
                     k_sz=None, v_sz=None, scale=None) -> jnp.ndarray:
    """Paged oracle: gather to dense (dequantizing int8 pools through the
    per-page (scale, zero) arrays when given), then the dense oracle."""
    if k_sz is not None:
        k = gather_pages_q8(k_pages, k_sz, block_tables, dtype=q.dtype)
        v = gather_pages_q8(v_pages, v_sz, block_tables, dtype=q.dtype)
    else:
        k = gather_pages(k_pages, block_tables)
        v = gather_pages(v_pages, block_tables)
    return decode_mha(q, k, v, lengths, scale=scale)


def decode_mha(
    q: jnp.ndarray,       # (B, H, D) one new token per sequence
    k: jnp.ndarray,       # (B, S, KV, D) cache
    v: jnp.ndarray,       # (B, S, KV, D)
    length,               # int or (B,) valid prefix length(s)
    *,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, S, KV, _ = k.shape
    R = H // KV
    scale = scale if scale is not None else D ** -0.5
    length = jnp.asarray(length)
    if length.ndim == 0:
        length = jnp.full((B,), length)

    qg = q.reshape(B, KV, R, D)
    logits = jnp.einsum(
        "bgrd,bsgd->bgrs", qg, k,
        preferred_element_type=jnp.float32,
    ) * scale                                            # (B,KV,R,S) f32
    mask = jnp.arange(S)[None, :] < length[:, None]      # (B,S)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    m = logits.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)     # length 0: all masked
    p = jnp.exp(logits - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum(
        "bgrs,bsgd->bgrd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ) / jnp.where(l > 0, l, 1.0)[..., 0:1]     # ... returns zeros
    return out.reshape(B, H, D).astype(q.dtype)
