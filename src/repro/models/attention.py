"""GQA attention blocks: self-attention (train / prefill / decode) and
cross-attention for the encoder-decoder arch.

The contraction itself is delegated to kernels/flash_attention (prefill) and
kernels/decode_attention (decode), which pick pallas on TPU and the jnp
oracle elsewhere.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.parallel import per_kv_shard
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import ops as flash_ops
from repro.models.layers import apply_rope
from repro.models.module import Initializer


def attn_init(init: Initializer, cfg: ModelConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    init.param("wq", (d, h, hd), ("embed", "qheads", "head_dim"))
    init.param("wk", (d, kv, hd), ("embed", "kvheads", "head_dim"))
    init.param("wv", (d, kv, hd), ("embed", "kvheads", "head_dim"))
    init.param("wo", (h, hd, d), ("qheads", "head_dim", "embed"))
    if cfg.qkv_bias and not cross:
        init.param("bq", (h, hd), ("qheads", "head_dim"), init="zeros")
        init.param("bk", (kv, hd), ("kvheads", "head_dim"), init="zeros")
        init.param("bv", (kv, hd), ("kvheads", "head_dim"), init="zeros")


def _qkv(params, x, cfg: ModelConfig, positions, rope: bool):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def sharded_mha(q, k, v, ctx, *, causal: bool = True):
    """Flash attention under shard_map: heads over the tp axis (with local
    GQA group slicing) and batch over dp. Inside shard_map all arrays are
    local, so the triangular scan's traced-index tile loads stay local
    slices — outside it, XLA SPMD 'involuntarily rematerializes' (measured:
    multiple TB of all-gather per step on kimi train_4k).
    """
    from jax.sharding import PartitionSpec as P

    B, S, H, D = q.shape
    KV = k.shape[2]
    R = H // KV
    if ctx is None or ctx.mesh is None:
        return flash_ops.mha(q, k, v, causal=causal)
    tp = ctx.axis_size(ctx.tp_axis)
    dp_ok = ctx.dp_axes and ctx.dp_size > 1 and B % ctx.dp_size == 0
    dp = ctx.dp_axes if dp_ok else None
    H_loc = H // tp if (ctx.tp_axis and tp > 1 and H % tp == 0) else H
    heads_sharded = H_loc != H
    # shard heads only if each shard's heads map onto whole/aligned groups
    if heads_sharded and not (H_loc % R == 0 or R % H_loc == 0):
        heads_sharded = False
        H_loc = H
    h_ax = ctx.tp_axis if heads_sharded else None
    if dp is None and h_ax is None:
        return flash_ops.mha(q, k, v, causal=causal)

    def body(ql, kl, vl):
        if h_ax is not None:
            s = jax.lax.axis_index(h_ax)
            if H_loc >= R:
                g0, G_loc = (s * H_loc) // R, H_loc // R
            else:
                g0, G_loc = (s * H_loc) // R, 1
            kl = jax.lax.dynamic_slice_in_dim(kl, g0, G_loc, axis=2)
            vl = jax.lax.dynamic_slice_in_dim(vl, g0, G_loc, axis=2)
        return flash_ops.mha(ql, kl, vl, causal=causal)

    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(
            P(dp, None, h_ax, None),
            P(dp, None, None, None),
            P(dp, None, None, None),
        ),
        out_specs=P(dp, None, h_ax, None),
        check_vma=False,
    )(q, k, v)


def self_attention(
    params,
    x,                      # (B, S, d)
    cfg: ModelConfig,
    positions=None,         # (B, S) absolute positions
    causal: bool = True,
    rope: bool = True,
    return_kv: bool = False,
    ctx=None,
):
    """Full-sequence self-attention (training / prefill)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k, v = _qkv(params, x, cfg, positions, rope)
    out = sharded_mha(q, k, v, ctx, causal=causal)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


def _cache_insert(cache, new, t):
    """Insert `new` (B,1,KV,hd) at sequence position(s) t — a scalar shared
    by the batch or a (B,) vector of per-slot positions (continuous
    batching) — via a masked elementwise write. A dynamic-update-slice at a
    traced index on a sequence-SHARDED cache makes XLA SPMD all-gather the
    whole cache (measured: 40 GB of wire per decoded token); the
    iota-compare form partitions with zero communication. A position >= S
    writes nothing (masked slots park their cursor out of range)."""
    S = cache.shape[1]
    t = jnp.asarray(t)
    if t.ndim == 0:
        mask = (jax.lax.iota(jnp.int32, S) == t)[None, :, None, None]
    else:
        mask = (jax.lax.iota(jnp.int32, S)[None, :] == t[:, None])
        mask = mask[:, :, None, None]
    return jnp.where(mask, new.astype(cache.dtype), cache)


def decode_self_attention(
    params,
    x,                      # (B, 1, d) the new token
    cfg: ModelConfig,
    k_cache,                # (B, S_max, KV, hd)
    v_cache,
    t,                      # scalar or (B,): current position(s) / valid len
    rope: bool = True,
):
    """Single-token decode: insert new KV at position t, attend to prefix.

    `t` may be a (B,) vector so that in-flight requests at different depths
    share one fixed-shape decode cell (the serving engine's slot batching);
    the cache length mask and RoPE positions are then per-slot.
    """
    B = x.shape[0]
    t = jnp.asarray(t)
    t_vec = t if t.ndim else jnp.full((B,), t)
    positions = t_vec[:, None]
    q, k, v = _qkv(params, x, cfg, positions, rope)
    k_cache = _cache_insert(k_cache, k, t)
    v_cache = _cache_insert(v_cache, v, t)
    out = decode_ops.decode_mha(q[:, 0], k_cache, v_cache, t_vec + 1)
    out = jnp.einsum("bhk,hkd->bd", out, params["wo"].astype(x.dtype))
    return out[:, None, :], (k_cache, v_cache)


def cross_attention(
    params,
    x,                      # (B, Sq, d) decoder states
    enc_kv,                 # (k, v): (B, Senc, KV, hd) precomputed from encoder
    cfg: ModelConfig,
):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k, v = enc_kv
    out = flash_ops.mha(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def encode_cross_kv(params, enc_out, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dt))
    return k, v


# ------------------------------------------------------- paged KV cache
def _page_coords(t_vec, block_table, page_tokens: int):
    """Per-slot (physical page, in-range mask, in-page offset) of write
    position(s) `t_vec` through the block table."""
    n_pages = block_table.shape[1]
    pidx = t_vec // page_tokens
    off = t_vec % page_tokens
    in_range = pidx < n_pages
    phys = jnp.take_along_axis(
        block_table, jnp.clip(pidx, 0, n_pages - 1)[:, None], axis=1
    )[:, 0]
    return phys, in_range, off


def paged_cache_insert(pool, new, t, block_table, page_tokens: int):
    """Write one token of K or V per slot into a PHYSICAL page pool.

    pool (P_phys, page, KV, hd); new (B, 1, KV, hd); t scalar or (B,)
    absolute position(s); block_table (B, n_pages) logical->physical page
    map (`KVPager.block_table` layout). The write lands at
    pool[bt[b, t//page], t%page]. Positions past the table (parked slots)
    scatter out of bounds and DROP — the paged twin of `_cache_insert`'s
    masked no-op. Physical pages are uniquely owned, so the scatter never
    collides."""
    B = new.shape[0]
    t = jnp.asarray(t)
    t_vec = (t if t.ndim else jnp.full((B,), t)).astype(jnp.int32)
    phys, in_range, off = _page_coords(t_vec, block_table, page_tokens)
    phys = jnp.where(in_range, phys, pool.shape[0])   # OOB -> dropped
    return pool.at[phys, off].set(new[:, 0].astype(pool.dtype),
                                  mode="drop")


def paged_quant_cache_insert(pool, sz, new, t, block_table,
                             page_tokens: int):
    """int8 twin of `paged_cache_insert`: write one fp token per slot
    into a BLOCK-QUANTIZED page pool. Per-page (scale, zero) quantization
    cannot splice a single int8 row into a page whose range it may move,
    so the insert is a read-modify-write of the slot's tail page:
    dequantize it, zero the rows past the write cursor (fresh free-list
    pages carry stale payload — the garbage must not pollute the range),
    land the token, and requantize the page with a fresh (scale, zero).
    One page per slot per step — the hot tail the pager keeps local —
    and rows whose range did not move requantize onto the identical int8
    grid, so steady pages round-trip bit-stably. Parked positions drop
    exactly like the fp path. Returns (pool, sz).

    With PER-TOKEN sub-scales (`sz` ranked like the pool itself:
    (P_phys, page, KV, 2) — the speculative-decoding hot-page layout)
    the round trip disappears entirely: each token row quantizes against
    its own (scale, zero) over head_dim and lands payload + sz row as a
    pure disjoint scatter, so a verify step's k rows per slot (distinct
    positions, hence distinct (page, offset) targets) never collide and
    nothing already stored is ever re-quantized."""
    from repro.kernels import quant

    B = new.shape[0]
    t = jnp.asarray(t)
    t_vec = (t if t.ndim else jnp.full((B,), t)).astype(jnp.int32)
    phys, in_range, off = _page_coords(t_vec, block_table, page_tokens)
    if sz.ndim == pool.ndim:                 # per-token sub-scales
        q8, tsz = quant.quantize_tokens(new[:, 0].astype(jnp.float32))
        phys_w = jnp.where(in_range, phys, pool.shape[0])  # OOB -> dropped
        pool = pool.at[phys_w, off].set(q8, mode="drop")
        sz = sz.at[phys_w, off].set(tsz, mode="drop")
        return pool, sz
    phys_r = jnp.where(in_range, phys, 0)        # safe gather, discarded
    page_q = pool[phys_r]                        # (B, page, KV, hd) int8
    page_f = quant.dequantize_pages(page_q, sz[phys_r])
    iota = jax.lax.iota(jnp.int32, page_tokens)[None, :, None, None]
    off_b = off[:, None, None, None]
    page_f = jnp.where(iota < off_b, page_f, 0.0)
    page_f = jnp.where(iota == off_b, new.astype(jnp.float32), page_f)
    q8, new_sz = quant.quantize_pages(page_f)
    phys_w = jnp.where(in_range, phys, pool.shape[0])   # OOB -> dropped
    pool = pool.at[phys_w].set(q8, mode="drop")
    sz = sz.at[phys_w].set(new_sz, mode="drop")
    return pool, sz


def paged_chunk_insert(pool, new, c0, block_row, page_tokens: int):
    """Write a page-aligned CHUNK of K or V through the block table.

    pool (P_phys, page, KV, hd); new (1, C, KV, hd) with C a multiple of
    `page_tokens`; c0 (traced) chunk start, also page-aligned; block_row
    (1, n_pages) the prefilling slot's block-table row. Whole pages are
    scattered at once — the chunked-prefill fast path."""
    _, C, KV, hd = new.shape
    n_wp = C // page_tokens
    p0 = jnp.asarray(c0, jnp.int32) // page_tokens
    phys = jax.lax.dynamic_slice(block_row, (jnp.int32(0), p0),
                                 (1, n_wp))[0]        # (n_wp,)
    tiles = new[0].reshape(n_wp, page_tokens, KV, hd)
    return pool.at[phys].set(tiles.astype(pool.dtype))


def paged_decode_self_attention(
    params,
    x,                      # (B, 1, d) the new token
    cfg: ModelConfig,
    cache,                  # attention cache dict: "k"/"v" physical page
    #                         pools (P_phys, page, KV, hd), plus
    #                         "k_sz"/"v_sz" (P_phys, KV, 2) when int8
    t,                      # scalar or (B,): current position(s)
    block_table,            # (B, n_pages) int32
    page_tokens: int,
    rope: bool = True,
    ctx=None,
):
    """Single-token decode against the paged cache: insert new KV through
    the block table, gather-attend via the paged decode kernel. Same
    contract as `decode_self_attention` — per-slot `t`, parked positions
    write nothing — but the cache IS the physical page pool the serving
    pager allocates from, so tier placement is real at the data layout.
    Block-quantized pools (the "k_sz"/"v_sz" leaves) quantize on insert
    and dequantize inside the kernel. Under a mesh the kernel runs per
    tp shard (`common.parallel.per_kv_shard`). Returns
    (out, cache_updates)."""
    B = x.shape[0]
    t = jnp.asarray(t)
    t_vec = t if t.ndim else jnp.full((B,), t)
    positions = t_vec[:, None]
    q, k, v = _qkv(params, x, cfg, positions, rope)
    quantized = "k_sz" in cache
    if quantized:
        k_pool, k_sz = paged_quant_cache_insert(
            cache["k"], cache["k_sz"], k, t_vec, block_table, page_tokens)
        v_pool, v_sz = paged_quant_cache_insert(
            cache["v"], cache["v_sz"], v, t_vec, block_table, page_tokens)
        out = per_kv_shard(
            ctx, cfg.num_kv_heads,
            lambda q, kp, vp, ks, vs, bt, ln: decode_ops.paged_decode_mha(
                q, kp, vp, bt, ln, k_sz=ks, v_sz=vs),
            (q[:, 0], k_pool, v_pool, k_sz, v_sz),
            (block_table, t_vec + 1),
        )
        updates = {"k": k_pool, "v": v_pool, "k_sz": k_sz, "v_sz": v_sz}
    else:
        k_pool = paged_cache_insert(cache["k"], k, t_vec, block_table,
                                    page_tokens)
        v_pool = paged_cache_insert(cache["v"], v, t_vec, block_table,
                                    page_tokens)
        out = per_kv_shard(
            ctx, cfg.num_kv_heads, decode_ops.paged_decode_mha,
            (q[:, 0], k_pool, v_pool), (block_table, t_vec + 1),
        )
        updates = {"k": k_pool, "v": v_pool}
    out = jnp.einsum("bhk,hkd->bd", out, params["wo"].astype(x.dtype))
    return out[:, None, :], updates


def paged_prefill_chunk_attention(
    params,
    x,                      # (1, C, d) one chunk of one request's prompt
    cfg: ModelConfig,
    cache,                  # attention cache dict (see
    #                         `paged_decode_self_attention`)
    c0,                     # (traced) absolute position of the chunk start
    block_row,              # (1, n_pages) the slot's block-table row
    page_tokens: int,
    rope: bool = True,
    ctx=None,
):
    """One prompt chunk against the paged cache via the FUSED
    insert+attend kernel: the chunk's K/V (int8 pools: pre-quantized
    payload + per-page (scale, zero) rows — elementwise math, no
    scatter) goes into the paged-prefill kernel as an operand and lands
    in the pool through `input_output_aliases` while the same pass
    flash-attends over everything prefilled so far. The standalone jnp
    page-scatter of the chunk's K/V — one full extra read+write of the
    chunk through HBM per layer — does not exist on the kernel backends
    (the reference backend runs the unfused oracle). C and c0 must be
    page-aligned (the engine enforces `prefill_chunk % page_tokens ==
    0`). Under a mesh the kernel runs per tp shard. Returns
    (out, cache_updates)."""
    B, C, _ = x.shape
    c0 = jnp.asarray(c0, jnp.int32)
    positions = c0 + jnp.broadcast_to(jnp.arange(C)[None, :], (B, C))
    q, k, v = _qkv(params, x, cfg, positions, rope)
    n_kv = cfg.num_kv_heads
    quantized = "k_sz" in cache
    if quantized and cache["k_sz"].ndim == cache["k"].ndim:
        # per-token sub-scale pool (speculative decoding): quantize each
        # chunk token row against its own (scale, zero), whole-page
        # scatter payload + sz rows, then gather-attend — the fused
        # insert kernel stays per-page-only, and chunked prefill is off
        # the decode hot loop so the unfused write is acceptable here
        from repro.kernels import quant

        k8, ksz = quant.quantize_tokens(k.astype(jnp.float32))
        v8, vsz = quant.quantize_tokens(v.astype(jnp.float32))
        k_pool = paged_chunk_insert(cache["k"], k8, c0, block_row,
                                    page_tokens)
        v_pool = paged_chunk_insert(cache["v"], v8, c0, block_row,
                                    page_tokens)
        k_sz = paged_chunk_insert(cache["k_sz"], ksz, c0, block_row,
                                  page_tokens)
        v_sz = paged_chunk_insert(cache["v_sz"], vsz, c0, block_row,
                                  page_tokens)
        out = per_kv_shard(
            ctx, n_kv,
            lambda q, kp, vp, ks, vs, bt, c0: flash_ops.paged_prefill_mha(
                q, kp, vp, bt, c0, k_sz=ks, v_sz=vs),
            (q, k_pool, v_pool, k_sz, v_sz), (block_row, c0),
        )
        updates = {"k": k_pool, "v": v_pool, "k_sz": k_sz, "v_sz": v_sz}
    elif quantized:
        from repro.kernels import quant

        n_wp = C // page_tokens
        KV, hd = k.shape[2], k.shape[3]
        k8, ksz = quant.quantize_pages(
            k.reshape(B, n_wp, page_tokens, KV, hd))
        v8, vsz = quant.quantize_pages(
            v.reshape(B, n_wp, page_tokens, KV, hd))
        out, k_pool, v_pool, k_sz, v_sz = per_kv_shard(
            ctx, n_kv, flash_ops.paged_prefill_insert_mha_q8,
            (q, cache["k"], cache["v"], cache["k_sz"], cache["v_sz"],
             k8.reshape(B, C, KV, hd), v8.reshape(B, C, KV, hd), ksz, vsz),
            (block_row, c0),
        )
        updates = {"k": k_pool, "v": v_pool, "k_sz": k_sz, "v_sz": v_sz}
    else:
        out, k_pool, v_pool = per_kv_shard(
            ctx, n_kv, flash_ops.paged_prefill_insert_mha,
            (q, cache["k"], cache["v"], k, v), (block_row, c0),
        )
        updates = {"k": k_pool, "v": v_pool}
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, updates


def decode_cross_attention(params, x, cross_kv, cfg: ModelConfig):
    """Decode-time cross-attention against the fixed encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k, v = cross_kv
    S_enc = k.shape[1]
    out = decode_ops.decode_mha(q[:, 0], k, v, S_enc)
    return jnp.einsum("bhk,hkd->bd", out, params["wo"].astype(dt))[:, None, :]


def make_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, n_layers: int,
                  dtype=None):
    """Stacked KV cache for the attention layers of a model."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (n_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
