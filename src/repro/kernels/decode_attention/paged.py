"""Paged flash-decode Pallas kernel: one query token vs a KV cache stored
as fixed-size pages scattered through a physical page pool, gathered via a
per-sequence block-index map — the kernel-level realization of the serving
pager's page grain (`serving/kv_pager.py` hands out exactly this layout
via `KVPager.block_table`).

The block tables and lengths ride the scalar-prefetch channel
(`pltpu.PrefetchScalarGridSpec`): they are resident in SMEM before the
kernel body runs, so the K/V BlockSpec index maps can chase
`bt[b, page_idx]` to DMA each NON-CONTIGUOUS physical page while the
previous page's flash update is still computing — the same
fetch-one-page-ahead overlap the prefetch subsystem models at the tier
level, here done by Mosaic's double-buffered pipeline at the VMEM level.

Tiling: a pool page is (page, KV, hd), so its (KV, hd) minor dims must be
fetched whole (Mosaic's block rule: the last two block dims equal the
array's or are (8, 128)-aligned). One grid step therefore DMAs one
physical page with ALL its KV heads and walks the heads in a static loop;
each KV head's `rep` GQA query heads attend to it as one (rep, hd) x
(hd, page) contraction. Queries arrive grouped as (B, KV, rep, hd) so
every block is whole in its minor dims too.

Block-quantized pools (`repro.kernels.quant`): with int8 page payloads the
per-page float32 (scale, zero) pairs travel as a (KV, 2) SMEM block chased
through the same block-table entry as the payload (per-token sub-scales as
a (page, KV, 2) VMEM block), and the kernel applies the dequant epilogue
`q * scale + zero` right after each page's gather — the fp values never
exist in HBM, only in the VMEM tile the flash update is about to consume.
(Scalar prefetch would put the whole (P_phys, KV, 2) array in SMEM, which
a full-size pool overflows.)

Grid (B, n_logical_pages); the page dimension is sequential ("arbitrary")
so the online-softmax accumulators live in VMEM scratch across
iterations, exactly like the dense `decode_attention.py` kernel.
Out-of-length positions are masked by an iota test against `lengths`;
block-table entries past a sequence's last valid page must still name a
real physical page (the public wrapper in ops.py clamps them to 0) so the
gather stays in bounds — the mask keeps them out of the math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def online_softmax_update(q, k, v, mask, scale, acc, m_sc, l_sc):
    """One KV tile's online-softmax update of a (rows, D) accumulator.
    q (rows, D), k/v (cols, D) float32; `mask` broadcasts to (rows, cols);
    acc/m_sc/l_sc are (rows, D) / (rows, 1) / (rows, 1) VMEM refs."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
    m_sc[...] = m_new
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def dequant_head(x, sz_ref, g, sz_mode):
    """Dequantize head `g` of a gathered tile. `sz_ref` is the page's
    (1, KV, 2) per-page block or (1, rows, KV, 2) per-token block."""
    if sz_mode == "page":
        return x * sz_ref[0, g, 0] + sz_ref[0, g, 1]   # SMEM scalars
    sz = sz_ref[0, :, g, :]                             # (rows, 2)
    return x * sz[:, 0:1] + sz[:, 1:2]


def _kernel(*refs, page: int, scale: float, n_pages: int, kv: int,
            sz_mode: str):
    if sz_mode == "none":
        (bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
         acc, m_sc, l_sc) = refs
    else:
        (bt_ref, len_ref, q_ref, k_ref, v_ref, ksz_ref, vsz_ref, o_ref,
         acc, m_sc, l_sc) = refs
    del bt_ref                       # chased by the index maps only
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    pos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < len_ref[b]                          # (1, page)
    for g in range(kv):
        k = k_ref[0, :, g, :].astype(jnp.float32)     # (page, D)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        if sz_mode != "none":
            # fused dequant epilogue on the freshly gathered page
            k = dequant_head(k, ksz_ref, g, sz_mode)
            v = dequant_head(v, vsz_ref, g, sz_mode)
        q = q_ref[0, g].astype(jnp.float32)           # (rep, D)
        online_softmax_update(q, k, v, valid, scale, acc.at[g],
                              m_sc.at[g], l_sc.at[g])

    @pl.when(pi == n_pages - 1)
    def _done():
        o_ref[0] = (
            acc[...] / jnp.maximum(l_sc[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_flash_decode(q, k_pages, v_pages, block_tables, lengths, *,
                       k_sz=None, v_sz=None, scale=None,
                       interpret: bool = False):
    """q (B,H,D) vs paged cache k/v (P_phys, page, KV, D) through
    block_tables (B, n_logical_pages) int32 physical-page ids; `lengths`
    (B,) valid token counts. Logical page `i` of sequence `b` holds
    tokens [i*page, (i+1)*page) and lives at physical page
    `block_tables[b, i]`. Entries past the valid length must be in
    [0, P_phys) — use ops.paged_decode_mha, which clamps.

    With `k_sz`/`v_sz` float32 (scale, zero) arrays, the pool payload is
    int8 and the kernel dequantizes each gathered page in the epilogue
    (`repro.kernels.quant` layout). The sz grain dispatches on rank:
    per-page (P_phys, KV, 2) or per-token (P_phys, page, KV, 2) — the
    speculative-decoding sub-scale layout; either travels as a block
    whose BlockSpec chases the same block-table entry as the payload."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    _, page, KV, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    rep = H // KV
    if k_sz is None:
        sz_mode = "none"
    elif jnp.ndim(k_sz) == k_pages.ndim:
        sz_mode = "token"
    else:
        sz_mode = "page"
    scale = scale if scale is not None else D ** -0.5
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    block_tables = jnp.asarray(block_tables, jnp.int32)

    def page_map(b, pi, bt, ln):
        return (bt[b, pi],) + (0,) * (k_pages.ndim - 1)

    q_spec = pl.BlockSpec((1, KV, rep, D), lambda b, pi, bt, ln: (b, 0, 0, 0))
    page_spec = pl.BlockSpec((1, page, KV, D), page_map)
    in_specs = [q_spec, page_spec, page_spec]
    operands = (q.reshape(B, KV, rep, D), k_pages, v_pages)
    if sz_mode != "none":
        sz_block = (1,) + tuple(jnp.shape(k_sz)[1:])
        sz_spec = pl.BlockSpec(
            sz_block,
            lambda b, pi, bt, ln: (bt[b, pi],) + (0,) * (len(sz_block) - 1),
            # a page's (KV, 2) scalars are read one by one: SMEM
            memory_space=pltpu.SMEM if sz_mode == "page" else None)
        in_specs += [sz_spec, sz_spec]
        operands += (jnp.asarray(k_sz, jnp.float32),
                     jnp.asarray(v_sz, jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # block tables + lengths
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, rep, D), jnp.float32),
            pltpu.VMEM((KV, rep, 1), jnp.float32),
            pltpu.VMEM((KV, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, scale=scale, n_pages=n_pages,
                          kv=KV, sz_mode=sz_mode),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        grid_spec=grid_spec,
        name="paged_flash_decode",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # MEGACORE partitioning: the batch grid dimension is
            # "parallel" so Mosaic splits it across TensorCores; only the
            # page dimension is sequential (online-softmax carry)
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
    )(block_tables, lengths, *operands)
    return out.reshape(B, H, D)
