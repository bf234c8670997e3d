"""Paged flash-decode Pallas kernel: one query token vs a KV cache stored
as fixed-size pages scattered through a physical page pool, gathered via a
per-sequence block-index map — the kernel-level realization of the serving
pager's page grain (`serving/kv_pager.py` hands out exactly this layout
via `KVPager.block_table`).

The kernel walks only live pages, `ppb` logical pages a grid step. A
row's pages below its length form blocks of `ppb` pages; the wrapper
lists the live blocks of all rows, row after row (`_work_list`), and the
grid has one step per entry of that list: its size is a runtime scalar,
so a row of length 0 (a parked slot, see `ops.paged_decode_mha`) costs
no step and a short row costs no step past its last live block. The
list, the block tables and the lengths ride the scalar-prefetch channel
(`pltpu.PrefetchScalarGridSpec`), resident in SMEM before the body runs.

Each step brings its block as `ppb` page operands per pool leaf: the same
pool array passed `ppb` times, operand `j`'s BlockSpec index map chasing
`bt[row, blk*ppb + j]`, so Mosaic's pipeline DMAs step i+1's pages while
step i computes. A page past the row's length in its last block repeats
an index the operand already holds (the same operand's page of the
previous block), and the pipeline issues no copy for an unchanged block
index; its stale contents are masked out of both the scores and the V
rows. (Copies issued by hand from a `pl.ANY` pool did not compile for
the (page, KV, hd) layout: Mosaic refuses a slice of an HBM ref whose
minor dims, (KV, hd) there, are not tile-aligned.)

Tiling: a pool page is (page, KV, hd); the kernel reads the pool through
a (P_phys, KV, page, hd) view (`swapaxes`, which XLA makes a layout and
not a copy where the pool is laid out page-and-head minor), so a block's
(page, hd) minor dims are fetched whole (Mosaic's block rule: the last
two block dims equal the array's or are (8, 128)-aligned), pad to far
fewer tiles than (KV, hd) would, and one operand brings a page with ALL
its KV heads. The kernel walks the heads in a static loop; each KV head's
`rep` GQA query heads attend to the block as one (rep, hd) x
(hd, ppb*page) contraction, after each page has been dequantized on its
own. Queries arrive grouped as (B, KV, rep, hd) so every block is whole
in its minor dims too. `ppb` follows from the shapes (`block_pages`):
about 256 tokens a block, fewer where the buffers would outgrow the VMEM
budget.

Block-quantized pools (`repro.kernels.quant`): with int8 page payloads
each page's float32 (scale, zero) pairs travel as their own operands
chasing the same block-table entry as the payload — per-page (KV, 2)
pairs as SMEM blocks (scalar reads; scalar prefetch of the whole
(P_phys, KV, 2) array would overflow SMEM at a full-size pool),
per-token (page, KV, 2) rows as VMEM blocks — and the kernel applies the
dequant epilogue `q * scale + zero` page by page right after the gather:
the fp values never exist in HBM, only in the VMEM tile the flash update
is about to consume.

The online-softmax accumulators live in VMEM scratch across a row's
steps, exactly like the dense `decode_attention.py` kernel; the output
block of a row is written at its last live block, and a row with no
live block is zero.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
BLOCK_TOKENS = 256              # tokens a block aims at
VMEM_BUDGET = 8 * 1024 * 1024   # bytes of page buffers: K, V, sz; two each


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


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer: the two minor dims pad to whole
    (sublane, 128-lane) tiles, a sublane tile being 32 bits deep."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    rows = -(-shape[-2] // sub) * sub
    lanes = -(-shape[-1] // 128) * 128
    return math.prod(shape[:-2]) * rows * lanes * item


def block_pages(page: int, kv: int, d: int, dtype, sz_mode: str,
                n_pages: int) -> int:
    """Logical pages a grid step: about BLOCK_TOKENS tokens, within
    VMEM_BUDGET for the double-buffered K, V and per-token scale pages."""
    per_page = _vmem_bytes((kv, page, d), dtype)
    if sz_mode == "token":
        per_page += _vmem_bytes((page, kv, 2), jnp.float32)
    per_page *= 2 * 2
    return max(1, min(n_pages, BLOCK_TOKENS // page,
                      VMEM_BUDGET // per_page))


def _work_list(live_pages, ppb: int, n_blocks: int):
    """The grid's steps: (row, block) of every live block, rows in order.
    live_pages (B,) pages below each row's length. Returns row (W,),
    block (W,) and the step count n (a scalar, at least 1), W = B *
    n_blocks + 1. Entries from n on repeat entry n - 1, so whatever the
    pipeline reads past the last step names blocks already fetched; with
    no live row the one step is row B - 1's block 0, at length 0."""
    B = live_pages.shape[0]
    nb = (live_pages + ppb - 1) // ppb
    end = jnp.cumsum(nb)
    n = jnp.maximum(end[-1], 1)
    w = jnp.minimum(jnp.arange(B * n_blocks + 1, dtype=jnp.int32), n - 1)
    row = jnp.minimum((w[:, None] >= end[None, :]).sum(1), B - 1)
    blk = w - (end - nb)[row]
    return row.astype(jnp.int32), blk.astype(jnp.int32), n


def _kernel(*refs, page: int, ppb: int, kv: int, scale: float,
            sz_mode: str):
    n_leaf = 2 if sz_mode == "none" else 4      # k, v[, k_sz, v_sz]
    _, len_ref, row_ref, blk_ref, q_ref = refs[:5]
    pages = refs[5:5 + n_leaf * ppb]
    o_ref, acc, m_sc, l_sc = refs[5 + n_leaf * ppb:]
    leaf = [pages[i * ppb:(i + 1) * ppb] for i in range(n_leaf)]
    i = pl.program_id(0)
    b = row_ref[i]
    blk = blk_ref[i]
    length = len_ref[b]
    bk = ppb * page

    @pl.when(blk == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def head(li, g):
        parts = []
        for j in range(ppb):
            x = leaf[li][j][0, g].astype(jnp.float32)         # (page, D)
            if sz_mode != "none":
                # fused dequant epilogue on the freshly gathered page
                x = dequant_head(x, leaf[li + 2][j], g, sz_mode)
            parts.append(x)
        return jnp.concatenate(parts, axis=0) if ppb > 1 else parts[0]

    pos = blk * bk
    cols = pos + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) < length
    rows = pos + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) < length
    for g in range(kv):
        k = head(0, g)                                      # (bk, D)
        # a page past the length holds stale bytes: mask its V rows too
        v = jnp.where(rows, head(1, g), 0.0)
        q = q_ref[0, g].astype(jnp.float32)                 # (rep, D)
        online_softmax_update(q, k, v, cols, scale, acc.at[g],
                              m_sc.at[g], l_sc.at[g])

    @pl.when(pos + bk >= length)                  # the row's last block
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
    (B,) valid token counts, in [0, n_logical_pages * page]. Logical page
    `i` of sequence `b` holds tokens [i*page, (i+1)*page) and lives at
    physical page `block_tables[b, i]`. Only entries below a row's
    length are read (and must lie in [0, P_phys)); a row of length 0
    returns zeros.

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
    ppb = block_pages(page, KV, D, k_pages.dtype, sz_mode, n_pages)
    scale = scale if scale is not None else D ** -0.5
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    block_tables = jnp.asarray(block_tables, jnp.int32)
    live = (lengths + page - 1) // page
    row, blk, n_work = _work_list(live, ppb, -(-n_pages // ppb))

    def page_spec(j, block):
        def index(i, bt, ln, wr, wb):
            b, first = wr[i], wb[i] * ppb
            n_live = (ln[b] + page - 1) // page
            lp = first + j
            # past the length: the operand's page of the previous block
            # (an unchanged index: no copy), or the row's first page
            lp = jnp.where(lp < n_live, lp,
                           jnp.where(first > 0, lp - ppb, 0))
            # clipped: an index map's read never leaves the pool
            phys = jnp.clip(bt[b, lp], 0, k_pages.shape[0] - 1)
            return (phys,) + (0,) * (len(block) - 1)
        # a page's (KV, 2) scalars are read one by one: SMEM
        space = pltpu.SMEM if len(block) == 3 else None
        return pl.BlockSpec(block, index, memory_space=space)

    q_spec = pl.BlockSpec((1, KV, rep, D),
                          lambda i, bt, ln, wr, wb: (wr[i], 0, 0, 0))
    leaves = [jnp.swapaxes(k_pages, 1, 2), jnp.swapaxes(v_pages, 1, 2)]
    if sz_mode != "none":
        leaves += [jnp.asarray(k_sz, jnp.float32),
                   jnp.asarray(v_sz, jnp.float32)]
    in_specs, operands = [q_spec], [q.reshape(B, KV, rep, D)]
    for x in leaves:
        block = (1,) + tuple(x.shape[1:])
        in_specs += [page_spec(j, block) for j in range(ppb)]
        operands += [x] * ppb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block tables, lengths, the work list's rows and blocks
        num_scalar_prefetch=4,
        grid=(n_work,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, rep, D), jnp.float32),
            pltpu.VMEM((KV, rep, 1), jnp.float32),
            pltpu.VMEM((KV, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, ppb=ppb, kv=KV, scale=scale,
                          sz_mode=sz_mode),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        grid_spec=grid_spec,
        name="paged_flash_decode",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # a row's blocks carry the online-softmax state: in order
            dimension_semantics=("arbitrary",),
        ) if not interpret else None,
    )(block_tables, lengths, row, blk, *operands)
    # a row with no live block is never visited: its output is zero
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0)
    return out.reshape(B, H, D)
