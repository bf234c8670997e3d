"""Paged chunked-prefill Pallas kernels: a fixed-size chunk of C prompt
tokens attends causally to everything already written to a KV cache laid
out as a physical page pool, gathered per logical page through a
per-sequence block table — the prefill-side twin of
`kernels/decode_attention/paged.py`, and the kernel the serving engine's
chunked prefill rides so a long prompt never serializes against in-flight
decode for more than one chunk.

Two entry points:

* `paged_prefill_flash` — gather-only attention: the chunk's K/V must
  already be in the pool (the PR-4 contract; kept as the parity oracle
  and for callers that scatter separately).
* `paged_prefill_insert_flash` — the FUSED fast path: the chunk's K/V
  tiles are INPUTS, the pool arrays are aliased input->output
  (`input_output_aliases`), and the kernel writes each chunk page into
  the pool while computing the chunk's attention in the same pass. The
  separate jnp page-scatter op — one full extra read+write of the
  chunk's K/V through HBM — disappears; non-chunk pages survive
  untouched because the output buffer IS the input buffer. Grid steps
  below the chunk re-write the chunk's first page with identical data
  (index maps clamp into the chunk's page range), so the write is
  idempotent.

Block-quantized pools (`repro.kernels.quant`): int8 page payloads with
per-page float32 (scale, zero) pairs. On the gather side each page's
(KV, 2) pairs arrive as an SMEM block chased through the same block-table
entry as the payload, and the dequant epilogue runs right after each
page's DMA; on the insert side the fused kernel writes the chunk's
pre-quantized int8 tiles AND their (scale, zero) rows through the same
aliasing, so a quantized chunked prefill also issues zero standalone
scatters.

The block tables and the chunk's start position ride the scalar-prefetch
channel (`pltpu.PrefetchScalarGridSpec`): both are resident in SMEM before
the body runs, so the K/V BlockSpec index maps chase `bt[b, pi]` to DMA
each NON-CONTIGUOUS physical page while the previous page's flash update
is still computing. The chunk offset `c0` is a runtime scalar, not a
Python constant, so every chunk of every request reuses ONE compiled
kernel — the engine's no-recompile contract extends to chunked prefill.

Tiling: a pool page is (page, KV, D) and Mosaic needs a block's two
minor dims whole, so each grid step DMAs one physical page with ALL its
KV heads and walks the query heads in a static loop; the chunk's queries
are presented head-major, (B, H, C, D), so each head's (C, D) tile is
whole as well.

Grid (B, n_logical_pages); the page dimension is sequential
("arbitrary") so the per-head (C, D) online-softmax accumulators live in
VMEM scratch across pages. Pages entirely above the causal frontier
(`page_start > c0 + C - 1`) are skipped via `pl.when` — the same
fully-masked-tile elision the dense flash kernel does for the causal
upper triangle. Block-table entries past the frontier must still name a
real physical page (ops.py clamps them to 0); the causal mask keeps them
out of the math. `c0` and C must be page-aligned in the fused kernels
(the engine enforces `prefill_chunk % page_tokens == 0`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode_attention.paged import (
    NEG_INF,
    dequant_head,
    online_softmax_update,
)


def _init_scratch(pi, acc, m_sc, l_sc):
    @pl.when(pi == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)


def _attend_page(q_ref, kv_of, c0, pi, *, kv: int, rep: int, page: int,
                 chunk: int, scale: float, acc, m_sc, l_sc):
    """One page's causal online-softmax update of every head's (C, D)
    accumulator. `kv_of(g)` returns KV head g's (page, D) float32 k/v."""
    qpos = c0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 0)
    kpos = pi * page + jax.lax.broadcasted_iota(jnp.int32, (chunk, page), 1)
    causal = qpos >= kpos
    for g in range(kv):
        k, v = kv_of(g)
        for r in range(rep):
            h = g * rep + r
            q = q_ref[0, h].astype(jnp.float32)        # (C, D)
            online_softmax_update(q, k, v, causal, scale, acc.at[h],
                                  m_sc.at[h], l_sc.at[h])


def _finalize(o_ref, acc, l_sc, pi, n_pages):
    @pl.when(pi == n_pages - 1)
    def _done():
        o_ref[0] = (
            acc[...] / jnp.maximum(l_sc[...], 1e-30)
        ).astype(o_ref.dtype)


def _gather_kernel(*refs, page: int, chunk: int, scale: float,
                   n_pages: int, kv: int, rep: int, sz_mode: str):
    """Attention only; the chunk's K/V is already in the pool."""
    if sz_mode == "none":
        (bt_ref, c0_ref, q_ref, k_ref, v_ref, o_ref,
         acc, m_sc, l_sc) = refs
    else:
        (bt_ref, c0_ref, q_ref, k_ref, v_ref, ksz_ref, vsz_ref, o_ref,
         acc, m_sc, l_sc) = refs
    del bt_ref                       # chased by the index maps only
    # program_id must be read at body top level (pl.when bodies lower
    # through lax.cond, outside the interpreter's grid context)
    b = pl.program_id(0)
    pi = pl.program_id(1)
    _init_scratch(pi, acc, m_sc, l_sc)
    c0 = c0_ref[b]

    def kv_of(g):
        k = k_ref[0, :, g, :].astype(jnp.float32)      # (page, D)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        if sz_mode != "none":
            k = dequant_head(k, ksz_ref, g, sz_mode)
            v = dequant_head(v, vsz_ref, g, sz_mode)
        return k, v

    @pl.when(pi * page <= c0 + chunk - 1)       # page below causal frontier
    def _tile():
        _attend_page(q_ref, kv_of, c0, pi, kv=kv, rep=rep, page=page,
                     chunk=chunk, scale=scale, acc=acc, m_sc=m_sc,
                     l_sc=l_sc)

    _finalize(o_ref, acc, l_sc, pi, n_pages)


def _fused_kernel(*refs, page: int, chunk: int, scale: float,
                  n_pages: int, kv: int, rep: int, quantized: bool):
    """Attention + aliased chunk write: pool outputs alias pool inputs,
    and every grid step writes its (clamped) chunk page tile — identical
    data on re-visits, so the write is idempotent and the chunk's pages
    hold exactly the chunk K/V when the kernel completes."""
    if quantized:
        (bt_ref, c0_ref, q_ref, kn_ref, vn_ref, kszn_ref, vszn_ref,
         kp_ref, vp_ref, kszp_ref, vszp_ref,
         o_ref, ko_ref, vo_ref, kszo_ref, vszo_ref,
         acc, m_sc, l_sc) = refs
    else:
        (bt_ref, c0_ref, q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
         o_ref, ko_ref, vo_ref, acc, m_sc, l_sc) = refs
    del bt_ref
    b = pl.program_id(0)
    pi = pl.program_id(1)
    _init_scratch(pi, acc, m_sc, l_sc)
    c0 = c0_ref[b]
    in_chunk = pi >= c0 // page
    # the fused scatter: kn/vn blocks and ko/vo blocks both chase the
    # clamped chunk page for this grid step (see the index maps), so this
    # plain copy lands each chunk tile at its block-table page
    ko_ref[...] = kn_ref[...]
    vo_ref[...] = vn_ref[...]
    if quantized:
        for g in range(kv):                    # (scale, zero) SMEM scalars
            for c in range(2):
                kszo_ref[0, g, c] = kszn_ref[0, 0, g, c]
                vszo_ref[0, g, c] = vszn_ref[0, 0, g, c]

    def kv_of(g):
        # the chunk's own pages attend to the tile being written (the
        # pool block holds stale data until this kernel's write lands);
        # earlier chunks' pages gather from the pool as usual
        k = jnp.where(in_chunk, kn_ref[0, :, g, :].astype(jnp.float32),
                      kp_ref[0, :, g, :].astype(jnp.float32))
        v = jnp.where(in_chunk, vn_ref[0, :, g, :].astype(jnp.float32),
                      vp_ref[0, :, g, :].astype(jnp.float32))
        if quantized:
            def pick(new_ref, pool_ref, c):
                return jnp.where(in_chunk, new_ref[0, 0, g, c],
                                 pool_ref[0, g, c])

            k = k * pick(kszn_ref, kszp_ref, 0) + pick(kszn_ref, kszp_ref, 1)
            v = v * pick(vszn_ref, vszp_ref, 0) + pick(vszn_ref, vszp_ref, 1)
        return k, v

    @pl.when(pi * page <= c0 + chunk - 1)
    def _tile():
        _attend_page(q_ref, kv_of, c0, pi, kv=kv, rep=rep, page=page,
                     chunk=chunk, scale=scale, acc=acc, m_sc=m_sc,
                     l_sc=l_sc)

    _finalize(o_ref, acc, l_sc, pi, n_pages)


def _scratch(H, C, D):
    from jax.experimental.pallas import tpu as pltpu

    return [
        pltpu.VMEM((H, C, D), jnp.float32),
        pltpu.VMEM((H, C, 1), jnp.float32),
        pltpu.VMEM((H, C, 1), jnp.float32),
    ]


def _specs(page: int, C: int, H: int, D: int, KV: int):
    """BlockSpecs shared by the gather and fused kernels (index maps take
    the grid (b, pi) plus the scalar-prefetch block tables and chunk
    starts). `rel` maps a grid page to its tile inside the chunk and
    `wpage` to the pool page the aliased write targets — both clamped
    into the chunk's page range, which is what makes out-of-chunk grid
    steps idempotent re-writes of a chunk tile."""
    from jax.experimental.pallas import tpu as pltpu

    n_wp = C // page
    smem = pltpu.SMEM

    def rel(pi, c0b):
        return jnp.clip(pi - c0b // page, 0, n_wp - 1)

    def wpage(b, pi, bt, c0):
        p0 = c0[b] // page                     # SMEM reads are scalar
        return bt[b, jnp.clip(pi, p0, p0 + n_wp - 1)]

    return {
        "q": pl.BlockSpec(
            (1, H, C, D), lambda b, pi, bt, c0: (b, 0, 0, 0)),
        "chunk": pl.BlockSpec(
            (1, page, KV, D),
            lambda b, pi, bt, c0: (b, rel(pi, c0[b]), 0, 0)),
        "chunk_sz": pl.BlockSpec(
            (1, 1, KV, 2),
            lambda b, pi, bt, c0: (b, rel(pi, c0[b]), 0, 0),
            memory_space=smem),
        "pool_in": pl.BlockSpec(
            (1, page, KV, D), lambda b, pi, bt, c0: (bt[b, pi], 0, 0, 0)),
        "pool_out": pl.BlockSpec(
            (1, page, KV, D),
            lambda b, pi, bt, c0: (wpage(b, pi, bt, c0), 0, 0, 0)),
        "pool_sz_in": pl.BlockSpec(
            (1, KV, 2), lambda b, pi, bt, c0: (bt[b, pi], 0, 0),
            memory_space=smem),
        "pool_sz_out": pl.BlockSpec(
            (1, KV, 2),
            lambda b, pi, bt, c0: (wpage(b, pi, bt, c0), 0, 0),
            memory_space=smem),
        "token_sz": pl.BlockSpec(
            (1, page, KV, 2), lambda b, pi, bt, c0: (bt[b, pi], 0, 0, 0)),
    }


_HEAD_MAJOR = (0, 2, 1, 3)      # (B, C, H, D) <-> (B, H, C, D)


def _compiler_params(interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    # MEGACORE partitioning: the batch dim is "parallel"; only the page
    # walk is sequential (online-softmax carry)
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_flash(q, k_pages, v_pages, block_tables, c0, *,
                        k_sz=None, v_sz=None, scale=None,
                        interpret: bool = False):
    """q (B, C, H, D) — chunk of C prompt tokens at absolute positions
    [c0[b], c0[b]+C) — vs paged cache k/v (P_phys, page, KV, D) through
    block_tables (B, n_logical_pages) int32 physical-page ids; `c0` (B,)
    int32 chunk starts. Causal: query i attends to positions <= c0+i.
    The chunk's own K/V must already be written into the pool. Entries
    past the causal frontier must be in [0, P_phys) — use
    ops.paged_prefill_mha, which clamps. `k_sz`/`v_sz` float32 switch on
    the int8 dequant epilogue; their grain dispatches on rank: per-page
    (P_phys, KV, 2) or per-token (P_phys, page, KV, 2), both block-indexed
    through the same table chase as the payload."""
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, D = q.shape
    _, page, KV, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    if k_sz is None:
        sz_mode = "none"
    elif jnp.ndim(k_sz) == k_pages.ndim:
        sz_mode = "token"
    else:
        sz_mode = "page"
    scale = scale if scale is not None else D ** -0.5
    c0 = jnp.broadcast_to(jnp.asarray(c0, jnp.int32), (B,))
    block_tables = jnp.asarray(block_tables, jnp.int32)

    sp = _specs(page, C, H, D, KV)
    in_specs = [sp["q"], sp["pool_in"], sp["pool_in"]]
    operands = (q.transpose(_HEAD_MAJOR), k_pages, v_pages)
    if sz_mode != "none":
        sz_spec = sp["pool_sz_in" if sz_mode == "page" else "token_sz"]
        in_specs += [sz_spec, sz_spec]
        operands += (jnp.asarray(k_sz, jnp.float32),
                     jnp.asarray(v_sz, jnp.float32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, page=page, chunk=C, scale=scale,
                          n_pages=n_pages, kv=KV, rep=H // KV,
                          sz_mode=sz_mode),
        out_shape=jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,               # block tables + c0
            grid=(B, n_pages),
            in_specs=in_specs,
            out_specs=sp["q"],
            scratch_shapes=_scratch(H, C, D),
        ),
        name="paged_prefill_flash",
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(block_tables, c0, *operands)
    return out.transpose(_HEAD_MAJOR)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_insert_flash(q, k_pages, v_pages, k_new, v_new,
                               block_tables, c0, *, scale=None,
                               interpret: bool = False):
    """FUSED fp chunk insert + attention. k_new/v_new (B, C, KV, D) in the
    POOL dtype (pre-cast by the caller so the in-chunk attention reads
    exactly the values the pool will hold). Returns (o, k_pages, v_pages)
    with the pool arrays updated in place via input_output_aliases —
    zero standalone scatter ops. C and c0 must be page-aligned, and the
    chunk's block-table entries must be live."""
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, D = q.shape
    _, page, KV, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    c0 = jnp.broadcast_to(jnp.asarray(c0, jnp.int32), (B,))
    block_tables = jnp.asarray(block_tables, jnp.int32)

    sp = _specs(page, C, H, D, KV)
    o, k_pages, v_pages = pl.pallas_call(
        functools.partial(_fused_kernel, page=page, chunk=C, scale=scale,
                          n_pages=n_pages, kv=KV, rep=H // KV,
                          quantized=False),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,               # block tables + c0
            grid=(B, n_pages),
            in_specs=[sp["q"], sp["chunk"], sp["chunk"],
                      sp["pool_in"], sp["pool_in"]],
            out_specs=[sp["q"], sp["pool_out"], sp["pool_out"]],
            scratch_shapes=_scratch(H, C, D),
        ),
        # inputs count the scalar-prefetch operands: bt(0) c0(1) q(2)
        # k_new(3) v_new(4) k_pages(5) v_pages(6)
        input_output_aliases={5: 1, 6: 2},
        name="paged_prefill_insert_flash",
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(block_tables, c0, q.transpose(_HEAD_MAJOR), k_new, v_new,
      k_pages, v_pages)
    return o.transpose(_HEAD_MAJOR), k_pages, v_pages


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_insert_flash_q8(q, k_pages, v_pages, k_sz, v_sz,
                                  k8_new, v8_new, ksz_new, vsz_new,
                                  block_tables, c0, *, scale=None,
                                  interpret: bool = False):
    """FUSED int8 chunk insert + attention. The chunk arrives
    pre-quantized (`repro.kernels.quant.quantize_pages` — elementwise, no
    scatter): k8/v8_new (B, C, KV, D) int8 payload, ksz/vsz_new
    (B, C//page, KV, 2) float32 per-page (scale, zero) rows. Previous
    pages dequantize through `k_sz`/`v_sz` (P_phys, KV, 2); the chunk's
    pages dequantize from their own fresh rows, so attention sees exactly
    what a later gather of the written pool would see. Returns
    (o, k_pages, v_pages, k_sz, v_sz) — payload AND (scale, zero) arrays
    updated through input_output_aliases."""
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, D = q.shape
    _, page, KV, _ = k_pages.shape
    n_pages = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    c0 = jnp.broadcast_to(jnp.asarray(c0, jnp.int32), (B,))
    block_tables = jnp.asarray(block_tables, jnp.int32)
    k_sz = jnp.asarray(k_sz, jnp.float32)
    v_sz = jnp.asarray(v_sz, jnp.float32)

    sp = _specs(page, C, H, D, KV)
    o, k_pages, v_pages, k_sz, v_sz = pl.pallas_call(
        functools.partial(_fused_kernel, page=page, chunk=C, scale=scale,
                          n_pages=n_pages, kv=KV, rep=H // KV,
                          quantized=True),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, C, D), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
            jax.ShapeDtypeStruct(k_sz.shape, jnp.float32),
            jax.ShapeDtypeStruct(v_sz.shape, jnp.float32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,               # block tables + c0
            grid=(B, n_pages),
            in_specs=[sp["q"], sp["chunk"], sp["chunk"],
                      sp["chunk_sz"], sp["chunk_sz"],
                      sp["pool_in"], sp["pool_in"],
                      sp["pool_sz_in"], sp["pool_sz_in"]],
            out_specs=[sp["q"], sp["pool_out"], sp["pool_out"],
                       sp["pool_sz_out"], sp["pool_sz_out"]],
            scratch_shapes=_scratch(H, C, D),
        ),
        # inputs count the scalar-prefetch operands: bt(0) c0(1) q(2)
        # k8(3) v8(4) kszn(5) vszn(6) kp(7) vp(8) ksz(9) vsz(10)
        input_output_aliases={7: 1, 8: 2, 9: 3, 10: 4},
        name="paged_prefill_insert_flash_q8",
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(block_tables, c0, q.transpose(_HEAD_MAJOR), k8_new, v8_new,
      ksz_new, vsz_new, k_pages, v_pages, k_sz, v_sz)
    return o.transpose(_HEAD_MAJOR), k_pages, v_pages, k_sz, v_sz
