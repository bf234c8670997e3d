"""Serve-step builders: prefill (prompt -> caches) and decode (one token vs
the KV cache / SSM state). `decode_32k` and `long_500k` cells lower the
decode step; `prefill_32k` lowers prefill — per the brief.

Two consumers:

* the dry-run/launcher path keeps the classic `ServeBundle` (one jitted
  prefill + one jitted decode over a uniform batch of per-slot contiguous
  caches);
* the continuous-batching engine (`repro.serving.engine`) uses
  `make_engine_cells`: a fixed set of jitted cells — one greedy decode cell
  over the whole slot batch with per-slot positions, one prefill cell per
  prompt bucket, and one cache-insert cell per bucket. Every shape is
  fixed at build time, so a steady-state serve loop never recompiles
  regardless of admissions/completions (slot masking via parked write
  positions, see `models.attention._cache_insert`).

With `paged=True` (the engine's default) the KV cache IS a physical page
pool: self-attention K/V leaves are (nb, n_slots * n_pages, page_tokens,
KV, hd) and every cell takes the live (n_slots, n_pages) block table from
`serving.kv_pager.KVPager.block_table()` — the single allocator whose
free list and tier tags drive both the kernel gather and the byte
accounting. The decode cell runs `kernels/decode_attention/paged.py`
(interpret mode on CPU, compiled pallas on TPU) over that table; the
insert cell lands a prefilled request's pages in the pool through the
aliased page-writer kernel (`kernels.page_io.write_pages` — in-place via
`input_output_aliases`, zero standalone scatters on the kernel
backends); and on attention-only stacks (`chunked_prefill_supported`) a
chunked-prefill cell (`kernels/flash_attention/paged_prefill.py`)
processes one page-aligned prompt chunk per call — the chunk's K/V
write is FUSED into the paged-prefill kernel itself (the chunk tiles
are operands, the pool arrays alias input->output), so the cell
flash-attends to everything prefilled so far without the separate jnp
page-scatter's extra read+write of the chunk — and the engine can
interleave prefill chunks with decode steps instead of stalling the
whole slot batch for a long prompt. The block table and the chunk index
are runtime arrays, never Python constants: slot churn, page churn and
chunk progress all replay through the same compiled cells.

`pool_dtype` makes the pool payload polymorphic ("fp" exact | "bf16"
cast | "int8" per-page block quantization): with int8 the attention
cache dicts carry per-page float32 (scale, zero) leaves ("k_sz"/"v_sz",
(nb, n_slots * n_pages, KV, 2), `repro.kernels.quant`), the insert and
chunk cells quantize whole pages on the way in (the decode cell
requantizes the slot's tail page around each new token), and both paged
kernels dequantize in their gather epilogue. `sz_granularity="token"`
swaps the per-page (scale, zero) rows for PER-TOKEN sub-scales
((nb, P, page_tokens, KV, 2) — rank-dispatched everywhere on
`sz.ndim == pool.ndim`): each cached token quantizes independently over
its head dim, so inserting a token is a pure disjoint scatter with no
read-modify-write of the page's neighbours — the layout speculative
verify requires (k candidate rows of one slot land in the same tail
page concurrently) and the KV-side twin of the W8A8 activation-row
quantization in `kernels/matmul_w8a8`.

SPECULATIVE DECODING: `build_decode_verify_paged` scores k candidate
tokens per slot in ONE paged-decode call by flattening (S, k)
candidates to S*k decode rows with vector positions t[s]+j and
k-repeated block-table rows. Greedy acceptance
(`serving.speculative.accept_greedy`) emits the longest candidate
prefix that matches what greedy decode would have produced — bit-
identical token streams by construction — so each sweep of the pool-
resident KV pages is amortized over `1 + accepted` tokens instead of
exactly one (decode is the lowest-arithmetic-intensity loop in the
system; this is the AI lever). Proposers live in `serving.speculative`:
"ngram" (self-speculative suffix matching over the slot's own history,
zero extra parameters) and "draft" (a small draft model decoded by
`build_decode_draft` against its own contiguous caches, weights shared
across a fleet through `EngineCells`). Rejected positions leave garbage
KV beyond the frontier; every kernel already masks beyond the slot
length and `KVPager.truncate` rolls back the page accounting. Bytes per cached token =
2 * KV * hd * payload_bytes * nb (+ 2 * KV * 8 * nb / page_tokens for
the int8 scale arrays) — `core.access.kv_pool_token_bytes` — which is
what the pager and admission corridor price.

Block tables may ALIAS (shared prompt prefixes, `serving.prefix_cache`):
the gather side reads an aliased page identically for every sharer, and
the write side never sees one — the pager guarantees write targets are
private, COW-splitting shared tail pages via `build_page_copy` (the
one cell sharing adds; the kernels themselves need zero changes). The
deduplicated footprint is then
(n_sharers * (n_tokens - shared) + shared) * token_bytes instead of
n_sharers * n_tokens * token_bytes — `core.access.kv_dedup_token_bytes`
is the closed-form twin of `KVPager.phys_tiers()` under sharing.

MESH-SHARDED PAGED SERVING: `make_engine_cells(mesh=...)` jits every
cell (paged decode, bucketed prefill, paged insert, chunked prefill,
COW page-copy) with explicit in/out shardings over two axes — KV heads
over the model (`tp`) axis for the pool payload and int8 scale leaves,
slots over the data (`dp`) axis for the resident leaves
(`runtime.sharding.paged_cache_pspec`; params follow the weight-
stationary `ShardingRules.for_serving` table). Block tables and the
per-slot token/position vectors are REPLICATED: each shard resolves
the identical logical->physical page mapping and gathers only its own
head slice, so the page allocator stays a single host-side object and
the token stream is bit-identical to the single-device engine (the CI
`sharded-parity` lane forces an 8-device host mesh and asserts exactly
that). A compiled Mosaic kernel cannot be partitioned by GSPMD, so the
paged decode, chunk and page-write kernels run per tp shard under
`shard_map` (`common.parallel.per_kv_shard`) on every backend.

TIER LAYOUT / TRANSFER-STREAM CONTRACT (`repro.serving.substrate`):
the cells only ever touch the DEVICE pool — the authoritative copy.
The engine additionally owns a `TierSubstrate` holding a pinned_host
(or emulated default-memory) zeros twin of the paged leaves; after
each pager step it reconciles the twin against
`KVPager.pool_page_ids()`, issuing async jitted gather/scatter streams
(page_out device->host, page_in host->device, drop on free) whose
completion-tracked `SubstrateLedger` measures real array bytes. The
contract: after every drain, `pager.pool_bytes_used()` equals
`ledger.placement_bytes()` — `phys_tiers()` pool accounting is actual
placement, not a derived price.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.config import ModelConfig
from repro.common.parallel import ParallelCtx, per_kv_shard
from repro.models import model as M
from repro.models.module import shape_mode
from repro.runtime import sharding as shd


def abstract_params(cfg: ModelConfig, serve_dtype: bool = True):
    """Abstract param tree; serving uses inference dtype (bf16) weights."""
    with shape_mode():
        params, axes = M.init_model(cfg, jax.random.PRNGKey(0))
    if serve_dtype:
        dt = jnp.dtype(cfg.dtype)

        def cast(p):
            if jnp.issubdtype(p.dtype, jnp.floating):
                return jax.ShapeDtypeStruct(p.shape, dt)
            return p

        params = jax.tree.map(cast, params)
    return params, axes


def init_params(cfg: ModelConfig, seed: int, shardings=None):
    """Serving weights made from `seed`, in the serving dtype
    (`abstract_params`), built under jit straight into `shardings` (the
    cells' param shardings; None = default placement) — a model that
    needs the mesh never has its whole float32 tree on one device."""
    dt = jnp.dtype(cfg.dtype)

    def init():
        params, _ = M.init_model(cfg, jax.random.PRNGKey(seed))
        return jax.tree.map(
            lambda p: p.astype(dt)
            if jnp.issubdtype(p.dtype, jnp.floating) else p,
            params,
        )

    return jax.jit(init, out_shardings=shardings)()


def abstract_caches(cfg: ModelConfig, batch: int, max_seq: int,
                    enc_len: int = 0):
    caches = jax.eval_shape(
        lambda: M.make_decode_caches(cfg, batch, max_seq, enc_len)
    )
    return caches


def build_prefill(cfg: ModelConfig, ctx: ParallelCtx, max_seq: int):
    def prefill_step(params, batch):
        return M.prefill(params, batch, cfg, ctx, max_seq)

    return prefill_step


def build_decode(cfg: ModelConfig, ctx: ParallelCtx):
    def decode_step(params, token, caches, t):
        return M.decode_step(params, token, caches, t, cfg, ctx)

    return decode_step


@dataclasses.dataclass
class ServeBundle:
    prefill_fn: Any
    decode_fn: Any
    param_shardings: Any
    cache_shardings: Any
    abstract_params: Any
    abstract_caches: Any


def make_bundle(cfg: ModelConfig, ctx: ParallelCtx,
                rules: shd.ShardingRules, mesh,
                batch: int, max_seq: int, enc_len: int = 0,
                param_shardings_override=None) -> ServeBundle:
    aparams, axes = abstract_params(cfg)
    param_sh = param_shardings_override or shd.shardings_for_tree(
        aparams, axes, rules, mesh
    )
    acaches = abstract_caches(cfg, batch, max_seq, enc_len)
    cache_sh = shd.named(
        mesh, shd.cache_pspec(acaches, ctx.dp_axes, ctx.tp_axis, mesh)
    )
    batch_shardable = (
        ctx.dp_axes and batch % max(ctx.dp_size, 1) == 0 and ctx.dp_size > 1
    )
    tok_sh = shd.named(
        mesh, P(ctx.dp_axes) if batch_shardable else P()
    )
    # prompt batch: dim0 (requests) over dp axes — a prefix sharding covers
    # every leaf of the batch dict (tokens / patches / frames)
    prompt_sh = shd.named(
        mesh, P(ctx.dp_axes) if batch_shardable else P()
    )
    prefill = jax.jit(
        build_prefill(cfg, ctx, max_seq),
        in_shardings=(param_sh, prompt_sh),
    )
    decode = jax.jit(
        build_decode(cfg, ctx),
        in_shardings=(param_sh, tok_sh, cache_sh, None),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    return ServeBundle(prefill, decode, param_sh, cache_sh, aparams, acaches)


# ------------------------------------------------- continuous batching
def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked prefill needs a pure-attention decoder with no frontend
    prefix and no encoder: an SSM/conv layer's prompt pass is a sequential
    reduction that cannot restart mid-stream from paged KV alone."""
    from repro.models import blocks

    if cfg.num_encoder_layers or cfg.frontend:
        return False
    return all(
        cfg.is_attn_layer(j) for j in range(blocks.super_period(cfg))
    )


def abstract_paged_caches(cfg: ModelConfig, n_slots: int, max_seq: int,
                          page_tokens: int, enc_len: int = 0,
                          pool_dtype: str = "fp",
                          sz_granularity: str = "page"):
    return jax.eval_shape(
        lambda: M.make_paged_decode_caches(
            cfg, n_slots, max_seq, page_tokens, enc_len,
            pool_dtype=pool_dtype, sz_granularity=sz_granularity,
        )
    )


def build_decode_greedy(cfg: ModelConfig, ctx: ParallelCtx):
    """Greedy decode cell: one token per slot, argmax inside the jit so the
    host only ever syncs an int32 vector plus a scalar finiteness flag
    (argmax of NaN logits would otherwise turn a numerical blow-up into
    silently wrong token streams). `t` is the per-slot position vector
    (see models.model.decode_step)."""

    def decode_cell(params, token, caches, t):
        logits, caches = M.decode_step(params, token, caches, t, cfg, ctx)
        finite = jnp.isfinite(logits).all(axis=-1)   # per slot: parked
        # slots carry garbage caches, so the engine masks them out
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), finite, caches

    return decode_cell


def build_prefill_greedy(cfg: ModelConfig, ctx: ParallelCtx, bucket: int):
    """Prefill cell for one prompt bucket: returns the request's decode
    caches (seq extent `bucket` + frontend prefix; cross-KV extent follows
    the frames in the batch) and its greedy first token. Prompts must be
    exactly `bucket` long (see serving.batcher)."""

    def prefill_cell(params, batch):
        caches, logits = M.prefill(params, batch, cfg, ctx, max_seq=bucket)
        return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return prefill_cell


def build_decode_greedy_paged(cfg: ModelConfig, ctx: ParallelCtx,
                              page_tokens: int):
    """Greedy decode cell over the PAGED caches: same contract as
    `build_decode_greedy` plus the live block table — the decode step
    reads and writes the physical page pool through
    `kernels/decode_attention/paged.py`."""

    def decode_cell(params, token, caches, t, block_table):
        logits, caches = M.decode_step(
            params, token, caches, t, cfg, ctx,
            block_table=block_table, page_tokens=page_tokens,
        )
        finite = jnp.isfinite(logits).all(axis=-1)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), finite, caches

    return decode_cell


def build_decode_verify_paged(cfg: ModelConfig, ctx: ParallelCtx,
                              page_tokens: int, k: int):
    """SPECULATIVE VERIFY cell: score k candidate tokens per slot in ONE
    paged-decode call — the vector-`t` extension of
    `build_decode_greedy_paged` that amortizes each sweep of the
    pool-resident KV pages over k tokens instead of one (the
    arithmetic-intensity lever the paper's pooled-memory corridor prices;
    greedy decode is the lowest-AI loop in the system).

    Contract: `cand` (S, k) int32 with cand[s, 0] the slot's last emitted
    (not yet inserted) token and cand[s, 1:] the proposer's drafts; `t`
    (S,) the position cand[s, 0] will occupy. The (S, k) batch flattens
    to S*k decode rows: row j of slot s feeds cand[s, j] at position
    t[s]+j against the slot's OWN block-table row (repeated k times), so
    the flattened KV insert lands all k candidate tokens before
    attention and row j's length mask (t+j+1) lets it see candidates
    0..j — teacher-forced causal scoring. Returns (greedy (S, k) int32,
    finite (S,), caches) where greedy[s, j] is the model's pick FOR
    position t[s]+j+1, i.e. the token that follows cand[s, j]:

        accept a = max prefix with cand[s, i+1] == greedy[s, i];
        emit greedy[s, 0..a] (a+1 tokens) — bit-identical to running
        greedy decode a+1 times, by construction.

    Positions t+e..t+k-1 of a partially-accepted slot hold wrong-token
    KV afterwards; every kernel masks them out (length <= frontier) and
    the next verify call overwrites them, so only the pager's page
    accounting needs rollback (`KVPager.truncate`). int8 pools MUST use
    the per-token sub-scale layout (`sz_granularity="token"`): the
    per-page requantize round trip would make a slot's k rows
    read-modify-write the same tail page concurrently."""

    def verify_cell(params, cand, caches, t, block_table):
        S = cand.shape[0]
        tok_flat = cand.reshape(S * k)
        t_flat = (
            t[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        ).reshape(S * k)
        bt_flat = jnp.repeat(block_table, k, axis=0)
        logits, caches = M.decode_step(
            params, tok_flat, caches, t_flat, cfg, ctx,
            block_table=bt_flat, page_tokens=page_tokens,
        )
        finite = jnp.isfinite(logits).all(axis=-1).reshape(S, k).all(axis=1)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy.reshape(S, k), finite, caches

    return verify_cell


def build_cache_insert():
    """Splice a prefilled request's caches (batch=1, short seq extent) into
    the global slot caches at a traced slot index. A dynamic-update-slice
    per leaf: leading (stack, batch) dims, then the seq/state extents."""

    def insert_cell(caches, slot_caches, slot):
        slot = jnp.asarray(slot, jnp.int32)

        def ins(big, small):
            idx = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), idx
            )

        return jax.tree.map(ins, caches, slot_caches)

    return insert_cell


def build_decode_draft(cfg: ModelConfig, ctx: ParallelCtx):
    """Draft-model decode cell for the speculative "draft" proposer: one
    greedy token per slot against the draft's own CONTIGUOUS caches
    (`M.make_decode_caches` — the draft prefix is short-lived scratch,
    so it skips the paged pool entirely). Same vector-`t` contract as
    `build_decode_greedy`; the finite flag is dropped (a non-finite
    draft can only propose tokens the verify cell rejects)."""

    def draft_cell(params, token, caches, t):
        logits, caches = M.decode_step(params, token, caches, t, cfg, ctx)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    return draft_cell


def build_paged_cache_insert(bucket_total: int, page_tokens: int,
                             pool_dtype: str = "fp",
                             sz_granularity: str = "page",
                             ctx: ParallelCtx | None = None):
    """Land a prefilled request's caches in the PAGED layout: the
    request's `bucket_total` tokens of K/V (batch=1, dense from the
    prefill cell) go whole-page into the physical pool at the pages the
    block table assigns to the traced slot index — through the aliased
    page-writer kernel (`kernels.page_io.write_pages`), the same
    in-place treatment the fused chunk kernel gives the chunked path,
    so the insert cell issues zero standalone page-scatter ops on the
    kernel backends. With `pool_dtype="int8"` the prompt pages are
    block-quantized first (`kernels.quant.quantize_pages` — elementwise)
    and the per-page (scale, zero) rows land through the same writer.
    Resident leaves (SSM state, conv tails, cross-KV) keep the dense
    dynamic-update-slice. The final partial page carries garbage beyond
    `bucket_total` — those positions are >= the slot's length, so the
    kernels' masks exclude them and decode overwrites them before the
    length ever reaches them (the quantized insert zero-fills them so
    they cannot pollute the page's range). With
    `sz_granularity="token"` the prompt pages get per-token sub-scales
    instead (`kernels.quant.quantize_tokens`) and the (page_tokens, KV,
    2) sz tiles land through the same generic page writer. Under a mesh
    the writer runs per tp shard (`common.parallel.per_kv_shard`)."""
    from repro.kernels import quant
    from repro.kernels.page_io import ops as page_ops

    n_wp = -(-bucket_total // page_tokens)     # pages the prompt spans
    pad = n_wp * page_tokens - bucket_total
    quantized = pool_dtype == "int8"
    per_token = quantized and sz_granularity == "token"

    def insert_cell(caches, slot_caches, slot, block_table):
        slot = jnp.asarray(slot, jnp.int32)
        row = jax.lax.dynamic_index_in_dim(
            block_table, slot, 0, keepdims=False
        )                                      # (n_pages,)
        phys = row[:n_wp]

        def ins_dense(big, small):
            idx = (jnp.int32(0), slot) + (jnp.int32(0),) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), idx
            )

        def write(pool, tiles):
            return per_kv_shard(ctx, pool.shape[-2], page_ops.write_pages,
                                (pool, tiles), (phys,))

        def page_tiles(small):
            sm = small[:, 0]                   # (nb, bucket_total, KV, hd)
            # zero-pad the partial-page tail: masked out of attention, and
            # under int8 it cannot widen the last page's quantization range
            sm = jnp.pad(sm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            nb, _, kv, hd = sm.shape
            return sm.reshape(nb, n_wp, page_tokens, kv, hd)

        out = {}
        for pos, c in caches.items():
            oc = {}
            for key, big in c.items():
                if key in ("k", "v", "k_sz", "v_sz"):
                    continue
                oc[key] = ins_dense(big, slot_caches[pos][key])
            for key in ("k", "v"):
                if key not in c:
                    continue
                tiles = page_tiles(slot_caches[pos][key])
                if quantized:
                    if per_token:
                        q8, sz_rows = quant.quantize_tokens(tiles)
                    else:
                        q8, sz_rows = quant.quantize_pages(tiles)
                    oc[key] = write(c[key], q8)
                    oc[key + "_sz"] = write(c[key + "_sz"], sz_rows)
                else:
                    oc[key] = write(c[key], tiles)
            out[pos] = oc
        return out

    return insert_cell


def build_page_copy():
    """Copy one PHYSICAL page (payload + int8 scale/zero rows when
    present) to another — the copy-on-write cell behind the prefix
    cache's shared pages (`serving.prefix_cache`): when a slot is about
    to write into a page whose refcount > 1, the pager repoints it at a
    free page (`KVPager.cow_split`) and the engine runs this cell to
    materialize the private duplicate BEFORE the decode cell's scatter —
    so a shared page is never mutated, which is the whole COW contract.
    One dynamic_slice + dynamic_update_slice per paged leaf along the
    physical-page axis; resident leaves pass through untouched (they are
    per-slot, never shared). `src`/`dst` are traced scalars: page churn
    replays through one compiled cell."""

    def page_copy_cell(caches, src, dst):
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)

        def cp(big):
            page = jax.lax.dynamic_slice_in_dim(big, src, 1, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                big, page, dst, axis=1
            )

        out = {}
        for pos, c in caches.items():
            oc = dict(c)
            for key in ("k", "v", "k_sz", "v_sz"):
                if key in c:
                    oc[key] = cp(c[key])
            out[pos] = oc
        return out

    return page_copy_cell


def build_prefill_chunk(cfg: ModelConfig, ctx: ParallelCtx,
                        page_tokens: int):
    """Chunked-prefill cell: one page-aligned chunk of one request's
    prompt against the global PAGED caches — no separate per-request
    caches, no insert step; the chunk's K/V goes straight through the
    block table into the pool. Returns the chunk's last-token greedy
    pick (the engine uses it only on the final chunk)."""

    def chunk_cell(params, tokens, caches, slot, chunk_idx, block_table):
        row = jax.lax.dynamic_index_in_dim(
            block_table, jnp.asarray(slot, jnp.int32), 0, keepdims=True
        )                                      # (1, n_pages)
        logits, caches = M.prefill_chunk(
            params, tokens, caches, chunk_idx, cfg, ctx, row, page_tokens
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    return chunk_cell


@dataclasses.dataclass
class EngineCells:
    """The fixed-shape jitted cells of the continuous-batching engine.

    Paged mode: `decode_fn` and `insert_fns` additionally take the live
    (n_slots, n_pages) int32 block table as their last argument, and
    `chunk_fn` (attention-only archs with `prefill_chunk` set) processes
    one page-aligned prompt chunk: (params, tokens (1, C), caches, slot,
    chunk_idx, block_table) -> (tok (1,), caches) [donates caches]."""

    decode_fn: Any                 # (params, tok (S,), caches, t (S,)[, bt])
    #                     -> (next_tok (S,), finite, caches) [donates caches]
    prefill_fns: Dict[int, Any]    # bucket -> (params, batch) -> (caches, tok)
    insert_fns: Dict[int, Any]     # bucket -> (caches, slot_caches, slot[, bt])
    param_shardings: Any
    cache_shardings: Any
    abstract_params: Any
    abstract_caches: Any
    n_prefix: int                  # frontend prefix tokens (vision)
    max_seq_total: int             # cache seq extent incl. n_prefix
    paged: bool = False            # physical page-pool cache layout
    page_tokens: int = 0           # tokens per page (paged mode)
    n_pages: int = 0               # logical pages per slot (paged mode)
    pool_dtype: str = "fp"         # pool payload: fp | bf16 | int8
    chunk_fn: Any = None           # chunked-prefill cell (paged mode only)
    chunk: int = 0                 # tokens per prefill chunk
    copy_fn: Any = None            # COW page-copy cell (paged mode):
    #                     (caches, src_phys, dst_phys) -> caches [donates]
    sz_granularity: str = "page"   # int8 sub-scale layout: page | token
    verify_fn: Any = None          # speculative verify cell (paged mode):
    #    (params, cand (S, k), caches, t (S,), bt) ->
    #    (greedy (S, k), finite (S,), caches) [donates caches]
    spec_k: int = 0                # candidate tokens per verify call
    draft_fn: Any = None           # draft-proposer decode cell:
    #    (params, tok (S,), caches, t (S,)) -> (tok (S,), caches) [donates]
    draft_params: Any = None       # draft weights (PRNGKey(0); one tree
    #                                shared across a fleet via the cells)
    draft_cfg: Any = None          # draft ModelConfig (sizes draft caches)

    def compile_counts(self) -> Dict[str, int]:
        """Executable-cache sizes of every cell — the no-recompile
        assertion reads this before/after steady state (-1 when the jax
        build does not expose `_cache_size`)."""

        def size(fn):
            probe = getattr(fn, "_cache_size", None)
            return int(probe()) if probe is not None else -1

        out = {"decode": size(self.decode_fn)}
        for b, fn in self.prefill_fns.items():
            out[f"prefill_{b}"] = size(fn)
        for b, fn in self.insert_fns.items():
            out[f"insert_{b}"] = size(fn)
        if self.chunk_fn is not None:
            out["prefill_chunk"] = size(self.chunk_fn)
        if self.copy_fn is not None:
            out["page_copy"] = size(self.copy_fn)
        if self.verify_fn is not None:
            out["verify"] = size(self.verify_fn)
        if self.draft_fn is not None:
            out["draft"] = size(self.draft_fn)
        return out


def make_engine_cells(cfg: ModelConfig, ctx: ParallelCtx,
                      rules=None, mesh=None, *,
                      n_slots: int, max_seq: int,
                      buckets: Sequence[int], enc_len: int = 0,
                      paged: bool = False, page_tokens: int = 16,
                      prefill_chunk: int = 0, pool_dtype: str = "fp",
                      sz_granularity: str = "page",
                      speculative: str = "off", spec_k: int = 4,
                      draft_cfg: ModelConfig | None = None,
                      ) -> EngineCells:
    """Build the engine's cells. With a mesh, shardings come from the same
    rules as `make_bundle` (this is the ServeBundle path refactored for
    slot batching); meshless builds plain single-device jits.

    `paged=True` lays the self-attention KV cache out as the physical
    page pool the serving pager allocates from (see module docstring);
    `prefill_chunk > 0` (paged, attention-only archs) additionally builds
    the chunked-prefill cell. `pool_dtype` picks the pool payload
    (models.blocks.POOL_DTYPES): "fp" is the exact safety net, "int8"
    block-quantizes every pool page (quantize-on-insert in the insert/
    chunk/decode cells, dequantize-in-kernel on the gather side).

    `speculative` ("off" | "ngram" | "draft") additionally builds the
    k-candidate verify cell (`build_decode_verify_paged`) and, for
    "draft", the draft-proposer cell + its weights. Speculative mode
    requires the paged layout and an attention-only decoder (the verify
    cell flattens S slots to S*k decode rows, which only the paged
    attention path supports), and int8 pools must use
    `sz_granularity="token"` (see module docstring)."""
    from repro.models import blocks as blk

    blk.pool_kv_dtype(cfg, pool_dtype)         # validate early
    if pool_dtype != "fp" and not paged:
        raise ValueError("pool_dtype applies to the paged layout only")
    if sz_granularity not in ("page", "token"):
        raise ValueError(f"unknown sz_granularity {sz_granularity!r}")
    if sz_granularity == "token" and pool_dtype != "int8":
        raise ValueError("sz_granularity='token' applies to int8 pools only")
    if speculative not in ("off", "ngram", "draft"):
        raise ValueError(f"unknown speculative mode {speculative!r}")
    if speculative != "off":
        if not paged:
            raise ValueError("speculative decoding requires the paged layout")
        if not chunked_prefill_supported(cfg):
            raise ValueError(
                f"{cfg.name}: speculative decoding needs an attention-only "
                "decoder without frontend/encoder (the verify cell batches "
                "S*k rows, which SSM/conv state cannot follow)"
            )
        if spec_k < 2:
            raise ValueError("spec_k must be >= 2 (k=1 is plain greedy)")
        if pool_dtype == "int8" and sz_granularity != "token":
            raise ValueError(
                "speculative + int8 pools need sz_granularity='token': the "
                "per-page requantize round trip would make a slot's k "
                "candidate rows read-modify-write the same tail page"
            )
        if speculative == "draft" and draft_cfg is None:
            raise ValueError("speculative='draft' needs a draft_cfg")
    npfx = cfg.num_prefix_tokens if cfg.frontend == "vision_stub" else 0
    if cfg.num_encoder_layers and len(set(buckets)) != 1:
        raise ValueError(
            "enc-dec archs need a single prefill bucket (cross-KV extent "
            "is fixed by the encoder length)"
        )
    max_seq_total = max_seq + npfx
    n_pages = -(-max_seq_total // page_tokens) if paged else 0
    if prefill_chunk:
        if not paged:
            raise ValueError("chunked prefill requires the paged layout")
        if not chunked_prefill_supported(cfg):
            raise ValueError(
                f"{cfg.name}: chunked prefill needs an attention-only "
                "decoder without frontend/encoder"
            )
        if prefill_chunk % page_tokens:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must be a multiple of "
                f"page_tokens {page_tokens} (chunks write whole pages)"
            )
        bad = [b for b in buckets if b % prefill_chunk]
        if bad:
            raise ValueError(
                f"prefill_chunk {prefill_chunk} must divide every prompt "
                f"bucket (got {bad}): prompts advance whole chunks"
            )

    param_sh = cache_sh = tok_sh = None
    aparams = acaches = None
    if mesh is not None:
        if rules is None:
            rules = shd.ShardingRules.for_serving(
                data_axis=ctx.fsdp_axis, tp_axis=ctx.tp_axis
            )
        bundle = make_bundle(
            cfg, ctx, rules, mesh, batch=n_slots, max_seq=max_seq_total,
            enc_len=enc_len,
        )
        param_sh = bundle.param_shardings
        aparams = bundle.abstract_params
        if paged:
            # paged mesh layout (shd.paged_cache_pspec): pool payload +
            # int8 scale leaves split on KV heads over tp, resident
            # leaves on slots over dp, block tables replicated (the
            # trailing None in_sharding below)
            acaches = abstract_paged_caches(
                cfg, n_slots, max_seq_total, page_tokens, enc_len,
                pool_dtype=pool_dtype, sz_granularity=sz_granularity,
            )
            cache_sh = shd.named(
                mesh,
                shd.paged_cache_pspec(
                    acaches, ctx.dp_axes, ctx.tp_axis, mesh
                ),
            )
        else:
            cache_sh = bundle.cache_shardings
            acaches = bundle.abstract_caches
        tok_sh = shd.named(mesh, P())
        decode_cell = (
            build_decode_greedy_paged(cfg, ctx, page_tokens) if paged
            else build_decode_greedy(cfg, ctx)
        )
        in_sh = (param_sh, tok_sh, cache_sh, None)
        decode = jax.jit(
            decode_cell,
            in_shardings=in_sh + (None,) if paged else in_sh,
            out_shardings=(None, None, cache_sh),
            donate_argnums=(2,),
        )
    else:
        aparams, _ = abstract_params(cfg)
        acaches = (
            abstract_paged_caches(cfg, n_slots, max_seq_total, page_tokens,
                                  enc_len, pool_dtype=pool_dtype,
                                  sz_granularity=sz_granularity)
            if paged else abstract_caches(cfg, n_slots, max_seq_total,
                                          enc_len)
        )
        decode_cell = (
            build_decode_greedy_paged(cfg, ctx, page_tokens) if paged
            else build_decode_greedy(cfg, ctx)
        )
        decode = jax.jit(decode_cell, donate_argnums=(2,))

    prefills, inserts = {}, {}
    for b in sorted(set(buckets)):
        cell = build_prefill_greedy(cfg, ctx, b)
        ins_cell = (
            build_paged_cache_insert(b + npfx, page_tokens, pool_dtype,
                                     sz_granularity, ctx)
            if paged else build_cache_insert()
        )
        if mesh is not None:
            prefills[b] = jax.jit(cell, in_shardings=(param_sh, None))
            # pin the global caches to the decode cell's sharding so the
            # insert->decode round trip never re-lays-out (and never
            # recompiles either cell after the first call)
            ins_in = (cache_sh, None, None)
            inserts[b] = jax.jit(
                ins_cell,
                in_shardings=ins_in + (None,) if paged else ins_in,
                out_shardings=cache_sh,
                donate_argnums=(0,),
            )
        else:
            prefills[b] = jax.jit(cell)
            inserts[b] = jax.jit(ins_cell, donate_argnums=(0,))

    chunk_fn = None
    if prefill_chunk:
        chunk_cell = build_prefill_chunk(cfg, ctx, page_tokens)
        if mesh is not None:
            chunk_fn = jax.jit(
                chunk_cell,
                in_shardings=(param_sh, None, cache_sh, None, None, None),
                out_shardings=(None, cache_sh),
                donate_argnums=(2,),
            )
        else:
            chunk_fn = jax.jit(chunk_cell, donate_argnums=(2,))

    copy_fn = None
    if paged:
        copy_cell = build_page_copy()
        if mesh is not None:
            copy_fn = jax.jit(
                copy_cell,
                in_shardings=(cache_sh, None, None),
                out_shardings=cache_sh,
                donate_argnums=(0,),
            )
        else:
            copy_fn = jax.jit(copy_cell, donate_argnums=(0,))

    verify_fn = draft_fn = draft_params = None
    if speculative != "off":
        verify_cell = build_decode_verify_paged(cfg, ctx, page_tokens,
                                                spec_k)
        if mesh is not None:
            # cand (S, k) and t (S,) replicated like the greedy token
            # vector; caches keep the decode cell's sharding
            verify_fn = jax.jit(
                verify_cell,
                in_shardings=(param_sh, None, cache_sh, None, None),
                out_shardings=(None, None, cache_sh),
                donate_argnums=(2,),
            )
        else:
            verify_fn = jax.jit(verify_cell, donate_argnums=(2,))
    if speculative == "draft":
        # the draft model is small scratch state: plain replicated jit
        # even under a mesh (its caches never join the paged pool).
        # PRNGKey(0) init makes the weights deterministic, so every
        # engine in a fleet — and every process — shares one bit-exact
        # draft tree through the shared EngineCells.
        draft_fn = jax.jit(build_decode_draft(draft_cfg, ctx),
                           donate_argnums=(2,))
        draft_params = init_params(draft_cfg, 0)

    return EngineCells(
        decode_fn=decode,
        prefill_fns=prefills,
        insert_fns=inserts,
        param_shardings=param_sh,
        cache_shardings=cache_sh,
        abstract_params=aparams,
        abstract_caches=acaches,
        n_prefix=npfx,
        max_seq_total=max_seq_total,
        paged=paged,
        page_tokens=page_tokens if paged else 0,
        n_pages=n_pages,
        pool_dtype=pool_dtype if paged else "fp",
        chunk_fn=chunk_fn,
        chunk=prefill_chunk,
        copy_fn=copy_fn,
        sz_granularity=sz_granularity if paged else "page",
        verify_fn=verify_fn,
        spec_k=spec_k if speculative != "off" else 0,
        draft_fn=draft_fn,
        draft_params=draft_params,
        draft_cfg=draft_cfg if speculative == "draft" else None,
    )
