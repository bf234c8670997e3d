"""Layer-pattern derivation and super-block scan assembly.

Heterogeneous layer stacks (Jamba's 1:7 attn:mamba with period-2 MoE) are
handled by scanning over *super-blocks*: the layer pattern repeats with
period = lcm(moe_period, attn_period); params for each position-in-period are
stacked over the num_layers/period blocks, and `lax.scan` runs over blocks.
HLO size is therefore independent of depth while the layer pattern is exact.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.common.parallel import ParallelCtx
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import mlp, mlp_init, rmsnorm, rmsnorm_init
from repro.models.module import Initializer, stack_inits


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    kind: str            # "attn" | "ssm"
    moe: bool
    cross: bool = False  # enc-dec decoder layer with cross-attention
    causal: bool = True


def super_period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.num_experts:
        p = math.lcm(p, cfg.moe_layer_period)
    if cfg.family == "hybrid" and cfg.attn_layer_period:
        p = math.lcm(p, cfg.attn_layer_period)
    return p


def pattern(cfg: ModelConfig, cross: bool = False, causal: bool = True):
    per = super_period(cfg)
    assert cfg.num_layers % per == 0, (cfg.num_layers, per)
    return [
        LayerDesc(
            kind="attn" if cfg.is_attn_layer(j) else "ssm",
            moe=cfg.is_moe_layer(j),
            cross=cross,
            causal=causal,
        )
        for j in range(per)
    ]


# ------------------------------------------------------------------ init
def layer_init(key, cfg: ModelConfig, desc: LayerDesc):
    init = Initializer(key, jnp.dtype(cfg.param_dtype))
    rmsnorm_init(init.child("pre_norm"), cfg.d_model)
    if desc.kind == "attn":
        attn.attn_init(init.child("attn"), cfg)
    else:
        ssm_mod.ssm_init(init.child("ssm"), cfg)
    if desc.cross:
        rmsnorm_init(init.child("cross_norm"), cfg.d_model)
        attn.attn_init(init.child("cross"), cfg, cross=True)
    rmsnorm_init(init.child("ffn_norm"), cfg.d_model)
    if desc.moe:
        moe_mod.moe_init(init.child("moe"), cfg)
    elif cfg.d_ff:
        mlp_init(init.child("mlp"), cfg)
    return init.collect()


def stack_init(key, cfg: ModelConfig, cross: bool = False,
               causal: bool = True, n_layers: Optional[int] = None):
    """Init all layers, stacked by position-in-period. Returns (params, axes)."""
    descs = pattern(cfg, cross, causal)
    n_layers = n_layers if n_layers is not None else cfg.num_layers
    nb = n_layers // len(descs)
    params, axes = {}, {}
    keys = jax.random.split(key, len(descs))
    for j, desc in enumerate(descs):
        pj, aj = stack_inits(
            lambda k, d=desc: layer_init(k, cfg, d), keys[j], nb
        )
        params[f"pos{j}"] = pj
        axes[f"pos{j}"] = aj
    return params, axes


# ------------------------------------------------------------------ apply
def _apply_layer_train(p, x, cfg: ModelConfig, desc: LayerDesc,
                       ctx: ParallelCtx, enc_out=None):
    """One layer, full-sequence. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
    if desc.kind == "attn":
        h = attn.self_attention(p["attn"], h, cfg, causal=desc.causal,
                                ctx=ctx)
    else:
        h = ssm_mod.ssm_block(p["ssm"], h, cfg)
    x = x + h
    if desc.cross:
        h = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        enc_kv = attn.encode_cross_kv(p["cross"], enc_out, cfg)
        x = x + attn.cross_attention(p["cross"], h, enc_kv, cfg)
    h = rmsnorm(p["ffn_norm"], x, cfg.norm_eps)
    if desc.moe:
        h, a = moe_mod.moe_ep(p["moe"], h, cfg, ctx)
        aux = aux + a
    elif cfg.d_ff:
        h = mlp(p["mlp"], h, cfg)
    else:
        h = jnp.zeros_like(x)
    return x + h, aux


def stack_apply(params, x, cfg: ModelConfig, ctx: ParallelCtx,
                cross: bool = False, causal: bool = True, enc_out=None):
    """Full-sequence stack (training / prefill without cache)."""
    descs = pattern(cfg, cross, causal)

    def body(carry, blk):
        x, aux = carry
        for j, desc in enumerate(descs):
            x, a = _apply_layer_train(blk[f"pos{j}"], x, cfg, desc, ctx,
                                      enc_out)
            aux = aux + a
        return (x, aux), None

    if ctx.remat == "block":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params)
    return x, aux


# --------------------------------------------------------------- caches
# pool payload dtypes for the paged KV cache: "fp" stores cfg.dtype
# exactly (the bit-identical safety net), "bf16" is a 2-byte cast-only
# pool, "int8" adds per-page float32 (scale, zero) arrays ("k_sz"/"v_sz"
# leaves, `repro.kernels.quant` layout) with quantize-on-insert and
# dequantize-in-kernel
POOL_DTYPES = ("fp", "bf16", "int8")


def pool_kv_dtype(cfg: ModelConfig, pool_dtype: str):
    """Resolve a pool-dtype name to the K/V payload jnp dtype."""
    if pool_dtype not in POOL_DTYPES:
        raise ValueError(f"unknown pool_dtype {pool_dtype!r}; "
                         f"expected one of {POOL_DTYPES}")
    if pool_dtype == "fp":
        return jnp.dtype(cfg.dtype)
    return jnp.dtype({"bf16": jnp.bfloat16, "int8": jnp.int8}[pool_dtype])


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                cross: bool = False, enc_len: int = 0, kv_shape=None,
                kv_dtype=None, kv_sz_shape=None):
    """Decode caches, mirroring the stacked-params structure. `kv_shape`/
    `kv_dtype` override the self-attention K/V leaf shape and dtype (the
    paged pool layout — see `init_paged_caches`); the K/V buffers are the
    engine's largest arrays, so they are allocated directly in their
    final shape. `kv_sz_shape` adds the per-page float32 (scale, zero)
    leaves of an int8 block-quantized pool."""
    descs = pattern(cfg, cross)
    nb = cfg.num_layers // len(descs)
    dtype = jnp.dtype(cfg.dtype)
    caches = {}
    for j, desc in enumerate(descs):
        c = {}
        if desc.kind == "attn":
            shape = kv_shape or (nb, batch, max_seq, cfg.num_kv_heads,
                                 cfg.head_dim)
            c["k"] = jnp.zeros(shape, kv_dtype or dtype)
            c["v"] = jnp.zeros(shape, kv_dtype or dtype)
            if kv_sz_shape is not None:
                c["k_sz"] = jnp.zeros(kv_sz_shape, jnp.float32)
                c["v_sz"] = jnp.zeros(kv_sz_shape, jnp.float32)
        else:
            H, Pd, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            gn = ssm_mod.NGROUPS * N
            W = cfg.conv_width
            c["state"] = jnp.zeros((nb, batch, H, Pd, N), jnp.float32)
            c["tail_x"] = jnp.zeros((nb, batch, W - 1, cfg.d_inner), dtype)
            c["tail_B"] = jnp.zeros((nb, batch, W - 1, gn), dtype)
            c["tail_C"] = jnp.zeros((nb, batch, W - 1, gn), dtype)
        if desc.cross:
            shape = (nb, batch, enc_len, cfg.num_kv_heads, cfg.head_dim)
            c["cross_k"] = jnp.zeros(shape, dtype)
            c["cross_v"] = jnp.zeros(shape, dtype)
        caches[f"pos{j}"] = c
    return caches


def init_paged_caches(cfg: ModelConfig, n_slots: int, max_seq: int,
                      page_tokens: int, cross: bool = False,
                      enc_len: int = 0, pool_dtype: str = "fp",
                      sz_granularity: str = "page"):
    """Decode caches with self-attention K/V laid out as a PHYSICAL page
    pool: (nb, n_slots * n_pages, page_tokens, KV, hd) instead of the
    per-slot contiguous (nb, n_slots, max_seq, KV, hd). Each valid
    (slot, logical page) owns one physical page handed out by the serving
    pager's free list; the (n_slots, n_pages) block table maps between
    them at every cache read/write. Non-attention state (SSM state, conv
    tails, cross-KV) is resident per slot and keeps the dense layout.

    `pool_dtype` picks the pool payload (see `POOL_DTYPES`): "fp" keeps
    cfg.dtype bit-identically; "bf16" stores a 2-byte cast; "int8" stores
    int8 payload plus float32 (scale, zero) arrays as "k_sz"/"v_sz"
    leaves. `sz_granularity` picks the quantization grain of those
    leaves: "page" (default) stores one pair per (physical page, KV head)
    — (nb, p_phys, KV, 2); "token" stores one pair per (page row, KV
    head) — (nb, p_phys, page_tokens, KV, 2) — the speculative-decoding
    hot-page layout whose token writes are pure disjoint scatters
    (`kernels.quant.quantize_tokens`). The kernels dispatch on the static
    rank of the sz leaf, so both layouts flow through the same cells."""
    if sz_granularity not in ("page", "token"):
        raise ValueError(f"unknown sz_granularity {sz_granularity!r}; "
                         "expected 'page' or 'token'")
    descs = pattern(cfg, cross)
    nb = cfg.num_layers // len(descs)
    n_pages = -(-max_seq // page_tokens)       # ceil
    p_phys = n_slots * n_pages
    if pool_dtype != "int8":
        sz_shape = None
    elif sz_granularity == "token":
        sz_shape = (nb, p_phys, page_tokens, cfg.num_kv_heads, 2)
    else:
        sz_shape = (nb, p_phys, cfg.num_kv_heads, 2)
    return init_caches(
        cfg, n_slots, max_seq, cross=cross, enc_len=enc_len,
        kv_shape=(nb, p_phys, page_tokens, cfg.num_kv_heads, cfg.head_dim),
        kv_dtype=pool_kv_dtype(cfg, pool_dtype),
        kv_sz_shape=sz_shape,
    )


# the physical-page-pool leaves of a paged cache tree; everything else
# (SSM state, conv tails, cross-KV) is slot-resident and never pooled
PAGED_LEAF_NAMES = ("k", "v", "k_sz", "v_sz")


def paged_pool_leaves(caches):
    """The PAGED leaves of `caches`: the physical page pool arrays (k/v
    payload plus the int8 (scale, zero) leaves), by layer position. The
    serving substrate (`repro.serving.substrate`) mirrors pool-tiered
    pages of these into its host twin. Returns {} for cache trees with
    no paged leaves (SSM-only stacks)."""
    pool = {}
    for pos, c in caches.items():
        sub = {name: c[name] for name in PAGED_LEAF_NAMES if name in c}
        if sub:
            pool[pos] = sub
    return pool


def _apply_layer_decode(p, c, x, t, cfg: ModelConfig, desc: LayerDesc,
                        ctx: ParallelCtx, block_table=None,
                        page_tokens: int = 0, attn_override=None):
    """One layer, one token (or, via `attn_override`, one prompt chunk).
    Returns (x, new_cache). With a block table the attention K/V lives in
    the physical page pool layout (fp or block-quantized — the paged
    paths read and return the whole attention cache dict, so the int8
    "k_sz"/"v_sz" leaves ride along invisibly); `attn_override(p_attn,
    h, c) -> (h, cache_updates)` swaps the attention contraction while
    the rest of the layer body stays shared (the chunked-prefill path —
    one body, so a layer change cannot silently diverge the chunked and
    serialized streams)."""
    nc = dict(c)
    h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
    if desc.kind == "attn":
        if attn_override is not None:
            h, updates = attn_override(p["attn"], h, c)
            nc.update(updates)
        elif block_table is not None:
            h, updates = attn.paged_decode_self_attention(
                p["attn"], h, cfg, c, t, block_table, page_tokens, ctx=ctx,
            )
            nc.update(updates)
        else:
            h, (nc["k"], nc["v"]) = attn.decode_self_attention(
                p["attn"], h, cfg, c["k"], c["v"], t
            )
    else:
        h, (nc["state"], (nc["tail_x"], nc["tail_B"], nc["tail_C"])) = (
            ssm_mod.ssm_decode_step(
                p["ssm"], h, cfg, c["state"],
                (c["tail_x"], c["tail_B"], c["tail_C"]),
            )
        )
    x = x + h
    if desc.cross:
        h = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        x = x + attn.decode_cross_attention(
            p["cross"], h, (c["cross_k"], c["cross_v"]), cfg
        )
    h = rmsnorm(p["ffn_norm"], x, cfg.norm_eps)
    if desc.moe:
        h, _ = moe_mod.moe_ep(p["moe"], h, cfg, ctx)
    elif cfg.d_ff:
        h = mlp(p["mlp"], h, cfg)
    else:
        h = jnp.zeros_like(x)
    return x + h, nc


def stack_decode(params, caches, x, t, cfg: ModelConfig, ctx: ParallelCtx,
                 cross: bool = False, block_table=None,
                 page_tokens: int = 0):
    """One decode step through the whole stack. x: (B, 1, d). With
    `block_table`, attention caches are the paged pool layout."""
    descs = pattern(cfg, cross)

    def body(x, inp):
        blk, cache = inp
        new_cache = {}
        for j, desc in enumerate(descs):
            x, new_cache[f"pos{j}"] = _apply_layer_decode(
                blk[f"pos{j}"], cache[f"pos{j}"], x, t, cfg, desc, ctx,
                block_table=block_table, page_tokens=page_tokens,
            )
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches


def stack_prefill_chunk(params, caches, x, c0, cfg: ModelConfig,
                        ctx: ParallelCtx, block_row, page_tokens: int):
    """One page-aligned prompt chunk through the whole stack against the
    PAGED caches: each attention layer writes the chunk's KV through the
    block table and flash-attends to everything prefilled so far. Only
    attention-only decoder stacks chunk (an SSM/conv prefix is a
    sequential reduction over the prompt; see
    `runtime.serve.chunked_prefill_supported`). x: (1, C, d)."""
    descs = pattern(cfg, cross=False)
    if any(d.kind != "attn" or d.cross for d in descs):
        raise ValueError("chunked prefill needs an attention-only stack")

    def chunk_attn(p_attn, h, c):
        return attn.paged_prefill_chunk_attention(
            p_attn, h, cfg, c, c0, block_row, page_tokens, ctx=ctx
        )

    def body(x, inp):
        blk, cache = inp
        new_cache = {}
        for j, desc in enumerate(descs):
            x, new_cache[f"pos{j}"] = _apply_layer_decode(
                blk[f"pos{j}"], cache[f"pos{j}"], x, None, cfg, desc, ctx,
                attn_override=chunk_attn,
            )
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params, caches))
    return x, new_caches


def stack_prefill(params, x, t0, cfg: ModelConfig, ctx: ParallelCtx,
                  max_seq: int, cross: bool = False, enc_out=None):
    """Prefill: full-sequence forward that also materializes decode caches."""
    descs = pattern(cfg, cross)
    B, S, _ = x.shape
    dtype = jnp.dtype(cfg.dtype)

    def body(carry, blk):
        x, aux = carry
        cache = {}
        for j, desc in enumerate(descs):
            p = blk[f"pos{j}"]
            c = {}
            h = rmsnorm(p["pre_norm"], x, cfg.norm_eps)
            if desc.kind == "attn":
                h, (k, v) = attn.self_attention(
                    p["attn"], h, cfg, causal=desc.causal, return_kv=True,
                    ctx=ctx,
                )
                pad = max_seq - S
                c["k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                c["v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            else:
                h, (state, tails) = ssm_mod.ssm_block(
                    p["ssm"], h, cfg, return_state=True
                )
                c["state"] = state
                c["tail_x"], c["tail_B"], c["tail_C"] = tails
            x = x + h
            if desc.cross:
                hh = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
                ck, cv = attn.encode_cross_kv(p["cross"], enc_out, cfg)
                c["cross_k"], c["cross_v"] = ck, cv
                x = x + attn.cross_attention(p["cross"], hh, (ck, cv), cfg)
            h = rmsnorm(p["ffn_norm"], x, cfg.norm_eps)
            if desc.moe:
                h, a = moe_mod.moe_ep(p["moe"], h, cfg, ctx)
                aux = aux + a
            elif cfg.d_ff:
                h = mlp(p["mlp"], h, cfg)
            else:
                h = jnp.zeros_like(x)
            x = x + h
            cache[f"pos{j}"] = c
        return (x, aux), cache

    (x, aux), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params
    )
    return x, caches
