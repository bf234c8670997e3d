"""Flash-decode Pallas kernel: one query token vs a long KV cache.

Grid (B, n_kv_blocks); the kv-block dimension is sequential ("arbitrary")
so the online-softmax accumulators live in VMEM scratch across iterations.
Out-of-length positions are masked with an iota test against `length`
(supports ragged batches); the lengths ride the scalar-prefetch channel.
Blocks are (block_k, KV, D) with every KV head at once — Mosaic needs the
cache's (KV, D) minor dims whole — and the kernel walks the KV heads in a
static loop, each head's `rep` GQA queries as one (rep, D) x (D, block_k)
contraction (the same body as the paged kernel in `paged.py`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decode_attention.paged import NEG_INF, online_softmax_update


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc, *,
            block_k: int, kv: int, scale: float, nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = pos < len_ref[b]
    for g in range(kv):
        k = k_ref[0, :, g, :].astype(jnp.float32)     # (bk, D)
        v = v_ref[0, :, g, :].astype(jnp.float32)
        q = q_ref[0, g].astype(jnp.float32)           # (rep, D)
        online_softmax_update(q, k, v, valid, scale, acc.at[g],
                              m_sc.at[g], l_sc.at[g])

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0] = (
            acc[...] / jnp.maximum(l_sc[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "block_k"))
def flash_decode(q, k, v, length, *, scale=None, interpret: bool = False,
                 block_k: int = 512):
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    _, S, KV, _ = k.shape
    rep = H // KV
    scale = scale if scale is not None else D ** -0.5
    bk = min(block_k, S)
    while S % bk:
        bk //= 2
    bk = max(bk, 1)
    nk = S // bk
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))

    q_spec = pl.BlockSpec((1, KV, rep, D), lambda b, ki, ln: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, KV, D), lambda b, ki, ln: (b, ki, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_k=bk, kv=KV, scale=scale, nk=nk),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,               # per-sequence lengths
            grid=(B, nk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((KV, rep, D), jnp.float32),
                pltpu.VMEM((KV, rep, 1), jnp.float32),
                pltpu.VMEM((KV, rep, 1), jnp.float32),
            ],
        ),
        name="flash_decode",
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ) if not interpret else None,
    )(length, q.reshape(B, KV, rep, D), k, v)
    return out.reshape(B, H, D)
