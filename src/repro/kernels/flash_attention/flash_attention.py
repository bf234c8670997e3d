"""Flash attention forward as a Pallas TPU kernel.

Grid (B, H, nq, nk) with the kv dimension sequential; online-softmax
accumulators (acc, m, l) live in VMEM scratch across kv iterations. Blocks
are MXU-aligned (block_q x D) / (block_k x D): the wrapper presents q/k/v
head-major, (B, H, S, D), so each block's minor dims are (tokens, D) as
Mosaic's tiling needs (a (block, 1, D) slice of the token-major layout
would cut the head dim). Fully-masked causal tiles are
skipped (`pl.when`), which is the 2x causal-waste saving the jnp chunked
path cannot express. Backward reuses the chunked-jnp flash backward via
custom_vjp (recompute-from-lse; the standard pairing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import chunked

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc, *,
            block_q: int, block_k: int, nk: int, rep: int, scale: float,
            causal: bool, kv_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    qpos0 = kv_offset + qi * block_q
    needed = (not causal) or (ki * block_k <= qpos0 + block_q - 1)

    @pl.when(needed)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                       # (bq, bk)
        if causal:
            qpos = qpos0 + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, NEG_INF)
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

    @pl.when(ki == nk - 1)
    def _done():
        l_safe = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l_safe)     # (bq, 1)


def _fwd_pallas(q, k, v, causal, scale, kv_offset, block_q, block_k,
                interpret):
    B, Sq, H, D = q.shape
    _, Skv, KV, _ = k.shape
    rep = H // KV
    scale = scale if scale is not None else D ** -0.5
    bq = chunked._pick_block(Sq, block_q)
    bk = chunked._pick_block(Skv, block_k)
    nq, nk = Sq // bq, Skv // bk

    grid = (B, H, nq, nk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"
            ),
        )
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, qi, ki: (b, h // rep, ki, 0))
    head_major = (0, 2, 1, 3)
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel, block_q=bq, block_k=bk, nk=nk, rep=rep, scale=scale,
            causal=causal, kv_offset=kv_offset,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(
            q_spec,
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="mha",
        interpret=interpret,
        **kwargs,
    )(q.transpose(head_major), k.transpose(head_major),
      v.transpose(head_major))
    return out.transpose(head_major), lse[..., 0]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_mha(q, k, v, causal=True, scale=None, kv_offset=0,
              block_q=512, block_k=512, interpret=False):
    out, _ = _fwd_pallas(q, k, v, causal, scale, kv_offset, block_q,
                         block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, kv_offset, block_q, block_k,
               interpret):
    out, lse = _fwd_pallas(q, k, v, causal, scale, kv_offset, block_q,
                           block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, kv_offset, block_q, block_k, interpret, res,
               dout):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G, R = KV, H // KV
    lse4 = lse.reshape(B, G, R, Sq)
    return chunked._bwd_inner(
        causal, scale, kv_offset, block_q, block_k,
        (q, k, v, out, lse4), dout,
    )


flash_mha.defvjp(_flash_fwd, _flash_bwd)
