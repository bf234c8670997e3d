"""Chunked SSD as a Pallas TPU kernel.

Grid (B, H, n_chunks) with the chunk dimension sequential; the recurrent
state (P x N) persists in VMEM scratch across chunk iterations. Each chunk
does three MXU matmuls — the C.B^T quadratic form, the (L o S) @ dX intra
term, and the dX^T @ (w o B) state update — so the sequential component is
only the O(n_chunks) scalar-decay recurrence, exactly the SSD decomposition
(arXiv:2405.21060) mapped onto the TPU memory hierarchy.

Layout: the wrapper presents every per-head operand head-major, (B, H, S,
·), so each block's minor dims are (Q, ·) as Mosaic's tiling needs, and
passes the step sizes `dt` twice — as a (1, Q) row and a (Q, 1) column —
so the in-chunk cumulative decay is built from masked (Q, Q) sums in
either orientation without a vector transpose. A and D are whole (H,)
SMEM arrays read by scalar index.

Backward: custom_vjp differentiates the (numerically identical) chunked-jnp
implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ssd_scan import chunked as cj


def _kernel(A_ref, D_ref, x_ref, dtr_ref, dtc_ref, B_ref, C_ref, h0_ref,
            y_ref, hT_ref, state, *, Q: int, nc: int, has_D: bool):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                 # (Q, P)
    dt_c = dtc_ref[0, 0].astype(jnp.float32)            # (Q, 1)
    Bm = B_ref[0, 0].astype(jnp.float32)                # (Q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)                # (Q, N)
    A = A_ref[h]

    a_r = A * dtr_ref[0, 0].astype(jnp.float32)         # (1, Q) <= 0
    a_c = A * dt_c                                      # (Q, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # inclusive cumulative sums of a, as a column and as a row
    cum_c = jnp.where(jj <= ii, a_r, 0.0).sum(axis=1, keepdims=True)
    cum_r = jnp.where(ii <= jj, a_c, 0.0).sum(axis=0, keepdims=True)
    total = a_r.sum(axis=1, keepdims=True)              # (1, 1)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # (Q, Q)  i x j
    L = jnp.where(ii >= jj, jnp.exp(cum_c - cum_r), 0.0)
    dx = dt_c * x                                       # (Q, P)
    y = jax.lax.dot_general(
        scores * L, dx, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # intra (Q, P)

    # inter-chunk: y += exp(cum) * C @ state^T
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if has_D:
        y = y + D_ref[h] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(total) h + dx^T @ (w o B)
    w = jnp.exp(total - cum_c)                          # (Q, 1)
    hc = jax.lax.dot_general(
        dx, w * Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # (P, N)
    state[...] = jnp.exp(total) * state[...] + hc

    @pl.when(c == nc - 1)
    def _done():
        hT_ref[0, 0] = state[...]


def _ssd_fwd_pallas(x, dt, A, Bmat, Cmat, D, init_state, chunk, interpret):
    B, S, H, P = x.shape
    _, _, G, N = Bmat.shape
    rep = H // G
    Q = cj._pick_chunk(S, chunk)
    nc = S // Q
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)
    has_D = D is not None
    D_arr = D.astype(jnp.float32) if has_D else jnp.zeros((H,), jnp.float32)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    head_major = (0, 2, 1, 3)
    dt_hm = dt.transpose(0, 2, 1)                       # (B, H, S)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)        # whole (H,) array
    y, hT = pl.pallas_call(
        functools.partial(_kernel, Q=Q, nc=nc, has_D=has_D),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ),
        grid=(B, H, nc),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        name="ssd",
        interpret=interpret,
        **kwargs,
    )(A.astype(jnp.float32), D_arr, x.transpose(head_major),
      dt_hm[:, :, None, :], dt_hm[..., None],
      Bmat.transpose(head_major), Cmat.transpose(head_major), init_state)
    return y.transpose(head_major), hT


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_pallas(x, dt, A, Bmat, Cmat, D=None, init_state=None,
               chunk: int = 128, interpret: bool = False):
    return _ssd_fwd_pallas(x, dt, A, Bmat, Cmat, D, init_state, chunk,
                           interpret)


def _ssd_fwd(x, dt, A, Bmat, Cmat, D, init_state, chunk, interpret):
    y, hT = _ssd_fwd_pallas(x, dt, A, Bmat, Cmat, D, init_state, chunk,
                            interpret)
    return (y, hT), (x, dt, A, Bmat, Cmat, D, init_state)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, A, Bmat, Cmat, D, init_state = res
    has_D = D is not None
    has_init = init_state is not None

    def f(x, dt, A, Bmat, Cmat, D, init_state):
        return cj.ssd_chunked_jnp(
            x, dt, A, Bmat, Cmat,
            D if has_D else None,
            init_state if has_init else None,
            chunk,
        )

    D_in = D if has_D else jnp.zeros((x.shape[2],), jnp.float32)
    init_in = (
        init_state if has_init
        else jnp.zeros(
            (x.shape[0], x.shape[2], x.shape[3], Bmat.shape[3]), jnp.float32
        )
    )
    _, vjp = jax.vjp(f, x, dt, A, Bmat, Cmat, D_in, init_in)
    dx, ddt, dA, dB, dC, dD, dh0 = vjp(cts)
    return (dx, ddt, dA, dB, dC,
            dD if has_D else None,
            dh0 if has_init else None)


ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)
