"""Plain reference of the served models, and the control that must fail.

A Llama-style decoder written from the configuration file alone
(`configs/<name>.json`, its "model" block): token embedding times
`embedding_multiplier`, then per layer RMSNorm -> GQA attention with
rotary positions (half-split rotation, theta `rope_theta`) -> residual,
RMSNorm -> SwiGLU MLP -> residual, final RMSNorm and the (tied or own)
output head. Everything is float32 at HIGHEST matmul precision; the
weights are the bfloat16 arrays the benchmark made (`weights.py`), so
upcasting them loses nothing. It imports nothing of the program.

It runs one sequence at a time, layer by layer, with attention in blocks
of query rows, so that a long prompt of a large model fits beside the
weights. `precision="fp8"` is the control: every matmul operand is first
rounded to float8_e4m3 with a per-tensor scale (amax / 448), the step
below the bfloat16 the configurations state.

`gaps()` is what decides `correct`: for every served token, how far its
reference logit lies below the reference's best logit at that position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
LEN_STEP = 512         # sequences are padded to a multiple of this


def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale, back to float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, control):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, H, D); rotate the two halves of D (the Llama convention)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(p, x, n_valid, *, eps, theta, control):
    """One decoder layer over x (S, d), causal; rows >= n_valid are
    padding (their keys are masked for every query)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rmsnorm(x, p["pre_norm"], eps)
    q = _rope(_mm("sd,dhk->shk", h, p["wq"], control), pos, theta)
    k = _rope(_mm("sd,dhk->shk", h, p["wk"], control), pos, theta)
    v = _mm("sd,dhk->shk", h, p["wv"], control)
    H, KV, D = q.shape[1], k.shape[1], q.shape[2]
    q = q.reshape(S, KV, H // KV, D) / math.sqrt(D)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qgrd,kgd->grqk", qb, k, control)               # (KV,R,Q,S)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        keep = (pos[None, :] <= rows[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(keep, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return _mm("grqk,kgd->qgrd", pr, v, control)             # (Q,KV,R,D)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    o = o.reshape(S, H, D)
    x = x + _mm("shk,hkd->sd", o, p["wo"], control)
    h = _rmsnorm(x, p["ffn_norm"], eps)
    g = _mm("sd,df->sf", h, p["w_gate"], control)
    u = _mm("sd,df->sf", h, p["w_up"], control)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, final_scale, w, rows, *, eps, control):
    """Logits (len(rows), V) at the given positions. `w` is (V, d) for a
    tied head (the embedding) and (d, V) otherwise."""
    h = _rmsnorm(jnp.take(x, rows, axis=0), final_scale, eps)
    if w.shape[0] == h.shape[1]:
        return _mm("sd,dv->sv", h, w, control)
    return _mm("sd,vd->sv", h, w, control)


def logits(weights, model: dict, tokens: np.ndarray, rows: np.ndarray, *,
           control: bool = False) -> np.ndarray:
    """Reference logits (len(rows), V) of `tokens` at positions `rows`."""
    n = len(tokens)
    S = -(-n // LEN_STEP) * LEN_STEP
    ids = np.zeros(S, np.int32)
    ids[:n] = tokens
    emb = weights["embed"]["embedding"]
    x = jnp.take(emb, jnp.asarray(ids), axis=0).astype(jnp.float32)
    x = x * model.get("embedding_multiplier", 1.0)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    blocks = weights["blocks"]["pos0"]
    for i in range(model["num_hidden_layers"]):
        p = {
            "pre_norm": blocks["pre_norm"]["scale"][i],
            "ffn_norm": blocks["ffn_norm"]["scale"][i],
            **{k: blocks["attn"][k][i] for k in ("wq", "wk", "wv", "wo")},
            **{k: blocks["mlp"][k][i] for k in ("w_gate", "w_up", "w_down")},
        }
        x = _layer(p, x, n, eps=eps, theta=theta, control=control)
    w = (emb if model["tie_word_embeddings"]
         else weights["head"]["lm_head"])
    out = _head(x, weights["final_norm"]["scale"], w,
                jnp.asarray(rows, jnp.int32), eps=eps, control=control)
    return np.asarray(out)


def gaps(weights, model: dict, prompt: np.ndarray, served, *,
         control: bool = False):
    """Per served token, ref_best - ref[token] at the position that
    produced it, teacher-forced on prompt + served tokens. With
    `control`, the token is the control's own first pick at each
    position instead of the served one. Returns (gaps, control_gaps or
    None)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = logits(weights, model, seq, rows)
    best = ref.max(axis=-1)
    g = best - ref[np.arange(len(served)), served]
    cg = None
    if control:
        low = logits(weights, model, seq, rows, control=True)
        pick = low.argmax(axis=-1)
        cg = best - ref[np.arange(len(served)), pick]
    return g, cg
