"""Operations and bytes the algorithm needs, from shapes alone.

Sizes come from a configuration file's "model" block (Hugging Face key
names) and its "engine" block; nothing here asks the program. The paged
attention kernels are counted from what the algorithm needs: the live
KV pages of the active slots (int8 payload plus their float32 scale
rows), q, the new K/V and the output. The grid the kernel walks today
(every logical page up to max_seq, dead ones clamped to page 0) is not
counted, so a kernel that stops walking dead pages reads as a higher
share of its roofline.

Per chip: attention heads are split over the tensor-parallel axis, so a
chip's share of a kernel call is 1/tp of the call's operations and bytes.
"""

from __future__ import annotations

import math
from typing import Iterable, Tuple

BF16 = 2                       # bytes of a bfloat16 activation
INT8 = 1                       # bytes of an int8 pool element
SZ_BYTES = 2 * 4               # (scale, zero) float32 per page and KV head


def dims(model: dict):
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    return d, h, kv, hd


def layer_params(model: dict) -> int:
    """Matmul weights of one decoder layer (attention + SwiGLU MLP)."""
    d, h, kv, hd = dims(model)
    ff = model["intermediate_size"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["hidden_size"]


def page_bytes(model: dict, page: int) -> int:
    """One layer's K and V page: int8 payload plus (scale, zero) rows."""
    _, _, kv, hd = dims(model)
    return 2 * (page * kv * hd * INT8 + kv * SZ_BYTES)


def attn_flops(model: dict, n_keys: int) -> int:
    """QK^T and PV of one query row over `n_keys` keys, all heads."""
    _, h, _, hd = dims(model)
    return 4 * h * hd * n_keys


def decode_call(model: dict, contexts: Iterable[int], page: int,
                tp: int = 1) -> Tuple[float, float]:
    """(flops, bytes) per chip of one paged-decode kernel call over all
    layers: one query row per active slot against its `contexts` live
    tokens (the token written this step included)."""
    _, h, _, hd = dims(model)
    L = model["num_hidden_layers"]
    flops = bytes_ = 0.0
    for c in contexts:
        flops += attn_flops(model, c)
        bytes_ += math.ceil(c / page) * page_bytes(model, page)
        bytes_ += 2 * h * hd * BF16                     # q in, out
    return flops * L / tp, bytes_ * L / tp


def prefill_call(model: dict, start: int, chunk: int, page: int,
                 tp: int = 1) -> Tuple[float, float]:
    """(flops, bytes) per chip of one fused paged-prefill kernel call
    over all layers: `chunk` query rows at positions [start,
    start + chunk), causal over everything before them; reads the live
    pages before the chunk, writes the chunk's pages."""
    _, h, kv, hd = dims(model)
    L = model["num_hidden_layers"]
    keys = chunk * start + chunk * (chunk + 1) // 2
    flops = attn_flops(model, keys)
    bytes_ = (start // page + chunk // page) * page_bytes(model, page)
    bytes_ += chunk * (2 * h * hd + 2 * kv * hd) * BF16   # q, out, k, v
    return flops * L / tp, bytes_ * L / tp


def model_flops(model: dict, prefill_chunks, decode_contexts,
                final_chunks: int) -> float:
    """Whole-model operations of a set of engine calls (all chips):
    2 x layer weights per prefilled and per decoded token, the head for
    each decoded token and each prompt's last token, and causal
    attention at each token's context.

    prefill_chunks: iterable of (start, chunk) chunk calls;
    decode_contexts: iterable of per-token contexts (one per active slot
    per decode step); final_chunks: chunk calls that ended a prompt."""
    L = model["num_hidden_layers"]
    w = 2 * layer_params(model) * L
    head = 2 * head_params(model)
    total = final_chunks * head
    for start, chunk in prefill_chunks:
        keys = chunk * start + chunk * (chunk + 1) // 2
        total += chunk * w + attn_flops(model, keys) * L
    for c in decode_contexts:
        total += w + head + attn_flops(model, c) * L
    return float(total)


def roofline_time(flops: float, bytes_: float, peaks: dict) -> float:
    """Least time the chip could take: compute or memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
