"""The statically-schedulable end of the prefetch spectrum (formerly
`runtime/prefetch.py`, now the `static` corner of the predictor zoo).

`scan_with_prefetch` runs a lax.scan over stacked layer params where the
pool-resident leaves are streamed host->device one layer AHEAD of use
(double buffer in the scan carry): XLA emits async copy-start/copy-done
pairs whose transfer overlaps the previous layer's compute, exactly like a
HW prefetcher hides CXL latency. Accuracy is structurally 100% (the layer
schedule is static); coverage is min(1, t_layer_compute / t_layer_transfer).

`layer_stream_trace` emits the same schedule as an `AccessTrace`, so the
`StaticSchedulePredictor` scores 1.0 accuracy/coverage through the SAME
`PrefetchEngine` that scores the dynamic predictors — the subsystem's
three-level mapping (trace -> predictor -> engine) covers the static case
as its trivially-predictable corner instead of special-casing it.

On backends without internal memory-kind transfers (XLA:CPU — see
runtime/capability.py) the transfer is an identity and the scan reduces to
a plain lax.scan, so the same code path runs everywhere.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.prefetch.trace import AccessTrace
from repro.runtime import capability


def to_device(x):
    if capability.supports_internal_transfer():
        return jax.device_put(x, jax.memory.Space.Device)
    return x


def scan_with_prefetch(
    body: Callable,
    carry,
    stacked_params,
    pool_mask,
    n_layers: int,
):
    """lax.scan over layers with layer-ahead prefetch of pooled leaves.

    body(carry, layer_params) -> (carry, out)
    pool_mask: pytree of bools matching stacked_params — True leaves are
    pool-resident and get the double-buffer treatment.
    """

    def slice_layer(i):
        return jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, i, 0, keepdims=False),
            stacked_params,
        )

    def fetch(layer, i):
        # transfer pooled leaves of layer i+? to device
        return jax.tree.map(
            lambda leaf, pooled: to_device(leaf) if pooled else leaf,
            layer, pool_mask,
        )

    first = fetch(slice_layer(0), 0)

    def step(state, i):
        carry, buf = state
        # kick off the NEXT layer's transfer before computing this one —
        # XLA schedules the copy concurrently with body()'s compute
        nxt = jnp.minimum(i + 1, n_layers - 1)
        next_buf = fetch(slice_layer(nxt), nxt)
        carry, out = body(carry, buf)
        return (carry, next_buf), out

    (carry, _), outs = jax.lax.scan(
        step, (carry, first), jnp.arange(n_layers)
    )
    return carry, outs


def layer_stream_trace(n_layers: int = 24, pages_per_layer: int = 8,
                       epochs: int = 4,
                       page_bytes: float = 1 << 20) -> AccessTrace:
    """The lax.scan layer stream as an `AccessTrace`: step i touches all
    pages of layer (i mod n_layers). Fully schedulable — the `static`
    predictor's home turf, and the structural-accuracy-1 lane of
    `benchmarks/bench_prefetch.py`."""
    steps = [
        [(i % n_layers) * pages_per_layer + p
         for p in range(pages_per_layer)]
        for i in range(n_layers * epochs)
    ]
    return AccessTrace(
        f"layer_stream_L{n_layers}", "layer", page_bytes,
        n_layers * pages_per_layer, steps,
    ).validate()
