"""Engine telemetry: host spans and the program clock.

`span(name, **stats)` is `jax.profiler.TraceAnnotation`. It records only
while a profiler trace runs (`jax.profiler.start_trace`); with the
profiler off a span with two stats costs ~1.5 us on an x86 host. The
profiler is the store and the exporter: the spans land in its
`.xplane.pb` beside the device's ops, nested by the thread that opened
them (the engine's). Stats known only inside a span go in through
`set_metadata`.

`now_ns()` is the program clock, `time.perf_counter_ns()`. Per-request
stamps (`Request.wall`) are taken on it. Each `engine.pump` span carries
`t_ns = now_ns()` at its entry, so a reader of the trace finds the
offset between this clock and the trace's as the median of
`start_ns - t_ns` over the pump spans, and places any stamp on the
device timeline through it. When the host holds a step's tokens is the
end of that step's `engine.decode.wait` span (a first token: the end of
the final `engine.prefill_chunk`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax

span = jax.profiler.TraceAnnotation


def now_ns() -> int:
    return time.perf_counter_ns()


# every span the serving engine writes, with what it covers
SPANS = {
    "engine.pump": "one engine-loop iteration (ServingEngine.pump); "
                   "stats step, t_ns (now_ns() at entry: the clock anchor)",
    "engine.admit": "a request takes a slot (bucket path: its whole "
                    "prefill and insert); stat request_id",
    "engine.prefill_chunk": "one prompt chunk: pager extend, chunk cell, "
                            "first-token read on the final chunk; stats "
                            "request_id, start, tokens, final, collectives "
                            "(cross-chip collective ops the chunk cell "
                            "runs a call, read once from its compiled "
                            "program; 0 without a mesh)",
    "engine.decode": "one decode (or speculative verify) step over the "
                     "slot batch; stats step, n_active, kv_pages (paged "
                     "cells: pages the decode kernel walks), collectives "
                     "(as engine.prefill_chunk's, of the decode or verify "
                     "cell)",
    "engine.decode.dispatch": "tail pages and COW copies, block-table "
                              "upload, the decode cell's call",
    "engine.decode.wait": "the host blocked on the device for the step's "
                          "tokens and finiteness flags",
    "engine.emit": "the token and retire loop of a step; stat n_retired",
    "pager.step": "KVPager.step, the pager's host accounting of one "
                  "step; stat step",
    "pager.touches": "the step's page reads: touch walk or expected "
                     "weights, and the predictor",
    "pager.writes": "the step's tail writes and COW splits",
    "pager.rebalance": "re-placing live pages (KVPager.rebalance); "
                       "stat pages (pages placed)",
    "substrate.drain": "reconciling the host twin with the pager's tier "
                       "map; stats n_out, n_in, n_drop (pages)",
    "engine.retire": "a request frees its slot and pages; stat "
                     "request_id",
}


@dataclasses.dataclass
class RequestWall:
    """One request's stamps on the program clock (ns). The engine writes
    them; nothing it decides reads them."""

    admitted_ns: Optional[int] = None     # slot taken
    first_chunk_ns: Optional[int] = None  # first prompt chunk (bucket
    # path: the whole prefill) dispatched
