"""Share of its roofline the fused paged-prefill kernel reaches: the
least time of the traced chunk calls (counts.prefill_call: each chunk's
live causal context) over the kernel's device time, summed over the
cell's chips."""

import counts
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    page = run.engine["page_tokens"]
    least = sum(counts.roofline_time(
        *counts.prefill_call(run.model, s, c, page, run.tp), run.peaks)
        for s, c, _ in run.traced("chunk")) * run.chips
    ns = sum(trace_reduce.kernel_ns(trace_reduce.device(run.trace, d),
                                    "paged_prefill")[0]
             for d in range(run.chips))
    return 100.0 * least / (ns / 1e9) if ns and least else None
