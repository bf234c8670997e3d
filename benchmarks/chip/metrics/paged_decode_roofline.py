"""Share of its roofline the paged decode kernel reaches: the least time
of the traced calls (counts.decode_call: live pages of active slots)
over the kernel's device time, summed over the cell's chips."""

import counts
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    page = run.engine["page_tokens"]
    least = sum(counts.roofline_time(
        *counts.decode_call(run.model, ctx, page, run.tp), run.peaks)
        for ctx in run.traced("decode")) * run.chips
    ns = sum(trace_reduce.kernel_ns(trace_reduce.device(run.trace, d),
                                    "paged_decode")[0]
             for d in range(run.chips))
    return 100.0 * least / (ns / 1e9) if ns and least else None
