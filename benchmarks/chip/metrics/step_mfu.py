"""Whole-step share of the chips' bf16 peak over the traced window: the
model operations of the decode and chunk calls made in it
(counts.model_flops) over (traced seconds x chips x peak)."""

import counts
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    chunks = run.traced("chunk")
    decodes = run.traced("decode")
    if not chunks and not decodes:
        return None
    flops = counts.model_flops(
        run.model, [(s, c) for s, c, _ in chunks],
        [x for ctx in decodes for x in ctx],
        sum(1 for _, _, final in chunks if final))
    t0, t1 = trace_reduce.window_ns(run.trace)
    peak = run.peaks["bf16_flops_per_s"] * run.chips * (t1 - t0) / 1e9
    return 100.0 * flops / peak
