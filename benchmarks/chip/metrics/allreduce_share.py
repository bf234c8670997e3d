"""Share of device 0's busy time in the traced window that cross-chip
collective ops cover (`collective_ops.COLLECTIVE`: all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and their
async and fused forms), in percent; the intervals are merged, so a
collective that overlaps compute counts once."""

import collective_ops
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    dev = trace_reduce.device(run.trace, 0)
    window = trace_reduce.window_ns(run.trace)
    busy = trace_reduce.busy_ns(dev, window)
    coll = trace_reduce.busy_ns({"ops": collective_ops.ops(dev)}, window)
    return 100.0 * coll / busy if busy else None
