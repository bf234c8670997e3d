"""1 - (union of device-op intervals / traced window), averaged over the
cell's chips, in percent."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    t0, t1 = trace_reduce.window_ns(run.trace)
    busy = [trace_reduce.busy_ns(trace_reduce.device(run.trace, d),
                                 (t0, t1))
            for d in range(run.chips)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))
