"""Device time of one execution of the engine's decode cell: the traced
executions of the program that holds the paged decode kernel, on device
0, over their count."""

import trace_reduce


def read(run):
    if run.trace is None:
        return None
    ns, n = trace_reduce.program_ns(trace_reduce.device(run.trace, 0),
                                    "decode")
    return ns / n / 1e6 if n else None
