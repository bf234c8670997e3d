"""Mean device time of one cross-chip collective in the engine's decode
step, in microseconds: the collective ops' time inside the traced
decode programs on device 0, over the collectives those programs ran
(their executions times the `collectives` stat of the program's
`engine.decode` spans, read from the compiled decode cell). None where
the program writes no such stat (an older checkout) or runs none (no
mesh)."""

import collective_ops
import program_trace
import trace_reduce


def read(run):
    spans = program_trace.of_run(run)
    if spans is None:
        return None
    per_step = [st["collectives"] for n, _, _, st in spans["spans"]
                if n == "engine.decode" and "collectives" in st]
    if not per_step or not sum(per_step):
        return None
    dev = trace_reduce.device(run.trace, 0)
    kinds = trace_reduce.program_kinds(dev)
    progs = [m for m in dev["modules"] if kinds.get(m[0]) == "decode"]
    if not progs:
        return None
    ns = sum(e[2] for e in collective_ops.inside(collective_ops.ops(dev),
                                                 progs))
    ran = len(progs) * sum(per_step) / len(per_step)
    return ns / ran / 1e3
