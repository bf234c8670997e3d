"""Mean decode-active slots per decode step over n_slots, in percent,
over the decode calls of the window (counted by the harness's wrapper of
the engine's decode cell)."""


def read(run):
    steps = run.calls.between("decode", run.t_start, run.t_end)
    if not steps:
        return None
    n = run.engine["n_slots"]
    return 100.0 * sum(len(c) for c in steps) / (len(steps) * n)
