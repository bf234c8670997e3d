"""Mean host time of the pager's accounting of one engine step: the
program's own `pager.step` spans (`serving.kv_pager`) in the traced
window, over their count."""

import program_trace


def read(run):
    spans = program_trace.of_run(run)
    if spans is None:
        return None
    d = [d for n, _, d, _ in spans["spans"] if n == "pager.step"]
    return sum(d) / len(d) / 1e6 if d else None
