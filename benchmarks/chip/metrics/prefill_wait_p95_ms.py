"""95th percentile of the wait from slot taken to the first prompt chunk
dispatched (the program's stamps `Request.wall.admitted_ns` and
`first_chunk_ns`, on the harness's clock), over the requests due in the
window that took a slot before its end; one with no chunk by the
window's end counts at its age then."""

import numpy as np


def read(run):
    end = run.t_end * 1e9
    waits = []
    for r in run.due_in_window():
        wall = getattr(r.req, "wall", None)
        if wall is None or wall.admitted_ns is None or wall.admitted_ns > end:
            continue
        first = wall.first_chunk_ns
        waits.append((first if first is not None and first <= end else end)
                     - wall.admitted_ns)
    return float(np.percentile(waits, 95)) / 1e6 if waits else None
