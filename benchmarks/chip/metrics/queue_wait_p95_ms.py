"""95th percentile of the wait from due (open loop) or sent (closed
loop) to slot taken, over the requests due in the window; one still
waiting at the window's end counts at its age then. Harness wall clock."""

import numpy as np


def read(run):
    waits = [(r.admitted if r.admitted is not None and r.admitted <= run.t_end
              else run.t_end) - r.due for r in run.due_in_window()]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
