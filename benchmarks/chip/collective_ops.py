"""Which device ops of a trace are cross-chip collectives.

The pattern is the benchmark's own, taken from the op names the TPU
trace shows: XLA names a collective after its HLO opcode ("all-reduce.6",
"all-gather.2" on a v5e-4 host today), and an asynchronous one as a
"-start" / "-done" pair ("all-reduce-start.3"); fused forms keep the
opcode as their prefix ("all-reduce-scatter-fusion.1").
"""

from __future__ import annotations

import bisect
import re
from typing import List

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|collective-permute)[\w-]*(\.\d+)?$")


def ops(dev: dict) -> List[list]:
    """The collective op events of one device (trace_reduce form)."""
    return [e for e in dev["ops"] if COLLECTIVE.match(e[0])]


def inside(events: List[list], programs: List[list]) -> List[list]:
    """The events that start inside one of the program executions."""
    progs = sorted(programs, key=lambda m: m[1])
    starts = [m[1] for m in progs]
    out = []
    for e in events:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < progs[i][1] + progs[i][2]:
            out.append(e)
    return out
