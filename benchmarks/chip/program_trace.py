"""The serving program's own spans in a run's profiler trace, and where
the device's idle time goes by them.

The program writes host spans (`jax.profiler.TraceAnnotation`) at its
layer boundaries, named "engine.*", "pager.*" and "substrate.*", with
stats. Each "engine.pump" span carries `t_ns`, the program clock
(`time.perf_counter_ns()`, the harness's clock too) at its entry. This
module reads those spans from the run's `.xplane.pb`, inside the
"bench.window" span where there is one (once per file), and gives:

* `offset_ns`: the trace's clock minus the program clock, the median of
  `start - t_ns` over the pump spans, with its spread (max - min). A
  program stamp (`Request.wall`) or a harness stamp plus the offset is a
  time on the device timeline;
* `idle_by_span`: the device's idle time in the window, cut at the
  program spans' edges, each piece to the innermost span over it
  ("host.other" where none);
* `span_table` and `request_phases`: each span's count, length and
  stats, and each request's wait, prefill and decode inside the engine.

A program that writes no such spans (an older checkout) gives none, and
every reader of them reads None.

    python3 benchmarks/chip/program_trace.py <trace dir>

prints, for the newest trace under the directory, the offset and its
spread, the idle breakdown by program span, the span table and the
request phases: what an operator reads to see where the host holds the
device back.
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

PROGRAM = ("engine.", "pager.", "substrate.")   # the program's span names
WINDOW = "bench.window"
NO_SPAN = "host.other"
IDENTS = ("request_id", "start", "step", "t_ns")  # stats that count nothing


# ---------------------------------------------------------------- reading
def read_spans(path: str) -> dict:
    """The program spans of one `.xplane.pb` that overlap its window:
    {"window": [t0, t1], "spans": [[name, start, duration, stats]]},
    times in the trace's nanoseconds. Parsed once per file."""
    return _read(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime: float) -> dict:
    from jax.profiler import ProfileData

    found, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = [int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns)]
                elif ev.name.startswith(PROGRAM):
                    found.append([ev.name, int(ev.start_ns),
                                  int(ev.duration_ns),
                                  {k: v for k, v in ev.stats}])
    if window is None:
        ends = [s + d for _, s, d, _ in found]
        window = [min((s for _, s, _, _ in found), default=0),
                  max(ends, default=0)]
    t0, t1 = window
    return {"window": window,
            "spans": sorted((e for e in found
                             if e[1] < t1 and e[1] + e[2] > t0),
                            key=lambda e: e[1])}


def of_run(run) -> Optional[dict]:
    """The program spans of a traced run (`harness.RunData`), or None."""
    import harness

    if run.trace is None:
        return None
    path = trace_reduce.newest_xplane(harness.TRACE_DIR)
    return read_spans(path) if path is not None else None


# -------------------------------------------------------------- the clock
def offset_ns(spans: dict) -> Optional[Tuple[float, float, int]]:
    """(median offset, spread, pump spans): the trace's clock minus the
    program's, from each pump span's start and its `t_ns` stat."""
    offs = [s - st["t_ns"] for n, s, _, st in spans["spans"]
            if n == "engine.pump" and "t_ns" in st]
    if not offs:
        return None
    return statistics.median(offs), max(offs) - min(offs), len(offs)


# -------------------------------------------------------- idle attribution
def idle_by_span(spans: dict, dev: dict) -> List[list]:
    """Device idle time inside the window, cut at the program spans'
    edges, each piece to the innermost span over it ("host.other" where
    none): [[span, seconds]], largest first."""
    t0, t1 = spans["window"]
    ivs = [(s, s + d, n) for n, s, d, _ in spans["spans"]]
    cuts = sorted({t0, t1} | {x for s, e, _ in ivs for x in (s, e)
                              if t0 < x < t1})
    busy: List[list] = []                   # disjoint, in time order
    for s, e in sorted((max(e[1], t0), min(e[1] + e[2], t1))
                       for e in dev["ops"]
                       if e[1] < t1 and e[1] + e[2] > t0):
        if busy and s <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], e)
        else:
            busy.append([s, e])
    tot: Dict[str, int] = {}
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        idle, k = b - a, j
        while k < len(busy) and busy[k][0] < b:
            idle -= min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
        if idle <= 0:
            continue
        over = [iv for iv in ivs if iv[0] <= a and b <= iv[1]]
        label = (min(over, key=lambda iv: iv[1] - iv[0])[2] if over
                 else NO_SPAN)
        tot[label] = tot.get(label, 0) + idle
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])]


def covered_share(breakdown: List[list]) -> Optional[float]:
    """Share of the idle time that some program span covers."""
    total = sum(s for _, s in breakdown)
    if not total:
        return None
    return 1.0 - sum(s for n, s in breakdown if n == NO_SPAN) / total


# ------------------------------------------------------- the spans' stats
def span_table(spans: dict) -> Dict[str, dict]:
    """Each span name's count, mean and total length, and for each of
    its count stats (all but the identifiers and the clock) the sum and
    the mean over its spans: `engine.decode`'s mean `n_active` is the
    batch the decode cell ran, `substrate.drain`'s sums the pages moved,
    `pager.rebalance`'s mean `pages` what its time grows with."""
    table: Dict[str, dict] = {}
    for n, _, d, st in spans["spans"]:
        row = table.setdefault(n, {"count": 0, "total_ns": 0, "stats": {}})
        row["count"] += 1
        row["total_ns"] += d
        for k, v in st.items():
            if k not in IDENTS:
                row["stats"][k] = row["stats"].get(k, 0) + v
    return {n: {"count": r["count"],
                "mean_ms": r["total_ns"] / r["count"] / 1e6,
                "total_s": r["total_ns"] / 1e9,
                **{k: {"sum": v, "mean": v / r["count"]}
                   for k, v in sorted(r["stats"].items())}}
            for n, r in sorted(table.items())}


def request_phases(spans: dict) -> Dict[str, dict]:
    """A request's time in the engine from its spans, tied by
    `request_id`: "wait" from `engine.admit` to its first chunk (the
    `engine.prefill_chunk` at `start` 0), "prefill" from there to the
    end of its `final` chunk (the host holds its first token), "decode"
    from there to `engine.retire`. For each phase that began and ended
    inside the window: the count, median and p95, in ms."""
    marks: Dict[int, Dict[str, int]] = {}
    for n, s, d, st in spans["spans"]:
        if "request_id" not in st:
            continue
        m = marks.setdefault(st["request_id"], {})
        if n == "engine.admit":
            m["admit"] = s
        elif n == "engine.prefill_chunk":
            if st["start"] == 0:
                m["first_chunk"] = s
            if st["final"]:
                m["first_token"] = s + d
        elif n == "engine.retire":
            m["retire"] = s
    out = {}
    for phase, a, b in (("wait", "admit", "first_chunk"),
                        ("prefill", "first_chunk", "first_token"),
                        ("decode", "first_token", "retire")):
        ms = [(m[b] - m[a]) / 1e6 for m in marks.values()
              if a in m and b in m]
        out[phase] = {"n": len(ms),
                      "p50_ms": float(np.median(ms)) if ms else None,
                      "p95_ms": float(np.percentile(ms, 95)) if ms else None}
    return out


# ---------------------------------------------------- per-request stamps
def admission_waits(run) -> List[Tuple[float, float]]:
    """(program, harness) wait from due to slot taken, in seconds, for
    each request due in the window that took a slot: the program's
    `Request.wall.admitted_ns` against the harness's stamp after the
    pump that admitted it."""
    out = []
    for r in run.due_in_window():
        wall = getattr(r.req, "wall", None)
        if wall is None or wall.admitted_ns is None or r.admitted is None:
            continue
        out.append((wall.admitted_ns / 1e9 - r.due, r.admitted - r.due))
    return out


# --------------------------------------------------------------------- CLI
def report(trace_dir: str) -> Optional[Dict[str, object]]:
    """The operator's view of the newest trace under `trace_dir`, or None
    where there is no trace or the program wrote no spans in it."""
    path = trace_reduce.newest_xplane(trace_dir)
    spans = read_spans(path) if path is not None else None
    if not spans or not spans["spans"]:
        return None
    dev = trace_reduce.device(trace_reduce.load_xplane(path), 0)
    t0, t1 = spans["window"]
    breakdown = idle_by_span(spans, dev)
    off = offset_ns(spans)
    return {
        "trace": path,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": trace_reduce.busy_ns(dev, (t0, t1)) / 1e9,
        "offset_ns": None if off is None else off[0],
        "offset_spread_ns": None if off is None else off[1],
        "pump_spans": 0 if off is None else off[2],
        "idle_by_span": breakdown,
        "idle_covered_share": covered_share(breakdown),
        "spans": span_table(spans),
        "requests": request_phases(spans),
    }


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit("usage: program_trace.py <trace dir>")
    got = report(sys.argv[1])
    if got is None:
        raise SystemExit(f"{sys.argv[1]}: no trace with the program's spans")
    print(json.dumps(got, indent=1))
