"""Reduce a profiler trace to the events the per-layer metrics read.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` wrote and keeps
three kinds of event, on one clock (nanoseconds):

* device ops: the "XLA Ops" line of every "/device:TPU:<n>" plane, by
  the op's HLO name;
* device programs: the "XLA Modules" line (one event per execution of a
  compiled program);
* host spans: the benchmark's own `TraceAnnotation`s (names "bench.*")
  on the host plane.

`trim` / `save` / `load` keep that reduced form as JSON, which is what
the committed test trace holds. The matching rules below (which op is
which kernel, which is a collective) are the benchmark's own, taken from
the names the trace shows today.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

# kernel -> pattern over device op names (a Mosaic custom call is named
# after the kernel function: "paged_flash_decode.8",
# "paged_prefill_insert_flash_q8.7" on TPU v5e today)
KERNELS = {
    "paged_decode": re.compile(r"^paged_flash_decode(\.\d+)?$"),
    "paged_prefill": re.compile(r"^paged_prefill\w*(\.\d+)?$"),
}
# ops that contain other ops (a scanned layer stack is one "while"):
# their time is their body's, so they are not listed among top ops
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPAN = "bench."


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def op_name(text: str) -> str:
    """An op event's HLO name: TPU traces name an op event by its whole
    HLO instruction ("%copy.155 = s8[...] copy(...)")."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """The reduced trace of one `.xplane.pb`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    cpu_ops: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
                    elif _stat(ev, "hlo_module") is not None:
                        # XLA:CPU runs its ops on host threads: the CPU
                        # rehearsal reads them as device 0
                        cpu_ops.append([op_name(ev.name), int(ev.start_ns),
                                        int(ev.duration_ns)])
    # only the "bench.window" stretch is kept, where there is one
    t0, t1 = next(((s, s + d) for n, s, d in host if n == "bench.window"),
                  (0, 1 << 62))
    inside = lambda ev: t0 <= ev.start_ns < t1  # noqa: E731
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == "XLA Ops":
                dev["ops"] = [[op_name(ev.name), int(ev.start_ns),
                               int(ev.duration_ns)]
                              for ev in line.events if inside(ev)]
            elif line.name == "XLA Modules":
                dev["modules"] = [[ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)]
                                  for ev in line.events if inside(ev)]
        devices[m.group(1)] = dev
    if not devices and cpu_ops:
        devices["0"] = {"ops": [e for e in cpu_ops if t0 <= e[1] < t1],
                        "modules": []}
    return {"devices": devices, "host": [e for e in host
                                         if e[0] == "bench.window"
                                         or t0 <= e[1] < t1]}


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def trim(trace: dict, t0: int, t1: int, devices=None) -> dict:
    """Only the events that start in [t0, t1), of the given devices; the
    "bench.window" span is cut to [t0, t1)."""
    keep = lambda evs: [e for e in evs if t0 <= e[1] < t1]  # noqa: E731
    host = [e if e[0] != "bench.window" else
            [e[0], max(e[1], t0), min(e[1] + e[2], t1) - max(e[1], t0)]
            for e in trace["host"]]
    return {
        "devices": {d: {"ops": keep(v["ops"]), "modules": keep(v["modules"])}
                    for d, v in trace["devices"].items()
                    if devices is None or d in devices},
        "host": [e for e in host if e[0] == "bench.window"] + keep(
            [e for e in host if e[0] != "bench.window"]),
    }


# ------------------------------------------------------------- reductions
def union(intervals: List[Tuple[int, int]]) -> int:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def device(trace: dict, i: int) -> dict:
    """The i-th device of the trace, by device id."""
    return trace["devices"][sorted(trace["devices"], key=int)[i]]


def busy_ns(dev: dict, window=None) -> int:
    """Nanoseconds in which some op ran on the device (inside `window`,
    a (t0, t1) pair, where given)."""
    ivs = [(e[1], e[1] + e[2]) for e in dev["ops"]]
    if window is not None:
        t0, t1 = window
        ivs = [(max(s, t0), min(e, t1)) for s, e in ivs if e > t0 and s < t1]
    return union(ivs)


def window_ns(trace: dict) -> Tuple[int, int]:
    """The traced window: the span of the benchmark's own "bench.window"
    host span where there is one, else of all events."""
    for name, s, d in trace["host"]:
        if name == "bench.window":
            return s, s + d
    pts = [(e[1], e[1] + e[2]) for v in trace["devices"].values()
           for e in v["ops"]] + [(s, s + d) for _, s, d in trace["host"]]
    return min(p[0] for p in pts), max(p[1] for p in pts)


def kernel_ns(dev: dict, kernel: str) -> Tuple[int, int]:
    """(device ns, calls) of one kernel's events on one device."""
    pat = KERNELS[kernel]
    evs = [e for e in dev["ops"] if pat.match(e[0])]
    return sum(e[2] for e in evs), len(evs)


def program_kinds(dev: dict) -> Dict[str, str]:
    """program name -> "decode" | "chunk", by the kernel that runs inside
    the program's executions (the engine's decode and chunk cells are
    both jitted functions named `cell`: "jit_cell(<fingerprint>)")."""
    mods = sorted(dev["modules"], key=lambda e: e[1])
    starts = [m[1] for m in mods]
    kinds: Dict[str, str] = {}
    for name, s, _ in dev["ops"]:
        kind = ("decode" if KERNELS["paged_decode"].match(name) else
                "chunk" if KERNELS["paged_prefill"].match(name) else None)
        if kind is None:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            kinds.setdefault(mods[i][0], kind)
    return kinds


def program_ns(dev: dict, kind: str) -> Tuple[int, int]:
    """(device ns, executions) of the programs of one kind."""
    kinds = program_kinds(dev)
    evs = [e for e in dev["modules"] if kinds.get(e[0]) == kind]
    return sum(e[2] for e in evs), len(evs)


def top_ops(dev: dict, n: int = 10) -> List[list]:
    """The n op names that took most device time: [[name, seconds]]."""
    tot: Dict[str, int] = {}
    for e in dev["ops"]:
        if CONTAINER.match(e[0]):
            continue
        key = re.sub(r"\.\d+$", "", e[0])
        tot[key] = tot.get(key, 0) + e[2]
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, dev: dict, n: int = 10) -> List[list]:
    """Device idle time inside the window, summed by what the host was
    doing (the innermost "bench.*" span over the gap's midpoint):
    [[host span, seconds]], largest first."""
    t0, t1 = window_ns(trace)
    spans = sorted((s, s + d, name) for name, s, d in trace["host"]
                   if name != "bench.window")
    ivs = sorted((e[1], e[1] + e[2]) for e in dev["ops"])
    gaps, cur = [], t0
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    tot: Dict[str, int] = {}
    for gs, ge in gaps:
        if ge <= gs:
            continue
        mid = (gs + ge) // 2
        inner = [sp for sp in spans if sp[0] <= mid < sp[1]]
        label = (min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner
                 else "host.other")
        tot[label] = tot.get(label, 0) + (ge - gs)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

