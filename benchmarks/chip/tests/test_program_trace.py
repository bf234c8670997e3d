"""CPU tests of `program_trace.py` and the readers of the program's own
spans and stamps (`pager_step_ms`, `prefill_wait_p95_ms`): the clock
offset, the idle attribution and the span table on a slice of a trace
recorded on the chip, the readers by hand, and traced rehearsals of each
cell. The slice (`testdata/`) holds, for a few engine steps of a traced
`smollm-360m.chat` run, the window, the program spans that start in it
(`program_trace.read_spans` rows) and the device's merged busy
intervals as ops: {"window": [t0, t1], "spans": [...], "device":
{"ops": [["busy", start, length], ...]}}.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import program_trace  # noqa: E402

TRACE = os.path.join(BENCH, "testdata",
                     "smollm-360m.chat.program_trace.json.gz")


@pytest.fixture(scope="module")
def chip_spans():
    with gzip.open(TRACE, "rt") as f:
        return json.load(f)


def test_clock_offset_is_steady_on_the_chip(chip_spans):
    off, spread, n = program_trace.offset_ns(chip_spans)
    assert n >= 3
    assert spread <= 100e3                          # ns
    # each pump span's start, less the offset, is its own t_ns
    for name, s, _, st in chip_spans["spans"]:
        if name == "engine.pump":
            assert abs(s - off - st["t_ns"]) <= spread


def test_idle_time_goes_to_program_spans_on_the_chip(chip_spans):
    dev = chip_spans["device"]
    t0, t1 = chip_spans["window"]
    busy = sum(d for _, _, d in dev["ops"])
    got = program_trace.idle_by_span(chip_spans, dev)
    assert sum(s for _, s in got) == pytest.approx((t1 - t0 - busy) / 1e9,
                                                   rel=1e-6)
    assert all(n.startswith(program_trace.PROGRAM)
               or n == program_trace.NO_SPAN for n, _ in got)
    assert program_trace.covered_share(got) >= 0.9
    assert "pager.rebalance" in dict(got)          # the step's host work


def test_spans_nest_by_request_and_step_on_the_chip(chip_spans):
    spans = chip_spans["spans"]
    steps = [s for s in spans if s[0] == "pager.step"]
    decodes = [s for s in spans if s[0] == "engine.decode"]
    assert steps and decodes
    for _, s, d, _ in steps:
        assert any(ds <= s and s + d <= ds + dd for _, ds, dd, _ in decodes)
    ids = {st["request_id"] for n, _, _, st in spans
           if n == "engine.prefill_chunk"}
    assert ids


def test_span_table_and_request_phases_on_the_chip(chip_spans):
    table = program_trace.span_table(chip_spans)
    assert table["engine.decode"]["n_active"]["mean"] > 0
    assert table["pager.rebalance"]["pages"]["mean"] > 0
    assert table["engine.prefill_chunk"]["tokens"]["sum"] > 0
    assert "request_id" not in table["engine.admit"]
    assert set(program_trace.request_phases(chip_spans)) == {
        "wait", "prefill", "decode"}


def test_idle_attribution_by_hand():
    """Window [0, 100) ns, ops over [0, 5), [40, 90) and [95, 125): idle
    [5, 40) and [90, 95). `pager.step` [10, 30) inside `engine.pump`
    [0, 110), which starts at t_ns 7: the first gap is 20 ns of
    `pager.step` and 15 of `engine.pump`, the last `engine.pump`'s. Cut
    short at 60, the pump leaves the last gap to no span."""
    spans = {"window": [0, 100],
             "spans": [["engine.pump", 0, 110, {"t_ns": 7}],
                       ["pager.step", 10, 20, {}]]}
    dev = {"ops": [["a", 0, 5], ["b", 40, 50], ["c", 95, 30]]}
    got = dict(program_trace.idle_by_span(spans, dev))
    assert got == pytest.approx({"pager.step": 20e-9, "engine.pump": 20e-9})
    assert program_trace.offset_ns(spans) == (-7, 0, 1)
    # idle outside every program span goes to no span
    spans["spans"][0][2] = 60
    got = dict(program_trace.idle_by_span(spans, dev))
    assert got == pytest.approx({"pager.step": 20e-9, "engine.pump": 15e-9,
                                 program_trace.NO_SPAN: 5e-9})
    assert program_trace.covered_share(list(got.items())) == pytest.approx(
        35 / 40)


def test_span_table_and_request_phases_by_hand():
    """Request 7: admitted at 0, chunks at 10 and 30 (the second final,
    ending at 45), retired at 100; request 8 admitted at 50, no chunk
    yet. The identifiers and the clock are not summed."""
    spans = {"window": [0, 200], "spans": [
        ["engine.admit", 0, 1, {"request_id": 7}],
        ["engine.prefill_chunk", 10, 5, {"request_id": 7, "start": 0,
                                         "tokens": 16, "final": 0}],
        ["engine.prefill_chunk", 30, 15, {"request_id": 7, "start": 16,
                                          "tokens": 16, "final": 1}],
        ["engine.admit", 50, 1, {"request_id": 8}],
        ["engine.decode", 60, 20, {"step": 3, "n_active": 1}],
        ["engine.decode", 80, 10, {"step": 4, "n_active": 2}],
        ["engine.retire", 100, 2, {"request_id": 7}]]}
    table = program_trace.span_table(spans)
    assert table["engine.prefill_chunk"]["count"] == 2
    assert table["engine.prefill_chunk"]["mean_ms"] == pytest.approx(1e-5)
    assert table["engine.prefill_chunk"]["tokens"] == {"sum": 32,
                                                       "mean": 16}
    assert table["engine.prefill_chunk"]["final"]["sum"] == 1
    assert table["engine.decode"]["n_active"] == {"sum": 3, "mean": 1.5}
    assert set(table["engine.decode"]) == {"count", "mean_ms", "total_s",
                                           "n_active"}
    got = program_trace.request_phases(spans)
    assert got["wait"] == {"n": 1, "p50_ms": 1e-5, "p95_ms": 1e-5}
    assert got["prefill"]["p50_ms"] == pytest.approx(35e-6)
    assert got["decode"]["p50_ms"] == pytest.approx(55e-6)


def _rec(due, admitted_ns=None, first_chunk_ns=None, wall=True):
    req = types.SimpleNamespace()
    if wall:
        req.wall = types.SimpleNamespace(admitted_ns=admitted_ns,
                                         first_chunk_ns=first_chunk_ns)
    return harness.Rec(req=req, due=due)


def _run(records, t_start=10.0, t_end=20.0, trace=None):
    return types.SimpleNamespace(
        records=records, t_start=t_start, t_end=t_end, trace=trace,
        due_in_window=lambda: [r for r in records
                               if t_start <= r.due < t_end])


def test_prefill_wait_by_hand():
    """Waits of 1 s, 2 s and, for one still without a chunk at the
    window's end (20 s), its age then: 3 s. Due before the window, and
    admitted after its end, do not count."""
    read = harness.metric_reader("prefill_wait_p95_ms")
    s = 1e9
    recs = [_rec(11.0, 11 * s, 12 * s), _rec(12.0, 12 * s, 14 * s),
            _rec(13.0, 17 * s), _rec(5.0, 5 * s, 15 * s),
            _rec(19.0, 21 * s, 22 * s)]
    assert read(_run(recs)) == pytest.approx(
        1e3 * (2 + 0.9 * (3 - 2)))                  # p95 of 1, 2, 3 s


def test_readers_read_none_without_the_programs_telemetry():
    """An older program: no `Request.wall`, no spans in the trace."""
    run = _run([_rec(11.0, wall=False)])
    assert harness.metric_reader("prefill_wait_p95_ms")(run) is None
    assert harness.metric_reader("pager_step_ms")(run) is None
    assert program_trace.offset_ns({"window": [0, 1], "spans": []}) is None
    assert program_trace.admission_waits(run) == []


REHEARSE = """
import json, sys
sys.path.insert(0, "benchmarks/chip/tests")
import rehearsal
import harness, program_trace
seen = {{}}
per_layer = harness.per_layer
def spy(data):
    seen["waits"] = program_trace.admission_waits(data)
    return per_layer(data)
harness.per_layer = spy
out = rehearsal.run({name!r}, seed={seed}, seconds=3.0, trace=True)
rep = program_trace.report(harness.TRACE_DIR)
print(json.dumps({{"metrics": out["metrics"], "correct": out["correct"],
                  "waits": seen["waits"], "pumps": rep["pump_spans"]}}))
"""


@pytest.mark.parametrize("name", ["smollm-360m.chat", "smollm-360m.fullctx"])
def test_traced_rehearsal_reads_the_programs_metrics(name):
    """A traced run on a reduced model on the CPU: both readers read,
    and the program's admission stamp never comes after the harness's
    stamp of the same request (which waits for the pump to return)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    r = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(name=name, seed=2**33 + 17)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["correct"] is True
    for m in ("prefill_wait_p95_ms", "pager_step_ms"):
        assert got["metrics"][m]["value"] > 0
    assert got["pumps"] > 0
    assert got["waits"]
    for program, bench in got["waits"]:
        assert program <= bench
