"""CPU tests of the tp cell's readers, `allreduce_share` and
`decode_collective_us`, on a trace written by hand in the reduced form
(`trace_reduce`) with the op names that the decode and chunk cells of
mistral_nemo_12b show when compiled for a v5e:2x2 (`all-reduce.6`,
`all-gather.2`, the kernels `paged_flash_decode.1` and
`paged_prefill_insert_flash_q8.1`), and the program's spans with their
`collectives` stat.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import collective_ops  # noqa: E402
import harness  # noqa: E402
import program_trace  # noqa: E402

US = 1000                      # the trace counts nanoseconds


def _trace():
    """Window [0, 3000) us on device 0. Decode programs run [100, 1000)
    and [2000, 2900), a chunk program [1100, 1900). Collectives: in the
    first decode all-reduces of 20 and 30 us and an all-gather of 5; in
    the chunk an all-reduce of 100; in the second decode an async pair,
    -start [2300, 2350) under a fusion [2320, 2400) and -done [2400,
    2410); a last all-reduce [2950, 3050) runs past the window."""
    ops = [["paged_flash_decode.1", 150, 100], ["all-reduce.6", 300, 20],
           ["all-reduce.7", 400, 30], ["fusion.3", 430, 170],
           ["all-gather.2", 900, 5],
           ["paged_prefill_insert_flash_q8.1", 1200, 300],
           ["all-reduce.9", 1600, 100],
           ["paged_flash_decode.1", 2100, 100],
           ["all-reduce-start.3", 2300, 50], ["fusion.4", 2320, 80],
           ["all-reduce-done.3", 2400, 10], ["all-reduce.2", 2950, 100]]
    mods = [["jit_decode_cell(1)", 100, 900], ["jit_chunk_cell(2)", 1100, 800],
            ["jit_decode_cell(1)", 2000, 900]]
    dev = {"ops": [[n, s * US, d * US] for n, s, d in ops],
           "modules": [[n, s * US, d * US] for n, s, d in mods]}
    return {"devices": {str(i): dev for i in range(4)},
            "host": [["bench.window", 0, 3000 * US]]}


def _spans(per_step=84):
    return {"window": [0, 3000 * US], "spans": [
        ["engine.decode", 90 * US, 950 * US,
         {"step": 1, "n_active": 8, "collectives": per_step}],
        ["engine.prefill_chunk", 1050 * US, 900 * US,
         {"request_id": 3, "start": 0, "tokens": 512, "final": 0,
          "collectives": 83}],
        ["engine.decode", 1990 * US, 950 * US,
         {"step": 2, "n_active": 8, "collectives": per_step}]]}


def _run(trace):
    return types.SimpleNamespace(trace=trace, chips=4)


def test_collective_names():
    for name in ("all-reduce.6", "all-gather.2", "all-reduce-start.3",
                 "all-reduce-done.3", "reduce-scatter.1", "all-to-all",
                 "collective-permute-start.4", "all-reduce-scatter-fusion.1"):
        assert collective_ops.COLLECTIVE.match(name), name
    for name in ("fusion.3", "paged_flash_decode.1", "copy.5", "reduce.3",
                 "while.25", "all_reduce_sum"):
        assert not collective_ops.COLLECTIVE.match(name), name


def test_allreduce_share_by_hand():
    """Busy: 100 + 20 + 30 + 170 + 5 + 300 + 100 + 100 + [2300, 2410)
    merged (110) + the last all-reduce cut at the window (50) = 985 us.
    Collectives: 20 + 30 + 5 + 100 + 50 + 10 + 50 = 265 us."""
    got = harness.metric_reader("allreduce_share")(_run(_trace()))
    assert got == pytest.approx(100 * 265 / 985)


def test_decode_collective_us_by_hand(monkeypatch):
    """Inside the two decode programs: 20 + 30 + 5 and 50 + 10 us of
    collectives (the chunk's and the one past the second program do
    not count), over 2 executions x 84 collectives a step."""
    monkeypatch.setattr(program_trace, "of_run", lambda run: _spans())
    got = harness.metric_reader("decode_collective_us")(_run(_trace()))
    assert got == pytest.approx((55 + 60) / (2 * 84))


def test_readers_read_none_without_a_trace_or_the_stat(monkeypatch):
    share = harness.metric_reader("allreduce_share")
    per = harness.metric_reader("decode_collective_us")
    assert share(_run(None)) is None
    monkeypatch.setattr(program_trace, "of_run", lambda run: None)
    assert per(_run(None)) is None
    # an older program: spans without the stat; a meshless one: 0
    old = _spans()
    for _, _, _, st in old["spans"]:
        del st["collectives"]
    monkeypatch.setattr(program_trace, "of_run", lambda run: old)
    assert per(_run(_trace())) is None
    monkeypatch.setattr(program_trace, "of_run", lambda run: _spans(0))
    assert per(_run(_trace())) is None
