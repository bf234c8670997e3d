"""CPU tests of the on-chip benchmark: the counts, the trace reduction on
a trace recorded on the chip, the traffic generator, the data-driven
layout, and rehearsals of whole runs on reduced models.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import counts  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _conf(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------------ counts
def test_decode_counts_by_hand():
    """One paged-decode call of smollm-360m: two active slots with 100
    and 17 live tokens, pages of 16. Per layer and slot: 4*H*hd*ctx
    flops; ceil(ctx/16) pages of 2*(16*5*64 + 5*8) bytes; q and out of
    2*15*64*2 bytes. Times 32 layers."""
    m = _conf("smollm-360m")["model"]
    flops, bytes_ = counts.decode_call(m, [100, 17], 16)
    assert flops == 32 * 4 * 15 * 64 * (100 + 17)
    page = 2 * (16 * 5 * 64 + 5 * 8)                    # 10320
    assert bytes_ == 32 * ((7 + 2) * page + 2 * 3840)


def test_prefill_counts_by_hand():
    """One fused paged-prefill call of smollm-360m: a chunk of 256 rows
    at positions [512, 768). Keys attended: 256*512 + 256*257/2 =
    163,968; flops 4*15*64 per key; pages read 32 and written 16 of
    2*(16*5*64 + 5*8) bytes; q/out/k/v rows of (2*15*64 + 2*5*64)*2
    bytes. Times 32 layers; at tp=2 each chip does half."""
    m = _conf("smollm-360m")["model"]
    flops, bytes_ = counts.prefill_call(m, 512, 256, 16)
    assert flops == 32 * 4 * 15 * 64 * 163968
    page = 2 * (16 * 5 * 64 + 5 * 8)                    # 10320
    assert bytes_ == 32 * (48 * page + 256 * 5120)
    assert counts.prefill_call(m, 512, 256, 16, tp=2) == (flops / 2,
                                                          bytes_ / 2)


def test_model_flops_by_hand():
    m = _conf("smollm-360m")["model"]
    lp = 960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560
    assert counts.layer_params(m) == lp
    got = counts.model_flops(m, [(0, 256)], [300], final_chunks=1)
    attn = 4 * 15 * 64 * 32
    want = (256 * 2 * lp * 32 + attn * (256 * 257 // 2)
            + 2 * lp * 32 + 2 * 49152 * 960 + attn * 300
            + 2 * 49152 * 960)
    assert got == want


def test_roofline_time_takes_the_larger_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_time(1000, 50, p) == 10.0
    assert counts.roofline_time(100, 50, p) == 5.0


# ------------------------------------------------------------------- trace
TRACE = os.path.join(BENCH, "testdata", "smollm-360m.fullctx.trace.json.gz")


@pytest.fixture(scope="module")
def chip_trace():
    return trace_reduce.load(TRACE)


def test_trace_has_kernels_and_programs(chip_trace):
    dev = trace_reduce.device(chip_trace, 0)
    ns, n = trace_reduce.kernel_ns(dev, "paged_decode")
    assert n > 0 and ns > 0
    ns, n = trace_reduce.kernel_ns(dev, "paged_prefill")
    assert n > 0 and ns > 0
    kinds = set(trace_reduce.program_kinds(dev).values())
    assert {"decode", "chunk"} <= kinds
    ns, n = trace_reduce.program_ns(dev, "decode")
    assert n > 0 and ns > 0


def test_trace_busy_and_idle(chip_trace):
    dev = trace_reduce.device(chip_trace, 0)
    w = trace_reduce.window_ns(chip_trace)
    busy = trace_reduce.busy_ns(dev, w)
    assert 0 < busy <= w[1] - w[0]
    gaps = trace_reduce.idle_gaps(chip_trace, dev)
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx((w[1] - w[0] - busy) / 1e9, rel=1e-6)
    assert all(name.startswith(("bench.", "host.")) for name, _ in gaps)
    top = trace_reduce.top_ops(dev)
    assert 0 < len(top) <= 10 and top[0][1] >= top[-1][1]


def test_union_of_intervals():
    assert trace_reduce.union([(0, 10), (5, 12), (20, 25)]) == 17
    assert trace_reduce.union([]) == 0


# ----------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", ["chat", "fullctx"])
def test_traffic_same_seed_same_requests(mix):
    spec = traffic.load(mix)
    gen = ((lambda s: traffic.open_loop(spec, s, 30.0, 1000))
           if spec["loop"] == "open"
           else (lambda s: traffic.closed_loop(spec, s, 1000)))
    a, b, c = gen(2**33 + 5), gen(2**33 + 5), gen(11)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
        assert x.offset_s == y.offset_s
    # another seed: the same sizes at the same times, other token ids
    shape = lambda r: [(len(i.prompt), i.max_new_tokens, i.offset_s)  # noqa: E731
                       for i in r]
    assert shape(a) == shape(c)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    p = spec["prompt"]
    for i in a:
        assert p["min"] <= len(i.prompt) <= p["max"]
        assert len(i.prompt) % 256 == 0
        assert len(i.prompt) + i.max_new_tokens <= 8192
    if spec["loop"] == "open":
        assert all(0 < i.offset_s < spec["warmup_s"] + 30.0 for i in a)


def test_open_loop_arrivals_start_before_the_window():
    """An open loop's arrivals run from `warmup_s` before the window to
    its end, at the mix's rate, so the window opens on a loaded engine."""
    spec = dict(traffic.load("chat"), rate_per_s=2.0, warmup_s=40.0)
    items = traffic.open_loop(spec, 7, 20.0, 1000)
    assert len(items) == 120
    offs = [i.offset_s for i in items]
    assert offs == sorted(offs) and offs[-1] < 60.0
    before = sum(o < 40.0 for o in offs)
    assert 60 <= before <= 100            # about rate x warmup_s


def _rec(due, first=None, done=None):
    r = harness.Rec(req=None, due=due)
    r.stamps = [] if first is None else [first]
    r.done = done
    return r


def test_backlog_counts_the_queue_for_a_first_token():
    """The queue (due, no first token yet) at the window's start and end,
    and the arrivals in it; the knee sweep calls a rate unsustained when
    the queue grows by more than a tenth of the arrivals."""
    import sweep

    recs = [_rec(0.0, 5.0), _rec(1.0, 12.0), _rec(2.0)] + [
        _rec(10.0 + i, 11.0 + i) for i in range(20)]
    b = harness.backlog(recs, 3.0, 40.0)
    assert b == {"due": 20, "waiting_at_start": 3, "waiting_at_end": 1}
    assert not sweep.grows(b)
    late = recs + [_rec(30.0 + i / 2) for i in range(8)]
    b = harness.backlog(late, 3.0, 40.0)
    assert b == {"due": 28, "waiting_at_start": 3, "waiting_at_end": 9}
    assert sweep.grows(b)


def test_check_samples_requests_finished_in_the_window():
    recs = [_rec(0.0, 1.0, done=2.0)] + [
        _rec(i, i + 1.0, done=10.0 + i) for i in range(6)]
    for i, r in enumerate(recs):
        r.req = harness.dataclasses.make_dataclass(
            "Req", ["prompt_len", "output"])(100 * i, [0] * i)
    got = harness.sample(recs, 2**33 + 1, 3, t0=10.0)
    assert len(got) == 3 and recs[0] not in got
    assert got[0] is recs[-1]             # the longest comes first


# ----------------------------------------------------------- data-driven
def test_every_entry_is_a_file_found_by_name():
    bench = _bench()
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert _conf(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
        assert os.path.isfile(os.path.join(BENCH, "limits",
                                           f"{w['name']}.json"))
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_code_names_no_cell_config_mix_or_metric():
    """A new cell, configuration, mix or metric is a new file plus an
    entry: the code never names one."""
    bench = _bench()
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [m["name"] for m in bench["per_layer"]])
    for f in ("harness.py", "run.py", "traffic.py", "counts.py",
              "trace_reduce.py", "reference.py", "weights.py"):
        with open(os.path.join(BENCH, f)) as fh:
            src = fh.read()
        for n in names:
            assert not re.search(rf"['\"]{re.escape(n)}['\"]", src), (f, n)


def test_new_metric_is_a_new_file(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "answer.py").write_text(
        "def read(run):\n    return 42.0\n")
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    assert harness.metric_reader("answer")(None) == 42.0


# ------------------------------------------------------------ rehearsals
def _run_script(code: str, timeout: int = 900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_real_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         _bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_fails_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         _bench()["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


REHEARSE = """
import json, sys
sys.path.insert(0, "benchmarks/chip/tests")
import rehearsal, harness
out = rehearsal.run({name!r}, seed={seed}, seconds={seconds}, trace={trace})
out.pop("_trace", None)
harness.emit(out)
"""


@pytest.mark.parametrize("name,trace", [
    ("smollm-360m.chat", False), ("smollm-360m.chat", True),
    ("smollm-360m.fullctx", True)])
def test_rehearsal_prints_the_result_line(name, trace):
    """The whole run on a reduced model on the CPU: the last line is the
    result object, correct, with the cell's metrics and the checks
    last."""
    r = _run_script(REHEARSE.format(name=name, seed=2**33 + 9, seconds=3.0,
                                    trace=trace))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    import rehearsal

    cell = rehearsal.cell(name)
    want = cell.per_layer if trace else cell.end_to_end
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "breakdown" in line
        # trace-read metrics need a TPU trace; the rest are read on CPU
        assert {"batch_occupancy", "device_idle_share"} <= set(
            line["metrics"])
    else:
        assert set(line["metrics"]) == {m["name"] for m in want}
    assert "compiles inside the window: 0" in r.stderr
