"""The serving engine's own spans and per-request stamps
(`serving.telemetry`), read back from a profiler trace of a chunked int8
engine on a reduced smollm: which spans appear, how they nest, how
`request_id` ties one request's spans together, that `engine.pump`'s
`t_ns` puts program stamps on the trace's clock, and that tracing leaves
the tokens alone."""

import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs
from repro.common.parallel import ParallelCtx
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving.telemetry import SPANS, now_ns

CTX = ParallelCtx(remat="none")
ENGINE = dict(n_slots=3, max_seq=96, prefill_buckets=(32, 48, 64),
              page_tokens=8, hot_window=8, local_budget_frac=0.5,
              admission="greedy", prefill_chunk=16, pool_dtype="int8")


def _requests(vocab, n=6, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(request_id=100 + i,
                    tokens=rng.integers(0, vocab, 16 * (2 + i % 3))
                    .astype(np.int32),
                    max_new_tokens=3 + 2 * i, arrival=0.0)
            for i in range(n)]


def _serve(**over):
    cfg = dataclasses.replace(configs.reduced("smollm_360m"),
                              dtype="float32")
    eng = ServingEngine.build(cfg, CTX, EngineConfig(**dict(ENGINE, **over)))
    reqs = _requests(cfg.vocab_size)
    eng.run(reqs)
    return eng, reqs


def _spans(path):
    """Program spans of one `.xplane.pb`: (name, start, end, stats, line)."""
    prefixes = tuple({n.split(".")[0] + "." for n in SPANS})
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, i)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        eng, reqs = _serve()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    return reqs, _spans(path)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _parents(spans, sp):
    """Spans on sp's thread that contain it, innermost first."""
    outer = [s for s in spans if s is not sp and s[4] == sp[4]
             and s[1] <= sp[1] and sp[2] <= s[2]]
    return [s[0] for s in sorted(outer, key=lambda s: s[2] - s[1])]


def _offsets(spans):
    return [s[1] - s[3]["t_ns"] for s in _named(spans, "engine.pump")]


def test_every_span_of_the_path_appears(traced):
    _, spans = traced
    names = {s[0] for s in spans}
    assert set(SPANS) <= names
    assert names <= set(SPANS)


def test_pager_step_nests_in_decode_in_pump(traced):
    _, spans = traced
    steps = _named(spans, "pager.step")
    assert steps
    for sp in steps:
        assert _parents(spans, sp)[:2] == ["engine.decode", "engine.pump"]
    for child in ("pager.touches", "pager.writes", "pager.rebalance"):
        for sp in _named(spans, child):
            assert _parents(spans, sp)[0] == "pager.step"
    for child in ("engine.decode.dispatch", "engine.decode.wait",
                  "engine.emit"):
        for sp in _named(spans, child):
            assert _parents(spans, sp)[0] == "engine.decode"


def test_request_id_ties_a_requests_spans(traced):
    reqs, spans = traced
    for r in reqs:
        mine = {name: [s for s in _named(spans, name)
                       if s[3].get("request_id") == r.request_id]
                for name in ("engine.admit", "engine.prefill_chunk",
                             "engine.retire")}
        (admit,), chunks, (retire,) = mine.values()
        assert len(chunks) == r.prompt_len // ENGINE["prefill_chunk"]
        assert [c[3]["start"] for c in chunks] == list(
            range(0, r.prompt_len, ENGINE["prefill_chunk"]))
        finals = [c[3]["final"] for c in chunks]
        assert finals == [0] * (len(chunks) - 1) + [1]
        assert admit[2] <= chunks[0][1] and chunks[-1][2] <= retire[1]
        assert {c[3]["tokens"] for c in chunks} == {ENGINE["prefill_chunk"]}


def test_pump_clock_offset_is_steady(traced):
    reqs, spans = traced
    offs = _offsets(spans)
    assert len(offs) > 10
    assert max(offs) - min(offs) < 200e3            # ns
    # through the offset a program stamp lands where the trace saw it
    off = float(np.median(offs))
    for r in reqs:
        admit, = [s for s in _named(spans, "engine.admit")
                  if s[3]["request_id"] == r.request_id]
        assert abs(r.wall.admitted_ns + off - admit[1]) < 200e3
        first, = [s for s in _named(spans, "engine.prefill_chunk")
                  if s[3]["request_id"] == r.request_id
                  and s[3]["start"] == 0]
        assert first[1] - 200e3 < r.wall.first_chunk_ns + off < first[2]


def test_rebalance_reports_the_pages_it_placed(traced):
    _, spans = traced
    rebal = _named(spans, "pager.rebalance")
    assert len(rebal) == len(_named(spans, "pager.step"))
    pages = [s[3]["pages"] for s in rebal]
    assert min(pages) > 0
    # the pages live at a step: at most every slot's prompt and output
    assert max(pages) <= ENGINE["n_slots"] * (
        ENGINE["max_seq"] // ENGINE["page_tokens"])


@pytest.mark.parametrize("mode", ["chunked", "bucket", "speculative"])
def test_request_stamps_are_ordered(mode):
    over = {"chunked": {}, "bucket": {"prefill_chunk": None},
            "speculative": {"speculative": "ngram"}}[mode]
    t0 = now_ns()
    _, reqs = _serve(**over)
    t1 = now_ns()
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        assert t0 < r.wall.admitted_ns <= r.wall.first_chunk_ns < t1
    # slots go to requests in arrival order, and so do first chunks
    for key in ("admitted_ns", "first_chunk_ns"):
        stamps = [getattr(r.wall, key) for r in reqs]
        assert stamps == sorted(stamps)


def test_greedy_tokens_same_with_profiler_on_and_off(traced):
    reqs, _ = traced
    _, plain = _serve()
    assert [r.output for r in plain] == [r.output for r in reqs]


def test_decode_span_counts_the_kernels_pages(traced):
    """`engine.decode`'s `kv_pages`: the pages the decode kernel walks,
    at least a page for each decoding row and none for a parked one, so
    never more than the decoding rows' whole tables."""
    _, spans = traced
    per_row = ENGINE["max_seq"] // ENGINE["page_tokens"]
    decodes = _named(spans, "engine.decode")
    assert decodes
    for sp in decodes:
        st = sp[3]
        assert st["n_active"] <= st["kv_pages"] <= st["n_active"] * per_row
    assert any(sp[3]["n_active"] < ENGINE["n_slots"] for sp in decodes)


def test_kv_pages_skips_parked_rows():
    cfg = dataclasses.replace(configs.reduced("smollm_360m"),
                              dtype="float32")
    eng = ServingEngine.build(cfg, CTX, EngineConfig(**ENGINE))
    page = ENGINE["page_tokens"]
    assert eng._kv_pages() == 0                     # every slot parked
    slots = eng.batcher.slots
    for slot, t in zip(slots, (0, page - 1, 2 * page + 3)):
        slot.request, slot.phase, slot.t = _requests(8, n=1)[0], "decode", t
    assert eng._kv_pages() == 1 + 1 + 3
    slots[1].phase = "prefill"                      # parked again
    assert eng._kv_pages() == 1 + 3


def test_collectives_stat_is_zero_without_a_mesh(traced):
    _, spans = traced
    cells = _named(spans, "engine.decode") + _named(spans,
                                                    "engine.prefill_chunk")
    assert cells
    assert {s[3]["collectives"] for s in cells} == {0}


MESH_RUN = """
import glob, json, re, sys, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro import configs, kernels
from repro.launch.mesh import ctx_for_mesh
from repro.serving import EngineConfig, Request, ServingEngine

cfg = configs.reduced("mistral_nemo_12b")
mesh = jax.make_mesh((1, 2), ("data", "model"), devices=jax.devices()[:2],
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
kernels.force_backend("interpret")
eng = ServingEngine.build(
    cfg, ctx_for_mesh(mesh, fsdp=False, remat="none"),
    EngineConfig(n_slots=2, max_seq=64, page_tokens=16, prefill_chunk=16,
                 prefill_buckets=(16,), pool_dtype="int8"), mesh=mesh)
d = tempfile.mkdtemp()
jax.profiler.start_trace(d)
eng.run([Request(request_id=i, tokens=np.arange(32, dtype=np.int32) + i,
                 max_new_tokens=3, arrival=0.0) for i in range(2)])
jax.profiler.stop_trace()
path, = glob.glob(d + "/**/*.xplane.pb", recursive=True)
stats = {}
for plane in jax.profiler.ProfileData.from_file(path).planes:
    for line in plane.lines:
        for ev in line.events:
            if ev.name in ("engine.decode", "engine.prefill_chunk"):
                stats.setdefault(ev.name, set()).add(
                    dict(ev.stats)["collectives"])

def text_count(fn, *args):
    # a collective in the layer scan's body runs once a layer
    n = 0
    for line in fn.lower(*args).compile().as_text().splitlines():
        op = re.search(r"= \\S+ ((all-reduce|all-gather|reduce-scatter"
                       r"|all-to-all|collective-permute)[\\w-]*)\\(", line)
        if op and not op.group(1).endswith("-done"):
            body = "/while/body/" in line
            n += cfg.num_layers if body else 1
    return n

bt = eng._block_table_dev()
vec = jnp.zeros(2, jnp.int32)
print("RESULT", json.dumps({
    "spans": {k: sorted(v) for k, v in stats.items()},
    "decode": text_count(eng.cells.decode_fn, eng.params, vec, eng.caches,
                         vec, bt),
    "chunk": text_count(eng.cells.chunk_fn, eng.params,
                        jnp.zeros((1, 16), jnp.int32), eng.caches,
                        np.int32(0), np.int32(0), bt)}))
"""


def test_collectives_stat_counts_the_compiled_cells_collectives():
    """On a (1, 2) mesh of forced CPU devices the stat is the count of
    collective ops in the compiled cell's text, each op in the layer
    scan's body once a layer: above 0, the same on every span."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    r = subprocess.run([sys.executable, "-c", MESH_RUN], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    got = json.loads(line[len("RESULT "):])
    assert got["decode"] > 0 and got["chunk"] > 0
    assert got["spans"] == {"engine.decode": [got["decode"]],
                            "engine.prefill_chunk": [got["chunk"]]}
