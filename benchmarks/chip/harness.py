"""The on-chip serving benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload smollm-360m.chat --seed 7 \
        --seconds 10 --trace 0

A cell (`BENCHMARK.json` "workloads") names a configuration
(`configs/<name>.json`) and a traffic mix (`traffic/<name>.json`); its
correctness limit is `limits/<cell>.json`, and each per-layer metric is a
reader `metrics/<name>.py`. The harness finds all of them by name, so a
new cell or metric is new files and entries, not an edit here.

A run:

1. set-up (counted in `setup_s`, from process start): checks the device
   (a TPU of a kind in `peaks.json`, as many chips as the cell asks),
   builds `ServingEngine` with weights made on the device from the seed
   (`weights.py`), then warms every shape the cell uses: each
   power-of-two substrate stream size up to the pool's page count, the
   chunk and decode cells. It then brings the load to its steady state:
   an open loop's arrivals start the mix's `warmup_s` (longer than a
   request's mean life) before the window; a closed loop's clients send
   until every one has had its first token (every slot filled);
2. the window (`--seconds` on the wall clock): `engine.pump` on a
   `RequestQueue`. The harness decides when a request is due and pushes
   it with an arrival the engine sees as past; after every pump, and
   after every prefill chunk, each token that reached the host is
   stamped with `time.perf_counter()`. Compiles inside the window are
   counted and printed;
3. with `--trace 1`, a profiler trace of the window's middle (at most
   `TRACE_SECONDS`) feeds the per-layer metrics;
4. the check: the engine serves on past the window (untimed, nothing
   new sent, at most CHECK_WAIT_S) until the mix's `check_requests` have
   finished since the window opened; a sample of those, drawn from the
   seed with the longest in it, is scored teacher-forced by the float32 reference
   (`reference.py`) once the program's state is freed; the widest gap by
   which a served token's logit lies below the reference's best must
   stay under the cell's limit.

The last stdout line is one JSON object; the numbers compared, each with
its limit, come last there and as the last stderr lines.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Callable, List, Optional

import numpy as np

import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_SECONDS = 8.0            # longest traced stretch of a window
TRACE_LEAD_S = 10.0            # open loop: the profiler starts this long
                               # before the window (its start stalls)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CHECK_WAIT_S = 60.0            # longest the check waits past the window
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class BenchError(Exception):
    """A run that cannot produce a result: no chip, a bad spec, a fault
    of the program that stops the window."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------- the spec
def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict              # configs/<name>.json
    mix: dict                 # traffic/<name>.json
    chips: int
    end_to_end: List[dict]    # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    limits: dict              # limits/<cell>.json


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(workload: str) -> Cell:
    bench = _json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload,
        config=_json(ROOT, conf["file"]),
        mix=traffic.load(w["traffic"]),
        chips=w["chips"],
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        limits=_json(HERE, "limits", f"{workload}.json"),
    )


def metric_reader(name: str) -> Callable:
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- device
def check_devices(chips: int):
    """The devices a cell runs on: fails unless JAX's devices are TPUs,
    at least `chips` of them, of a kind `peaks.json` describes."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is on platform "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips], peaks_for(devs[0].device_kind)


def peaks_for(kind: str) -> dict:
    table = _json(HERE, "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (JAX_COMPILATION_CACHE_DIR,
    else `<checkout>/.jax_cache`), keeping every program, however small
    or quick to compile, so that a run after the first compiles nothing."""
    import jax

    from repro.common import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts lowerings (each jit cache miss lowers, whether or not the
    persistent cache then has the program) while `active`."""

    active = False
    count = 0
    _registered = False

    @classmethod
    def install(cls):
        if not cls._registered:
            import jax

            def on_event(event, duration, **kw):
                if cls.active and event == LOWERING_EVENT:
                    cls.count += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._registered = True


# ------------------------------------------------------------------ build
def program_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked
    against the file's published sizes."""
    from repro import configs

    prog = conf["program"]
    cfg = (configs.reduced(prog["arch"]) if prog.get("reduced")
           else configs.get(prog["arch"]))
    cfg = dataclasses.replace(cfg, **prog.get("overrides", {}))
    m = conf["model"]
    pairs = {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "attention_bias": cfg.qkv_bias, "torch_dtype": cfg.dtype,
    }
    bad = {k: (m[k], v) for k, v in pairs.items() if m[k] != v}
    if not math.isclose(m["embedding_multiplier"], math.sqrt(cfg.d_model)):
        bad["embedding_multiplier"] = (m["embedding_multiplier"],
                                       math.sqrt(cfg.d_model))
    if cfg.act != "swiglu" or m["hidden_act"] != "silu":
        bad["hidden_act"] = (m["hidden_act"], cfg.act)
    if bad:
        raise BenchError(f"{conf['name']}: the program departs from the "
                         f"configuration (file, program): {bad}")
    return cfg


def build_engine(conf: dict, devices, seed: int):
    """ServingEngine of the configuration on `devices`, with the
    benchmark's weights from `seed`. Returns (engine, weights)."""
    import jax

    from repro.common.parallel import ParallelCtx
    from repro.launch.mesh import ctx_for_mesh
    from repro.serving import EngineConfig, ServingEngine

    import weights

    cfg = program_config(conf)
    mesh = None
    ctx = ParallelCtx(remat="none")
    if conf["mesh"]:
        axes = tuple(conf["mesh"])
        mesh = jax.make_mesh(
            tuple(conf["mesh"][a] for a in axes), axes, devices=devices,
            axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        ctx = ctx_for_mesh(mesh, fsdp=False, remat="none")
    e = dict(conf["engine"])
    e["prefill_buckets"] = tuple(e.get("prefill_buckets",
                                       (e["prefill_chunk"],)))
    ecfg = EngineConfig(**e)
    # the program's own init is skipped: the weights are the benchmark's
    engine = ServingEngine.build(cfg, ctx, ecfg, params=(), mesh=mesh)
    engine.params = weights.make(engine.cells.abstract_params,
                                 engine.cells.param_shardings, seed)
    jax.block_until_ready(engine.params)
    return engine, engine.params


class _PoolIds:
    """What a substrate drain reads of a pager: the pool-tier page ids
    and live reference counts."""

    def __init__(self, ids, n_phys):
        self.ids = np.asarray(ids, np.int64)
        self.ref = np.ones(n_phys, np.int64)

    def pool_page_ids(self):
        return self.ids


def warm_substrate(engine) -> int:
    """Run every power-of-two page_out and page_in stream size up to the
    pool's page count once; leaves the twin empty, as the pager is."""
    sub = engine.substrate
    if sub is None:
        return 0
    k = 1
    while True:
        n = min(k, sub.n_phys)
        sub.drain(_PoolIds(np.arange(n), sub.n_phys), engine.caches)
        sub.drain(_PoolIds([], sub.n_phys), engine.caches)
        sub.sync()
        if n == sub.n_phys:
            return k
        k *= 2


# --------------------------------------------------------------- the load
@dataclasses.dataclass
class Rec:
    req: object               # serving.queue.Request
    due: float                # perf_counter when due (open) / sent
    client: Optional[int] = None
    admitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None


class Load:
    """Drives `engine.pump` against a queue the harness fills on the wall
    clock, and stamps what comes back."""

    def __init__(self, engine, cell: Cell, seed: int, seconds: float):
        from repro.serving import RequestQueue

        self.engine = engine
        self.q = RequestQueue()
        self.vocab = engine.cfg.vocab_size
        self.open = cell.mix["loop"] == "open"
        if self.open:
            self.items = traffic.open_loop(cell.mix, seed, seconds,
                                           self.vocab)
        else:
            self.items = traffic.closed_loop(cell.mix, seed, self.vocab)
            self.clients = cell.mix["clients"]
            self.clients_served: set = set()   # clients with a token
        self.next_item = 0
        self.live: List[Rec] = []
        self.records: List[Rec] = []
        self.late_s = 0.0
        self.t_open = None            # open loop: the window's start
        self.sending = True           # closed loop: clients send again
        orig = engine._prefill_tick

        def tick():
            ran = orig()
            if ran:
                self.stamp(time.perf_counter())
            return ran

        engine._prefill_tick = tick   # first tokens reach the host here

    def send(self, due: float, client=None) -> None:
        from repro.serving import Request

        item = self.items[self.next_item % len(self.items)]
        req = Request(request_id=len(self.records),
                      tokens=item.prompt, max_new_tokens=item.max_new_tokens,
                      arrival=0.0)
        self.next_item += 1
        rec = Rec(req, due, client)
        self.q.push(req)
        self.live.append(rec)
        self.records.append(rec)

    def release(self, now: float) -> None:
        """Open loop: push every request due by `now`."""
        while (self.open and self.next_item < len(self.items)
               and self.t_open + self.items[self.next_item].offset_s <= now):
            due = self.t_open + self.items[self.next_item].offset_s
            self.late_s = max(self.late_s, now - due)
            self.send(due)

    def stamp(self, t: float) -> None:
        still, finished = [], []
        for rec in self.live:
            req = rec.req
            if rec.admitted is None and not math.isnan(req.admitted):
                rec.admitted = t
            n = len(req.output)
            if n > len(rec.stamps):
                if not self.open and not rec.stamps:
                    self.clients_served.add(rec.client)
                rec.stamps.extend([t] * (n - len(rec.stamps)))
            if req.done:
                rec.done = t
                finished.append(rec)
            else:
                still.append(rec)
        self.live = still
        if not self.open and self.sending:
            for rec in finished:          # the client sends its next one
                self.send(t, rec.client)

    def start_clients(self, t: float) -> None:
        for c in range(self.clients):
            self.send(t, c)

    def drive(self, until: float, stop: Optional[Callable] = None,
              on_pump: Optional[Callable] = None) -> None:
        import jax

        annotate = on_pump is not None
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                return
            self.release(now)
            if annotate:
                with jax.profiler.TraceAnnotation("bench.pump"):
                    act = self.engine.pump(self.q)
            else:
                act = self.engine.pump(self.q)
            t = time.perf_counter()
            self.stamp(t)
            if act in ("dead", "stalled"):
                raise BenchError(f"engine reported {act!r}")
            if on_pump is not None:
                on_pump(t)
            if act == "idle" and not self.live:
                nxt = (self.t_open + self.items[self.next_item].offset_s
                       if self.open and self.next_item < len(self.items)
                       else until)
                time.sleep(max(0.0, min(nxt, until) - t))


# ------------------------------------------------------------ recording
class Recorder:
    """Traced runs only: wraps the engine instance's decode and chunk
    cells, pager step and substrate drain in host spans, and records
    what each decode and chunk call computed (for the counts)."""

    def __init__(self, engine):
        import jax

        self.calls: List[tuple] = []
        ann = jax.profiler.TraceAnnotation
        b = engine.batcher
        cells = engine.cells

        def decode(*a, **k):
            ctx = [s.t + 1 for s in b.slots if s.active]
            t = time.perf_counter()
            with ann("bench.decode"):
                out = dec(*a, **k)
            self.calls.append(("decode", t, ctx))
            return out

        def chunk(*a, **k):
            C = a[1].shape[1]
            slot, start = int(a[3]), int(a[4]) * C
            final = start + C == b.slots[slot].request.prompt_len
            t = time.perf_counter()
            with ann("bench.chunk"):
                out = chk(*a, **k)
            self.calls.append(("chunk", t, (start, C, final)))
            return out

        def span(name, fn):
            def wrapped(*a, **k):
                with ann(name):
                    return fn(*a, **k)
            return wrapped

        dec, chk = cells.decode_fn, cells.chunk_fn
        cells.decode_fn = decode
        if chk is not None:
            cells.chunk_fn = chunk
        engine.pager.step = span("bench.pager_step", engine.pager.step)
        if engine.substrate is not None:
            engine.substrate.drain = span("bench.drain",
                                          engine.substrate.drain)

    def between(self, kind: str, t0: float, t1: float):
        return [c[2] for c in self.calls if c[0] == kind and t0 <= c[1] < t1]


@dataclasses.dataclass
class RunData:
    """What the per-layer readers (`metrics/*.py`) read."""
    cell: Cell
    model: dict
    engine: dict
    chips: int
    tp: int
    peaks: dict
    seconds: float
    t_start: float
    t_end: float
    records: List[Rec]
    calls: Recorder
    ledger0: dict
    ledger1: dict
    trace: Optional[dict] = None      # trace_reduce form
    trace_t0: float = 0.0             # perf_counter span of the trace
    trace_t1: float = 0.0

    def due_in_window(self) -> List[Rec]:
        return [r for r in self.records if self.t_start <= r.due < self.t_end]

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.records for s in r.stamps
                   if self.t_start <= s <= self.t_end)

    def traced(self, kind: str):
        return self.calls.between(kind, self.trace_t0, self.trace_t1)


# ---------------------------------------------------------------- metrics
def end_to_end(data: RunData, setup_s: float) -> dict:
    """The cell's end-to-end metrics, from the wall-clock stamps."""
    t0, t1 = data.t_start, data.t_end
    ttft, itl = [], []
    for r in data.due_in_window():
        first = r.stamps[0] if r.stamps else None
        ttft.append((first if first is not None and first <= t1 else t1)
                    - r.due)
    for r in data.records:
        s = [x for x in r.stamps if t0 <= x <= t1]
        itl.extend(np.diff(s))
    vals = {
        "output_tok_s": data.tokens_in_window() / data.seconds,
        "ttft_p95_ms": (float(np.percentile(ttft, 95)) * 1e3
                        if ttft else None),
        "itl_p50_ms": float(np.percentile(itl, 50)) * 1e3 if itl else None,
        "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3 if itl else None,
        "setup_s": setup_s,
    }
    out = {}
    for m in data.cell.end_to_end:
        v = vals[m["name"]]
        if v is None:
            raise BenchError(f"{m['name']}: nothing to measure in the "
                             "window")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    log(f"window: {len(data.due_in_window())} requests due, "
        f"{data.tokens_in_window()} tokens, {len(itl)} gaps, "
        f"{len(ttft)} first tokens timed")
    return out


def per_layer(data: RunData) -> dict:
    out = {}
    for m in data.cell.per_layer:
        v = metric_reader(m["name"])(data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def backlog(records: List[Rec], t0: float, t1: float) -> dict:
    """The requests that came due in the window, and those due but still
    without a first token at its start and at its end. The queue grows
    by the arrivals less the first tokens served: at a rate the engine
    sustains, it ends about where it started. (In chunked mode a request
    takes a slot at once and queues for its prefill chunks, so the first
    token, not the slot, shows the queue.)"""
    def waiting(t):
        return sum(1 for r in records
                   if r.due < t and not (r.stamps and r.stamps[0] <= t))

    return {"due": sum(1 for r in records if t0 <= r.due < t1),
            "waiting_at_start": waiting(t0), "waiting_at_end": waiting(t1)}


# ------------------------------------------------------------------ check
def sample(records: List[Rec], seed: int, n: int,
           t0: float) -> List[Rec]:
    """Requests finished since `t0` (the window's start) to score: the
    longest, then others drawn from the seed."""
    done = [r for r in records if r.done is not None and r.done >= t0]
    if not done:
        return []
    done.sort(key=lambda r: -(r.req.prompt_len + len(r.req.output)))
    rng = traffic.rng_for(seed + 1)
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1]
    return [done[0]] + rest[:n - 1]


def check(weights, model: dict, picked: List[Rec], control=False) -> dict:
    """Widest gap of the served tokens (and of the control's picks)."""
    import reference

    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    per = []
    for r in picked:
        g, cg = reference.gaps(weights, model, r.req.tokens, r.req.output,
                               control=control)
        widest = max(widest, float(g.max()))
        n_tok += len(g)
        per.append([r.req.prompt_len, len(g), float(g.max()),
                    int(g.argmax()), int((g > 0).sum())])
        if cg is not None:
            widest_ctl = max(widest_ctl, float(cg.max()))
    out = {"widest_gap": widest, "tokens": n_tok, "requests": len(picked),
           "per_request": per}
    if control:
        out["control_widest_gap"] = widest_ctl
    return out


# -------------------------------------------------------------------- run
def measure(cell: Cell, seed: int, seconds: float, trace: bool, *, devices,
            peaks: dict, t_process: float, fault=None, control=False,
            check_output=True) -> dict:
    """One run of `cell`; returns the result object (and, under the key
    "_check", the raw check readings). `check_output=False` (the knee
    sweep only) skips the reference."""
    import jax

    CompileCounter.install()
    engine, weights = build_engine(cell.config, devices, seed)
    if fault is not None:
        fault(engine)
    n_streams = warm_substrate(engine)
    from repro.serving import Request

    engine.run([Request(request_id=-1 - i, max_new_tokens=3,
                        tokens=np.arange(b, dtype=np.int32)
                        % engine.cfg.vocab_size)
                for i, b in enumerate(engine.ecfg.prefill_buckets)])
    log(f"set-up: weights + cells + {n_streams.bit_length()} substrate "
        f"stream sizes warmed at {time.perf_counter() - t_process:.1f} s")
    load = Load(engine, cell, seed, seconds)
    rec = Recorder(engine) if trace else None
    if load.open:
        # the arrivals start `warmup_s` before the window, so that the
        # window opens on the engine's steady load
        load.t_open = time.perf_counter()
        t_start = load.t_open + cell.mix["warmup_s"]
        load.drive(t_start - (TRACE_LEAD_S if trace else 0.0))
    else:
        # fill every slot: each client's first prompt prefilled, so the
        # window starts with the KV cache (and its tiering) at size
        load.start_clients(time.perf_counter())
        load.drive(float("inf"),
                   stop=lambda: len(load.clients_served) == load.clients)
    if trace:
        # the profiler starts and stops outside the window (each stalls
        # the host for seconds); its "bench.window" span marks the
        # stretch the per-layer metrics read, in the window's middle
        if os.path.isdir(TRACE_DIR):
            shutil.rmtree(TRACE_DIR)
        jax.profiler.start_trace(TRACE_DIR)
    if load.open:
        load.drive(t_start)
    else:
        t_start = time.perf_counter()
    ledger0 = (engine.substrate.counters() if engine.substrate else {})
    counts0 = engine.compile_counts()
    setup_s = t_start - t_process
    t_end = t_start + seconds
    tr = {"t0": 0.0, "t1": 0.0, "ann": None}
    on_pump = None
    if trace:
        span = min(TRACE_SECONDS, seconds)
        tr_from = t_start + (seconds - span) / 2

        def on_pump(t):
            if tr["ann"] is None and tr["t0"] == 0.0 and t >= tr_from:
                tr["ann"] = jax.profiler.TraceAnnotation("bench.window")
                tr["ann"].__enter__()
                tr["t0"] = time.perf_counter()
            elif tr["ann"] is not None and t >= tr_from + span:
                tr["t1"] = time.perf_counter()
                tr["ann"].__exit__(None, None, None)
                tr["ann"] = None

    CompileCounter.count = 0
    CompileCounter.active = True
    load.drive(t_end, on_pump=on_pump)
    CompileCounter.active = False
    load.release(t_end)           # due before the close: counted, unserved
    if trace:
        if tr["ann"] is not None:
            tr["t1"] = time.perf_counter()
            tr["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = CompileCounter.count
    counts1 = engine.compile_counts()
    if check_output:
        # serve on, untimed and with nothing new sent, until enough
        # requests have finished for the check (an answer that comes late
        # is late, not wrong)
        load.sending = False
        need = cell.mix["check_requests"]
        load.drive(time.perf_counter() + CHECK_WAIT_S,
                   stop=lambda: sum(r.done is not None and r.done >= t_start
                                    for r in load.records) >= need)
    log(f"compiles inside the window: {compiles} (cell cache sizes "
        f"{counts0} -> {counts1}); generator ran at most "
        f"{load.late_s * 1e3:.1f} ms late")
    ledger1 = (engine.substrate.counters() if engine.substrate else {})
    mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
              if d.memory_stats() else 0 for d in devices)
    data = RunData(
        cell=cell, model=cell.config["model"], engine=cell.config["engine"],
        chips=len(devices),
        tp=(cell.config["mesh"] or {}).get("model", 1),
        peaks=peaks, seconds=seconds, t_start=t_start, t_end=t_end,
        records=load.records, calls=rec, ledger0=ledger0, ledger1=ledger1,
        trace_t0=tr["t0"], trace_t1=tr["t1"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    result = {"correct": False, "attempted": len(data.due_in_window()),
              "failed": 0}
    breakdown = None
    if trace:
        import trace_reduce

        path = trace_reduce.newest_xplane(TRACE_DIR)
        if path is None:
            raise BenchError("the profiler wrote no trace")
        data.trace = trace_reduce.load_xplane(path)
        devs = [trace_reduce.device(data.trace, i)
                for i in range(len(devices))]
        w0, w1 = trace_reduce.window_ns(data.trace)
        busy = [trace_reduce.busy_ns(d, (w0, w1)) / 1e9 for d in devs]
        device["busy_s"] = float(np.mean(busy))
        device["window_s"] = (w1 - w0) / 1e9
        breakdown = {"device_ops": trace_reduce.top_ops(devs[0]),
                     "idle_gaps": trace_reduce.idle_gaps(data.trace,
                                                         devs[0])}
        result["metrics"] = per_layer(data)
        result["_trace"] = data.trace
    else:
        result["metrics"] = end_to_end(data, setup_s)
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown

    result["_backlog"] = backlog(load.records, t_start, t_end)

    result["_compiles"] = compiles
    if not check_output:
        return result

    # the check, once the program's state is freed
    picked = sample(load.records, seed, cell.mix["check_requests"], t_start)
    del load, engine, rec, data
    gc.collect()
    if not picked:
        raise BenchError("no request finished")
    t = time.perf_counter()
    got = check(weights, cell.config["model"], picked, control=control)
    limit = cell.limits["widest_gap"]
    result["correct"] = bool(got["widest_gap"] <= limit)
    log(f"check: {got['requests']} requests, {got['tokens']} served tokens "
        f"scored by the reference in {time.perf_counter() - t:.1f} s")
    result["checks"] = {"widest_gap": {"value": got["widest_gap"],
                                       "limit": limit}}
    result["_check"] = dict(got, limit=limit)
    return result


def main(argv=None, t_process=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="on-chip serving benchmark")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_process = t_process if t_process is not None else time.perf_counter()
    try:
        cell = load_cell(args.workload)
        enable_compile_cache()
        devices, peaks = check_devices(cell.chips)
        out = measure(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peaks=peaks, t_process=t_process)
    except BenchError as e:
        log(f"bench: FAIL: {e}")
        return 2
    emit(out)
    return 0


def emit(out: dict) -> None:
    """Numbers compared as the last stderr lines; the result object as
    the last stdout line, with the checks last."""
    for name, c in out["checks"].items():
        log(f"{name}: {c['value']!r} (limit {c['limit']!r})")
    line = {k: v for k, v in out.items()
            if not k.startswith("_") and k != "checks"}
    line["checks"] = out["checks"]
    print(json.dumps(line), flush=True)
