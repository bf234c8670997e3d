"""Smoke run of the serving main path on TPU — the quickest proof that the
system still starts on the chip. It is not a benchmark: the tok/s it
prints is a smoke figure, taken over a few requests with compilation
excluded.

    python chip_smoke.py              # one chip: smollm_360m
    python chip_smoke.py --chips 4    # four chips: mistral_nemo_12b, tp=4

Each run builds the model at its published widths from a seed (random
bf16 weights) through `ServingEngine.build` and serves chat requests with
`engine.run` under the engine's defaults — paged int8 KV pool, host
substrate, compiled Pallas kernels — first with whole-prompt (bucketed)
prefill, then with chunked prefill, which runs the fused paged-prefill
kernel. It then runs the same forwards with the jnp reference ops
(`kernels.force_backend("reference")`) on the same devices. It fails, with
a non-zero exit, when:

* JAX finds no TPU, or a device kind `common/hw.py` does not describe;
* the kernels do not resolve to "pallas" or the substrate to "physical";
* a request finishes short of its tokens, or a cell compiles after
  warm-up;
* pool pages sent HBM -> pinned host -> HBM through the substrate's
  transfer streams do not come back bit for bit;
* a logit of the Pallas path differs from the reference by more than
  `logit_tol`, relative to the reference's largest |logit|;
* fewer than `INT8_TOKEN_AGREEMENT` of the served tokens equal the
  reference's greedy pick on the same history (teacher-forced: the
  reference scores the engine's own token stream, so one early near-tie
  cannot take the rest of a request with it).

The last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

SEED = 0
PAGE = 16                     # EngineConfig.page_tokens default
BF16_ROUNDOFF = 2.0 ** -8     # unit roundoff of bfloat16 (8-bit significand)


def logit_tol(n_layers: int) -> float:
    """Allowed max |logit - reference| over max |reference logit|.

    Both paths compute in bfloat16 between kernels; they differ in where
    a value is rounded (the kernels keep f32 inside, the jnp reference
    rounds dequantized K/V and probabilities to bf16), so at each layer a
    value may land one bf16 rounding away from the reference's. Charged
    linearly over the layers — the worst case, with no cancellation — that
    is one unit roundoff per layer relative to the logit scale."""
    return n_layers * BF16_ROUNDOFF


# (arch, prompt lengths, new tokens, slots, prefill chunk) per chip count
RUNS = {
    1: dict(arch="smollm_360m", prompts=(128, 256, 512, 128, 384, 256, 512,
                                         128), gen=32, slots=4, chunk=128),
    4: dict(arch="mistral_nemo_12b", prompts=(128, 256, 256, 128), gen=16,
            slots=4, chunk=128),
}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def make_requests(prompts, gen: int, vocab: int):
    from repro.serving import Request

    rng = np.random.default_rng(SEED)
    return [Request(request_id=i,
                    tokens=rng.integers(0, vocab, size=n).astype(np.int32),
                    max_new_tokens=gen, arrival=0.0)
            for i, n in enumerate(prompts)]


def serve_phase(name, cfg, ctx, mesh, params, run, chunk):
    """Build an engine (weights from SEED when `params` is None), warm
    every cell with one request per prompt bucket, then serve the measured
    trace; checks completion and the no-recompile contract. Returns the
    engine and the served requests."""
    from repro.serving import EngineConfig, ServingEngine

    buckets = tuple(sorted(set(run["prompts"])))
    ecfg = EngineConfig(n_slots=run["slots"],
                        max_seq=max(buckets) + run["gen"],
                        prefill_buckets=buckets, prefill_chunk=chunk)
    t0 = time.perf_counter()
    engine = ServingEngine.build(cfg, ctx, ecfg, params=params, mesh=mesh,
                                 seed=SEED)
    if params is None:
        import jax

        jax.block_until_ready(engine.params)
        print(f"weights: {cfg.param_count() / 1e9:.2f} B params made from "
              f"seed {SEED} in {time.perf_counter() - t0:.1f} s")
    if engine.substrate is None or engine.substrate.mode != "physical":
        fail(f"{name}: substrate resolved to "
             f"{engine.substrate and engine.substrate.mode!r}, "
             "not 'physical'")
    t0 = time.perf_counter()
    engine.run(make_requests(buckets, 2, cfg.vocab_size))
    warm_s = time.perf_counter() - t0
    counts = engine.compile_counts()
    reqs = make_requests(run["prompts"], run["gen"], cfg.vocab_size)
    stats = engine.run(reqs)
    short = [r.request_id for r in reqs if len(r.output) != run["gen"]]
    if short:
        fail(f"{name}: requests {short} finished short of {run['gen']} "
             "tokens")
    if engine.compile_counts() != counts:
        fail(f"{name}: cells compiled after warm-up: {counts} -> "
             f"{engine.compile_counts()}")
    s = stats.summary()
    print(f"{name}: compile + warm-up (1 request per bucket) "
          f"{warm_s:.1f} s; compile counts {counts}")
    print(f"{name}: served {s['n_requests']} requests / {s['tokens']} "
          f"tokens in {stats.wall_s:.2f} s wall = "
          f"{s['tok_per_s_wall']:.1f} tok/s (smoke figure, not a benchmark "
          f"result); substrate moved "
          f"{s['substrate_transfer_bytes']:.0f} bytes HBM<->host "
          f"({stats.substrate['page_out_pages']} pages out, "
          f"{stats.substrate['page_in_pages']} in)")
    return engine, reqs


class _PoolSet:
    """The two pager reads a substrate drain makes: the pool-tiered page
    ids and the live reference counts."""

    def __init__(self, ids, n_phys):
        self.ids = np.asarray(ids, np.int64)
        self.ref = np.ones(n_phys, np.int64)

    def pool_page_ids(self):
        return self.ids


def check_substrate(engine, mesh):
    """Round-trip every page of the served pool through a fresh substrate
    of the engine's mode and partitioning: one drain pages them all out
    to the host twin, a second promotes them all back; the promoted
    pages must equal the device pool's bit for bit."""
    import jax

    from repro.models.blocks import paged_pool_leaves
    from repro.serving.substrate import TierSubstrate

    pool = paged_pool_leaves(engine.caches)
    pspec = (None if mesh is None
             else jax.tree.map(lambda a: a.sharding.spec, pool))
    sub = TierSubstrate(engine.caches, mesh, engine.substrate.mode,
                        pool_pspec=pspec)
    ids = np.arange(sub.n_phys)
    sub.drain(_PoolSet(ids, sub.n_phys), engine.caches)
    sub.drain(_PoolSet([], sub.n_phys), engine.caches)
    back = sub.ledger.events[-1].payload       # the page_in stream's pages
    for got, want in zip(back, jax.tree.leaves(pool)):
        if not np.array_equal(np.asarray(got)[:, :len(ids)],
                              np.asarray(want)):
            fail("substrate: pages came back from the host twin changed")
    print(f"substrate: {len(ids)} pool pages ({sub.page_bytes * len(ids):.0f}"
          f" bytes) round-tripped HBM -> {sub.mode} host twin -> HBM "
          "bit for bit")


def padded_histories(reqs):
    """Each request's prompt + served tokens, right-padded to one length
    that the flash kernel tiles in 128-row blocks (causal attention: the
    padding changes no earlier position)."""
    seqs = [np.concatenate([r.tokens, np.asarray(r.output[:-1], np.int32)])
            for r in reqs]
    n = -(-max(len(s) for s in seqs) // 128) * 128
    out = np.zeros((len(seqs), n), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def chunked_logits(params, prompt, cfg, ctx, mesh, chunk):
    """Last-token logits of `prompt` prefilled chunk by chunk into a
    one-slot int8 page pool — the engine's chunk path (fused insert +
    attend kernel on the Pallas backend)."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    n = len(prompt)
    caches = M.make_paged_decode_caches(cfg, 1, n, PAGE, pool_dtype="int8")
    if mesh is not None:
        caches = jax.device_put(caches, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))
    row = jnp.arange(n // PAGE, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, t, c, i: M.prefill_chunk(p, t, c, i, cfg, ctx,
                                                      row, PAGE),
                   donate_argnums=(2,))
    for i in range(n // chunk):
        logits, caches = step(params, jnp.asarray(prompt[None, i * chunk:
                                                         (i + 1) * chunk]),
                              caches, jnp.int32(i))
    return logits


def compare(cfg, ctx, mesh, params, phases, chunk_prompt, chunk):
    """Pallas vs reference: logits over every served history (the dense
    flash-prefill path) and over a chunked prefill (the fused paged
    kernel), and teacher-forced greedy agreement of the served tokens."""
    import jax
    import jax.numpy as jnp

    from repro import kernels
    from repro.models import model as M
    from repro.serving import INT8_TOKEN_AGREEMENT

    def logits_all():
        fwd = jax.jit(lambda p, t: M.forward(p, {"tokens": t}, cfg, ctx)[0])
        out = {name: fwd(params, padded_histories(reqs))
               for name, reqs in phases.items()}
        out["fused chunk kernel"] = chunked_logits(params, chunk_prompt,
                                                   cfg, ctx, mesh, chunk)
        return out

    got = logits_all()
    kernels.force_backend("reference")
    jax.clear_caches()           # every op re-traces with the jnp oracle
    try:
        ref = logits_all()
    finally:
        kernels.force_backend(None)
    tol = logit_tol(cfg.num_layers)
    for name in got:
        g = got[name].astype(jnp.float32)
        r = ref[name].astype(jnp.float32)
        err = float(jnp.abs(g - r).max() / jnp.abs(r).max())
        print(f"logits[{name}]: max |pallas - reference| / max |reference| "
              f"= {err:.3e} (tolerance {tol:.3e} = {cfg.num_layers} layers "
              f"x bf16 roundoff 2^-8)")
        if not math.isfinite(err) or err > tol:
            fail(f"logits[{name}] differ from the reference by {err:.3e} > "
                 f"{tol:.3e}")
    for name, reqs in phases.items():
        picks = np.asarray(jnp.argmax(ref[name], axis=-1))
        hits = total = 0
        for i, r in enumerate(reqs):
            pos = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.output))
            hits += int((picks[i, pos] == np.asarray(r.output)).sum())
            total += len(r.output)
        agree = hits / total
        print(f"tokens[{name}]: {hits}/{total} served tokens equal the "
              f"reference's greedy pick = {agree:.3f} (required >= "
              f"{INT8_TOKEN_AGREEMENT})")
        if agree < INT8_TOKEN_AGREEMENT:
            fail(f"tokens[{name}]: agreement {agree:.3f} < "
                 f"{INT8_TOKEN_AGREEMENT}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=sorted(RUNS),
                    help="1: smollm_360m on one chip (default); 4: the "
                    "tp-sharded mistral_nemo_12b phase only")
    args = ap.parse_args(argv)
    run = RUNS[args.chips]
    try:
        from repro.common import compile_cache
    except ImportError as e:
        fail(f"cannot import the repro package next to this script: {e}")
    compile_cache.enable()

    import jax

    from repro import configs, kernels
    from repro.common import hw
    from repro.common.parallel import ParallelCtx
    from repro.launch.mesh import ctx_for_mesh

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is on platform {dev.platform!r}")
    try:
        hw.chip_for(dev.device_kind)
    except ValueError as e:
        fail(str(e))
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")
    if kernels.backend() != "pallas":
        fail(f"kernels resolved to {kernels.backend()!r}, not 'pallas'")
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}")

    cfg = configs.get(run["arch"])
    if args.chips == 1:
        mesh, ctx = None, ParallelCtx(remat="none")
    else:
        mesh = jax.make_mesh((1, args.chips), ("data", "model"),
                             devices=devices[:args.chips],
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = ctx_for_mesh(mesh, fsdp=False, remat="none")
    print(f"model: {cfg.name} at published widths ({cfg.num_layers} layers, "
          f"d {cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} KV, "
          f"head_dim {cfg.head_dim}, vocab {cfg.vocab_size})"
          + (f", mesh {dict(mesh.shape)}" if mesh is not None else ""))

    engine, bucket = serve_phase("bucket", cfg, ctx, mesh, None, run, None)
    params = engine.params
    del engine                   # free its page pool before the next one
    engine, chunked = serve_phase("chunked prefill", cfg, ctx, mesh, params,
                                  run, run["chunk"])
    check_substrate(engine, mesh)
    del engine
    # the fused kernel's logits over a prompt of several chunks, so later
    # chunks attend to pages written by earlier ones
    chunk_prompt = max((r.tokens for r in chunked), key=len)
    compare(cfg, ctx, mesh, params,
            {"bucket prefill": bucket, "chunked prefill": chunked},
            chunk_prompt, run["chunk"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
