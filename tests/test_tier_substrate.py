"""Physical memory substrate: capability-mode resolution, transfer
ledger accounting, TierSubstrate drain reconciliation against a live
engine, and the tentpole placement contract (`KVPager.pool_bytes_used`
== ledger `placement_bytes` after every drain) — plus the emulated-vs-
physical shape contract that keeps the CPU fallback honest."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.common.parallel import ParallelCtx
from repro.models import model as M
from repro.models.blocks import PAGED_LEAF_NAMES, paged_pool_leaves
from repro.runtime import capability
from repro.serving import (
    EngineConfig,
    Request,
    RequestQueue,
    ServingEngine,
)
from repro.serving.substrate import SubstrateLedger, TierSubstrate
from repro.serving.substrate.ledger import KINDS

CTX = ParallelCtx(remat="none")


def _cfg(arch="smollm_360m"):
    return dataclasses.replace(configs.reduced(arch), dtype="float32")


def _burst(n, vocab, prompt_len, gen, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(request_id=i,
                tokens=rng.integers(0, vocab, prompt_len).astype(np.int32),
                max_new_tokens=gen, arrival=0.0)
        for i in range(n)
    ]


def _spilling_engine(pool_dtype="fp", substrate="auto"):
    """A small engine whose local budget forces pool placement."""
    cfg = _cfg()
    ecfg = EngineConfig(
        n_slots=2, max_seq=32, prefill_buckets=(8,), page_tokens=4,
        hot_window=4, local_budget_frac=0.35, admission="greedy",
        pool_dtype=pool_dtype, substrate=substrate,
    )
    return cfg, ServingEngine.build(cfg, CTX, ecfg)


# -------------------------------------------- capability mode resolver
@pytest.mark.parametrize("requested", ["off", "emulated"])
@pytest.mark.parametrize("host_input", [False, True])
@pytest.mark.parametrize("internal", [False, True])
def test_resolve_fixed_modes_ignore_probes(requested, host_input,
                                           internal):
    assert capability.resolve_substrate_mode(
        requested, host_input=host_input, host_output=False,
        internal=internal) == requested


@pytest.mark.parametrize("host_input,internal,expect", [
    (True, True, "physical"),
    (True, False, "emulated"),
    (False, True, "emulated"),
    (False, False, "emulated"),
])
def test_resolve_auto_follows_probes(host_input, internal, expect):
    assert capability.resolve_substrate_mode(
        "auto", host_input=host_input, host_output=True,
        internal=internal) == expect
    # page_out lands in the host twin through an output sharding: without
    # host output placement (XLA:CPU) nothing is physical
    assert capability.resolve_substrate_mode(
        "auto", host_input=host_input, host_output=False,
        internal=internal) == "emulated"


@pytest.mark.parametrize("host_input,internal", [
    (True, False), (False, True), (False, False),
])
def test_resolve_physical_requires_both_probes(host_input, internal):
    for host_output in (False, True):
        with pytest.raises(RuntimeError, match="physical"):
            capability.resolve_substrate_mode(
                "physical", host_input=host_input, host_output=host_output,
                internal=internal)
    with pytest.raises(RuntimeError, match="physical"):
        capability.resolve_substrate_mode(
            "physical", host_input=True, host_output=False, internal=True)
    assert capability.resolve_substrate_mode(
        "physical", host_input=True, host_output=True,
        internal=True) == "physical"


def test_resolve_rejects_unknown_mode():
    with pytest.raises(ValueError, match="substrate"):
        capability.resolve_substrate_mode(
            "hbm", host_input=True, host_output=True, internal=True)


@pytest.mark.parametrize("platform,surfaces", [("cpu", False),
                                               ("tpu", True)])
def test_probe_failure_surfaces_only_on_tpu(monkeypatch, platform,
                                            surfaces):
    """A probe that raises reads as "unsupported" on the CPU, where the
    emulated substrate is the designed fallback; on a TPU it is a fault
    and must surface instead of quietly demoting the substrate."""
    def refused():
        raise RuntimeError("UNIMPLEMENTED: pinned_host placement")

    monkeypatch.setattr(capability.jax, "default_backend", lambda: platform)
    if surfaces:
        with pytest.raises(RuntimeError, match="pinned_host"):
            capability._probe(refused)
    else:
        assert capability._probe(refused) is False
    assert capability._probe(lambda: None) is True


def test_substrate_mode_probes_this_backend():
    """On any backend the probed resolution is a valid mode and agrees
    with the pure resolver fed the same probes."""
    mode = capability.substrate_mode("auto")
    assert mode in ("physical", "emulated")
    assert mode == capability.resolve_substrate_mode(
        "auto",
        host_input=capability.supports_host_input(),
        host_output=capability.supports_host_output(),
        internal=capability.supports_internal_transfer(),
    )


# ------------------------------------------------------ ledger contract
def test_ledger_placement_and_byte_accounting():
    led = SubstrateLedger(page_bytes=100.0, mode="emulated")
    led.record("page_out", 4, step=0)
    assert led.placement_bytes() == 400.0
    led.record("page_in", 1, step=1)
    led.record("drop", 2, step=1)
    assert led.resident_pages == 1
    led.record("handoff", 3, step=2)       # moves bytes, placement flat
    c = led.counters()
    assert c["placement_bytes"] == 100.0
    assert c["page_out_bytes"] == 400.0
    assert c["page_in_bytes"] == 100.0
    assert c["drop_bytes"] == 0.0          # frees move nothing
    assert c["handoff_bytes"] == 300.0
    assert c["events"] == 4 and c["in_flight"] == 0


def test_ledger_rejects_unknown_kind():
    with pytest.raises(ValueError, match="stream kind"):
        SubstrateLedger(1.0, "emulated").record("promote", 1, step=0)


def test_ledger_shapes_identical_across_modes():
    """The emulated fallback must report byte accounting in EXACTLY the
    physical ledger's shape — same counter keys, same event fields — so
    CPU CI exercises the same contract the pinned_host path serves."""
    counters = {}
    for mode in ("physical", "emulated"):
        led = SubstrateLedger(page_bytes=64.0, mode=mode)
        led.record("page_out", 2, step=0)
        led.record("page_in", 1, step=1)
        led.record("drop", 1, step=2)
        led.record("handoff", 1, step=3)
        counters[mode] = led.counters()
        assert led.events[0].mode == mode
    phys, emu = counters["physical"], counters["emulated"]
    assert set(phys) == set(emu)
    for k in phys:
        if k != "mode":
            assert phys[k] == emu[k], k
    assert {f.name for f in dataclasses.fields(led.events[0])} >= {
        "step", "kind", "n_pages", "bytes", "mode", "completed"}


# ------------------------------------------------- TierSubstrate drains
def test_tier_substrate_mode_must_be_resolved():
    cfg = _cfg()
    caches = M.make_paged_decode_caches(cfg, 2, 32, 8)
    for bad in ("auto", "off", "hbm"):
        with pytest.raises(ValueError, match="resolve"):
            TierSubstrate(caches, None, bad)


def test_pool_twin_mirrors_paged_leaves_only():
    cfg = _cfg()
    caches = M.make_paged_decode_caches(cfg, 2, 32, 8,
                                        pool_dtype="int8")
    pool = paged_pool_leaves(caches)
    assert pool
    for pos, sub in pool.items():
        assert set(sub) <= set(PAGED_LEAF_NAMES)
        for name, leaf in sub.items():
            assert leaf is caches[pos][name]


def test_substrate_disabled_on_ssm_only_stack():
    cfg = _cfg("mamba2_780m")
    eng = ServingEngine.build(cfg, CTX, EngineConfig(
        n_slots=2, max_seq=32, prefill_buckets=(8,), page_tokens=8,
        admission="greedy", pool_dtype="fp", substrate="auto",
    ))
    assert eng.substrate is None           # no paged KV leaves to place
    sub = TierSubstrate(eng.caches, None, "emulated")
    assert not sub.enabled
    assert sub.drain(eng.pager, eng.caches) == {}
    assert sub.counters()["events"] == 0


def test_substrate_off_disables_wiring():
    _, eng = _spilling_engine(substrate="off")
    assert eng.substrate is None
    eng.run(_burst(2, 100, 8, 4), max_steps=6)   # runs fine without it


def test_placement_contract_holds_mid_run():
    """The tentpole acceptance, checked at EVERY decode step (not just
    at the drained end): after each drain the ledger's measured
    placement bytes equal the pager's derived pool footprint, and the
    measured page bytes match the pager's closed-form page bytes."""
    cfg, eng = _spilling_engine()
    assert eng.substrate is not None
    assert eng.substrate.mode == capability.substrate_mode("auto")
    assert eng.substrate.page_bytes == pytest.approx(
        eng.pager.page_bytes)
    reqs = _burst(4, cfg.vocab_size, 8, 6, seed=3)
    q = RequestQueue(reqs)
    cap = eng.begin_capture()
    checks = 0
    while len(q) or eng.batcher.n_busy:
        act = eng.pump(q)
        if act == "decode":
            # slot retirements free pages AFTER the in-step drain, so
            # reconcile before reading — the contract is "after every
            # drain", and a drain with no tier changes is a no-op
            eng.substrate.drain(eng.pager, eng.caches, step=eng.steps)
            assert eng.pager.pool_bytes_used() == pytest.approx(
                eng.substrate.counters()["placement_bytes"])
            checks += 1
        elif act == "idle":
            break
    stats = eng.capture_stats(cap, reqs)
    assert checks > 0, "trace never decoded"
    assert eng.substrate.counters()["events"] > 0, (
        "trace never exercised the substrate")
    assert eng.substrate.counters()["in_flight"] == 0   # capture syncs
    s = stats.summary()
    assert s["substrate_transfer_bytes"] > 0
    assert s["substrate_placement_bytes"] == pytest.approx(
        eng.pager.pool_bytes_used())


def test_drain_reconciles_out_in_drop_streams():
    """Page-out on first spill, page-in on promotion, drop on free —
    observed end-to-end over a run that admits, spills and completes."""
    cfg, eng = _spilling_engine()
    eng.run(_burst(4, cfg.vocab_size, 8, 6, seed=3))
    eng.substrate.sync()
    c = eng.substrate.counters()
    assert c["page_out_pages"] > 0
    assert c["drop_pages"] > 0             # completed slots freed pages
    assert c["page_out_bytes"] == pytest.approx(
        c["page_out_pages"] * eng.substrate.page_bytes)
    assert c["in_flight"] == 0
    # final reconciliation: whatever the pager still holds in the pool
    # is exactly what the ledger says is host-resident
    assert eng.pager.pool_bytes_used() == pytest.approx(
        c["placement_bytes"])


def test_handoff_recording():
    _, eng = _spilling_engine()
    eng.run(_burst(2, 100, 8, 4))
    before = eng.substrate.counters()
    eng.substrate.record_handoff(3, step=eng.steps)
    c = eng.substrate.counters()
    assert c["handoff_pages"] == before["handoff_pages"] + 3
    assert c["handoff_bytes"] == pytest.approx(
        before["handoff_bytes"] + 3 * eng.substrate.page_bytes)
    # handoffs move bytes but never change placement
    assert c["resident_pages"] == before["resident_pages"]
    eng.substrate.record_handoff(0)        # no-op, not an event
    assert eng.substrate.counters()["events"] == c["events"]


def test_int8_pool_substrate_measures_quantized_bytes():
    """With the int8 default pool the twin carries the quantized payload
    plus scale planes, and measured page bytes track the pager's
    dtype-aware accounting (the ~4x cut is the point of the flip)."""
    cfg, eng8 = _spilling_engine(pool_dtype="int8")
    _, engf = _spilling_engine(pool_dtype="fp")
    assert eng8.substrate.page_bytes == pytest.approx(
        eng8.pager.page_bytes)
    assert eng8.substrate.page_bytes < 0.5 * engf.substrate.page_bytes
    eng8.run(_burst(4, cfg.vocab_size, 8, 6, seed=3))
    eng8.substrate.sync()
    assert eng8.pager.pool_bytes_used() == pytest.approx(
        eng8.substrate.counters()["placement_bytes"])


class _PoolSet:
    """The two pager reads `TierSubstrate.drain` makes."""

    def __init__(self, ids, n_phys):
        self.ids = np.asarray(ids, dtype=np.int64)
        self.ref = np.ones(n_phys, dtype=np.int64)

    def pool_page_ids(self):
        return self.ids


def _random_pool(caches, seed=0):
    rng = np.random.default_rng(seed)
    out = {}
    for pos, c in caches.items():
        out[pos] = dict(c)
        for name in PAGED_LEAF_NAMES:
            if name in c:
                a = rng.integers(-127, 128, c[name].shape)
                out[pos][name] = jnp.asarray(a.astype(c[name].dtype))
    return out


@pytest.mark.parametrize("pool_dtype", ["fp", "int8"])
def test_page_streams_round_trip_pool_pages(pool_dtype):
    """page_out lands each pool page's bytes in the twin and page_in
    returns exactly those bytes in the pool's own layout."""
    caches = _random_pool(M.make_paged_decode_caches(
        _cfg(), 2, 32, 8, pool_dtype=pool_dtype))
    sub = TierSubstrate(caches, None, capability.substrate_mode("auto"))
    sub.drain(_PoolSet([1, 3, 4], sub.n_phys), caches)
    ids = [4, 1, 3, 3]
    got = sub._page_in_fn(sub.twin, jnp.asarray(ids, jnp.int32))
    for pos, leaves in got.items():
        for name, pages in leaves.items():
            want = np.asarray(caches[pos][name])[:, ids]
            np.testing.assert_array_equal(np.asarray(pages), want)


@pytest.mark.parametrize("shape,dim,shards", [
    ((3, 5, 4, 6, 16), 3, 2),     # k/v (nb, P, page, KV, hd), KV over 2
    ((3, 5, 4, 6, 16), 3, 3),
    ((3, 5, 6, 2), 2, 2),         # per-page (scale, zero) (nb, P, KV, 2)
    ((3, 5, 4, 6, 16), None, 1),  # replicated
])
def test_twin_row_layout_is_per_shard(shape, dim, shards):
    """Each shard's twin rows hold exactly that shard's slice of the
    split dim, and `from_rows` inverts `to_rows`."""
    from repro.serving.substrate.tier_substrate import RowLayout

    x = jnp.asarray(np.random.default_rng(1).normal(size=shape),
                    jnp.float32)
    ids = jnp.asarray([4, 0, 2], jnp.int32)
    per_shard = int(np.prod(shape[:1] + shape[2:])) // shards
    lay = RowLayout(shape, dim, ("model",) if dim else None, shards,
                     -(-per_shard // 1024) * 8)
    rows = lay.to_rows(x, ids)
    assert rows.shape == (3, shards, lay.rows, 128)
    np.testing.assert_array_equal(np.asarray(lay.from_rows(rows)),
                                  np.asarray(x[:, ids]))
    if dim is None:
        return
    width = shape[dim] // shards
    for s in range(shards):
        part = jax.lax.slice_in_dim(x, s * width, (s + 1) * width, axis=dim)
        one = RowLayout(part.shape, None, None, 1, lay.rows)
        np.testing.assert_array_equal(np.asarray(rows[:, s:s + 1]),
                                      np.asarray(one.to_rows(part, ids)))
