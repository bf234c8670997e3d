"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs the pure-jnp oracle
(deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as dops
from repro.kernels.decode_attention import ref as dref
from repro.kernels.decode_attention.decode_attention import flash_decode
from repro.kernels.decode_attention.paged import paged_flash_decode
from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_attention import ref as fref
from repro.kernels.flash_attention.chunked import mha_chunked
from repro.kernels.flash_attention.flash_attention import flash_mha
from repro.kernels.flash_attention.paged_prefill import paged_prefill_flash
from repro.kernels.lbench import ref as lref
from repro.kernels.lbench.lbench import lbench_pallas
from repro.kernels.ssd_scan import ref as sref
from repro.kernels.ssd_scan.chunked import ssd_chunked_jnp
from repro.kernels.ssd_scan.ssd_scan import ssd_pallas

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ------------------------------------------------------------- lbench
@pytest.mark.parametrize("nflop", [1, 2, 5, 16, 32])
@pytest.mark.parametrize("n", [512, 4096])
def test_lbench_sweep(nflop, n):
    a = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32)
    r = lref.lbench(a, nflop)
    p = lbench_pallas(a, nflop, interpret=True)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


def test_lbench_flops_model():
    assert lref.flops(100, 1) == 100
    assert lref.flops(100, 2) == 200
    assert lref.flops(100, 5) == 500
    assert lref.bytes_moved(100) == 800


# ----------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "B,Sq,Skv,H,KV,D,causal,dtype",
    [
        (2, 256, 256, 4, 2, 64, True, jnp.float32),
        (1, 512, 512, 4, 1, 128, True, jnp.float32),
        (2, 128, 512, 4, 4, 64, True, jnp.float32),   # decode-window offset
        (2, 256, 256, 4, 2, 64, False, jnp.float32),
        (2, 256, 256, 8, 2, 64, True, jnp.bfloat16),
    ],
)
def test_flash_pallas_sweep(B, Sq, Skv, H, KV, D, causal, dtype):
    off = Skv - Sq if Skv != Sq else 0
    ks = jax.random.split(jax.random.PRNGKey(Sq + H), 3)
    q = _rand(ks[0], (B, Sq, H, D), dtype)
    k = _rand(ks[1], (B, Skv, KV, D), dtype)
    v = _rand(ks[2], (B, Skv, KV, D), dtype)
    r = fref.mha(q, k, v, causal=causal, kv_offset=off)
    p = flash_mha(q, k, v, causal, None, off, 128, 128, True)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(p, np.float32), np.asarray(r, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_chunked_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (2, 256, 4, 64), jnp.float32)
    k = _rand(ks[1], (2, 256, 2, 64), jnp.float32)
    v = _rand(ks[2], (2, 256, 2, 64), jnp.float32)
    g1 = jax.grad(lambda *a: (mha_chunked(*a, True, None, 0, 64, 64) ** 2)
                  .sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (fref.mha(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_flash_pallas_bwd_pairing():
    """Pallas fwd (interpret) + chunked bwd == oracle grads."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (1, 256, 4, 64), jnp.float32)
    k = _rand(ks[1], (1, 256, 2, 64), jnp.float32)
    v = _rand(ks[2], (1, 256, 2, 64), jnp.float32)
    g1 = jax.grad(
        lambda *a: (flash_mha(*a, True, None, 0, 128, 128, True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g2 = jax.grad(lambda *a: (fref.mha(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


# ----------------------------------------------------- decode attention
@pytest.mark.parametrize(
    "B,S,H,KV,D,dtype",
    [
        (2, 512, 8, 2, 64, jnp.float32),
        (1, 1024, 4, 4, 128, jnp.float32),
        (3, 256, 6, 2, 32, jnp.float32),
        (2, 512, 8, 2, 64, jnp.bfloat16),
    ],
)
def test_decode_pallas_sweep(B, S, H, KV, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + D), 3)
    q = _rand(ks[0], (B, H, D), dtype)
    k = _rand(ks[1], (B, S, KV, D), dtype)
    v = _rand(ks[2], (B, S, KV, D), dtype)
    length = jnp.array([(S // 2 + 7 * i) % S + 1 for i in range(B)],
                       jnp.int32)
    r = dref.decode_mha(q, k, v, length)
    p = flash_decode(q, k, v, length, interpret=True, block_k=128)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(p, np.float32), np.asarray(r, np.float32),
        rtol=tol, atol=tol,
    )


def test_decode_ragged_lengths():
    B, S, H, KV, D = 4, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    length = jnp.array([1, 17, 100, 256], jnp.int32)
    r = dref.decode_mha(q, k, v, length)
    p = flash_decode(q, k, v, length, interpret=True, block_k=64)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# ----------------------------------------------- paged decode attention
def _paged_layout(k, v, page, seed=0, extra_phys=3):
    """Scatter a dense (B,S,KV,D) cache into a permuted physical page
    pool + block tables (non-contiguous, interleaved physical order)."""
    B, S, KV, D = k.shape
    n_log = S // page
    n_phys = B * n_log + extra_phys          # a few never-mapped pages
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_phys)[: B * n_log]
    bt = perm.reshape(B, n_log).astype(np.int32)
    kp = np.asarray(
        jax.random.normal(jax.random.PRNGKey(99), (n_phys, page, KV, D))
    ).astype(np.asarray(k).dtype)            # garbage in unmapped pages
    vp = kp.copy()
    kr = np.asarray(k).reshape(B, n_log, page, KV, D)
    vr = np.asarray(v).reshape(B, n_log, page, KV, D)
    for b in range(B):
        for i in range(n_log):
            kp[bt[b, i]] = kr[b, i]
            vp[bt[b, i]] = vr[b, i]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


def _quant_pool(kp, vp, sz):
    """The pool as the kernel takes it: fp pages (sz "none"), or int8
    pages with per-page / per-token (scale, zero) leaves."""
    if sz == "none":
        return kp, vp, {}
    from repro.kernels import quant

    quantize = quant.quantize_pages if sz == "page" else quant.quantize_tokens
    k8, ksz = quantize(jnp.asarray(kp, jnp.float32))
    v8, vsz = quantize(jnp.asarray(vp, jnp.float32))
    return k8, v8, {"k_sz": ksz, "v_sz": vsz}


def _paged_ref(q, kp, vp, bt, lengths, sz_kw):
    """The oracle over the same (possibly quantized) pool."""
    return dops.paged_decode_mha(q, kp, vp, bt, lengths, impl="reference",
                                 **sz_kw)


@pytest.mark.parametrize("sz", ["none", "page", "token"])
@pytest.mark.parametrize("page", [16, 64, 128])
@pytest.mark.parametrize(
    "B,S,H,KV,D,dtype",
    [
        (2, 512, 8, 2, 64, jnp.float32),
        (1, 256, 4, 4, 128, jnp.float32),
        (2, 512, 8, 2, 64, jnp.bfloat16),
    ],
)
def test_paged_decode_matches_dense_ref(sz, page, B, S, H, KV, D, dtype):
    """Acceptance: the paged kernel == the dense oracle token-for-token
    across page sizes {16, 64, 128} with scattered physical pages and
    ragged lengths; with an int8 pool (per-page or per-token scales) ==
    the dequant-gather oracle over the same pool."""
    ks = jax.random.split(jax.random.PRNGKey(S + D + page), 3)
    q = _rand(ks[0], (B, H, D), dtype)
    k = _rand(ks[1], (B, S, KV, D), dtype)
    v = _rand(ks[2], (B, S, KV, D), dtype)
    lengths = jnp.array([(S // 2 + 17 * i) % S + 1 for i in range(B)],
                        jnp.int32)
    kp, vp, bt = _paged_layout(k, v, page, seed=page)
    kp, vp, sz_kw = _quant_pool(kp, vp, sz)
    if sz == "none":
        r = dref.decode_mha(q, k, v, lengths)
    else:
        r = _paged_ref(q, kp, vp, bt, lengths, sz_kw)
    p = paged_flash_decode(q, kp, vp, bt, lengths, interpret=True, **sz_kw)
    tol = TOL[dtype]
    np.testing.assert_allclose(
        np.asarray(p, np.float32), np.asarray(r, np.float32),
        rtol=tol, atol=tol,
    )


def _block_tokens(page, KV, D, dtype, sz, n_pages):
    from repro.kernels.decode_attention import paged as pg

    return page * pg.block_pages(page, KV, D, dtype, sz, n_pages)


@pytest.mark.parametrize("sz", ["none", "page", "token"])
def test_paged_decode_ragged_lengths_at_page_and_block_edges(sz):
    """Lengths of 1, a page, a page + 1, a block (the kernel's pages a
    grid step), a block + 1 and the whole table, in one call."""
    S, H, KV, D, page = 1024, 4, 2, 64, 16
    bk = _block_tokens(page, KV, D, jnp.int8 if sz != "none"
                       else jnp.float32, sz, S // page)
    assert page < bk < S
    lengths = jnp.array([1, page, page + 1, bk, bk + 1, S], jnp.int32)
    B = lengths.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(k, v, page, seed=4)
    kp, vp, sz_kw = _quant_pool(kp, vp, sz)
    r = _paged_ref(q, kp, vp, bt, lengths, sz_kw)
    p = dops.paged_decode_mha(q, kp, vp, bt, lengths, impl="interpret",
                              **sz_kw)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)
    if sz == "none":
        np.testing.assert_allclose(
            np.asarray(p), np.asarray(dref.decode_mha(q, k, v, lengths)),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "interpret"])
@pytest.mark.parametrize("sz", ["none", "page"])
def test_paged_decode_parked_and_empty_rows_are_zero(impl, sz):
    """A parked row (length past the table: the engine parks idle slots
    at n_pages * page, so t + 1 lands one past it) and a row of length 0
    have no live page: zeros, finite, on the kernel and the oracle; the
    live rows of the same call are untouched."""
    B, S, H, KV, D, page = 4, 256, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(k, v, page, seed=2)
    kp, vp, sz_kw = _quant_pool(kp, vp, sz)
    lengths = jnp.array([S + 1, 40, 0, S], jnp.int32)
    out = np.asarray(dops.paged_decode_mha(q, kp, vp, bt, lengths,
                                           impl=impl, **sz_kw))
    assert np.isfinite(out).all()
    assert (out[[0, 2]] == 0).all()
    live = _paged_ref(q[1::2], kp, vp, bt[1::2], lengths[1::2], sz_kw)
    np.testing.assert_allclose(out[1::2], np.asarray(live), rtol=2e-5,
                               atol=2e-5)
    none = dops.paged_decode_mha(q, kp, vp, bt, jnp.array([0, S + 1] * 2),
                                 impl=impl, **sz_kw)
    assert (np.asarray(none) == 0).all()        # no live row at all


@pytest.mark.parametrize("sz", ["page", "token"])
def test_paged_decode_repeated_table_rows(sz):
    """The verify cell's call: B = slots x k rows, each slot's block-table
    row repeated k times, row j of a slot at length t + j + 1 (one of
    them past the table: parked)."""
    n_slots, k_rep, S, H, KV, D, page = 2, 3, 256, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    kd = _rand(ks[1], (n_slots, S, KV, D), jnp.float32)
    vd = _rand(ks[2], (n_slots, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(kd, vd, page, seed=3)
    kp, vp, sz_kw = _quant_pool(kp, vp, sz)
    t = np.array([page * 5 - 2, S - 2])
    lengths = jnp.asarray((t[:, None] + 1 + np.arange(k_rep)).reshape(-1),
                          jnp.int32)
    bt_rows = jnp.repeat(bt, k_rep, axis=0)
    B = n_slots * k_rep
    assert bt_rows.shape[0] == B != bt.shape[0]
    q = _rand(ks[0], (B, H, D), jnp.float32)
    r = dops.paged_decode_mha(q, kp, vp, bt_rows, lengths,
                              impl="reference", **sz_kw)
    p = dops.paged_decode_mha(q, kp, vp, bt_rows, lengths,
                              impl="interpret", **sz_kw)
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)
    assert (np.asarray(p)[-1] == 0).all()        # S - 2 + 2 + 1 > S


@pytest.mark.parametrize("sz", ["none", "page"])
def test_paged_decode_ignores_garbage_past_the_length(sz):
    """Everything past a row's length — the tail rows of its last page,
    the block-table entries after it and the physical pages they name —
    filled with large finite garbage leaves the output as it was; so does
    NaN in a physical page that no live entry names."""
    B, S, H, KV, D, page = 3, 512, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    lengths = np.array([37, 300, 16], np.int32)
    kp, vp, bt = _paged_layout(k, v, page, seed=5, extra_phys=4)
    kp, vp, bt = np.asarray(kp).copy(), np.asarray(vp).copy(), np.asarray(bt)
    n_log = S // page
    live = np.arange(n_log)[None, :] * page < lengths[:, None]
    spare = np.setdiff1d(np.arange(kp.shape[0]), bt[live])
    # reference output: dead table entries at a spare zero page, the
    # pages nothing live names zeroed, the tail rows of last pages zeroed
    clean_k, clean_v = kp.copy(), vp.copy()
    clean_k[spare] = 0.0
    clean_v[spare] = 0.0
    for b in range(B):
        last, off = divmod(int(lengths[b]), page)
        if off:
            clean_k[bt[b, last], off:] = 0.0
            clean_v[bt[b, last], off:] = 0.0
    clean_bt = np.where(live, bt, spare[0])
    dirty_k, dirty_v = clean_k.copy(), clean_v.copy()
    dirty_k[spare] = 3e4
    dirty_v[spare] = -3e4
    for b in range(B):
        last, off = divmod(int(lengths[b]), page)
        if off:
            dirty_k[bt[b, last], off:] = 2e4
            dirty_v[bt[b, last], off:] = -2e4
    dirty_bt = bt.copy()                 # dead entries name dirty pages
    dead_targets = spare[1:]
    dirty_bt[~live] = np.resize(dead_targets, (~live).sum())

    def run(kq, vq, table):
        kq, vq, sz_kw = _quant_pool(jnp.asarray(kq), jnp.asarray(vq), sz)
        return np.asarray(dops.paged_decode_mha(
            q, kq, vq, jnp.asarray(table), jnp.asarray(lengths),
            impl="interpret", **sz_kw))

    want = run(clean_k, clean_v, clean_bt)
    if sz == "none":
        np.testing.assert_allclose(
            want, np.asarray(dref.decode_mha(q, k, v, lengths)),
            rtol=2e-5, atol=2e-5)
    # with int8 pages the garbage in a live page's tail moves that page's
    # (scale, zero); the comparison is then with the oracle on that pool
    got = run(dirty_k, dirty_v, dirty_bt)
    if sz == "none":
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    else:
        kq, vq, sz_kw = _quant_pool(jnp.asarray(dirty_k),
                                    jnp.asarray(dirty_v), sz)
        np.testing.assert_allclose(
            got, np.asarray(_paged_ref(q, kq, vq, jnp.asarray(dirty_bt),
                                       jnp.asarray(lengths), sz_kw)),
            rtol=2e-5, atol=2e-5)
    nan_k, nan_v = dirty_k.copy(), dirty_v.copy()
    nan_k[dead_targets[0]] = np.nan
    nan_v[dead_targets[0]] = np.nan
    if sz == "none":
        got = run(nan_k, nan_v, dirty_bt)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    else:
        # NaN pages quantize to NaN scales: still never read
        got = run(nan_k, nan_v, dirty_bt)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, run(dirty_k, dirty_v, dirty_bt),
                                   rtol=0, atol=0)


def test_paged_ops_clamps_dead_table_entries():
    """ops.paged_decode_mha must tolerate garbage block-table entries
    past the valid length (the pager's freed/unallocated slots)."""
    B, S, H, KV, D, page = 2, 256, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    lengths = jnp.array([40, 200], jnp.int32)
    kp, vp, bt = _paged_layout(k, v, page)
    bt = np.asarray(bt).copy()
    n_phys = kp.shape[0]
    live = np.arange(bt.shape[1])[None, :] * page < np.asarray(lengths)[:, None]
    bt[~live] = n_phys + 10_000              # out-of-bounds garbage
    r = dref.decode_mha(q, k, v, lengths)
    for impl in ("reference", "interpret"):
        out = dops.paged_decode_mha(q, kp, vp, jnp.asarray(bt), lengths,
                                    impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


def test_paged_kernel_reads_kv_pager_block_table():
    """The pager's page grain is real at the kernel level: admit
    interleaved requests into a KVPager, lay K/V out physically by its
    block_table(), and the paged kernel must reproduce the dense oracle
    token-for-token."""
    from repro.serving.kv_pager import KVPager, PagerConfig

    B, H, KV, D, page_tokens = 3, 4, 2, 64, 16
    max_seq = 128
    pager = KVPager(
        B, max_seq, bytes_per_token=2.0 * KV * D * 2, resident_bytes=0.0,
        pcfg=PagerConfig(page_tokens=page_tokens, policy="none"),
    )
    # interleaved admits/releases scatter physical pages across slots
    pager.admit(0, 64)
    pager.admit(1, 128)
    pager.release(0)
    pager.admit(0, 96)
    pager.admit(2, 48)
    lengths = jnp.asarray(pager.lengths, jnp.int32)
    bt = pager.block_table()
    assert bt.shape == (B, max_seq // page_tokens)
    mapped = bt[pager.valid]
    assert len(set(mapped)) == len(mapped)    # no phys page shared

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, max_seq, KV, D), jnp.float32)
    v = _rand(ks[2], (B, max_seq, KV, D), jnp.float32)
    n_phys = B * (max_seq // page_tokens)
    kp = np.zeros((n_phys, page_tokens, KV, D), np.float32)
    vp = np.zeros_like(kp)
    kr = np.asarray(k).reshape(B, -1, page_tokens, KV, D)
    vr = np.asarray(v).reshape(B, -1, page_tokens, KV, D)
    for s, p in zip(*np.nonzero(pager.valid)):
        kp[bt[s, p]] = kr[s, p]
        vp[bt[s, p]] = vr[s, p]
    r = dref.decode_mha(q, k, v, lengths)
    out = dops.paged_decode_mha(q, jnp.asarray(kp), jnp.asarray(vp),
                                jnp.asarray(bt), lengths, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), rtol=2e-5,
                               atol=2e-5)


# -------------------------------------------------------------- SSD
@pytest.mark.parametrize(
    "B,S,H,P,G,N,Q",
    [
        (2, 256, 4, 16, 1, 32, 64),
        (1, 128, 4, 32, 2, 16, 32),
        (2, 128, 8, 16, 1, 64, 128),
        (1, 192, 2, 8, 1, 8, 64),    # non-pow2 S
    ],
)
def test_ssd_pallas_sweep(B, S, H, P, G, N, Q):
    ks = jax.random.split(jax.random.PRNGKey(S + N + P), 6)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    yr, hr = sref.ssd(x, dt, A, Bm, Cm, D)
    yp, hp = ssd_pallas(x, dt, A, Bm, Cm, D, None, Q, True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr), rtol=2e-4,
                               atol=2e-4)


def test_ssd_chunked_initial_state_and_grads():
    B, S, H, P, G, N = 1, 128, 4, 16, 1, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 7)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    D = jax.random.normal(ks[5], (H,))
    h0 = jax.random.normal(ks[6], (B, H, P, N)) * 0.1
    yr, hr = sref.ssd(x, dt, A, Bm, Cm, D, h0)
    yc, hc = ssd_chunked_jnp(x, dt, A, Bm, Cm, D, h0, 32)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yr), rtol=2e-4,
                               atol=2e-4)
    g1 = jax.grad(lambda x: (ssd_chunked_jnp(x, dt, A, Bm, Cm, D, h0, 32)[0]
                             ** 2).sum())(x)
    g2 = jax.grad(lambda x: (sref.ssd(x, dt, A, Bm, Cm, D, h0)[0]
                             ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4,
                               atol=2e-4)


def test_ssd_decode_matches_scan():
    B, H, P, G, N = 2, 4, 16, 1, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    state = jax.random.normal(ks[5], (B, H, P, N)) * 0.3
    x = jax.random.normal(ks[0], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bt = jax.random.normal(ks[3], (B, G, N))
    Ct = jax.random.normal(ks[4], (B, G, N))
    D = jnp.ones((H,))
    y1, s1 = sref.ssd_decode(x, dt, A, Bt, Ct, D, state)
    # one-step full scan from the same initial state
    y2, s2 = sref.ssd(x[:, None], dt[:, None], A, Bt[:, None], Ct[:, None],
                      D, state)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2[:, 0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)


# ----------------------------------------------- paged chunked prefill
@pytest.mark.parametrize("page", [16, 64, 128])
@pytest.mark.parametrize(
    "B,S,C,H,KV,D,dtype",
    [
        (2, 512, 128, 8, 2, 64, jnp.float32),
        (1, 256, 128, 4, 4, 128, jnp.float32),
        (2, 512, 128, 8, 2, 64, jnp.bfloat16),
    ],
)
def test_paged_prefill_matches_dense_ref(page, B, S, C, H, KV, D, dtype):
    """The chunked paged-prefill kernel == dense causal attention with a
    kv offset, across page sizes {16, 64, 128}, chunk offsets and
    scattered physical pages."""
    ks = jax.random.split(jax.random.PRNGKey(S + D + page + 1), 3)
    q = _rand(ks[0], (B, C, H, D), dtype)
    k = _rand(ks[1], (B, S, KV, D), dtype)
    v = _rand(ks[2], (B, S, KV, D), dtype)
    kp, vp, bt = _paged_layout(k, v, page, seed=page + 1)
    tol = TOL[dtype]
    for c0 in (0, C, S - C):                  # first / middle / last chunk
        r = fref.mha(q, k[:, : c0 + C], v[:, : c0 + C], causal=True,
                     kv_offset=c0)
        c0v = jnp.full((B,), c0, jnp.int32)
        p = paged_prefill_flash(q, kp, vp, bt, c0v, interpret=True)
        np.testing.assert_allclose(
            np.asarray(p, np.float32), np.asarray(r, np.float32),
            rtol=tol, atol=tol,
        )


def test_paged_prefill_ops_clamps_frontier_entries():
    """ops.paged_prefill_mha must tolerate garbage block-table entries
    above the causal frontier (pages the prompt has not reached yet)."""
    B, S, C, H, KV, D, page = 2, 256, 64, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (B, C, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(k, v, page)
    c0 = 64
    bt = np.asarray(bt).copy()
    live = np.arange(bt.shape[1])[None, :] * page < c0 + C
    bt[np.broadcast_to(~live, bt.shape)] = kp.shape[0] + 10_000
    r = fref.mha(q, k[:, : c0 + C], v[:, : c0 + C], causal=True,
                 kv_offset=c0)
    for impl in ("reference", "interpret"):
        out = fops.paged_prefill_mha(q, kp, vp, jnp.asarray(bt),
                                     jnp.full((B,), c0, jnp.int32),
                                     impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


def test_paged_prefill_chunk_walk_over_live_pager_table():
    """Chunked prefill against a LIVE KVPager block table: extend() the
    slot one chunk at a time, scatter each chunk's K/V through the
    table (`models.attention.paged_chunk_insert`), and every chunk's
    paged attention must equal the dense causal reference over the
    prefix — the end-to-end write-then-gather loop the serving engine
    runs."""
    from repro.models.attention import paged_chunk_insert
    from repro.serving.kv_pager import KVPager, PagerConfig

    B, H, KV, D = 1, 4, 2, 64
    page_tokens, C, S = 16, 32, 128
    pager = KVPager(
        2, S, bytes_per_token=2.0 * KV * D * 2, resident_bytes=0.0,
        pcfg=PagerConfig(page_tokens=page_tokens, policy="none"),
    )
    pager.admit(1, 40)                       # co-resident slot scatters
    slot = 0
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    k = _rand(ks[0], (B, S, KV, D), jnp.float32)
    v = _rand(ks[1], (B, S, KV, D), jnp.float32)
    n_phys = 2 * (S // page_tokens)
    kp = jnp.zeros((n_phys, page_tokens, KV, D), jnp.float32)
    vp = jnp.zeros_like(kp)
    for c0 in range(0, S, C):
        pager.extend(slot, c0 + C)
        row = jnp.asarray(pager.block_table()[slot][None, :])
        kp = paged_chunk_insert(kp, k[:, c0:c0 + C], c0, row, page_tokens)
        vp = paged_chunk_insert(vp, v[:, c0:c0 + C], c0, row, page_tokens)
        q = _rand(jax.random.fold_in(ks[2], c0), (B, C, H, D), jnp.float32)
        r = fref.mha(q, k[:, : c0 + C], v[:, : c0 + C], causal=True,
                     kv_offset=c0)
        for impl in ("reference", "interpret"):
            out = fops.paged_prefill_mha(q, kp, vp, row,
                                         jnp.full((B,), c0, jnp.int32),
                                         impl=impl)
            np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                                       rtol=2e-5, atol=2e-5)


# ------------------------------------- block-quantized (int8) page pools
from repro.kernels import quant
from repro.kernels.page_io import ops as pops


@pytest.mark.parametrize("page", [16, 64, 128])
def test_page_quant_roundtrip_error_bounded(page):
    """Satellite acceptance: per-page int8 round-trip error <= scale/2
    across page sizes {16, 64, 128} and adversarial ranges (all-zero
    page, single-outlier page)."""
    KV, D = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(page), (4, page, KV, D),
                          jnp.float32) * 3.0
    zero_page = jnp.zeros((1, page, KV, D), jnp.float32)
    outlier = jnp.zeros((1, page, KV, D), jnp.float32
                        ).at[0, page // 2, 1, 3].set(500.0)
    for pages in (x, zero_page, outlier):
        q8, sz = quant.quantize_pages(pages)
        back = quant.dequantize_pages(q8, sz)
        err = np.abs(np.asarray(back - pages))
        # bound per (page, head): half a quantization step
        bound = np.asarray(sz[..., 0])[:, None, :, None] / 2
        assert (err <= bound + 1e-6).all()
    # the all-zero page round-trips exactly
    q8, sz = quant.quantize_pages(zero_page)
    assert np.abs(np.asarray(quant.dequantize_pages(q8, sz))).max() == 0.0


@pytest.mark.parametrize("page", [16, 64, 128])
def test_paged_decode_quant_kernel_matches_quant_oracle(page):
    """The int8 decode kernel (scales on the scalar-prefetch channel,
    dequant epilogue) == the dequant-gather oracle exactly, and both
    track the fp dense oracle within the quantization drift."""
    B, S, H, KV, D = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(page), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    lengths = jnp.array([(S // 2 + 17 * i) % S + 1 for i in range(B)],
                        jnp.int32)
    kp, vp, bt = _paged_layout(k, v, page, seed=page)
    k8, ksz = quant.quantize_pages(kp)
    v8, vsz = quant.quantize_pages(vp)
    r = dops.paged_decode_mha(q, k8, v8, bt, lengths, k_sz=ksz, v_sz=vsz,
                              impl="reference")
    p = dops.paged_decode_mha(q, k8, v8, bt, lengths, k_sz=ksz, v_sz=vsz,
                              impl="interpret")
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)
    dense = dref.decode_mha(q, k, v, lengths)
    assert float(jnp.abs(p - dense).max()) < 0.05


def test_paged_prefill_quant_gather_matches_quant_oracle():
    """The int8 gather-only prefill kernel == the dequant-gather oracle."""
    B, S, C, H, KV, D, page = 1, 256, 64, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (B, C, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(k, v, page, seed=3)
    k8, ksz = quant.quantize_pages(kp)
    v8, vsz = quant.quantize_pages(vp)
    for c0 in (0, 64, S - C):
        c0v = jnp.full((B,), c0, jnp.int32)
        r = fops.paged_prefill_mha(q, k8, v8, bt, c0v, k_sz=ksz, v_sz=vsz,
                                   impl="reference")
        p = fops.paged_prefill_mha(q, k8, v8, bt, c0v, k_sz=ksz, v_sz=vsz,
                                   impl="interpret")
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


# --------------------------------------------- fused chunk insert+attend
def test_fused_prefill_insert_bit_for_bit_cache_parity_fp():
    """Acceptance: the fused insert+attend kernel (chunk write through
    input_output_aliases) produces BIT-FOR-BIT the same pool as the
    unfused scatter-then-attend reference in fp mode, with matching
    attention output, over a full chunk walk."""
    B, S, C, H, KV, D, page = 1, 256, 64, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    k = _rand(ks[0], (B, S, KV, D), jnp.float32)
    v = _rand(ks[1], (B, S, KV, D), jnp.float32)
    n_log = S // page
    n_phys = 2 * n_log
    rng = np.random.default_rng(5)
    bt = jnp.asarray(rng.permutation(n_phys)[:n_log]
                     .reshape(B, n_log).astype(np.int32))
    kp = jnp.zeros((n_phys, page, KV, D), jnp.float32)
    vp = jnp.zeros_like(kp)
    kp_ref, vp_ref = kp, vp
    for c0 in range(0, S, C):
        qc = _rand(jax.random.fold_in(ks[2], c0), (B, C, H, D),
                   jnp.float32)
        kn, vn = k[:, c0:c0 + C], v[:, c0:c0 + C]
        c0v = jnp.full((B,), c0, jnp.int32)
        o, kp, vp = fops.paged_prefill_insert_mha(
            qc, kp, vp, kn, vn, bt, c0v, impl="interpret")
        o_ref, kp_ref, vp_ref = fops.paged_prefill_insert_mha(
            qc, kp_ref, vp_ref, kn, vn, bt, c0v, impl="reference")
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(kp_ref))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(vp_ref))
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-5, atol=2e-5)
        dense = fref.mha(qc, k[:, :c0 + C], v[:, :c0 + C], causal=True,
                         kv_offset=c0)
        np.testing.assert_allclose(np.asarray(o), np.asarray(dense),
                                   rtol=2e-5, atol=2e-5)


def test_fused_prefill_insert_q8_parity():
    """The int8 fused kernel writes payload AND (scale, zero) arrays
    exactly like the unfused quantize-scatter-attend reference."""
    B, S, C, H, KV, D, page = 1, 128, 32, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    k = _rand(ks[0], (B, S, KV, D), jnp.float32)
    v = _rand(ks[1], (B, S, KV, D), jnp.float32)
    n_log = S // page
    n_phys = 2 * n_log
    rng = np.random.default_rng(7)
    bt = jnp.asarray(rng.permutation(n_phys)[:n_log]
                     .reshape(B, n_log).astype(np.int32))
    pools = {
        impl: [jnp.zeros((n_phys, page, KV, D), jnp.int8),
               jnp.zeros((n_phys, page, KV, D), jnp.int8),
               jnp.zeros((n_phys, KV, 2), jnp.float32),
               jnp.zeros((n_phys, KV, 2), jnp.float32)]
        for impl in ("interpret", "reference")
    }
    n_wp = C // page
    for c0 in range(0, S, C):
        qc = _rand(jax.random.fold_in(ks[2], c0), (B, C, H, D),
                   jnp.float32)
        k8, ksz = quant.quantize_pages(
            k[:, c0:c0 + C].reshape(B, n_wp, page, KV, D))
        v8, vsz = quant.quantize_pages(
            v[:, c0:c0 + C].reshape(B, n_wp, page, KV, D))
        k8, v8 = k8.reshape(B, C, KV, D), v8.reshape(B, C, KV, D)
        c0v = jnp.full((B,), c0, jnp.int32)
        outs = {}
        for impl in ("interpret", "reference"):
            kp, vp, kszp, vszp = pools[impl]
            outs[impl], kp, vp, kszp, vszp = \
                fops.paged_prefill_insert_mha_q8(
                    qc, kp, vp, kszp, vszp, k8, v8, ksz, vsz, bt, c0v,
                    impl=impl)
            pools[impl] = [kp, vp, kszp, vszp]
        for a, b in zip(pools["interpret"], pools["reference"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(outs["interpret"]),
                                   np.asarray(outs["reference"]),
                                   rtol=2e-5, atol=2e-5)


def test_page_writer_matches_scatter_and_is_scatter_free():
    """kernels.page_io: the aliased writer == the jnp scatter oracle on
    fp/int8 payloads and (scale, zero) rows, and its jaxpr contains no
    scatter primitive."""
    nb, P, page, KV, hd, n_wp = 2, 12, 8, 2, 16, 3
    pool = _rand(jax.random.PRNGKey(0), (nb, P, page, KV, hd),
                 jnp.float32)
    tiles = _rand(jax.random.PRNGKey(1), (nb, n_wp, page, KV, hd),
                  jnp.float32)
    phys = jnp.array([9, 0, 4], jnp.int32)
    a = pops.write_pages(pool, tiles, phys, impl="reference")
    b = pops.write_pages(pool, tiles, phys, impl="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sz = jnp.zeros((nb, P, KV, 2), jnp.float32)
    szt = _rand(jax.random.PRNGKey(2), (nb, n_wp, KV, 2), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pops.write_pages(sz, szt, phys, impl="interpret")),
        np.asarray(pops.write_pages(sz, szt, phys, impl="reference")))
    jx = jax.make_jaxpr(
        lambda *a: pops.write_pages(*a, impl="interpret")
    )(pool, tiles, phys)
    assert "scatter" not in repr(jx)


def test_chunked_prefill_cell_issues_zero_page_scatters():
    """Acceptance: with the kernels active (interpret backend, the same
    dispatch TPU takes), the whole chunked-prefill CELL — embedding,
    layer stack, paged attention, cache write — lowers to a jaxpr with
    ZERO scatter ops in BOTH pool dtypes: the chunk's KV write rides the
    paged-prefill kernel's output aliasing instead of a standalone jnp
    page scatter. The fp fused path's bit-for-bit cache parity vs the
    unfused oracle is asserted in
    `test_fused_prefill_insert_bit_for_bit_cache_parity_fp`."""
    import dataclasses

    from repro import configs, kernels
    from repro.common.parallel import ParallelCtx
    from repro.models import model as M
    from repro.runtime.serve import build_prefill_chunk

    cfg = dataclasses.replace(configs.reduced("smollm_360m"),
                              dtype="float32")
    ctx = ParallelCtx(remat="none")
    page, chunk, n_slots, max_seq = 4, 8, 2, 16
    params, _ = M.init_model(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((1, chunk), jnp.int32)
    bt = jnp.zeros((n_slots, max_seq // page), jnp.int32)
    kernels.force_backend("interpret")
    try:
        for pool_dtype in ("fp", "int8"):
            caches = M.make_paged_decode_caches(
                cfg, n_slots, max_seq, page, pool_dtype=pool_dtype)
            cell = build_prefill_chunk(cfg, ctx, page)
            jx = jax.make_jaxpr(cell)(
                params, toks, caches, jnp.int32(0), jnp.int32(0), bt)
            assert "scatter" not in repr(jx), pool_dtype
    finally:
        kernels.force_backend(None)


def test_select_impl_dispatch():
    """The shared dispatch helper all ops.py modules route through."""
    from repro.kernels import select_impl

    assert select_impl("reference") == ("reference", False)
    assert select_impl("interpret") == ("pallas", True)
    assert select_impl("pallas") == ("pallas", False)
    with pytest.raises(ValueError):
        select_impl("cuda")

# ------------------------- per-token sub-scales (speculative int8 pools)
def test_token_sz_roundtrip_tighter_than_page():
    """Per-token (scale, zero) rows are a strict refinement of per-page
    blocks: the round-trip error is bounded by half the TOKEN row's step
    and never exceeds the per-page round-trip error materially."""
    page, KV, D = 16, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(11), (4, page, KV, D),
                          jnp.float32)
    # one hot token per page stretches the page-level range
    x = x.at[:, 3].mul(50.0)
    q8t, szt = quant.quantize_tokens(x)
    back_t = quant.dequantize_tokens(q8t, szt)
    err_t = np.abs(np.asarray(back_t - x))
    bound_t = np.asarray(szt[..., 0])[..., None] / 2
    assert (err_t <= bound_t + 1e-6).all()
    q8p, szp = quant.quantize_pages(x)
    err_p = np.abs(np.asarray(quant.dequantize_pages(q8p, szp) - x))
    # the cold tokens next to the outlier are where per-page collapses
    assert err_t.mean() < err_p.mean()
    # all-zero rows round-trip exactly (MIN_SCALE floor, no 0/0)
    z8, zsz = quant.quantize_tokens(jnp.zeros_like(x))
    assert np.abs(np.asarray(quant.dequantize_tokens(z8, zsz))).max() == 0.0


@pytest.mark.parametrize("page", [16, 64])
def test_paged_decode_token_sz_matches_quant_oracle(page):
    """The decode kernel with PER-TOKEN sub-scales (k_sz/v_sz carrying a
    page_tokens axis) == the dequant-gather oracle, and tracks the fp
    dense oracle within a TIGHTER drift than the per-page path needs."""
    B, S, H, KV, D = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(page + 1), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    lengths = jnp.array([(S // 2 + 17 * i) % S + 1 for i in range(B)],
                        jnp.int32)
    kp, vp, bt = _paged_layout(k, v, page, seed=page)
    k8, ksz = quant.quantize_tokens(kp)
    v8, vsz = quant.quantize_tokens(vp)
    assert ksz.shape == (kp.shape[0], page, KV, 2)
    r = dops.paged_decode_mha(q, k8, v8, bt, lengths, k_sz=ksz, v_sz=vsz,
                              impl="reference")
    p = dops.paged_decode_mha(q, k8, v8, bt, lengths, k_sz=ksz, v_sz=vsz,
                              impl="interpret")
    np.testing.assert_allclose(np.asarray(p), np.asarray(r), rtol=2e-5,
                               atol=2e-5)
    dense = dref.decode_mha(q, k, v, lengths)
    assert float(jnp.abs(p - dense).max()) < 0.05


def test_paged_prefill_token_sz_gather_matches_quant_oracle():
    """The gather-only prefill kernel with per-token sub-scales == the
    dequant-gather oracle across chunk offsets."""
    B, S, C, H, KV, D, page = 1, 256, 64, 4, 2, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = _rand(ks[0], (B, C, H, D), jnp.float32)
    k = _rand(ks[1], (B, S, KV, D), jnp.float32)
    v = _rand(ks[2], (B, S, KV, D), jnp.float32)
    kp, vp, bt = _paged_layout(k, v, page, seed=13)
    k8, ksz = quant.quantize_tokens(kp)
    v8, vsz = quant.quantize_tokens(vp)
    for c0 in (0, 64, S - C):
        c0v = jnp.full((B,), c0, jnp.int32)
        r = fops.paged_prefill_mha(q, k8, v8, bt, c0v, k_sz=ksz, v_sz=vsz,
                                   impl="reference")
        p = fops.paged_prefill_mha(q, k8, v8, bt, c0v, k_sz=ksz, v_sz=vsz,
                                   impl="interpret")
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------ W8A8 int8 matmul cell
from repro.kernels.matmul_w8a8 import ops as w8ops


@pytest.mark.parametrize(
    "M_,K_,N_",
    [
        (128, 128, 128),    # exact single block
        (256, 384, 256),    # multi-block K walk (megacore grid)
        (130, 96, 200),     # ragged: every axis zero-padded to blocks
        (1, 128, 256),      # decode-like single row
    ],
)
def test_matmul_w8a8_pallas_matches_ref(M_, K_, N_):
    """The pallas W8A8 kernel (int32 VMEM accumulator, dequant epilogue
    on the last K step) == the pure-jnp int8 reference on exact and
    ragged shapes, and both track the fp matmul within the symmetric
    per-row/per-column quantization drift."""
    ka, kb = jax.random.split(jax.random.PRNGKey(M_ + K_ + N_))
    a = jax.random.normal(ka, (M_, K_), jnp.float32)
    b = jax.random.normal(kb, (K_, N_), jnp.float32) * 0.5
    a8, sa = w8ops.quantize_rows(a)             # per activation row
    b8, sb = w8ops.quantize_rows(b, axis=0)     # per weight column
    r = w8ops.matmul_w8a8(a8, b8, sa, sb, impl="reference")
    p = w8ops.matmul_w8a8(a8, b8, sa, sb, impl="interpret")
    np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                               rtol=1e-6, atol=1e-6)
    fp = a @ b
    denom = max(float(jnp.abs(fp).max()), 1e-6)
    assert float(jnp.abs(p - fp).max()) / denom < 0.05


def test_matmul_w8a8_zero_operands_exact():
    """All-zero operands survive the MIN_SCALE floor exactly (no 0/0)."""
    a8, sa = w8ops.quantize_rows(jnp.zeros((64, 128), jnp.float32))
    b8, sb = w8ops.quantize_rows(jnp.zeros((128, 64), jnp.float32),
                                 axis=0)
    out = w8ops.matmul_w8a8(a8, b8, sa, sb, impl="interpret")
    assert np.abs(np.asarray(out)).max() == 0.0
