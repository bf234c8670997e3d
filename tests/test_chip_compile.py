"""Compile the main-path Pallas kernels for a described TPU v5e at the
published widths of the served models, with no chip attached.

Interpret mode (the numerical oracle in test_kernels.py) cannot see what
Mosaic refuses: block tiling, scratch shapes, SMEM capacity, int8 sublane
packing. These tests hand the TPU compiler the real shapes and fail on
any such refusal. They compile only; nothing runs and nothing is timed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, so every worker must collect
the same tests and only the worker that runs this file loads it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import ops as dops
from repro.kernels.flash_attention import ops as fops
from repro.kernels.page_io import ops as pops
from repro.kernels.ssd_scan.ssd_scan import ssd_pallas

# smollm_360m (configs/smollm_360m.py) at the serving defaults
H, KV, HD, PAGE = 15, 5, 64, 16
N_PHYS = 4096                 # pool pages: a full-size pool, not a toy
SLOTS, N_LOG = 4, 64          # block table (slots, logical pages)
CHUNK = 128                   # prefill chunk
NB = 32                       # stacked attention layers


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("sz", ["page", "token", "none"])
def test_paged_decode_compiles(one_chip, sz):
    pool_dt = jnp.bfloat16 if sz == "none" else jnp.int8
    shapes = [((SLOTS, H, HD), jnp.bfloat16),
              ((N_PHYS, PAGE, KV, HD), pool_dt),
              ((N_PHYS, PAGE, KV, HD), pool_dt),
              ((SLOTS, N_LOG), jnp.int32),
              ((SLOTS,), jnp.int32)]
    if sz == "none":
        fn = functools.partial(dops.paged_decode_mha, impl="pallas")
    else:
        sz_shape = ((N_PHYS, KV, 2) if sz == "page"
                    else (N_PHYS, PAGE, KV, 2))
        shapes += [(sz_shape, jnp.float32)] * 2

        def fn(q, kp, vp, bt, ln, ksz, vsz):
            return dops.paged_decode_mha(q, kp, vp, bt, ln, k_sz=ksz,
                                         v_sz=vsz, impl="pallas")
    _compile(one_chip, fn, *shapes)


def test_paged_decode_compiles_at_benchmark_shapes(one_chip):
    """The benchmark's smollm_360m pool: 64 slots x 128 logical pages of
    16 tokens, 8,192 physical pages, int8 with per-page scales."""
    slots, n_log = 64, 128
    def fn(q, kp, vp, bt, ln, ksz, vsz):
        return dops.paged_decode_mha(q, kp, vp, bt, ln, k_sz=ksz, v_sz=vsz,
                                     impl="pallas")
    _compile(one_chip, fn,
             ((slots, H, HD), jnp.bfloat16),
             ((slots * n_log, PAGE, KV, HD), jnp.int8),
             ((slots * n_log, PAGE, KV, HD), jnp.int8),
             ((slots, n_log), jnp.int32),
             ((slots,), jnp.int32),
             ((slots * n_log, KV, 2), jnp.float32),
             ((slots * n_log, KV, 2), jnp.float32))


def test_paged_decode_compiles_mistral_nemo_tp_shard(one_chip):
    """One of four tp shards of mistral_nemo_12b: 32/4 query heads over
    8/4 KV heads, head_dim 128, int8 pool with per-page scales."""
    h, kv, hd = 8, 2, 128
    def fn(q, kp, vp, bt, ln, ksz, vsz):
        return dops.paged_decode_mha(q, kp, vp, bt, ln, k_sz=ksz, v_sz=vsz,
                                     impl="pallas")
    _compile(one_chip, fn,
             ((SLOTS, h, hd), jnp.bfloat16),
             ((N_PHYS, PAGE, kv, hd), jnp.int8),
             ((N_PHYS, PAGE, kv, hd), jnp.int8),
             ((SLOTS, N_LOG), jnp.int32),
             ((SLOTS,), jnp.int32),
             ((N_PHYS, kv, 2), jnp.float32),
             ((N_PHYS, kv, 2), jnp.float32))


def test_dense_decode_compiles(one_chip):
    """The contiguous-cache decode kernel (draft model, cross-attention
    decode)."""
    S = 1024
    _compile(one_chip, functools.partial(dops.decode_mha, impl="pallas"),
             ((SLOTS, H, HD), jnp.bfloat16),
             ((SLOTS, S, KV, HD), jnp.bfloat16),
             ((SLOTS, S, KV, HD), jnp.bfloat16),
             ((SLOTS,), jnp.int32))


def test_dense_flash_prefill_compiles(one_chip):
    S = 512
    _compile(one_chip, functools.partial(fops.mha, impl="pallas"),
             ((1, S, H, HD), jnp.bfloat16),
             ((1, S, KV, HD), jnp.bfloat16),
             ((1, S, KV, HD), jnp.bfloat16))


def test_fused_bf16_paged_prefill_insert_compiles(one_chip):
    fn = functools.partial(fops.paged_prefill_insert_mha, impl="pallas")
    _compile(one_chip, fn,
             ((1, CHUNK, H, HD), jnp.bfloat16),
             ((N_PHYS, PAGE, KV, HD), jnp.bfloat16),
             ((N_PHYS, PAGE, KV, HD), jnp.bfloat16),
             ((1, CHUNK, KV, HD), jnp.bfloat16),
             ((1, CHUNK, KV, HD), jnp.bfloat16),
             ((1, N_LOG), jnp.int32),
             ((1,), jnp.int32))


def test_paged_prefill_gather_token_scales_compiles(one_chip):
    """The speculative-decoding pool: per-token (scale, zero) rows."""
    def fn(q, kp, vp, bt, c0, ksz, vsz):
        return fops.paged_prefill_mha(q, kp, vp, bt, c0, k_sz=ksz, v_sz=vsz,
                                      impl="pallas")
    _compile(one_chip, fn,
             ((1, CHUNK, H, HD), jnp.bfloat16),
             ((N_PHYS, PAGE, KV, HD), jnp.int8),
             ((N_PHYS, PAGE, KV, HD), jnp.int8),
             ((1, N_LOG), jnp.int32),
             ((1,), jnp.int32),
             ((N_PHYS, PAGE, KV, 2), jnp.float32),
             ((N_PHYS, PAGE, KV, 2), jnp.float32))


def test_fused_int8_paged_prefill_insert_compiles(one_chip):
    fn = functools.partial(fops.paged_prefill_insert_mha_q8, impl="pallas")
    _compile(one_chip, fn,
             ((1, CHUNK, H, HD), jnp.bfloat16),
             ((N_PHYS, PAGE, KV, HD), jnp.int8),
             ((N_PHYS, PAGE, KV, HD), jnp.int8),
             ((N_PHYS, KV, 2), jnp.float32),
             ((N_PHYS, KV, 2), jnp.float32),
             ((1, CHUNK, KV, HD), jnp.int8),
             ((1, CHUNK, KV, HD), jnp.int8),
             ((1, CHUNK // PAGE, KV, 2), jnp.float32),
             ((1, CHUNK // PAGE, KV, 2), jnp.float32),
             ((1, N_LOG), jnp.int32),
             ((1,), jnp.int32))


@pytest.mark.parametrize("leaf", ["payload", "scales"])
def test_write_pages_compiles(one_chip, leaf):
    n_wp = 512 // PAGE
    if leaf == "payload":
        dims, dt = (PAGE, KV, HD), jnp.int8
    else:
        dims, dt = (KV, 2), jnp.float32
    _compile(one_chip, functools.partial(pops.write_pages, impl="pallas"),
             ((NB, N_PHYS) + dims, dt),
             ((NB, n_wp) + dims, dt),
             ((n_wp,), jnp.int32))


def test_ssd_scan_compiles_mamba2_780m(one_chip):
    """mamba2_780m: d_inner 3072 = 48 heads x 64, state 128, one group,
    chunk 128."""
    S, Hs, P, N = 1024, 48, 64, 128
    def fn(x, dt, A, Bm, Cm, D):
        return ssd_pallas(x, dt, A, Bm, Cm, D, None, 128, False)
    _compile(one_chip, fn,
             ((1, S, Hs, P), jnp.bfloat16),
             ((1, S, Hs), jnp.float32),
             ((Hs,), jnp.float32),
             ((1, S, 1, N), jnp.bfloat16),
             ((1, S, 1, N), jnp.bfloat16),
             ((Hs,), jnp.float32))


@pytest.mark.parametrize("arch,tp", [("smollm_360m", 1),
                                     ("mistral_nemo_12b", 4)])
def test_substrate_page_streams_compile(topo, one_chip, arch, tp):
    """The host substrate's page_out / page_in programs over a pinned_host
    twin of the int8 pool, at page bursts of 1 and 16, on one chip and
    with the pool's KV heads split over a 4-chip tp axis."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.models import model as M
    from repro.models.blocks import paged_pool_leaves
    from repro.runtime import sharding as shd
    from repro.serving.substrate import tier_substrate as ts

    cfg = configs.get(arch)
    mesh = Mesh(np.asarray(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"))
    pool = paged_pool_leaves(jax.eval_shape(
        lambda: M.make_paged_decode_caches(cfg, SLOTS, 1024, PAGE,
                                           pool_dtype="int8")))
    pspec = shd.paged_cache_pspec(pool, (), "model", mesh)
    layout = ts.twin_layout(pool, pspec, mesh)
    host_sh = ts.twin_shardings(layout, mesh, "pinned_host")
    twin = jax.tree.map(
        lambda lay, leaf, sh: jax.ShapeDtypeStruct(lay.twin_shape(),
                                                   leaf.dtype, sharding=sh),
        layout, pool, host_sh,
        is_leaf=lambda x: isinstance(x, ts.RowLayout))
    dev = jax.tree.map(
        lambda leaf, sh: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                              sharding=sh),
        pool, shd.named(mesh, pspec))
    page_out, page_in = ts.page_streams(layout, "physical")
    for n in (1, 16):
        ids = jax.ShapeDtypeStruct((n,), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        jax.jit(page_out, out_shardings=host_sh, donate_argnums=0).lower(
            twin, dev, ids).compile()
        jax.jit(page_in, out_shardings=shd.named(mesh, pspec)).lower(
            twin, ids).compile()


def test_engine_decode_cell_compiles_smollm_360m(one_chip):
    """The serving engine's paged int8 decode step for smollm_360m at its
    published widths: 32 scanned layers around the paged decode kernel."""
    from repro import configs, kernels
    from repro.common.parallel import ParallelCtx
    from repro.runtime import serve as serve_rt

    cfg = configs.get("smollm_360m")
    kernels.force_backend("pallas")
    try:
        cells = serve_rt.make_engine_cells(
            cfg, ParallelCtx(remat="none"), n_slots=SLOTS, max_seq=1024,
            buckets=(512,), paged=True, page_tokens=PAGE, pool_dtype="int8")

        def shaped(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)

        vec = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
        bt = jax.ShapeDtypeStruct((SLOTS, cells.n_pages), jnp.int32,
                                  sharding=one_chip)
        compiled = cells.decode_fn.lower(
            shaped(cells.abstract_params), vec,
            shaped(cells.abstract_caches), vec, bt).compile()
    finally:
        kernels.force_backend(None)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", ["decode", "chunk"])
def test_engine_cells_fit_mistral_nemo_12b_tp4(topo, one_chip, cell):
    """The benchmark's tp cell: mistral_nemo_12b at its published widths
    over a (data=1, model=4) mesh of the described v5e:2x2, 16 slots x
    8192 tokens of int8 pool in pages of 16, chunks of 512. Each cell's
    arguments plus temporaries a chip fit the chip's 16 GB of HBM."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import configs, kernels
    from repro.launch.mesh import ctx_for_mesh
    from repro.runtime import serve as serve_rt

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"))
    slots = 16
    kernels.force_backend("pallas")
    try:
        cells = serve_rt.make_engine_cells(
            configs.get("mistral_nemo_12b"),
            ctx_for_mesh(mesh, fsdp=False, remat="none"), mesh=mesh,
            n_slots=slots, max_seq=8192, buckets=(512,), paged=True,
            page_tokens=PAGE, prefill_chunk=512, pool_dtype="int8")

        def shaped(tree, shardings):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, shardings)

        rep = NamedSharding(mesh, P())
        params = shaped(cells.abstract_params, cells.param_shardings)
        caches = shaped(cells.abstract_caches, cells.cache_shardings)
        bt = jax.ShapeDtypeStruct((slots, cells.n_pages), jnp.int32,
                                  sharding=rep)
        if cell == "decode":
            vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep)
            compiled = cells.decode_fn.lower(params, vec, caches, vec,
                                             bt).compile()
        else:
            idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
            toks = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=rep)
            compiled = cells.chunk_fn.lower(params, toks, caches, idx, idx,
                                            bt).compile()
    finally:
        kernels.force_backend(None)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
