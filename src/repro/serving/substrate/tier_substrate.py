"""`TierSubstrate` — owns the host-resident pool twin and the jitted
transfer streams that reconcile it against the pager's tier map.

See the package docstring for the model. Shape notes: every paged leaf
of the device pool has the physical page axis at position 1 (k/v: (nb,
P_phys, page_tokens, KV, hd); k_sz/v_sz: (nb, P_phys, KV, 2)), so one
gather index vector drives all leaves of a stream.

The twin is laid out page-major, one row block per page: (P_phys, S,
rows, 128), where S is the number of shards the pool leaf is split into
(its tp axis) and each shard's page elements are flattened and padded
to whole (sublane, lane) tiles of the dtype. A page then moves as one
dynamic-update-slice (page_out) or dynamic-slice (page_in) along the
twin's most-major axis: the transfer XLA:TPU lowers to a contiguous DMA
between HBM and pinned host memory. A page-axis scatter into the
pool-shaped array would need strided host DMA, which XLA:TPU refuses.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import blocks
from repro.runtime import sharding as shd
from repro.serving.substrate.ledger import SubstrateLedger
from repro.serving.telemetry import span

_LANES = 128


def _pool_leaves(caches, twin):
    """The device-pool subtree of `caches` matching `twin`'s structure."""
    return {pos: {k: caches[pos][k] for k in sub}
            for pos, sub in twin.items()}


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """How one pool leaf's pages map to twin rows.

    shape     — the pool leaf's shape (page axis at 1)
    dim/axes  — the leaf dim split over mesh `axes` (None: replicated)
    shards    — S, the number of shards of that dim
    rows      — sublane rows of 128 lanes per shard and page, padded to
                the dtype's sublane tile (8 rows of 32-bit words)
    """
    shape: tuple
    dim: int | None
    axes: tuple | None
    shards: int
    rows: int

    @classmethod
    def of(cls, leaf, spec, mesh):
        spec = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        split = [(d, a) for d, a in enumerate(spec) if a is not None]
        if len(split) > 1 or any(d < 2 for d, _ in split):
            raise ValueError(
                f"pool leaf sharding {spec} splits the layer/page axes or "
                "more than one dim; the twin supports one split dim")
        dim, axes, shards = None, None, 1
        if split:
            dim, a = split[0]
            axes = a if isinstance(a, tuple) else (a,)
            shards = math.prod(mesh.shape[x] for x in axes)
        per_shard = math.prod(leaf.shape[:1] + leaf.shape[2:]) // shards
        sublanes = 32 // jnp.dtype(leaf.dtype).itemsize
        rows = -(-per_shard // (_LANES * sublanes)) * sublanes
        return cls(tuple(leaf.shape), dim, axes, shards, rows)

    @property
    def per_shard(self) -> int:
        return math.prod(self.shape[:1] + self.shape[2:]) // self.shards

    def twin_shape(self) -> tuple:
        return (self.shape[1], self.shards, self.rows, _LANES)

    def twin_spec(self) -> P:
        return P(None, self.axes) if self.axes else P()

    def to_rows(self, leaf, ids):
        """Pages `ids` of a pool leaf -> (n, S, rows, 128) twin rows."""
        g = jnp.moveaxis(jnp.take(leaf, ids, axis=1), 1, 0)  # (n, nb, ...)
        n = g.shape[0]
        if self.dim is not None:
            d = self.dim
            g = g.reshape(g.shape[:d] + (self.shards, -1) + g.shape[d + 1:])
            g = jnp.moveaxis(g, d, 1)
        g = g.reshape(n, self.shards, self.per_shard)
        g = jnp.pad(g, ((0, 0), (0, 0),
                        (0, self.rows * _LANES - self.per_shard)))
        return g.reshape(n, self.shards, self.rows, _LANES)

    def from_rows(self, rows):
        """Inverse of `to_rows`: (n, S, rows, 128) -> pool-layout pages
        (nb, n, ...) in the leaf's own axis order."""
        n = rows.shape[0]
        g = rows.reshape(n, self.shards, -1)[:, :, :self.per_shard]
        rest = self.shape[:1] + self.shape[2:]            # (nb, ...)
        if self.dim is None:
            g = g.reshape((n,) + rest)
        else:
            d = self.dim
            local = rest[:d - 1] + (rest[d - 1] // self.shards,) + rest[d:]
            g = g.reshape((n, self.shards) + local)
            g = jnp.moveaxis(g, 1, d)
            g = g.reshape((n,) + rest)
        return jnp.moveaxis(g, 0, 1)


def _is_layout(x) -> bool:
    return isinstance(x, RowLayout)


def twin_layout(pool, pool_pspec, mesh):
    """`RowLayout` tree of the pool leaves (arrays or shape structs)
    under their PartitionSpec tree on `mesh`."""
    return jax.tree.map(lambda leaf, spec: RowLayout.of(leaf, spec, mesh),
                        pool, pool_pspec, is_leaf=lambda x: isinstance(x, P))


def twin_shardings(layout, mesh, memory_kind=None):
    """NamedShardings of the twin leaves, in `memory_kind` (None: the
    default memory)."""
    return shd.named(mesh, jax.tree.map(RowLayout.twin_spec, layout,
                                        is_leaf=_is_layout),
                     memory_kind=memory_kind)


def page_streams(layout, mode: str):
    """The (page_out, page_in) stream programs over a twin whose leaves
    follow the `RowLayout` tree `layout`. Each page is one DMA along the
    twin's major axis: into pinned host memory on the way out, back to
    device memory on the way in ("physical"); "emulated" keeps the same
    program with no memory-kind transfer."""
    if mode == "physical":
        to_host = lambda x: jax.device_put(x, jax.memory.Space.Host)  # noqa: E731
        to_dev = lambda x: jax.device_put(x, jax.memory.Space.Device)  # noqa: E731
    else:
        to_host = to_dev = lambda x: x  # noqa: E731

    def page_out(twin, pool, ids):
        def leaf(lay, t, p):
            rows = lay.to_rows(p, ids)

            def put(k, t):
                row = to_host(jax.lax.dynamic_slice_in_dim(rows, k, 1))
                return jax.lax.dynamic_update_slice_in_dim(t, row, ids[k], 0)

            return jax.lax.fori_loop(0, ids.shape[0], put, t)

        return jax.tree.map(leaf, layout, twin, pool, is_leaf=_is_layout)

    def page_in(twin, ids):
        def leaf(lay, t):
            def get(k, acc):
                row = to_dev(jax.lax.dynamic_slice_in_dim(t, ids[k], 1))
                return jax.lax.dynamic_update_slice_in_dim(acc, row, k, 0)

            acc = jnp.zeros((ids.shape[0],) + t.shape[1:], t.dtype)
            return lay.from_rows(jax.lax.fori_loop(0, ids.shape[0], get, acc))

        return jax.tree.map(leaf, layout, twin, is_leaf=_is_layout)

    return page_out, page_in


class TierSubstrate:
    """Two-tier physical placement for the paged KV pool.

    mode       — "physical" (pinned_host twin) or "emulated" (default
                 memory, identical program shapes); resolve it with
                 `runtime.capability.substrate_mode` first — this class
                 does not probe.
    pool_pspec — optional PartitionSpec tree matching the PAGED subset
                 of the cache tree (`runtime.sharding.paged_cache_pspec`
                 output restricted to k/v/k_sz/v_sz). The twin carries
                 the same partitioning as the device pool so per-shard
                 transfers never reshard. Default: replicated.
    host_memory_kind — the jax memory kind the physical twin lands in;
                 the engine feeds `TierTopology.pool.memory_kind`
                 (`core.tiers.TierSpec`), so the pool tier the virtual
                 clock prices is the tier the bytes physically occupy.
    """

    def __init__(self, caches, mesh, mode: str, *,
                 pool_pspec=None, host_memory_kind: str = "pinned_host"):
        if mode not in ("physical", "emulated"):
            raise ValueError(
                f"mode={mode!r}; resolve 'auto'/'off' via "
                "runtime.capability.substrate_mode before constructing")
        self.mode = mode
        # fault-injection wiring (serving.faults): the fleet router sets
        # `faults`/`engine_id` after construction; when unset every
        # transfer succeeds on the first attempt and no retry state is
        # touched — the fault-free path is byte-identical to pre-fault
        # builds of this class.
        self.faults = None
        self.engine_id = 0
        self.retries = 0
        self.retry_bytes = 0.0
        self._backoff_pending_s = 0.0
        pool = blocks.paged_pool_leaves(caches)
        self.enabled = bool(pool)
        if not self.enabled:        # SSM-only stack: no paged KV leaves
            self.twin = None
            self.ledger = SubstrateLedger(0.0, mode)
            return
        if mesh is None:
            mesh = jax.sharding.Mesh(
                np.asarray(jax.devices()[:1]), ("_substrate",))
        if pool_pspec is None:
            pool_pspec = jax.tree.map(lambda _: P(), pool)
        layout = twin_layout(pool, pool_pspec, mesh)
        self._host_sh = twin_shardings(
            layout, mesh, host_memory_kind if mode == "physical" else None)
        self._dev_sh = shd.named(mesh, pool_pspec)
        self.twin = jax.jit(lambda: jax.tree.map(
            lambda lay, leaf: jnp.zeros(lay.twin_shape(), leaf.dtype),
            layout, pool, is_leaf=_is_layout),
            out_shardings=self._host_sh)()
        self.n_phys = int(jax.tree.leaves(pool)[0].shape[1])
        # MEASURED page bytes: the pool arrays' nbytes over the page axis
        # (the twin's rows carry tile padding on top), not the closed-form
        # kv-byte walk (they agree to float rounding)
        self.page_bytes = float(sum(
            leaf.nbytes / leaf.shape[1] for leaf in jax.tree.leaves(pool)))
        self.ledger = SubstrateLedger(self.page_bytes, mode)
        self._resident: set = set()

        page_out, page_in = page_streams(layout, mode)
        # out_shardings pin the stream direction: page_out lands in the
        # twin's (host) placement, page_in returns pool-layout pages
        # (nb, n, ...) in device memory under the pool's own pspec.
        self._page_out_fn = jax.jit(
            page_out, out_shardings=self._host_sh, donate_argnums=0)
        self._page_in_fn = jax.jit(
            page_in, out_shardings=self._dev_sh)

    # ----------------------------------------------------------- streams
    def _attempt_transfer(self, site: str, n_pages: int, step: int) -> None:
        """Consult the fault injector before issuing a stream: each
        injected failure logs a `retry` event (wasted link bytes, zero
        placement delta) and accrues exponential backoff on the pending
        virtual-time bill (`take_backoff`). Bounded: after
        `plan.max_retries` failed attempts the fault is fatal — an
        unreachable tier must surface, not spin."""
        if self.faults is None:
            return
        attempt = 1
        while self.faults.transfer_fails(f"substrate/{site}"):
            self.ledger.record("retry", n_pages, step=step)
            self.retries += 1
            self.retry_bytes += n_pages * self.page_bytes
            self._backoff_pending_s += self.faults.backoff_s(attempt)
            attempt += 1
            if attempt > self.faults.plan.max_retries:
                raise RuntimeError(
                    f"substrate {site} failed "
                    f"{self.faults.plan.max_retries} consecutive "
                    f"attempts (engine {self.engine_id}, step {step}) — "
                    f"tier unreachable")

    def take_backoff(self) -> float:
        """Drain the accumulated retry backoff (seconds of virtual time
        the engine must charge to its clock)."""
        dt, self._backoff_pending_s = self._backoff_pending_s, 0.0
        return dt

    def _pad_ids(self, ids) -> jnp.ndarray:
        """Pad a page-id burst to the next power of two by repeating the
        last id (writing a page twice with identical data is a no-op) so the
        transfer cells compile O(log n_phys) distinct shapes."""
        n = len(ids)
        m = 1 << max(0, n - 1).bit_length() if n else 1
        arr = np.full(m, ids[-1], dtype=np.int32)
        arr[:n] = ids
        return jnp.asarray(arr)

    def drain(self, pager, caches, *, step: int = 0) -> dict:
        """Reconcile host placement against the pager's tier map: issue
        the page_out / page_in / drop streams for every page whose
        placement changed since the last drain. Async — call `sync()`
        to wait on the issued transfers. Returns the per-kind page
        counts of this drain."""
        if not self.enabled:
            return {}
        with span("substrate.drain") as sp:
            target = set(pager.pool_page_ids().tolist())
            outs = sorted(target - self._resident)
            gone = self._resident - target
            promoted = sorted(p for p in gone if pager.ref[p] > 0)
            freed = sorted(p for p in gone if pager.ref[p] <= 0)
            sp.set_metadata(n_out=len(outs), n_in=len(promoted),
                            n_drop=len(freed))
            if freed:
                self.ledger.record("drop", len(freed), step=step)
            if promoted:
                self._attempt_transfer("page_in", len(promoted), step)
                # gather BEFORE page_out donates (and thus invalidates) the
                # current twin buffer
                got = self._page_in_fn(self.twin, self._pad_ids(promoted))
                self.ledger.record("page_in", len(promoted), step=step,
                                   payload=tuple(jax.tree.leaves(got)))
            if outs:
                self._attempt_transfer("page_out", len(outs), step)
                self.twin = self._page_out_fn(
                    self.twin, _pool_leaves(caches, self.twin),
                    self._pad_ids(outs))
                self.ledger.record("page_out", len(outs), step=step,
                                   payload=tuple(jax.tree.leaves(self.twin)))
            self._resident = target
            return {"page_out": len(outs), "page_in": len(promoted),
                    "drop": len(freed)}

    def record_handoff(self, n_pages: int, *, step: int = 0) -> None:
        """Account a fleet prefill->decode handoff copy (roles.py runs
        the page copy along the physical axis; the substrate prices it
        at measured page bytes). No placement change: the source pages
        stay wherever their tier map says."""
        if self.enabled and n_pages:
            self.ledger.record("handoff", int(n_pages), step=step)

    # -------------------------------------------------------- accounting
    def sync(self) -> int:
        """Complete every in-flight stream (block_until_ready)."""
        return self.ledger.sync()

    def counters(self) -> dict:
        return self.ledger.counters()
