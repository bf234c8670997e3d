"""Parallel context threaded through model apply functions.

Carries the mesh and the axis roles so blocks that need explicit collectives
(MoE expert-parallel dispatch) can shard_map themselves, while everything
else relies on pjit auto-sharding + constraints.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Optional[jax.sharding.Mesh] = None
    dp_axes: tuple[str, ...] = ()       # batch axes, e.g. ("pod","data")
    fsdp_axis: Optional[str] = None     # param-shard axis (usually "data")
    tp_axis: Optional[str] = None       # tensor/expert-parallel axis ("model")
    shard_seq_moe: bool = True          # reshard seq over tp inside MoE
    remat: str = "block"                # none | block
    moe_fsdp_mode: str = "rowcol"       # rowcol | gather (see models/moe.py)

    @property
    def all_axes(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names) if self.mesh is not None else ()

    def axis_size(self, name: Optional[str]) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.axis_size(a)
        return n

    def batch_spec(self) -> P:
        return P(self.dp_axes if self.dp_axes else None)

    def constrain(self, x, spec: P):
        """Sharding constraint if a mesh is active, else identity."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )


SINGLE = ParallelCtx()  # no mesh: pure single-device semantics


def per_kv_shard(ctx: Optional[ParallelCtx], n_kv: int, fn, split, whole):
    """Call `fn(*split, *whole)` — a Pallas kernel op — once per device.

    A Mosaic custom call cannot be partitioned by GSPMD, so under a mesh
    the paged kernels run inside `shard_map`, as `sharded_mha` does for
    the dense flash kernel. `split` operands and every output carry their
    heads on the second-minor axis (q (B, H, D), chunks and pool pages
    (..., KV, D), (scale, zero) rows (..., KV, 2)); they are split over
    the tp axis when it divides the `n_kv` KV heads (whole GQA groups per
    shard — the pool's own split, `runtime.sharding.paged_cache_pspec`),
    else every device runs the whole kernel. `whole` operands (block
    tables, lengths, page ids) are replicated. Meshless: a plain call."""
    if ctx is None or ctx.mesh is None:
        return fn(*split, *whole)
    tp = ctx.axis_size(ctx.tp_axis)
    ax = ctx.tp_axis if tp > 1 and n_kv % tp == 0 else None

    def heads(x):
        return P(*([None] * (len(x.shape) - 2)), ax, None)

    out = jax.eval_shape(fn, *split, *whole)
    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=tuple(heads(x) for x in split) + (P(),) * len(whole),
        out_specs=jax.tree.map(heads, out),
        check_vma=False,
    )(*split, *whole)
