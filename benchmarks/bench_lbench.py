"""Paper Fig 11: (left) LoI scales linearly with configured intensity;
(middle) raw-counter bandwidth saturates at the link while LBench's IC keeps
resolving contention; (right) per-app interference coefficient. Also times
the LBench op per NFLOP setting through `kernels.lbench.ops`: the compiled
Pallas kernel on a TPU, the jnp reference elsewhere — the emitted line
names the backend, so a CPU timing never reads as a kernel timing."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import configs, kernels
from repro.core import interference as itf
from repro.core import tiers as tr
from repro.core.quantify import analyze
from repro.kernels.lbench import ops as lops
from benchmarks.common import emit, timed


def run():
    rows = []
    topo = tr.v5e_topology()

    # left panel: LoI vs configured intensity + kernel timing
    a = jax.random.normal(jax.random.PRNGKey(0), (1 << 16,), jnp.float32)
    impl = kernels.backend()
    for nflop in (1, 2, 4, 8, 16, 32):
        out, us = timed(
            lambda: jax.block_until_ready(lops.lbench(a, nflop)),
            repeats=2,
        )
        loi = itf.lbench_loi(nflop, a.size, topo)
        flops = lops.flops(a.size, nflop)
        emit(
            f"fig11_lbench_nflop{nflop}", us,
            f"loi={loi:.3f} ai={nflop / 8:.3f}flop/B kernel_flops={flops} "
            f"impl={impl} platform={jax.devices()[0].platform}",
        )
        rows.append({"nflop": nflop, "loi": loi, "us": us})

    # middle panel: PCM saturation vs LBench IC
    sweep = itf.lbench_intensity_sweep(topo)
    for r in sweep:
        emit(
            f"fig11_saturation_nflop{r['nflop']}", 0.0,
            f"pcm_bw={r['pcm_bw'] / 1e9:.1f}GB/s ic={r['ic']:.2f}",
        )

    # right panel: per-app IC (decode workloads on 50% pooling)
    for arch in configs.list_archs():
        def one():
            an = analyze(arch, "decode_32k", policy="hotness",
                         pool_fraction="auto", use_dryrun=True)
            return an.level3["interference_coefficient"], \
                an.level3["injected_loi"]

        (ic, inj), us = timed(one, repeats=1)
        emit(f"fig11_ic_{arch}", us, f"ic={ic:.3f} injected_loi={inj:.3f}")
        rows.append({"arch": arch, "ic": ic})
    return rows
