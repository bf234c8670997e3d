"""Backend capability probes for memory-kind (tier) annotations.

XLA:TPU supports pinned_host placement on inputs, outputs and internal
transfers; XLA:CPU accepts pinned_host inputs and internal transfers but
hits UNIMPLEMENTED on output placement annotations. The tier engine degrades on the CPU only:
placements are always tracked logically, and physical annotations are
applied per capability. On a TPU a probe that raises is a fault and
surfaces — it never reads as "unsupported", which would quietly turn real
HBM<->host page moves into counted bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _probe(check) -> bool:
    """True when `check()` compiles. On the CPU backend a refusal means
    "unsupported"; on a TPU it is an error and is raised."""
    try:
        check()
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        return False
    return True


def _host_sharding():
    mesh = jax.sharding.Mesh(jax.devices()[:1], ("x",))
    return NamedSharding(mesh, P(), memory_kind="pinned_host")


@functools.cache
def supports_host_input() -> bool:
    """A pinned_host input read into device memory (page_in's pattern:
    XLA:TPU computes on device-memory operands only)."""
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    return _probe(lambda: jax.jit(
        lambda a: jax.device_put(a, jax.memory.Space.Device) * 2,
        in_shardings=_host_sharding()).lower(x).compile())


@functools.cache
def supports_host_output() -> bool:
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    return _probe(lambda: jax.jit(
        lambda a: a * 2, out_shardings=_host_sharding()).lower(x).compile())


@functools.cache
def supports_internal_transfer() -> bool:
    def f(a):
        b = jax.device_put(a, jax.memory.Space.Host)
        return jax.device_put(b, jax.memory.Space.Device) * 2

    return _probe(lambda: jax.jit(f).lower(jnp.ones((8,))).compile())


def resolve_substrate_mode(requested: str, *, host_input: bool,
                           host_output: bool, internal: bool) -> str:
    """Pure mode resolution for the physical KV substrate.

    The substrate keeps a host-resident twin of the pool pages: page_in
    reads it as a pinned_host input (host_input), page_out returns it
    with a pinned_host output sharding (host_output), and both move each
    page with an in-program transfer between memory kinds (internal).
    All three are needed.

      requested="physical"  — demand the real thing; raise if unsupported
      requested="emulated"  — force default-memory twin (same code shape,
                              same ledger; bytes counted, not moved
                              across memory kinds)
      requested="auto"      — physical when the backend can, else emulated
      requested="off"       — no substrate at all
    """
    if requested not in ("auto", "off", "emulated", "physical"):
        raise ValueError(
            f"substrate={requested!r} not in ('auto', 'off', 'emulated', "
            f"'physical')")
    if requested in ("off", "emulated"):
        return requested
    physical_ok = host_input and host_output and internal
    if requested == "physical":
        if not physical_ok:
            raise RuntimeError(
                "substrate='physical' requested but the backend probes "
                f"report host_input={host_input} internal={internal} "
                f"(host_output={host_output}); use 'auto' or 'emulated'")
        return "physical"
    return "physical" if physical_ok else "emulated"


def substrate_mode(requested: str = "auto") -> str:
    """Resolve the substrate mode against this process's backend probes."""
    return resolve_substrate_mode(
        requested,
        host_input=supports_host_input(),
        host_output=supports_host_output(),
        internal=supports_internal_transfer(),
    )
