import jax
import pytest

# NB: deliberately NO xla_force_host_platform_device_count here — smoke
# tests and benches must see 1 device (multi-device coverage runs in
# subprocesses; see test_multidevice.py).

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def smoke_mesh():
    from repro.launch.mesh import make_smoke_mesh

    return make_smoke_mesh()
