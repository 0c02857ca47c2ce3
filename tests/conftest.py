import jax
import pytest

# Tests run on the single host CPU device; the multi-device mesh tests
# force 8 host devices in a child process (``host_mesh_env`` below).
jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def host_mesh_env():
    """Environment for subprocess-spawned multi-device CPU tests.

    ``--xla_force_host_platform_device_count`` only takes effect before
    the process's first jax device query, so the 8-device mesh tests
    (tests/test_mesh.py) run in a child pytest marked by
    ``REPRO_MESH_CHILD`` — the rest of tier-1 keeps the single default
    device and is completely unaffected. The child is pinned to the CPU:
    forced host devices are what it tests, and an accelerator would
    already be held by this process."""
    import os
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["REPRO_MESH_CHILD"] = "1"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), root,
                    env.get("PYTHONPATH", "")) if p)
    return env
