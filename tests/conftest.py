"""Test configuration.

Control-plane tests need no accelerator. Compute-path tests run on a
virtual 8-device CPU mesh: the env vars below MUST be set before the first
`import jax` anywhere in the test process.
"""

import os

# Force the CPU: tests always run on the virtual 8-device CPU backend,
# whatever accelerator the host has — they check results and counts,
# never times, and several test processes could not share one chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DLROVER_LOG_LEVEL", "WARNING")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _reap_worker_subprocesses():
    """Session-end sweep of serving-worker subprocesses: a test that
    fails (or is interrupted) between spawn and shutdown must not leave
    orphan workers alive to hang the suite or leak ports.  The
    supervisor registers every Popen it creates in a module-level table;
    this reaps whatever is still running."""
    yield
    try:
        from dlrover_tpu.serving.remote.supervisor import reap_orphans
    except Exception:  # the fabric may be un-importable mid-refactor
        return
    reaped = reap_orphans()
    if reaped:
        print(f"\n[conftest] reaped {reaped} leaked worker subprocesses")


@pytest.fixture()
def local_master():
    """In-process master + gRPC server on a free port; yields (master, addr).

    Mirrors the reference's `start_local_master` test fixture (reference:
    dlrover/python/tests/test_utils.py).
    """
    from dlrover_tpu.master.local_master import LocalJobMaster

    # port 0: prepare() binds a kernel-assigned port race-free and
    # exposes it as .port (the dlint DL001 idiom; find_free_port's
    # bind-then-close pre-pick can lose the port to another process)
    master = LocalJobMaster(0, node_num=1)
    master.prepare()
    yield master, f"127.0.0.1:{master.port}"
    master.stop()


@pytest.fixture()
def master_client(local_master):
    from dlrover_tpu.agent.master_client import MasterClient

    master, addr = local_master
    client = MasterClient(addr, node_id=0, node_type="worker")
    yield client
    client.close()


@pytest.fixture()
def fresh_compiles():
    """No persistent compile cache around a test that reads a compiled
    program's metadata: jax's cache key leaves metadata out, so an entry
    another tree wrote would carry that tree's scopes."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
