"""Test configuration.

Control-plane tests need no accelerator. Compute-path tests run on a
virtual 8-device CPU mesh: the env vars below MUST be set before the first
`import jax` anywhere in the test process.
"""

import collections
import os
import shutil
import tempfile
import uuid

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
# One persistent compile cache for the run, new with it and gone with it:
# every xdist worker of a run has the run's name from xdist
# (``pytest_configure`` below hands xdist the controller's), and the
# processes the tests start inherit the variable.  With it set,
# ``utils/compile_cache.ensure_compile_cache`` sets nothing (its first
# rule), so whether a test sees a cache no longer depends on which test
# built a trainer before it in its worker, and never the checkout's
# ``.jax_cache`` or a directory another run wrote.  Every program is
# kept, not only those that took JAX's default second to compile: the
# tests' programs are tiny and many (the three latent serving files,
# each alone: 491 s with no cache, 338 s with the default floor, 292 s
# with none; CHANGES.md, PR 46).
_RUN = os.environ.get("PYTEST_XDIST_TESTRUNUID") or uuid.uuid4().hex
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    tempfile.gettempdir(), f"dlrover_tpu_tests_jax_cache_{_RUN}")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


# The files a run hands out FIRST, in this order, one to a worker.  Each
# holds one test that is a multi-process world of 45-220 s (most of it
# sleeps by design), and xdist's ``--dist loadfile`` hands files out by
# their number of tests, most first: a file of one test goes LAST and
# runs alone while the other workers have shut down (PR 59's run: the
# last 150-200 s of 1 153 were these, ROADMAP D12).  The rule: a test
# file of fewer than three tests that takes more than 60 s is added
# here, not left to be the run's tail.
WORLDS_FIRST = (
    "test_goodput_e2e.py",
    "test_multislice_elastic_e2e.py",
    "test_elastic_spmd_e2e.py",
    "test_elastic_spmd_grow_e2e.py",
)


def file_order(nodeids):
    """Rank of every file among ``nodeids``: ``WORLDS_FIRST`` in its
    order, then the files by their number of tests, most first, ties by
    name (what xdist did for them).  Reads nothing but the node ids, so
    every worker of a run collects the same order."""
    counts = collections.Counter(n.split("::", 1)[0] for n in nodeids)

    def key(path):
        name = path.rsplit("/", 1)[-1]
        if name in WORLDS_FIRST:
            return (0, WORLDS_FIRST.index(name), path)
        return (1, -counts[path], path)

    return {path: rank for rank, path in enumerate(sorted(counts, key=key))}


def pytest_configure(config):
    # the controller of an xdist run: its workers see this name as
    # PYTEST_XDIST_TESTRUNUID, so all of them share the directory above
    if getattr(config.option, "testrunuid", "") is None:
        config.option.testrunuid = _RUN
    # ... and it hands files out in the order they were collected
    # (``pytest_collection_modifyitems`` below), not by count alone
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)  # behind ``-m``: count what will run
def pytest_collection_modifyitems(items):
    # stable and keyed by file only: a file's tests stay in its own order
    rank = file_order([item.nodeid for item in items])
    items.sort(key=lambda item: rank[item.nodeid.split("::", 1)[0]])


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session):
    if not hasattr(session.config, "workerinput"):  # not a worker's to remove
        shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"],
                      ignore_errors=True)


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
