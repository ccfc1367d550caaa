"""chip_smoke.py's contract, as far as a CPU box can hold it to it: the
parent stays off JAX, a run without a chip fails and prints no result,
and the compile cache is placed by the one rule.  Light on purpose — no
model, no launcher: the real run is ``python chip_smoke.py`` on a chip.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("DLROVER_DISABLE_PALLAS",)}
    full.update(env)
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        timeout=300, env=full, cwd=REPO)


def _ok_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("ok") is True:
            out.append(obj)
    return out


def _smoke_module():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    return chip_smoke


def test_parent_never_imports_jax():
    """A process that has touched JAX holds the chip its children need:
    neither the script nor what its parent half imports (the launcher
    it names, the router, the supervisor) may pull jax in."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke\n"
        "import dlrover_tpu.agent.launcher\n"
        "import dlrover_tpu.serving.remote\n"
        "import dlrover_tpu.serving.router\n"
        "chip_smoke.main(['--help'])\n" % REPO)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys\ntry:\n    exec(%r)\nexcept SystemExit:\n    pass\n"
         "assert 'jax' not in sys.modules, 'jax was imported'\n"
         "assert 'flax' not in sys.modules\nprint('CLEAN')" % code],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("CLEAN")


def test_without_a_chip_it_fails_and_prints_no_result():
    """Held to the CPU, the probe child reports platform ``cpu``: the
    script must exit non-zero, run no phase, and print no ok line."""
    proc = _run([], JAX_PLATFORMS="cpu")
    assert proc.returncode not in (0, None)
    assert _ok_lines(proc.stdout) == []
    assert '"phase": "train"' not in proc.stdout
    assert "not tpu" in proc.stderr


def test_refuses_to_run_with_pallas_disabled():
    proc = _run([], JAX_PLATFORMS="cpu", DLROVER_DISABLE_PALLAS="1")
    assert proc.returncode == 2 and proc.stdout == ""


@pytest.mark.parametrize("report,chips,fails", [
    ({"platform": "tpu", "device_kind": "TPU v5 lite",
      "device_count": 1}, 1, False),
    ({"platform": "cpu", "device_kind": "cpu", "device_count": 1},
     1, True),
    ({"platform": "tpu", "device_kind": "TPU v5 lite",
      "device_count": 1}, 4, True),
    ({}, 1, True),
])
def test_a_child_off_the_chip_fails_the_phase(report, chips, fails):
    """What a child reports is what counts: another platform than tpu,
    or another device count than asked for, fails the phase."""
    chip_smoke = _smoke_module()
    if fails:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.check_device("child", report, chips, False)
    else:
        assert chip_smoke.check_device("child", report, chips, False) == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_children_write_only_under_the_checkout_and_tmpdir(
        monkeypatch, tmp_path):
    """The driver gives a checkout a TMPDIR of its own: nothing the
    smoke starts may use a fixed /tmp path.  The files agent and worker
    share go under the smoke's work directory; what the package places
    itself (sockets, stack dumps, the default metrics file) follows
    TMPDIR and carries the job's id."""
    import tempfile

    from dlrover_tpu.agent.monitor import stack_dump, training
    from dlrover_tpu.common import multi_process
    from dlrover_tpu.common.constants import ConfigPath

    work = str(tmp_path / "work")
    env = _smoke_module().child_env(work, DLROVER_JOB_UID="j1")
    for var in ("DLROVER_RUNTIME_METRICS_PATH", "DLROVER_PARAL_CONFIG_PATH",
                "DLROVER_STACK_DUMP_DIR"):
        assert env[var].startswith(work + os.sep), var
    assert env["DLROVER_JOB_UID"] == "j1"

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setenv("DLROVER_JOB_UID", "j2")
    for var in ("DLROVER_RUNTIME_METRICS_PATH", "DLROVER_PARAL_CONFIG_PATH"):
        monkeypatch.delenv(var, raising=False)
    placed = [training.metrics_path(), ConfigPath.paral_config(),
              stack_dump.default_dump_dir(),
              multi_process._socket_path("queue")]
    for path in placed:
        assert path.startswith(str(tmp_path) + os.sep), path
        assert "j2" in os.path.basename(path), path


def test_compile_cache_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code.  Unset:
    the one fixed path inside the checkout, never a temporary one."""
    import jax

    from dlrover_tpu.utils import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        placed = str(tmp_path / "placed")
        monkeypatch.setenv(compile_cache.ENV_VAR, placed)
        assert compile_cache.ensure_compile_cache() == placed
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV_VAR)
        fixed = os.path.join(REPO, ".jax_cache")
        assert compile_cache.DEFAULT_DIR == fixed
        assert compile_cache.ensure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
