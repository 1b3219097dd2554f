"""The no-fallback rules of the chip bring-up (CPU-side halves).

What only a chip can say is said by ``chip_smoke.py`` there; these pin
what a CPU can: the measurement entry points refuse without a TPU
before compiling, the compile cache is placed from outside, the
platform test does not swallow, and a supervising parent never
initialises a backend.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_root_module(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(argv, cwd=REPO, env_extra=None, timeout=120, pythonpath=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_LOG_COMPILES="1")
    if pythonpath:
        env["PYTHONPATH"] = os.pathsep.join(
            [REPO, env.get("PYTHONPATH", "")])
    else:
        env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


# -- compile cache placed from outside ----------------------------------
class TestCompileCache:
    def _updates(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.__setitem__(k, v))
        return calls

    def test_env_set_means_no_directory_in_code(self, monkeypatch,
                                                tmp_path):
        from fedtorch_tpu.utils import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = self._updates(monkeypatch)
        assert enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in calls
        assert "jax_persistent_cache_min_compile_time_secs" in calls
        # a cached executable is this program's own, scope names and
        # all: what a profile of it shows is never another tree's
        assert calls["jax_compilation_cache_include_metadata_in_key"] \
            is True

    def test_unset_means_repo_jax_cache(self, monkeypatch):
        from fedtorch_tpu.utils import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = self._updates(monkeypatch)
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert calls["jax_compilation_cache_dir"] == want


# -- the platform test ---------------------------------------------------
class TestPlatformTest:
    def test_on_tpu_false_on_cpu(self):
        from fedtorch_tpu.ops.pallas import flash_attention, quant_kernel
        assert quant_kernel._on_tpu() is False
        assert flash_attention.on_tpu() is False

    def test_on_tpu_does_not_swallow(self, monkeypatch):
        from fedtorch_tpu.ops.pallas import quant_kernel

        def boom():
            raise RuntimeError("backend failed to initialise")

        monkeypatch.setattr(jax, "default_backend", boom)
        with pytest.raises(RuntimeError, match="failed to initialise"):
            quant_kernel._on_tpu()

    def test_require_tpu_refuses_on_cpu(self):
        from fedtorch_tpu.utils import device_stamp, require_tpu
        stamp = device_stamp()
        assert stamp == {"platform": "cpu", "kind": "cpu",
                         "count": len(jax.devices())}
        with pytest.raises(SystemExit) as e:
            require_tpu("a test")
        assert e.value.code not in (0, None)
        assert "needs a TPU" in str(e.value.code)


# -- entry points refuse without a chip, before compiling -----------------
def test_entry_point_refuses_on_cpu_before_compiling():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    # no result line, and nothing was compiled on the way to refusing
    assert '"ok"' not in out.stdout
    assert "Compiling" not in out.stderr
    assert "chip_smoke phase" not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo:
    non-zero, no result — the script drives the program, it is not a
    stand-in for it."""
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py", "--test-size"], cwd=str(tmp_path),
               pythonpath=False)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_failed_phase_fails_the_smoke():
    smoke = _load_root_module("chip_smoke")

    def bad():
        raise RuntimeError("kernel refused")

    def exits():
        raise SystemExit(0)  # an entry point bailing out is a failure

    report, ok = smoke.run_phases([("a", dict), ("b", bad),
                                   ("c", exits), ("d", dict)])
    assert ok is False
    assert "kernel refused" in report["b"]["error"]
    assert "SystemExit" in report["c"]["error"]
    assert "error" not in report["a"] and "error" not in report["d"]
    assert smoke.run_phases([("a", dict)])[1] is True


def test_chip_smoke_cifar_batches_load_through_the_real_loader(tmp_path):
    from fedtorch_tpu.data.datasets import load_cifar
    smoke = _load_root_module("chip_smoke")
    root = smoke.write_cifar10_batches(str(tmp_path), 0, 20, 10)
    splits = load_cifar("cifar10", root)
    assert splits.train_x.shape == (100, 32, 32, 3)
    assert splits.test_x.shape == (10, 32, 32, 3)
    assert set(splits.train_y.tolist()) <= set(range(10))


@pytest.mark.slow
def test_chip_smoke_test_size_passes_on_the_cpu_mesh():
    out = _run(["chip_smoke.py", "--test-size"], timeout=900,
               env_extra={"JAX_LOG_COMPILES": "0"})
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu",
        "count": last["device"]["count"]}}


# -- one process for each chip -------------------------------------------
def test_supervising_parent_never_initialises_a_backend():
    """``fedtorch-tpu supervise`` launches chip-using children; a parent
    that had touched the backend would hold the chip and the child would
    fail or hang. Importing fedtorch_tpu pulls in jax — reaching
    ``jax.devices()`` is what must not happen."""
    code = (
        "import sys\n"
        "from fedtorch_tpu.cli import main\n"
        "rc = main(['supervise', '--', sys.executable, '-c', 'pass'])\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb.backends_are_initialized(), 'backend touched'\n"
        "sys.exit(rc)\n")
    out = _run(["-c", code])
    assert out.returncode == 0, out.stderr[-2000:]
