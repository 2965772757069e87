"""CPU rehearsal of ``chip_smoke.py``: every phase at a tiny size.

The phases run here on the CPU with the Pallas kernels in interpret mode,
through the same code the chip runs; ``main`` must refuse a CPU-only JAX.
The mesh phase runs in-process on one device and, in a subprocess, on four
forced host devices; there ``MeshEngine`` is given ``use_pallas=True``,
since on the CPU its default is the XLA reference.  Also covers the compile-cache placement the script
turns on.
"""
import functools
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
TINY = dict(n_per_dim=4, leaf_n=16, bs=8)      # n = 64


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_multiply_phase(smoke):
    r = smoke.phase_multiply(**TINY)
    assert r["n"] == 64 and r["waves"] >= 2 and r["pairs"] > 0
    assert r["rel_err"] <= smoke.MULTIPLY_TOL


def test_replay_phase_compiles_nothing(smoke):
    r = smoke.phase_replay(**TINY)
    assert r["compiles"] == 0 and r["new_tasks"] == 0
    assert len(r["replay_s"]) == 3 and r["waves"] == 3


def test_scf_phase_reaches_solve_waves(smoke):
    r = smoke.phase_scf(n=32, leaf_n=16, bs=8)
    assert r["solve_waves"] > 0 and r["replay_tasks"] == 0
    assert r["rel_err"] <= smoke.SCF_TOL


def test_mesh_phase_one_device(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "MeshEngine",
                        functools.partial(smoke.MeshEngine, use_pallas=True))
    r = smoke.phase_mesh(**TINY, n_dev=1)
    assert r["n_dev"] == 1 and r["rel_err_vs_pallas"] == 0.0


def test_mesh_phase_four_devices():
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r}); "
            "import functools, chip_smoke as cs; "
            "cs.MeshEngine = functools.partial(cs.MeshEngine, "
            "use_pallas=True); "
            "cs.phase_mesh(n_per_dim=4, leaf_n=16, bs=8, n_dev=4)")
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stderr[-2000:]
    line = res.stdout.strip().splitlines()[-1]
    assert line.startswith("phase=mesh") and "n_dev=4" in line
    assert "FAILED" not in line


def test_failed_check_raises_after_printing(smoke, capsys):
    with pytest.raises(smoke.SmokeCheckError, match="rel_err"):
        smoke.report("demo", {"rel_err": 1.0}, {"rel_err": False})
    assert "FAILED=rel_err" in capsys.readouterr().out


def test_main_refuses_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "'cpu'" in out.err


def test_script_exits_nonzero_without_tpu():
    res = subprocess.run([sys.executable, str(SCRIPT)], capture_output=True,
                         text=True, timeout=300, cwd=ROOT, env=_env())
    assert res.returncode != 0
    assert '"ok"' not in res.stdout and "'cpu'" in res.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = _env()
    env.pop("PYTHONPATH")
    res = subprocess.run([sys.executable, SCRIPT.name], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path, env=env)
    assert res.returncode != 0 and res.stdout == ""


class TestCompileCache:
    def test_default_dir_is_repo_root(self, monkeypatch):
        from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            assert use_compile_cache() == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_env_dir_is_used_and_written(self, tmp_path):
        code = ("import jax, jax.numpy as jnp; "
                "from repro.launch.compile_cache import use_compile_cache; "
                "d = use_compile_cache(); "
                "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
                ".block_until_ready(); "
                "print(d, jax.config.jax_compilation_cache_dir)")
        res = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env=_env(
                JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
        assert res.returncode == 0, res.stderr[-2000:]
        assert res.stdout.split() == [str(tmp_path)] * 2
        assert any(tmp_path.iterdir()), "no cache entry was written"

