"""Process-level XLA setup: forced host devices and the compile cache."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro import xla_env


@pytest.mark.parametrize("argv, want", [
    (["train", "--debug-mesh", "4x2"], 8),
    (["train", "--debug-mesh=2x2x2"], 8),
    (["train", "--rounds", "3"], None),
])
def test_debug_mesh_devices(monkeypatch, argv, want):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    xla_env.debug_mesh_devices(argv)
    flags = os.environ["XLA_FLAGS"]
    assert flags.startswith("--xla_dump_to=x")
    if want is None:
        assert "device_count" not in flags
    else:
        assert flags.endswith(f"--xla_force_host_platform_device_count={want}")


def _run(env_dir: str | None, code: str) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    prog = ("import sys\nsys.path.insert(0, 'src')\n"
            "from repro.xla_env import CACHE_DIR, enable_compile_cache\n"
            + textwrap.dedent(code))
    proc = subprocess.run([sys.executable, "-c", prog], env=env, cwd=".",
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_compile_cache_defaults_to_fixed_repo_dir():
    out = _run(None, """
        import jax
        where = enable_compile_cache()
        assert where == str(CACHE_DIR), where
        assert CACHE_DIR.name == ".jax_cache" and CACHE_DIR.parent.joinpath(
            "pyproject.toml").exists(), CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == where
        print("OK")
        """)
    assert "OK" in out


def test_compile_cache_honours_env_dir(tmp_path):
    out = _run(str(tmp_path), """
        import os
        import jax, jax.numpy as jnp
        where = enable_compile_cache()
        assert where == os.environ["JAX_COMPILATION_CACHE_DIR"], where
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
        print("OK")
        """)
    assert "OK" in out
    assert any(tmp_path.iterdir()), "no compiled program landed in the dir"
