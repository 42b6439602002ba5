"""The persistent compile cache lands in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``<root>/.jax_cache``, and nowhere else.

Each case runs in a fresh process: jax initializes its cache once per
process."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    root, env_dir = tmp_path / "root", tmp_path / "env_cache"
    root.mkdir()
    env = {"PYTHONPATH": os.path.join(REPO, "src"), "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import use_compile_cache
        print(use_compile_cache({str(root)!r}))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 2)(
            jnp.ones(8)))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    want = env_dir if from_env else root / ".jax_cache"
    assert out.stdout.strip() == str(want)
    assert want.is_dir() and any(want.iterdir())
    # Nothing else under the scratch tree holds cache entries.
    others = {p for p in tmp_path.iterdir() if p.is_dir()} - {root, want}
    assert not others, others
    assert set(root.iterdir()) <= {root / ".jax_cache"}
    if from_env:
        assert not (root / ".jax_cache").exists()
