"""Where the persistent compile cache lives (rt1_tpu/compilation_cache.py):
`JAX_COMPILATION_CACHE_DIR` set -> the code sets no directory (jax reads the
variable itself); unset -> the fixed `<checkout>/.jax_cache`."""

import os
import subprocess
import sys

import jax
import pytest

from rt1_tpu import compilation_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "env_dir, expected",
    [("/some/dir", None), (None, os.path.join(REPO, ".jax_cache"))],
    ids=["env_set_code_sets_no_dir", "env_unset_fixed_checkout_dir"],
)
def test_cache_dir_selection(monkeypatch, env_dir, expected):
    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    compilation_cache.enable_persistent_cache()
    assert updates.get("jax_compilation_cache_dir") == expected
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 1.0


def test_env_dir_is_what_jax_uses(tmp_path):
    """End to end in a fresh interpreter: with the variable set, the
    directory jax ends up with is the variable's, not the checkout's."""
    out = subprocess.run(
        [sys.executable, "-c",
         "from rt1_tpu import compilation_cache as c; "
         "c.enable_persistent_cache(); import jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
