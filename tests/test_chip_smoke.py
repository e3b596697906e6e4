"""chip_smoke.py refuses to run off a TPU; the compile-cache helper keeps
``JAX_COMPILATION_CACHE_DIR`` or falls back to the checkout's .jax_cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_chip_smoke_fails_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode != 0, res.stdout
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


@pytest.mark.parametrize("env_dir", ["/some/shared/cache", ""],
                         ids=["env_set", "env_unset"])
def test_compile_cache_dir(monkeypatch, env_dir):
    if env_dir:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))

    path = compile_cache.enable_compile_cache()
    if env_dir:
        # JAX reads the variable itself; the helper sets no other directory
        assert path == env_dir and updates == []
    else:
        assert path == str(REPO / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
