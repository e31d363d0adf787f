"""Where the persistent XLA compile cache lives (core/cloud.py
_enable_compile_cache — the package's one cache call site).

The rule: with JAX_COMPILATION_CACHE_DIR set, JAX reads the variable and
the package sets no directory; unset, the directory is
``<checkout>/.jax_cache`` — fixed, so two processes started from one
checkout agree and the second hits what the first wrote.  The decision
is once per process, so each case runs in a child.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SRC = """
import jax
from h2o_tpu.core.cloud import Cloud
Cloud.boot()
print(jax.config.jax_compilation_cache_dir)
"""


def _cache_dir_of_child(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "H2O_TPU_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
    r = subprocess.run([sys.executable, "-c", _SRC], cwd=cwd, env=env,
                       capture_output=True, timeout=300)
    assert r.returncode == 0, r.stderr.decode()[-2000:]
    return r.stdout.decode().strip().splitlines()[-1]


def test_env_var_places_the_cache_and_the_package_leaves_it_alone(tmp_path):
    where = str(tmp_path / "elsewhere")
    assert _cache_dir_of_child(
        REPO, H2O_TPU_COMPILE_CACHE="1",
        JAX_COMPILATION_CACHE_DIR=where) == where


def test_default_is_the_checkout_and_two_processes_agree(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_of_child(REPO, H2O_TPU_COMPILE_CACHE="1") == want
    assert _cache_dir_of_child(str(tmp_path),
                               H2O_TPU_COMPILE_CACHE="1") == want


def test_cpu_without_opt_in_sets_nothing(tmp_path):
    assert _cache_dir_of_child(str(tmp_path)) == "None"


def test_a_directory_in_the_switch_is_refused(monkeypatch):
    from h2o_tpu.core import cloud
    monkeypatch.setattr(cloud, "_cache_enabled", False)
    monkeypatch.setenv("H2O_TPU_COMPILE_CACHE", "/some/dir")
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        cloud._enable_compile_cache()
