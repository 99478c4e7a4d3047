"""The compile cache can be placed from outside (utils/compile_cache.py):
with JAX_COMPILATION_CACHE_DIR set the code sets no directory at all;
unset, it is one fixed path under the checkout, whatever the cwd."""

import os
import subprocess
import sys

import jax
import pytest

from fms_fsdp_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_set_means_code_sets_nothing(monkeypatch, restore_cache_config):
    jax.config.update("jax_compilation_cache_dir", "/somewhere/else")
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    assert compile_cache.configure_compile_cache() == "/placed/from/outside"
    # the helper neither overrode jax's own setting nor the variable
    assert jax.config.jax_compilation_cache_dir == "/somewhere/else"
    assert os.environ[compile_cache.ENV_VAR] == "/placed/from/outside"


def test_env_unset_uses_the_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # exported, so replicas and supervised children land in the same place
    assert os.environ[compile_cache.ENV_VAR] == path
    monkeypatch.delenv(compile_cache.ENV_VAR)


def test_same_path_from_two_working_directories(tmp_path):
    """Derived from the package's location: no cwd, temporary name, pid
    or clock enters it (two processes, two cwds, one answer)."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1]);"
        "from fms_fsdp_tpu.utils.compile_cache import"
        " configure_compile_cache as c; print(c());"
        "assert 'jax' not in sys.modules"  # usable by a jax-free parent
    )
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    seen = set()
    for cwd in (str(tmp_path), REPO):
        out = subprocess.run(
            [sys.executable, "-c", code, REPO], cwd=cwd, env=env,
            check=True, capture_output=True, text=True,
        ).stdout.strip()
        seen.add(out)
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_cache_entry_count(tmp_path):
    assert compile_cache.cache_entry_count(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert compile_cache.cache_entry_count(str(tmp_path)) == 1
