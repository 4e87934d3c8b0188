"""Compile-cache placement (edl_tpu/utils/jaxcache.py): the directory is
the operator's when JAX_COMPILATION_CACHE_DIR is set, and otherwise ONE
fixed path inside the checkout — the same in every call and process,
because a directory that moves never hits."""

import getpass
import os
import subprocess
import sys
import tempfile

import jax
import pytest

from edl_tpu.utils import jaxcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_leaves_the_directory_to_jax(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append(name)
    )
    assert jaxcache.configure() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates


def test_unset_is_one_fixed_path_in_the_checkout(
    monkeypatch, restore_cache_dir
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = jaxcache.configure()
    assert first == jaxcache.configure() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # nothing that differs between machines, users, processes or runs
    for moving in (tempfile.gettempdir(), getpass.getuser(), str(os.getpid())):
        assert moving not in os.path.relpath(first, REPO)


def test_unset_is_the_same_path_in_two_processes():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = (
        "import jax; from edl_tpu.utils import jaxcache; "
        "jaxcache.configure(); print(jax.config.jax_compilation_cache_dir)"
    )
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, check=True,
            capture_output=True, text=True, timeout=120,
        ).stdout.strip()
        for cwd in (REPO, tempfile.gettempdir())
    }
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_cache_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
