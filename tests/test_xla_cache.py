"""The compile cache has one place: JAX_COMPILATION_CACHE_DIR when the
launcher sets it (and then nothing is configured in code), else one
fixed directory inside the checkout — the same for every process."""

import os
import subprocess
import sys

import jax

from torchbeast_tpu.utils import xla_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_decides_and_config_is_left_alone(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: updates.append(a)
    )
    assert xla_cache.use_compile_cache() == str(tmp_path)
    assert updates == []


def test_unset_means_the_fixed_in_checkout_directory(monkeypatch):
    updates = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: updates.append(a)
    )
    expected = os.path.join(REPO, ".jax_cache")
    assert xla_cache.use_compile_cache() == expected
    assert updates == [("jax_compilation_cache_dir", expected)]
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_two_processes_agree_on_the_path():
    """No pid, host, time or temp name in it: processes started in
    different directories with different HOMEs get the same string."""
    code = (
        "from torchbeast_tpu.utils.xla_cache import use_compile_cache;"
        "print(use_compile_cache())"
    )
    env = {
        k: v for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code], cwd=cwd,
            env=dict(env, HOME=home, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
            stdout=subprocess.PIPE, text=True,
        )
        for cwd, home in ((REPO, "/tmp/home-a"), ("/", "/tmp/home-b"))
    ]
    paths = [p.communicate(timeout=120)[0].strip() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")
