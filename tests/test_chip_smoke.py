"""chip_smoke.py: its serving loop and checks at a tiny size on the CPU,
and its refusal to run without a TPU."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.configs import get_smoke_config
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_serves_and_checks_at_tiny_size(capsys):
    cs = _load_chip_smoke()
    cs.smoke(get_smoke_config(cs.ARCH), seed=0, max_batch=4, max_len=64,
             n_requests=6, prompt_len=(4, 40), max_new=8,
             counter=cs.CompileCounter().register())
    out = capsys.readouterr().out
    assert "0 compiles in the loop" in out, out
    assert "consistency:" in out, out


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "without_the_repo"])
def test_refuses_to_run_without_a_tpu_or_the_repo(alone, tmp_path):
    """On the CPU it exits nonzero after naming the device; copied into a
    directory without the rest of the repo it fails to import."""
    where = ROOT
    if alone:
        where = tmp_path
        shutil.copy(ROOT / "chip_smoke.py", where)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, cwd=where, env=env,
                       timeout=300)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert '"ok"' not in r.stdout, r.stdout
    if not alone:
        assert "platform=cpu" in r.stdout, r.stdout


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"],
                         ids=["default", "from_env"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to JAX; otherwise the
    cache goes to the one fixed directory in the checkout."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        set_dir = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    if env_dir is None:
        assert got == set_dir == str(CACHE_DIR)
        assert CACHE_DIR.parent == ROOT
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    else:
        assert got == env_dir and set_dir == was
