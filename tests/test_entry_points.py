"""Entry points: `chip_smoke.py` and the compilation-cache placement.

The chip smoke's phases run here at smoke width on the CPU, through the
same `serve.run_npec` path the chip runs at published width; the script
itself must refuse to run without a TPU.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                        init_compilation_cache)

ROOT = Path(__file__).resolve().parent.parent


def _chip_smoke():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import chip_smoke
    return chip_smoke


@pytest.mark.parametrize("phase", ["float_phase", "npe_phase"])
def test_chip_smoke_phases_at_smoke_width(phase, capsys):
    cs = _chip_smoke()
    failures = getattr(cs, phase)(cs.SERVE_ARGV + ("--smoke",))
    out = capsys.readouterr().out
    assert failures == []
    assert "served_tokens=16" in out and "L=2 d=128" in out


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(where, tmp_path):
    """Run as the chip check runs it (no PYTHONPATH): from the checkout
    it names the missing chip; copied into an otherwise empty directory
    it cannot import the program.  Neither prints a result."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        (tmp_path / "chip_smoke.py").write_text(script.read_text())
        script = tmp_path / "chip_smoke.py"
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    if where == "repo":
        assert "no TPU found" in p.stderr


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert init_compilation_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiles land there and the
    helper sets no other directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import init_compilation_cache\n"
            "print(init_compilation_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert any(f.name.endswith("-cache") for f in tmp_path.iterdir())
