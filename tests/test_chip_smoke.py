"""``chip_smoke.py`` and the compile cache it turns on.

On the chip the script checks every phase against a reference on the
same device. Here, without one, it must refuse to run, and its phases
run at tiny sizes with the Pallas kernels in interpret mode, so the
checks the chip relies on are themselves exercised.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_alone(script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_exits_nonzero_without_a_tpu(where, tmp_path):
    """No TPU, or no repository next to the script: a non-zero exit and
    no result line."""
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    res = _run_alone(script)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_cnn_phase_checks_kernel_against_ref(smoke):
    paths = smoke.cnn_phase("resnet18", 0, in_hw=32, width=0.25,
                            mode="kernel")
    assert list(paths) == ["interpret"] and len(paths["interpret"]) == 21


def test_decode_and_fleet_phases_match_their_oracles(smoke):
    tokens = smoke.decode_phase(0, mode="kernel")
    assert len(tokens) == smoke.N_TOKENS
    assert smoke.fleet_phase(0) == 0


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []                  # left to JAX, nothing else set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path   # fixed
    assert calls == [("jax_compilation_cache_dir", path)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
