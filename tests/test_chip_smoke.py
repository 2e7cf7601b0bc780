"""chip_smoke.py: its CPU rehearsal runs every phase, and it refuses to run
(non-zero exit, no result line) where it finds no TPU or no repo."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, env=None):
    env = dict(os.environ if env is None else env)
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, str(cwd / SCRIPT.name), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("chips,phases", [
    (1, ["[serve]", "[scientist]", "[train]"]),
    (4, ["[train-mesh]", "[serve-mesh]"]),
])
def test_rehearsal_runs_every_phase(chips, phases):
    r = _run(["--rehearse", "--chips", str(chips)])
    assert r.returncode == 0, r.stdout + r.stderr
    lines = r.stdout.strip().splitlines()
    phase_lines = [ln for ln in lines if ln.startswith("[")]
    assert [ln.split()[0] for ln in phase_lines] == phases
    for ln in phase_lines:
        assert f" ok on cpu cpu x{chips}: " in ln
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": chips}}


def test_without_a_tpu_it_fails_before_any_phase():
    r = _run([], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "needs 1 tpu device" in r.stderr
    assert '"ok"' not in r.stdout and "[serve]" not in r.stdout


def test_compile_cache_location(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR when set (JAX reads it; nothing else is
    set in code), else the fixed <repo>/.jax_cache."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(["--rehearse"], cwd=tmp_path, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
