"""The dry-run CLI: importing it changes nothing in the process, and bad
arguments end through argparse."""
import os
import subprocess
import sys

import pytest

from repro.launch import dryrun


def test_import_leaves_xla_flags_and_the_backend_alone():
    """``main`` sets the 512-device flag itself; the import neither sets it
    nor starts the backend, so a flag set after the import still counts."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = "src"
    code = """
import os
before = os.environ.get("XLA_FLAGS")
import repro.launch.dryrun
after = os.environ.get("XLA_FLAGS")
assert after == before, (before, after)
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
assert jax.device_count() == 8, jax.device_count()
print("UNCHANGED", before)
"""
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.getcwd())
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "UNCHANGED None" in r.stdout


def test_main_without_a_cell_exits_through_argparse(capsys):
    with pytest.raises(SystemExit) as e:
        dryrun.main([])
    assert e.value.code == 2
    assert "--arch and --shape, or --all" in capsys.readouterr().err
