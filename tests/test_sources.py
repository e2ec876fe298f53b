"""Every Python source of the repo parses, and the launch modules import.

Modules that no other test imports (launch scripts, benchmark drivers,
``chip_smoke.py``) would otherwise break silently on a syntax error.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PACKAGE = ROOT / "src" / "repro"
GROUPS = sorted(p.name for p in PACKAGE.iterdir()
                if p.is_dir() and not p.name.startswith(("_", ".")))


def _sources(group: str):
    if group == "repro":
        return sorted(PACKAGE.glob("*.py"))
    if group == "scripts":
        return sorted(ROOT.glob("*.py")) + sorted(
            (ROOT / "benchmarks").glob("*.py")) + sorted(
            (ROOT / "examples").glob("*.py"))
    return sorted((PACKAGE / group).rglob("*.py"))


@pytest.mark.parametrize("group", ["repro", "scripts", *GROUPS])
def test_sources_parse(group):
    files = _sources(group)
    assert files, group
    for path in files:
        compile(path.read_text(), str(path), "exec")


def test_launch_modules_import():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    code = ("import repro.launch.dryrun, repro.launch.serve, "
            "repro.launch.train, repro.launch.cells")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
