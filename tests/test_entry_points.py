"""Every runnable file outside ``src/`` loads: its imports resolve against
the package as it stands, so a script left importing a deleted module fails
here and not on first use.  Each file loads in a fresh subprocess from the
checkout root; no ``main`` runs."""
import glob
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry_points() -> list[str]:
    files = (sorted(glob.glob(os.path.join(ROOT, "benchmarks", "*.py")))
             + sorted(glob.glob(os.path.join(ROOT, "examples", "*.py")))
             + [os.path.join(ROOT, "chip_smoke.py")])
    return [os.path.relpath(f, ROOT) for f in files]


def _loader(rel: str) -> str:
    if rel.startswith("benchmarks" + os.sep):
        # a package whose modules import their siblings relatively
        mod = "benchmarks." + os.path.splitext(os.path.basename(rel))[0]
        return f"import importlib; importlib.import_module({mod!r})"
    return f"import runpy; runpy.run_path({rel!r}, run_name='__entry_check__')"


@pytest.mark.parametrize("rel", _entry_points())
def test_entry_point_loads(rel):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(["src", "."]),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _loader(rel)], cwd=ROOT,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
