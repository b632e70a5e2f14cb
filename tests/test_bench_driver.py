"""benchmarks/run.py reports a failing module as a row and exits non-zero."""

import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_failed_module_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "no_such_figure"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert "no_such_figure.FAILED" in proc.stdout
    assert proc.returncode != 0
    assert "benchmarks failed: no_such_figure" in proc.stderr
