"""Tests of the benchmark itself, on the CPU: ``python -m pytest chipbench/tests``.

They put the program's ``src/`` on the path, as the harness does, and run
JAX on the CPU (Pallas in interpret mode)."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
