"""`pytest benchmarks/checks -q` runs on the CPU: set before JAX is imported."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
