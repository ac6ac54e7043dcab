import os
import sys

# tests see ONE cpu device (the dry-run sets 512 in its own process only)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.join(os.path.dirname(__file__), "..")
# the repo root holds chip_smoke.py and the benchmarks package
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
