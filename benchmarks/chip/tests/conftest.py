"""The benchmark's own tests: ``pytest benchmarks/chip``, on the CPU."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
