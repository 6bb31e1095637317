"""CPU tests of the chip benchmark's own code, at small sizes:

    PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(CHIP.parents[1] / "src"))
