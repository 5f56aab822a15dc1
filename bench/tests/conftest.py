"""The benchmark's own tests (``python -m pytest bench/tests``): the
repository root and ``src`` go on the path, and JAX stays on the CPU."""
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src"), str(Path(__file__).parent)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
