"""The benchmark's own tests run on the CPU, the Pallas kernels in
interpret mode; bench/run.py itself refuses to run there."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = pathlib.Path(__file__).resolve().parents[2]
for path in (REPO, REPO / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
