"""The benchmark's own tests run on the CPU at tiny sizes:
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
