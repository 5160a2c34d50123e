"""The benchmark's own tests run on the CPU at tiny sizes and never in a
timed path: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests``."""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for p in (REPO, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
