"""The benchmark's own tests run on the CPU at tiny sizes; they import the
benchmark's modules by name and the program from ``src``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.normpath(os.path.join(HERE, "..", "..", "src"))):
    if path not in sys.path:
        sys.path.insert(0, path)
