"""The benchmark's CPU tests import the program from ``src/`` (there is no
installation step) and the benchmark as the ``bench`` package."""
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
