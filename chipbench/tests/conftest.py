"""The benchmark's own tests run on the CPU and touch no TPU topology."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.pop("XLA_FLAGS", None)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)
