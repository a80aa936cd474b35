"""The benchmark's own tests run on the CPU, from the checkout's root:

    PYTHONPATH=src python -m pytest bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
