"""Tests of the benchmark's own code run off the chip: the CPU backend is
chosen before jax is imported, and nothing here loads libtpu."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
