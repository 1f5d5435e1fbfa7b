"""These tests run by hand (``python -m pytest benchmarks/tests``), on the
CPU; tier-1 (``pytest tests/``) does not collect them."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
