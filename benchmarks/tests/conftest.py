"""Tests of the benchmark's own arithmetic. Run with
``python -m pytest benchmarks/tests -q``; tier-1 (``tests/``) does not
collect them."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
