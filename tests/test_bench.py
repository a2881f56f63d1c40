"""The benchmark's CPU tests of its pure parts, collected with the repo's
tests: trace reduction (``xtrace``, ``spans``), operation counts, metric
arithmetic, the PHY tables' agreement with the program, and the refusal
to run without a TPU (``bench/tests/test_harness.py`` and
``bench/tests/test_spans.py``, a few seconds together).

``bench/tests/test_correct.py`` serves whole windows and takes minutes;
run it with ``PYTHONPATH=src python -m pytest -q bench/tests``.
"""
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "bench", "tests"))

from test_harness import *  # noqa: E402,F401,F403
from test_spans import *  # noqa: E402,F401,F403
