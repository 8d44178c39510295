"""Tier-1 collects benchmark/tests/test_window_moe_family.py, so a metric
reader that a rename breaks fails here and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_window_moe_family import *  # noqa: F401,F403
