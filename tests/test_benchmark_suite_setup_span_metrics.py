"""Tier-1 collects benchmark/tests/test_setup_span_metrics.py, so a
start-up or compile span whose rename breaks a metric reader fails here
and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_setup_span_metrics import *  # noqa: F401,F403
