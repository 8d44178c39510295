"""Tier-1 collects benchmark/tests/test_setup_span_metrics.py, so a
start-up or compile span whose rename breaks a metric reader fails here
and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_setup_span_metrics import *  # noqa: F401,F403
from benchmark.tests import test_setup_span_metrics as _metrics


def test_the_six_entries_are_as_the_issue_names_them():  # noqa: F811
  """The benchmark's own test of this name holds the six entries to the
  LAST places of BENCHMARK.json's `per_layer`, where PR 36 appended them.
  PR 38 appended a cell's six metrics behind them, and no PR but a
  `benchmark` one may edit the benchmark's files, so here the same facts
  are held wherever the entries lie: found by name, in the issue's order,
  and applying to every cell."""
  import os

  from deepconsensus_tpu.obs import trace

  entries = _metrics.new_entries()
  assert [m['name'] for m in entries] == list(_metrics.NAMES)
  for m in entries:
    in_window = m['name'] == 'compile_ms_in_window'
    assert m == {
        'name': m['name'], 'unit': 'ms' if in_window else 's',
        'better': 'lower', 'source': 'program_span',
        'moves': 'windows_per_s' if in_window else 'setup_s',
        'layer': 'dispatch' if in_window else 'set-up'}
    path = os.path.join(_metrics.BENCH_DIR, 'metrics', m['name'] + '.py')
    assert os.path.exists(path)
    # It names a span that the program keeps until tracing is configured.
    assert _metrics.READS[m['name']] in trace.STARTUP_SPANS
    with open(path) as f:
      assert _metrics.READS[m['name']] in f.read()
