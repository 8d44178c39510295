"""Tier-1 collects benchmark/tests/test_power_retention_family.py, so a
metric reader that a rename breaks fails here and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_power_retention_family import *  # noqa: F401,F403
from benchmark.tests import test_power_retention_family as _family


def test_cell_configuration_traffic_and_metrics_are_appended_entries(real):  # noqa: F811
  """The benchmark's own test of this name holds the cell's entries to the
  LAST places of BENCHMARK.json's lists, where PR 28 appended them. A later
  cell is appended behind them, and no PR but a `benchmark` one may edit
  the benchmark's files, so here the same facts are held wherever the
  entries lie (PERF.md, Open questions, names the edit)."""
  import os

  loaded, family, _shape = real
  bench = loaded.bench
  cell, metrics = _family.CELL, list(_family.NEW_METRICS)
  assert family.__file__ == os.path.join(
      _family.ROOT, 'benchmark', 'families', 'power_retention_encoder.py')
  assert [w for w in bench['workloads'] if w['name'] == cell] == [loaded.cell]
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw8'
  (entry,) = [c for c in bench['configs']
              if c['name'] == loaded.cell['config']]
  assert entry['name'] == 'brumby14b_8of40_L100'
  assert entry['reduced'] == loaded.config['reduced'] == ['num_hidden_layers']
  assert entry['source'] == (
      'https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/'
      'config.json')
  mine = [m for m in bench['per_layer'] if m.get('workloads') == [cell]]
  assert [m['name'] for m in mine] == metrics
  for metric in mine:
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
  # The metrics that carry no list (14 when the cell came, six more since
  # PR 36) all apply to the cell.
  shared = [m for m in bench['per_layer'] if 'workloads' not in m]
  assert len(shared) >= 14
  assert len(loaded.per_layer) == len(shared) + len(metrics)
  assert [m['name'] for m in loaded.per_layer
          if 'workloads' in m] == metrics
  assert set(loaded.limits) == {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}
