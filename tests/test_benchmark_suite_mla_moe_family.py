"""Tier-1 collects benchmark/tests/test_mla_moe_family.py, so a
metric reader that a rename breaks fails here and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_mla_moe_family import *  # noqa: F401,F403
from benchmark.tests import test_mla_moe_family as _family
from tests import helpers


def test_cell_configuration_traffic_and_metrics_are_entries_of_their_own(real):  # noqa: F811
  """The benchmark's own test of this name holds the cell's five metrics
  to the LAST places of BENCHMARK.json's list, where PR 34 appended them,
  and the count of those that apply to the cell to a literal 19. PR 36
  appended six metrics that carry no list behind them, and no PR but a
  `benchmark` one may edit the benchmark's files, so here the same facts
  are held wherever the entries lie."""
  loaded, family, _shape = real
  helpers.check_benchmark_cell_entries(
      loaded, family, _family, family_file='mla_moe_encoder.py',
      traffic='window_stream_zmw32', reduced=['num_hidden_layers'],
      source=('https://huggingface.co/kakaocorp/'
              'kanana-2-30b-a3b-instruct-2601/blob/main/config.json'),
      metric_sources={
          'latent_roofline': 'device_trace',
          'latent_device_share': 'device_trace',
          'moe128_roofline': 'device_trace',
          'moe128_device_share': 'device_trace',
          'moe128_load_max_over_mean': 'program_counter'})


def test_the_cells_the_benchmark_had_are_as_they_were():  # noqa: F811
  """The benchmark's own test of this name holds the list of cells to the
  five there were when PR 34 appended `kanana_polish`. PR 38 appended a
  sixth behind them, and no PR but a `benchmark` one may edit the
  benchmark's files, so here the same facts are held of the first five
  places."""
  import json

  with open(_family.BENCH) as f:
    bench = json.load(f)
  assert [w['name'] for w in bench['workloads']][:5] == [
      'teacher_polish', 'student_polish', 'brumby_polish', 'qwen3next_polish',
      _family.CELL]
  assert [c['name'] for c in bench['configs']][:4] == [
      'teacher_6x280_L100', 'student_5x280_L100', 'brumby14b_8of40_L100',
      'qwen3next80b_4of48_e256_L100']
  assert bench['run_seconds'] == 30
  assert [m['name'] for m in bench['end_to_end']] == ['windows_per_s',
                                                      'setup_s']
  qwen = [m['name'] for m in bench['per_layer']
          if m.get('workloads') == ['qwen3next_polish']]
  assert qwen == ['moe_roofline', 'gdn_roofline', 'moe_device_share',
                  'expert_load_max_over_mean']
