"""Tier-1 collects benchmark/tests/test_parallel_moe_family.py, so a
metric reader that a rename breaks fails here and not on the chip.

One test is redefined here under its own name: the benchmark's copy holds
the cells that read `window_stream_zmw8` to exactly two, and a later cell
(`jamba_polish`) shares that traffic file too. This one holds the same
facts with the cells that came after found by name."""
import json

from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_parallel_moe_family import *  # noqa: F401,F403
from benchmark.tests.test_parallel_moe_family import BENCH, CELL


def test_the_cells_the_benchmark_had_are_as_they_were():
  with open(BENCH) as f:
    bench = json.load(f)
  assert [w['name'] for w in bench['workloads']][:5] == [
      'teacher_polish', 'student_polish', 'brumby_polish', 'qwen3next_polish',
      'kanana_polish']
  assert [c['name'] for c in bench['configs']][:5] == [
      'teacher_6x280_L100', 'student_5x280_L100', 'brumby14b_8of40_L100',
      'qwen3next80b_4of48_e256_L100', 'kanana2_30b_8of48_L100']
  assert bench['run_seconds'] == 30
  assert [m['name'] for m in bench['end_to_end']] == ['windows_per_s',
                                                      'setup_s']
  for cell, names in (
      ('qwen3next_polish', ['moe_roofline', 'gdn_roofline',
                            'moe_device_share', 'expert_load_max_over_mean']),
      ('kanana_polish', ['latent_roofline', 'latent_device_share',
                         'moe128_roofline', 'moe128_device_share',
                         'moe128_load_max_over_mean'])):
    assert [m['name'] for m in bench['per_layer']
            if m.get('workloads') == [cell]] == names
  # No four-chip cell, and the traffic file of `brumby_polish` is shared,
  # not copied: by this cell and, behind it, by `jamba_polish`.
  assert all(w['chips'] == 1 for w in bench['workloads'])
  assert [w['name'] for w in bench['workloads']
          if w['traffic'] == 'window_stream_zmw8'] == [
              'brumby_polish', CELL, 'jamba_polish']
