"""Shared test fixtures/builders (counterpart of the reference's
utils/test_utils.py:49-161)."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from deepconsensus_tpu import constants


def seq_to_array(seq: str) -> np.ndarray:
  """ASCII sequence -> float vocab ids ('A T' -> [1, 0, 2])."""
  return np.array(
      [constants.SEQ_VOCAB.index(c) for c in seq], dtype=np.float32
  )


def seq_to_one_hot(seq: str) -> np.ndarray:
  """ASCII sequence -> one-hot [len, vocab] distribution."""
  eye = np.eye(constants.SEQ_VOCAB_SIZE, dtype=np.float32)
  return np.stack([eye[constants.SEQ_VOCAB.index(c)] for c in seq])


def get_one_hot(index: int) -> np.ndarray:
  return np.eye(constants.SEQ_VOCAB_SIZE, dtype=np.float32)[index]


def multiseq_to_array(seqs: Sequence[str]) -> np.ndarray:
  """List of equal-length sequences -> [n, len] vocab-id matrix."""
  return np.stack([seq_to_array(s) for s in seqs])


def convert_seqs(
    sequences: Tuple[Sequence[str], Sequence[str]]
) -> Tuple[np.ndarray, np.ndarray]:
  """(labels, predictions) string lists -> (y_true ids, y_pred one-hot)."""
  y_true = multiseq_to_array(sequences[0])
  y_pred = np.stack([seq_to_one_hot(s) for s in sequences[1]])
  return y_true, y_pred


def load_dataset_examples(pattern: str) -> List[bytes]:
  """All serialized examples matching a TFRecord glob."""
  from deepconsensus_tpu.io.tfrecord import read_tfrecords

  return list(read_tfrecords(pattern))


def check_benchmark_cell_entries(loaded, family, family_module, *,
                                 family_file: str, traffic: str,
                                 reduced: Sequence[str], source: str,
                                 metric_sources: dict) -> None:
  """What benchmark/tests/test_*_moe_family.py's
  `test_cell_configuration_traffic_and_metrics_are_entries_of_their_own`
  hold of a cell, wherever its entries lie in BENCHMARK.json's lists: the
  cell's own metrics found by name and in order, and the count of those
  that apply to it as the entries without a `workloads` list plus its own
  (the benchmark's copies hold literal counts and last places, which
  metrics appended later break; PERF.md, Open questions)."""
  import os

  bench = loaded.bench
  cell, config = family_module.CELL, family_module.CONFIG
  metrics = list(family_module.NEW_METRICS)
  assert family.__file__ == os.path.join(
      family_module.ROOT, 'benchmark', 'families', family_file)
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == traffic
  assert loaded.cell['config'] == config
  assert [w['name'] for w in bench['workloads']].count(cell) == 1
  (entry,) = [c for c in bench['configs'] if c['name'] == config]
  assert entry['reduced'] == loaded.config['reduced'] == list(reduced)
  assert entry['source'] == source
  assert loaded.config['source'].startswith(entry['source'])
  assert len(entry['why']) <= 200
  mine = [m for m in bench['per_layer'] if m.get('workloads') == [cell]]
  assert [m['name'] for m in mine] == metrics
  for metric in mine:
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
  assert {m['name']: m['source'] for m in mine} == metric_sources
  # The metrics that carry no list apply to the cell as they are.
  shared = [m for m in bench['per_layer'] if 'workloads' not in m]
  assert len(shared) >= 14
  assert len(loaded.per_layer) == len(shared) + len(metrics)
  assert [m['name'] for m in loaded.per_layer if 'workloads' in m] == metrics
  assert set(loaded.limits) <= {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}
  assert loaded.limits
