"""The eight per-layer metrics that read the program's host spans (PR 25).

A hand-made span file with the ids and parents the program writes
(`obs.stage`: `args.span`, `args.parent`), on which each reader returns the
number worked out by hand; a span file of a program that lacks the spans
(the parent commit), on which each returns nothing and does not raise; and
the toy cell traced on the CPU, where all eight appear.

Run with: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH_DIR = os.path.join(ROOT, 'benchmark')
TOY = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.json')

T0 = 1_700_000_000.0  # the spans' clock is time.time()

NAMES = (
    'host_ms_per_pack', 'stack_ms_per_pack', 'format_ms_per_pack',
    'pack_cut_ms_per_pack', 'pack_cast_ms_per_pack', 'drain_ms_per_pack',
    'deliver_ms_per_pack', 'host_unattributed_ms_per_pack')


def _event(name, start, dur, cat='stage', **args):
  return {'name': name, 'cat': cat, 'ph': 'X', 'ts': (T0 + start) * 1e6,
          'dur': dur * 1e6, 'pid': 1, 'tid': 1, 'args': args}


def hand_made_events():
  """Two submits and a flush on one thread, seconds from T0.

  submit 1, [0, 5): the grouping 0.5 and a stack 1.0, format 2.0, a cut
  0.4, a dispatch of 0.6 (cast 0.3, launch 0.1, h2d 0.1).
  submit 2, [5, 10): a stack 2.0, a cut 0.6, a dispatch of 0.5 (cast 0.3,
  h2d 0.1), a drain 0.3, a deliver 0.2.
  flush, [10, 14): a drain 0.2, a deliver 0.1, a drain 0.3 at 12.9.
  Two pack_waits and a device_compute lie over it all.
  """
  return [
      {'name': 'process_name', 'ph': 'M', 'pid': 1, 'tid': 0,
       'args': {'name': 'dctpu-bench'}},
      _event('submit', 0.0, 5.0, span=1, n_windows=15000, formatted=0),
      _event('stack_windows', 0.0, 0.5, span=2, parent=1),
      _event('stack_windows', 0.5, 1.0, span=3, parent=1),
      _event('format_rows', 1.5, 2.0, span=4, parent=1),
      _event('pack_cut', 3.5, 0.4, span=5, parent=1),
      _event('dispatch', 3.9, 0.6, span=6, parent=1, pack=1),
      _event('pack_cast', 3.9, 0.3, span=7, parent=6),
      _event('forward_launch', 4.2, 0.1, span=8, parent=6),
      _event('h2d_transfer', 4.3, 0.1, span=9, parent=6),
      _event('submit', 5.0, 5.0, span=10, n_windows=15000, formatted=0),
      _event('stack_windows', 5.0, 2.0, span=11, parent=10),
      _event('pack_cut', 7.0, 0.6, span=12, parent=10),
      _event('dispatch', 7.6, 0.5, span=13, parent=10, pack=2),
      _event('pack_cast', 7.6, 0.3, span=14, parent=13),
      _event('h2d_transfer', 7.9, 0.1, span=15, parent=13),
      _event('finalize_drain', 8.1, 0.3, span=16, parent=10, pack=1),
      _event('deliver', 8.4, 0.2, span=17, parent=10),
      _event('flush', 10.0, 4.0, span=18),
      _event('finalize_drain', 10.0, 0.2, span=19, parent=18, pack=2),
      _event('deliver', 10.2, 0.1, span=20, parent=18),
      _event('finalize_drain', 12.9, 0.3, span=21, parent=18, pack=3),
      # Waits: long, over everything, no ids; no reader counts them.
      _event('pack_wait', 0.0, 3.9, cat='wait', bucket=100),
      _event('pack_wait', 3.9, 3.7, cat='wait', bucket=100),
      _event('device_compute', 4.2, 4.2, cat='wait', pack=1),
  ]


def parent_commit_events():
  """What the program wrote before PR 25: no ids, none of the new names."""
  return [
      _event('pack_wait', 6.0, 3.0, bucket=100, n_rows=8192),
      _event('h2d_transfer', 9.0, 0.1, pack=1),
      _event('finalize_drain', 9.5, 0.2, pack=1),
      _event('device_compute', 9.1, 0.6, pack=1),
  ]


def reading_of(tmp_path, events, n_packs=2, window=(T0 + 6.0, T0 + 13.0)):
  from benchmark.lib import spans as spans_lib

  path = tmp_path / 'spans.jsonl'
  path.write_text('[\n' + ''.join(json.dumps(e) + ',\n' for e in events))
  return types.SimpleNamespace(
      spans=spans_lib.read_spans(str(path)), span_window=window,
      result={'counters': {'n_packs': n_packs}}, spans_lib=spans_lib)


def read_metric(name, reading):
  from benchmark import run

  return run.load_by_name(BENCH_DIR, 'metrics', name).read(reading)


def new_entries():
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  return [m for m in bench['per_layer'] if m['name'] in NAMES]


def test_the_eight_entries_are_as_the_issue_names_them():
  entries = new_entries()
  assert [m['name'] for m in entries] == list(NAMES)
  for m in entries:
    assert m == {
        'name': m['name'], 'unit': 'ms', 'better': 'lower',
        'source': 'program_span', 'moves': 'windows_per_s',
        'layer': ('dispatch' if m['name'] in (
            'pack_cast_ms_per_pack', 'drain_ms_per_pack')
                  else 'engine and packer')}
    assert os.path.exists(
        os.path.join(BENCH_DIR, 'metrics', m['name'] + '.py'))


@pytest.mark.parametrize('name', list(NAMES))
def test_reader_on_the_hand_made_span_file(tmp_path, name):
  """At a window that holds the whole file, [0, 14), then at [6, 13), which
  cuts spans at both ends."""
  whole = reading_of(tmp_path, hand_made_events(),
                     window=(T0, T0 + 14.0))
  # Whole file, 2 packs: submits 10 + flush 4 = 14 s.
  by_hand_whole = {
      'host_ms_per_pack': 14.0,
      'stack_ms_per_pack': 0.5 + 1.0 + 2.0,
      'format_ms_per_pack': 2.0,
      'pack_cut_ms_per_pack': 0.4 + 0.6,
      'pack_cast_ms_per_pack': 0.6,
      'drain_ms_per_pack': 0.3 + 0.2 + 0.3,
      'deliver_ms_per_pack': 0.2 + 0.1,
      # submit 1: 5 - (0.5 + 1 + 2 + 0.4 + 0.6) = 0.5; dispatch 1: 0.1;
      # submit 2: 5 - (2 + 0.6 + 0.5 + 0.3 + 0.2) = 1.4; dispatch 2: 0.1;
      # flush: 4 - (0.2 + 0.1 + 0.3) = 3.4.
      'host_unattributed_ms_per_pack': 0.5 + 0.1 + 1.4 + 0.1 + 3.4,
  }
  assert read_metric(name, whole) == pytest.approx(
      1e3 * by_hand_whole[name] / 2)
  # The window [6, 13) cuts submit 2 (4 s inside), its stack (1 s inside),
  # the flush (3 s inside) and the last drain (0.1 s inside); submit 1 and
  # all of its children fall outside.
  cut = reading_of(tmp_path, hand_made_events())
  by_hand_cut = {
      'host_ms_per_pack': 4.0 + 3.0,
      'stack_ms_per_pack': 1.0,
      'format_ms_per_pack': 0.0,
      'pack_cut_ms_per_pack': 0.6,
      'pack_cast_ms_per_pack': 0.3,
      'drain_ms_per_pack': 0.3 + 0.2 + 0.1,
      'deliver_ms_per_pack': 0.2 + 0.1,
      # submit 2: 4 - (1 + 0.6 + 0.5 + 0.3 + 0.2) = 1.4; dispatch 2: 0.1;
      # flush: 3 - (0.2 + 0.1 + 0.1) = 2.6.
      'host_unattributed_ms_per_pack': 1.4 + 0.1 + 2.6,
  }
  assert read_metric(name, cut) == pytest.approx(1e3 * by_hand_cut[name] / 2)


def test_leaves_and_remainder_add_up_to_the_host_time(tmp_path):
  """host = the six leaves + h2d + forward_launch + the remainder: nothing
  is counted twice and nothing falls between two readers."""
  reading = reading_of(tmp_path, hand_made_events(), window=(T0, T0 + 14.0))
  leaves = sum(read_metric(name, reading) for name in (
      'stack_ms_per_pack', 'format_ms_per_pack', 'pack_cut_ms_per_pack',
      'pack_cast_ms_per_pack', 'drain_ms_per_pack', 'deliver_ms_per_pack',
      'h2d_ms_per_pack'))
  launch = 1e3 * 0.1 / 2
  assert leaves + launch + read_metric(
      'host_unattributed_ms_per_pack', reading) == pytest.approx(
          read_metric('host_ms_per_pack', reading))


@pytest.mark.parametrize('name', list(NAMES))
def test_reader_finds_nothing_in_the_parents_span_file(tmp_path, name):
  """The driver lays these files over the parent's checkout too: there the
  program writes none of the new spans, and a reader returns nothing."""
  reading = reading_of(tmp_path, parent_commit_events())
  if name == 'drain_ms_per_pack':
    # `finalize_drain` existed before; no metric read it.
    assert read_metric(name, reading) == pytest.approx(1e3 * 0.2 / 2)
  else:
    assert read_metric(name, reading) is None
  empty = reading_of(tmp_path, [])
  assert read_metric(name, empty) is None
  no_packs = reading_of(tmp_path, hand_made_events(), n_packs=0)
  assert read_metric(name, no_packs) is None


def test_toy_cell_traced_reports_all_eight(tmp_path):
  """The toy cell through run_cell on the CPU, with a benchmark file that
  lists the eight entries beside the fixture's own."""
  import jax

  from benchmark import run

  jax.config.update('jax_enable_compilation_cache', False)
  with open(TOY) as f:
    bench = json.load(f)
  fixtures = os.path.dirname(TOY)
  for config in bench['configs']:
    config['file'] = os.path.join(fixtures, config['file'])
  bench['per_layer'] += new_entries()
  path = tmp_path / 'BENCHMARK.toy8.json'
  path.write_text(json.dumps(bench))
  result = run.run_cell(str(path), 'toy_polish', 2**31 + 25, 0.3, True,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True
  metrics = {k: v['value'] for k, v in result['metrics'].items()}
  for name in NAMES:
    assert metrics[name] > 0, name
  leaves = sum(metrics[name] for name in (
      'stack_ms_per_pack', 'format_ms_per_pack', 'pack_cut_ms_per_pack',
      'pack_cast_ms_per_pack', 'drain_ms_per_pack', 'deliver_ms_per_pack',
      'h2d_ms_per_pack'))
  assert leaves + metrics['host_unattributed_ms_per_pack'] <= (
      metrics['host_ms_per_pack'] * (1 + 1e-6))
