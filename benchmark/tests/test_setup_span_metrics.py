"""The six per-layer metrics that read the program's start-up and compile
spans (PR 36): five that move `setup_s`, one that moves `windows_per_s`.

A hand-made span file as the program writes one when its tracer is configured
after set-up (the start-up record first: `import_runner`, `runner_init` and
JAX's `jit_trace` / `jit_lower` / `xla_compile` events with `args.under`),
on which each reader returns the number worked out by hand; a span file of a
program without the instrument (the parent commit), on which each returns
nothing and does not raise; and the toy cell traced on the CPU, where all six
appear.

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
WINDOW = (T0 + 40.0, T0 + 70.0)

# Metric -> the span it reads.
READS = {
    'program_import_s': 'import_runner',
    'runner_init_s': 'runner_init',
    'forward_trace_s': 'jit_trace',
    'forward_lower_s': 'jit_lower',
    'forward_compile_s': 'xla_compile',
    'compile_ms_in_window': 'xla_compile',
}
NAMES = tuple(READS)


def _event(name, start, dur, **args):
  return {'name': name, 'cat': 'stage', 'ph': 'X', 'ts': (T0 + start) * 1e6,
          'dur': dur * 1e6, 'pid': 1, 'tid': 1, 'args': args}


def setup_events():
  """Seconds from T0; the window is [40, 70).

  import_runner [2, 13). The weights' program, jitted outside any stage
  before the runner exists: trace [14, 15), lower [15, 15.5), compile
  [15.5, 17). A first runner_init [17, 18) (a runner thrown away), the one
  that counts [20, 25) with weights_prepare and weights_place inside it.
  The warm-up pack's forward_launch [30, 39): the forward's trace [30, 36)
  with three kernels traced inside it (1 s each, one of them with a helper
  of its own inside), lower [36, 38) with a trace [36.5, 37) inside it,
  compile [38, 38.7) out of the cache.
  """
  under = {'under': 'forward_launch', 'pack': 1}
  return [
      {'name': 'process_name', 'ph': 'M', 'pid': 1, 'tid': 0,
       'args': {'name': 'dctpu-bench'}},
      _event('import_runner', 2.0, 11.0, jax_preloaded=True),
      _event('jit_trace', 14.0, 1.0, fun='make'),
      _event('jit_lower', 15.0, 0.5, fun='jit(make)'),
      _event('xla_compile', 15.5, 1.5, fun='jit(make)', cache_hit=False),
      _event('runner_init', 17.0, 1.0, weight_bytes=8, block_kind='toy',
             mesh_dp=1),
      _event('weights_prepare', 20.0, 1.0, under='runner_init'),
      _event('weights_place', 21.0, 3.0, under='runner_init'),
      _event('runner_init', 20.0, 5.0, weight_bytes=8, block_kind='toy',
             mesh_dp=1),
      # Inner events end, and are written, before the outer one.
      _event('jit_trace', 31.2, 0.5, fun='helper', **under),
      _event('jit_trace', 31.0, 1.0, fun='kernel', **under),
      _event('jit_trace', 32.0, 1.0, fun='kernel', **under),
      _event('jit_trace', 33.0, 1.0, fun='kernel', **under),
      _event('jit_trace', 30.0, 6.0, fun='forward', **under),
      _event('jit_trace', 36.5, 0.5, fun='rule', **under),
      _event('jit_lower', 36.0, 2.0, fun='jit(forward)', **under),
      _event('xla_compile', 38.0, 0.7, fun='jit(forward)', cache_hit=True,
             cache_retrieval_s=0.6, **under),
      _event('forward_launch', 30.0, 9.0, pack=1),
  ]


def window_events(recompile=False):
  """The window's own spans, written after the start-up record. With
  `recompile`, pack 7 meets a new shape: trace [50, 50.4) with a helper
  inside, lower [50.4, 50.5), compile [50.5, 52.5), and a jit outside any
  stage compiles [69.5, 70.5), half of it inside the window."""
  under = {'under': 'forward_launch', 'pack': 7, 'parent': 70, 'span': 0}
  events = [
      _event('submit', 40.0, 15.0, span=60, n_windows=8, formatted=0),
      _event('forward_launch', 41.0, 0.001, span=61, parent=60, pack=2),
  ]
  if recompile:
    events += [
        _event('jit_trace', 50.1, 0.2, fun='helper', **under),
        _event('jit_trace', 50.0, 0.4, fun='forward', **under),
        _event('jit_lower', 50.4, 0.1, fun='jit(forward)', **under),
        _event('xla_compile', 50.5, 2.0, fun='jit(forward)',
               cache_hit=False, **under),
        _event('forward_launch', 50.0, 2.6, span=70, parent=60, pack=7),
        _event('xla_compile', 69.5, 1.0, fun='jit(late)', cache_hit=False),
    ]
  return events


def parent_commit_events():
  """What the program wrote before PR 36: the window's chain, nothing of
  set-up and no compile event."""
  return [
      _event('submit', 40.0, 15.0, span=60, n_windows=8, formatted=0),
      _event('forward_launch', 41.0, 0.001, span=61, parent=60, pack=2),
      _event('finalize_drain', 42.0, 0.2, span=62, parent=60, pack=1),
  ]


def reading_of(tmp_path, events, window=WINDOW):
  from benchmark.lib import spans as spans_lib

  path = tmp_path / 'spans.jsonl'
  path.write_text('[\n' + ''.join(json.dumps(e) + ',\n' for e in events))
  return types.SimpleNamespace(
      spans=spans_lib.read_spans(str(path)), span_window=window,
      result={'counters': {'n_packs': 2}}, spans_lib=spans_lib)


def read_metric(name, reading):
  from benchmark import run

  return run.load_by_name(BENCH_DIR, 'metrics', name).read(reading)


def new_entries():
  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  return [m for m in bench['per_layer'] if m['name'] in NAMES]


def test_the_six_entries_are_as_the_issue_names_them():
  from deepconsensus_tpu.obs import trace

  with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    bench = json.load(f)
  # Appended: the last six, in the issue's order.
  assert [m['name'] for m in bench['per_layer'][-6:]] == list(NAMES)
  for m in new_entries():
    in_window = m['name'] == 'compile_ms_in_window'
    assert m == {
        'name': m['name'], 'unit': 'ms' if in_window else 's',
        'better': 'lower', 'source': 'program_span',
        'moves': 'windows_per_s' if in_window else 'setup_s',
        'layer': 'dispatch' if in_window else 'set-up'}
    path = os.path.join(BENCH_DIR, 'metrics', m['name'] + '.py')
    assert os.path.exists(path)
    # It names a span that the program keeps until tracing is configured.
    assert READS[m['name']] in trace.STARTUP_SPANS
    with open(path) as f:
      assert READS[m['name']] in f.read()


BY_HAND = {
    'program_import_s': 11.0,
    # The one that ends before the window, not the runner thrown away.
    'runner_init_s': 5.0,
    # [30, 36) with everything inside it once, and [36.5, 37) inside the
    # lowering; not the weights' program, which is under no launch.
    'forward_trace_s': 6.0 + 0.5,
    'forward_lower_s': 2.0,
    'forward_compile_s': 0.7,
    'compile_ms_in_window': 0.0,
}


@pytest.mark.parametrize('name', list(NAMES))
def test_reader_on_the_hand_made_span_file(tmp_path, name):
  reading = reading_of(tmp_path, setup_events() + window_events())
  assert read_metric(name, reading) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize('name', list(NAMES))
def test_a_compile_inside_the_window_moves_one_reader_only(tmp_path, name):
  """Trace, lower and compile of pack 7 tile [50, 52.5), the helper's trace
  counted once, and the late jit has 0.5 s inside the window: 3,000 ms. No
  set-up metric sees any of it."""
  reading = reading_of(
      tmp_path, setup_events() + window_events(recompile=True))
  by_hand = dict(BY_HAND, compile_ms_in_window=1e3 * (2.5 + 0.5))
  assert read_metric(name, reading) == pytest.approx(by_hand[name])


@pytest.mark.parametrize('name', list(NAMES))
def test_reader_finds_nothing_where_its_span_is_missing(tmp_path, name):
  """The driver lays these files over the parent's checkout too: there the
  program writes none of the new spans, and a reader returns nothing."""
  assert read_metric(name, reading_of(tmp_path, parent_commit_events())) is None
  assert read_metric(name, reading_of(tmp_path, [])) is None
  # Each one's own span taken out of the whole file.
  without = [e for e in setup_events() + window_events()
             if e['name'] != READS[name]]
  if name == 'compile_ms_in_window':
    # Any compile span says the instrument is there: 0.0, not nothing.
    assert read_metric(name, reading_of(tmp_path, without)) == 0.0
    without = [e for e in without if e['name'] != 'forward_launch']
  assert read_metric(name, reading_of(tmp_path, without)) is None


def test_toy_cell_traced_reports_all_six(tmp_path):
  """The toy cell through run_cell on the CPU, with a benchmark file that
  lists the six entries beside the fixture's own. The harness configures the
  tracer after set-up, so what the readers find is the start-up record."""
  import jax

  from benchmark import run
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.obs import trace

  jax.config.update('jax_enable_compilation_cache', False)
  # A long pytest process: whatever other tests compiled is not this
  # run's, and the import's span may be gone with it.
  trace.clear_early()
  runner_lib.record_import_span()
  with open(TOY) as f:
    bench = json.load(f)
  fixtures = os.path.dirname(TOY)
  for config in bench['configs']:
    config['file'] = os.path.join(fixtures, config['file'])
  bench['per_layer'] += new_entries()
  path = tmp_path / 'BENCHMARK.toy6.json'
  path.write_text(json.dumps(bench))
  result = run.run_cell(str(path), 'toy_polish', 2**31 + 36, 0.3, True,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True
  metrics = {k: v['value'] for k, v in result['metrics'].items()}
  for name in NAMES[:-1]:
    assert metrics[name] > 0, name
  assert metrics['compile_ms_in_window'] == 0.0
  assert result['compared']['compiles_in_window']['value'] == 0
  # The forward's three phases follow one another inside the warm-up's
  # launch, after the runner was built.
  spans = json.loads(
      '[' + (tmp_path / 'spans.toy_polish.jsonl').read_text()
      .split('[\n', 1)[1].rstrip().rstrip(',') + ']')
  compiles = [e for e in spans if e['name'] == 'xla_compile'
              and e['args'].get('under') == 'forward_launch']
  assert compiles and all(e['args']['cache_hit'] is False for e in compiles)
  init = next(e for e in spans if e['name'] == 'runner_init')
  assert init['ts'] + init['dur'] <= min(e['ts'] for e in compiles)
