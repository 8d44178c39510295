"""Tests of the benchmark's own code, at toy size on the CPU.

Run with: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
(they are under `paths`, so the tier-1 run of tests/ does not collect them).
"""
from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.json')
BENCH = os.path.join(ROOT, 'BENCHMARK.json')

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


def toy_run(tmp_path, trace, seed=2**31 + 11, **kw):
  from benchmark import run
  return run.run_cell(TOY, 'toy_polish', seed, 0.3, trace,
                      require_chip=False, out_dir=str(tmp_path), **kw)


# ---------------------------------------------------------------- harness

@pytest.mark.parametrize('trace', [False, True])
def test_toy_rehearsal_end_to_end(tmp_path, no_cache, trace):
  result = toy_run(tmp_path, trace)
  assert result['correct'] is True
  assert result['failed'] == 0 and result['attempted'] > 0
  assert list(result)[-1] == 'compared'
  assert result['device']['platform'] == 'cpu'
  if trace:
    # Device metrics are never computed off a TPU; counters and spans are.
    assert 'pad_row_share' in result['metrics']
    assert 'h2d_ms_per_pack' in result['metrics']
    assert 'forward_mfu' not in result['metrics']
    assert 'device_idle_share' not in result['metrics']
    assert 'window_s' in result['device']
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


def test_cell_config_traffic_and_metric_are_added_by_files_only(
    tmp_path, no_cache):
  """The toy cells, their configurations, their traffic mix, the metric
  `toy_packs` and the second model family `toy_fc` exist only as fixture
  files and entries of the fixture's BENCHMARK file; nothing under
  benchmark/ names them."""
  result = toy_run(tmp_path, True)
  assert result['metrics']['toy_packs']['value'] >= 1
  from benchmark import run
  second = run.run_cell(TOY, 'toy_fc_polish', 2**31 + 11, 0.3, False,
                        require_chip=False, out_dir=str(tmp_path))
  assert second['correct'] is True and second['attempted'] > 0
  for dirpath, _dirs, files in os.walk(os.path.join(ROOT, 'benchmark')):
    if 'tests' in dirpath.split(os.sep):
      continue
    for name in files:
      if name.endswith(('.py', '.json')):
        with open(os.path.join(dirpath, name)) as f:
          text = f.read()
        assert 'toy_packs' not in text and 'toy_stream' not in text
        assert 'toy_fc' not in text


def test_entry_submits_as_run_inference_does(tmp_path, no_cache, monkeypatch):
  """`runner.py:2000`: one submit per featurize batch, a LIST of per-window
  float32 tensors, each a strided view into its ZMW's pile-up matrix
  (`pileup.py:447`); never the uniform ndarray that only tools send."""
  from deepconsensus_tpu.inference import engine as engine_lib
  real = engine_lib.ConsensusEngine.submit
  calls = []

  def spy(self, raw_windows, tickets):
    calls.append((raw_windows, len(tickets)))
    return real(self, raw_windows, tickets)

  monkeypatch.setattr(engine_lib.ConsensusEngine, 'submit', spy)
  result = toy_run(tmp_path, False)
  assert result['correct'] is True and len(calls) >= 3
  for raw, n_tickets in calls:
    assert isinstance(raw, list) and len(raw) == n_tickets
    w = raw[0]
    assert w.shape == (25, 20, 1) and w.dtype == np.float32
    assert w.base is not None and not w.flags['C_CONTIGUOUS']
  # Whole featurize batches (the pool: 8 ZMWs x 16 windows), then one
  # short last batch that completes the pack.
  sizes = [n for _raw, n in calls[1:]]
  assert set(sizes[:-1]) <= {128} and result['attempted'] % 32 == 0


def test_refuses_a_machine_without_the_chip():
  from benchmark import run
  with pytest.raises(SystemExit):
    run.run_cell(BENCH, 'teacher_polish', 1, 0.1, False)


def _break_finalize(monkeypatch, mutate):
  from deepconsensus_tpu.inference import runner as runner_lib
  real = runner_lib.ModelRunner.finalize

  def broken(self, handle):
    ids, quals = real(self, handle)
    return mutate(np.array(ids), np.array(quals))

  monkeypatch.setattr(runner_lib.ModelRunner, 'finalize', broken)


@pytest.mark.parametrize('fault', ['ids_altered', 'rows_shifted',
                                   'quals_altered'])
def test_answer_altered_where_it_is_produced_is_not_correct(
    tmp_path, no_cache, monkeypatch, fault):
  """The rest of a run with the timed path broken underneath."""
  def mutate(ids, quals):
    if fault == 'ids_altered':
      ids[::4] = (ids[::4] + 1) % 5
    elif fault == 'rows_shifted':
      ids, quals = np.roll(ids, 1, axis=0), np.roll(quals, 1, axis=0)
    else:
      quals = quals + 3
    return ids, quals

  _break_finalize(monkeypatch, mutate)
  assert toy_run(tmp_path, False)['correct'] is False


def test_lost_windows_are_not_correct(tmp_path, no_cache, monkeypatch):
  from deepconsensus_tpu.inference import engine as engine_lib
  real = engine_lib._WindowPacker._deliver_pack

  def lossy(self, tickets, pred_ids, quality, t0):
    return real(self, tickets[:-1], pred_ids[:-1], quality[:-1], t0)

  monkeypatch.setattr(engine_lib._WindowPacker, '_deliver_pack', lossy)
  result = toy_run(tmp_path, False)
  assert result['failed'] > 0 and result['correct'] is False


# ------------------------------------------------------------- generators

def test_generator_is_deterministic_in_the_seed_and_differs_across_seeds():
  from benchmark.generators import pileup_windows as gen
  kw = dict(max_passes=5, length=20, passes_min=2, passes_max=5,
            error_rate=0.1, insert_col_rate=0.08, partial_pass_rate=0.15,
            kinetics_mean=30.0, sn_min=4.0, sn_max=20.0)
  a = gen.make_windows(64, seed=2**31 + 5, **kw)
  b = gen.make_windows(64, seed=2**31 + 5, **kw)
  c = gen.make_windows(64, seed=2**31 + 6, **kw)
  assert a.shape == (64, 25, 20, 1) and a.dtype == np.float32
  assert np.array_equal(a, b) and not np.array_equal(a, c)
  bases, pw, strand = a[:, :5, :, 0], a[:, 5:10, :, 0], a[:, 15:20, :, 0]
  assert bases.min() >= 0 and bases.max() <= 4
  assert pw.max() <= 255 and ((pw > 0) == (bases > 0)).all()
  assert set(np.unique(strand)) <= {0.0, 1.0, 2.0}
  assert (a[:, 21:, :, 0] >= 4).all() and (a[:, 21:, :, 0] <= 20).all()
  # Absent passes stay zero, as the featurizer leaves them.
  assert (bases.reshape(64, 5, -1).max(axis=2) == 0).any()


def test_weights_tree_is_the_programs_and_seeded():
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from deepconsensus_tpu.models import model as model_lib

  loaded = run.load_cell(TOY, 'toy_polish')
  config, family = loaded.config, loaded.family
  params = run.program_params(config, family)
  model = model_lib.get_model(params)
  want = jax.eval_shape(
      lambda k: model.init(k, jnp.zeros((1, 25, 20, 1))),
      jax.random.PRNGKey(0))['params']
  got = family.make_params(family.shape_of(config), 2**31 + 3)
  shapes = lambda t: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), t)
  assert shapes(got) == shapes(want)
  again = family.make_params(family.shape_of(config), 2**31 + 3)
  other = family.make_params(family.shape_of(config), 3)
  leaves = jax.tree_util.tree_leaves
  assert all(np.array_equal(x, y) for x, y in zip(leaves(got), leaves(again)))
  assert not all(np.array_equal(x, y)
                 for x, y in zip(leaves(got), leaves(other)))
  alpha = got['encoder']['attention_wrapper_0']['alpha']
  assert 0.5 <= float(alpha) < 1.0


# ------------------------------------------------------------------- work

def real_cell(name):
  """(family module, shape) of one of the benchmark's own cells."""
  from benchmark import run
  loaded = run.load_cell(BENCH, name)
  return loaded.family, loaded.family.shape_of(loaded.config)


@pytest.mark.parametrize('through', ['lib', 'family'])
def test_work_against_a_hand_count_at_toy_widths(through):
  from benchmark.lib import work
  if through == 'family':  # what the metrics are handed as `reading.work`
    work_of = real_cell('teacher_polish')[0]
  else:
    work_of = work
  shape = dict(max_length=4, hidden_size=6, filter_size=10,
               num_hidden_layers=2, attn_win_size=1, condense_input_size=8)
  # Pairs with |i-j| <= 1 in 4 positions: 2 + 3 + 3 + 2.
  assert work.band_pairs(4, 1) == 10
  parts = work_of.flops_per_window(shape)
  assert parts['condense'] == 2 * 4 * 8 * 6
  assert parts['qkvo'] == 2 * 4 * (2 * 4 * 6 * 6)
  assert parts['band_scores'] == 2 * (2 * 10 * 6) == parts['band_values']
  assert parts['ffn'] == 2 * 2 * (2 * 4 * 6 * 10)
  assert parts['head'] == 2 * 4 * 6 * 5
  assert parts['total'] == sum(v for k, v in parts.items() if k != 'total')


@pytest.mark.parametrize('cell,xla_flops,n_params', [
    ('teacher_polish', 1.864e9, 8_943_775),
    ('student_polish', 1.559e9, 7_480_965)])
def test_work_against_the_compiled_programs_count(cell, xla_flops, n_params):
  """XLA counts the full 100x100 attention and elementwise work too, so
  the algorithm's matmul count lies a few percent below it."""
  work, shape = real_cell(cell)
  total = work.flops_per_window(shape)['total']
  assert 0.95 * xla_flops < total < xla_flops
  assert work.param_count(shape) == n_params
  from benchmark.lib import peaks
  least = work.least_seconds_per_pack(shape, 8192, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'


def test_peaks_table():
  from benchmark.lib import peaks
  assert peaks.peaks_for('TPU v5 lite')['bf16_flops_per_s'] == 197e12
  assert peaks.peaks_for('TPU v5e')['hbm_bytes_per_s'] == 819e9
  with pytest.raises(KeyError):
    peaks.peaks_for('TPU v9 imaginary')


# -------------------------------------------------------------- reference

def test_reference_agrees_with_the_flax_model_in_float32(no_cache):
  import jax
  from benchmark import run
  from benchmark.generators import pileup_windows as gen
  from deepconsensus_tpu.models import model as model_lib

  loaded = run.load_cell(TOY, 'toy_polish')
  family = loaded.family
  shape = family.shape_of(loaded.config)
  params = run.program_params(loaded.config, family)
  tree = family.make_params(shape, 17)
  windows = gen.make(shape, loaded.traffic, 17)[:32]
  out = model_lib.get_model(params).apply(
      {'params': tree}, windows, method='apply_with_intermediates')
  with jax.default_matmul_precision('highest'):
    ref = family.reference_logits(tree, windows, shape, block=32)
  assert np.abs(np.asarray(out['logits']) - ref).max() < 1e-4
  # And the lower precisions really are lower.
  low = family.reference_logits(tree, windows, shape, 'fp8', block=32)
  assert np.abs(low - ref).max() > 1e-2


def test_reference_imports_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'reference', 'forward.py')) as f:
    text = f.read()
  assert 'deepconsensus_tpu' not in text.split('"""', 2)[2]


def test_phred_epilogue_of_the_reference():
  from benchmark.reference import forward as ref
  q = ref.phred(np.array([0.0, 0.5, 0.9, 0.99, 1.0]))
  assert q.tolist() == [0, 3, 10, 20, 93]


def test_compare_numbers_and_judge():
  from benchmark.lib import compare
  logits = np.zeros((1, 3, 5)); logits[0, :, 2] = [2.0, 1.0, 3.0]
  ids, quals = compare.served_from_logits(logits)
  clean = compare.numbers(logits, ids, quals)
  assert clean['id_gap_max'] == 0 and clean['qual_diff_mean'] == 0
  wrong = ids.copy(); wrong[0, 1] = 0
  dirty = compare.numbers(logits, wrong, quals + 2)
  assert dirty['id_gap_max'] == pytest.approx(1.0)
  assert dirty['id_mismatch_share'] == pytest.approx(1 / 3)
  assert dirty['qual_diff_mean'] == pytest.approx(2.0)
  yard = logits.copy(); yard[0, 1, 0] = 1.5  # one flip, gap 1.0
  ratio = compare.numbers(logits, wrong, quals + 2, yard)
  assert ratio['id_gap_mean_vs_bf16'] == pytest.approx(1.0)
  judged = compare.judge(dirty, {'id_gap_max': {'limit': 0.5}})
  assert judged == [('id_gap_max', pytest.approx(1.0), 0.5, False)]
  assert compare.judge({}, {'id_gap_max': {'limit': 0.5}})[0][3] is False


# ----------------------------------------------------------------- xplane

def test_union_and_gaps_on_a_hand_made_trace():
  from benchmark.lib import xplane
  assert xplane.union_seconds([(0, 4e9), (2e9, 5e9), (7e9, 8e9)]) == 6.0
  planes = {
      '/device:TPU:0': {
          'XLA Ops': [('fusion.1', 1e9, 2e9), ('dot.2', 2.5e9, 1e9),
                      ('fusion.1', 6e9, 1e9)],
          'XLA Modules': [('jit_forward(1)', 1e9, 2.5e9),
                          ('jit_forward(1)', 6e9, 1e9),
                          ('jit_other(2)', 8e9, 1e9)]},
      '/host:CPU': {'python': [('bench_window', 0.0, 10e9),
                               ('bench_submit', 3.4e9, 2.7e9)]},
  }
  lo, hi = xplane.window_of(planes, 'bench_window')
  assert (lo, hi) == (0.0, 10e9)
  assert xplane.busy_seconds(planes, lo, hi) == pytest.approx(3.5)
  assert xplane.module_durations(planes, 'jit_forward', lo, hi) == [2.5, 1.0]
  assert xplane.top_ops(planes, lo, hi)[0] == ['fusion.1', 3.0]
  gaps = dict(xplane.idle_gaps(planes, lo, hi, ['bench_submit']))
  assert gaps['bench_submit'] == pytest.approx(2.5)
  assert gaps['host_other'] == pytest.approx(4.0)


def test_loader_reads_a_trace_this_jax_writes(tmp_path, no_cache):
  import jax
  import jax.numpy as jnp
  from benchmark.lib import xplane
  jax.profiler.start_trace(str(tmp_path))
  with jax.profiler.TraceAnnotation('bench_window'):
    jnp.ones((8, 8)).sum().block_until_ready()
  jax.profiler.stop_trace()
  planes = xplane.load(xplane.find_trace(str(tmp_path)), ['bench_window'])
  lo, hi = xplane.window_of(planes, 'bench_window')
  assert hi > lo
  assert xplane.device_planes(planes) == []  # no TPU here


RECORDED = os.path.join(HERE, 'fixtures', 'recorded_trace_v5e.json')


@pytest.mark.skipif(not os.path.exists(RECORDED), reason='no recorded trace')
def test_reduction_on_the_recorded_chip_trace():
  from benchmark.lib import xplane
  with open(RECORDED) as f:
    rec = json.load(f)
  planes = {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in rec['planes'].items()}
  lo, hi = xplane.window_of(planes, 'bench_window')
  want = rec['expected']
  assert xplane.busy_seconds(planes, lo, hi) == pytest.approx(want['busy_s'])
  durations = xplane.module_durations(planes, 'jit_forward', lo, hi)
  assert len(durations) == want['n_forward']
  assert xplane.median(durations) == pytest.approx(want['forward_median_s'])
  assert xplane.top_ops(planes, lo, hi)[0][0] == want['top_op']


def test_scope_seconds_on_a_hand_made_trace():
  """Device seconds of the operations whose scope matches: a union, so an
  operation nested in another of the same scope is not counted twice; the
  mean over the device planes; nothing without the side table."""
  from benchmark.lib import xplane
  ops = [('fusion.1', 1e9, 2e9), ('dot.2', 1.5e9, 1e9), ('copy.3', 4e9, 1e9),
         ('fusion.4', 6e9, 1e9)]
  planes = xplane.Planes({
      '/device:TPU:0': {'XLA Ops': list(ops)},
      '/device:TPU:1': {'XLA Ops': list(ops[:1])},
      '/host:CPU': {'python': [('bench_window', 0.0, 10e9)]}})
  planes.scopes['/device:TPU:0'] = [
      'jit(f)/encoder/ffn_0/dot_general', 'jit(f)/encoder/ffn_0/add', '',
      'jit(f)/encoder/attention_1/dot_general']
  planes.scopes['/device:TPU:1'] = ['jit(f)/encoder/ffn_0/dot_general']
  lo, hi = xplane.window_of(planes, 'bench_window')
  # ffn_0: [1, 3) and the nested [1.5, 2.5) on device 0, [1, 3) on device 1.
  assert xplane.scope_seconds(planes, lo, hi, 'ffn_0') == pytest.approx(2.0)
  assert xplane.scope_seconds(planes, lo, hi, r'attention_\d') == (
      pytest.approx(0.5))
  assert xplane.scope_seconds(planes, lo, hi, '^$') == pytest.approx(0.5)
  assert xplane.scope_seconds(planes, lo, hi, 'no_such_scope') == 0.0
  # Every operation: what `busy_seconds` reads; clipped to the window.
  assert xplane.scope_seconds(planes, lo, hi, '') == pytest.approx(
      xplane.busy_seconds(planes, lo, hi)) == pytest.approx(3.0)
  assert xplane.scope_seconds(planes, 2e9, 6.5e9, 'encoder') == (
      pytest.approx((1.0 + 0.5 + 1.0) / 2))
  plain = {k: v for k, v in planes.items()}
  assert xplane.scope_seconds(plain, lo, hi, '') == 0.0
  assert any('scopes | 4 distinct' in line for line in xplane.describe(planes))
  # The side table survives a recording, and an Event stays a 3-tuple.
  back = xplane.from_recording(json.loads(json.dumps(
      xplane.to_recording(planes))))
  assert back.scopes == planes.scopes and back == planes
  assert all(len(e) == 3 for e in back['/device:TPU:0']['XLA Ops'])


def _put(number, payload):
  """One length-delimited protobuf field."""
  assert number < 16 and len(payload) < 128 * 128
  size = len(payload)
  head = bytes([size]) if size < 128 else bytes([size & 0x7F | 0x80, size >> 7])
  return bytes([number << 3 | 2]) + head + payload


def test_op_scopes_reads_the_event_metadata_of_device_planes(tmp_path):
  """The wire reader on a hand-made XSpace: the scope is the `tf_op` stat
  of an event's metadata, as a string or as a reference to a stat
  metadata's name; lines are skipped; host planes are left out."""
  from benchmark.lib import xplane
  varint = lambda number, value: bytes([number << 3, value])
  stat_meta = lambda i, name: _put(5, varint(1, i) + _put(
      2, varint(1, i) + _put(2, name)))
  event_meta = lambda i, name, stat: _put(4, varint(1, i) + _put(
      2, varint(1, i) + _put(2, name) + _put(5, stat)))
  line = _put(3, _put(2, b'XLA Ops') + _put(4, varint(1, 7) + varint(3, 9)))
  plane = (_put(2, b'/device:TPU:0') + line
           + stat_meta(1, b'tf_op') + stat_meta(2, b'source')
           + stat_meta(3, b'jit(f)/encoder/ffn_0/add:')
           + event_meta(7, b'%fusion.1 = bf16[8]', varint(1, 1) + _put(
               5, b'jit(f)/encoder/ffn_0/dot_general:'))
           + event_meta(8, b'%add.2 = bf16[8]', varint(1, 1) + varint(7, 3))
           + event_meta(9, b'%copy.3 = bf16[8]', varint(1, 2) + _put(
               5, b'model.py:92')))
  host = _put(2, b'/host:CPU') + stat_meta(1, b'tf_op') + event_meta(
      7, b'main', varint(1, 1) + _put(5, b'not a device'))
  path = tmp_path / 't.xplane.pb'
  path.write_bytes(_put(1, plane) + _put(1, host))
  assert xplane.op_scopes(str(path)) == {'/device:TPU:0': {
      '%fusion.1 = bf16[8]': 'jit(f)/encoder/ffn_0/dot_general',
      '%add.2 = bf16[8]': 'jit(f)/encoder/ffn_0/add',
      '%copy.3 = bf16[8]': ''}}


RECORDED_SCOPES = os.path.join(HERE, 'fixtures',
                               'recorded_trace_v5e_scopes.json')


def test_scope_seconds_on_the_recorded_chip_traces():
  """The recording of PR 27 carries the side table; the one of PR 24 was
  made before it, keeps loading, and reads 0.0."""
  from benchmark.lib import xplane
  with open(RECORDED) as f:
    old = xplane.from_recording(json.load(f))
  lo, hi = xplane.window_of(old, 'bench_window')
  assert old.scopes == {} and xplane.busy_seconds(old, lo, hi) > 0
  assert xplane.scope_seconds(old, lo, hi, '') == 0.0
  with open(RECORDED_SCOPES) as f:
    rec = json.load(f)
  planes = xplane.from_recording(rec)
  lo, hi = xplane.window_of(planes, 'bench_window')
  want = rec['expected']
  assert xplane.busy_seconds(planes, lo, hi) == pytest.approx(want['busy_s'])
  assert len(want['scope_s']) >= 3
  for pattern, seconds in want['scope_s'].items():
    assert xplane.scope_seconds(planes, lo, hi, pattern) == pytest.approx(
        seconds)
  # Every operation is what busy_seconds reads; a layer is a part of it.
  assert want['scope_s'][''] == pytest.approx(want['busy_s'], rel=0.01)
  parts = [s for p, s in want['scope_s'].items() if p]
  assert all(0 < s < want['busy_s'] for s in parts)
  durations = xplane.module_durations(planes, 'jit_forward', lo, hi)
  assert len(durations) == want['n_forward']
  assert xplane.top_ops(planes, lo, hi)[0][0] == want['top_op']


# ------------------------------------------------------ BENCHMARK.json

def test_benchmark_json_keeps_to_the_contract():
  with open(BENCH) as f:
    bench = json.load(f)
  assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
  assert os.path.getsize(BENCH) < 64 * 1024
  assert 1 <= bench['run_seconds'] <= 51
  cells = {c['name']: c for c in bench['workloads']}
  configs = {c['name']: c for c in bench['configs']}
  for c in bench['configs']:
    assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
    assert NAME.match(c['name']) and len(c['source']) <= 200
    assert c['file'].startswith(tuple(p + '/' for p in bench['paths']))
    assert os.path.exists(os.path.join(ROOT, c['file']))
    assert c['name'] in {w['config'] for w in bench['workloads']}
  for w in bench['workloads']:
    assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert NAME.match(w['name']) and NAME.match(w['traffic'])
    assert w['config'] in configs and w['chips'] in (1, 4)
    assert 1 <= len(w['why']) <= 200 and '\n' not in w['why']
    assert os.path.exists(os.path.join(
        ROOT, 'benchmark', 'traffic', w['traffic'] + '.json'))
    assert os.path.exists(os.path.join(
        ROOT, 'benchmark', 'limits', w['name'] + '.json'))
  e2e = {m['name']: m for m in bench['end_to_end']}
  assert 'setup_s' in e2e
  for m in bench['end_to_end']:
    assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                      'source'}
    assert m['source'] in ('host_clock', 'device_trace')
    assert 0 < m['bound'] <= 0.1
  names = [m['name'] for m in bench['end_to_end'] + bench['per_layer']]
  assert len(names) == len(set(names))
  for m in bench['per_layer']:
    assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                      'layer', 'moves'}
    assert m['moves'] in e2e and m['source'] in SOURCES
    assert os.path.exists(os.path.join(
        ROOT, 'benchmark', 'metrics', m['name'] + '.py'))
    if m['name'].endswith('_roofline') or 'mfu' in m['name']:
      assert m['unit'] == '%'
    moved = e2e[m['moves']]
    for cell in m.get('workloads', cells):
      assert cell in cells
      assert cell in moved.get('workloads', cells)
  for m in bench['end_to_end'] + bench['per_layer']:
    assert NAME.match(m['name']) and UNIT.match(m['unit'])
    assert m['better'] in ('lower', 'higher')


# ---------------------------------------------------------------- control

@pytest.mark.parametrize('cell', ['teacher_polish', 'student_polish'])
def test_control_fp8_reference_fails_the_cells_limits(cell, no_cache):
  """The reference at the next precision below bf16, put in the program's
  place at the published widths (64 windows: what a test run can hold),
  must come out as not correct under the cell's own limits."""
  from benchmark import run
  from benchmark.generators import pileup_windows as gen
  from benchmark.lib import compare

  loaded = run.load_cell(BENCH, cell)
  family = loaded.family
  shape = family.shape_of(loaded.config)
  tree = family.make_params(shape, 5)
  windows = gen.make_windows(
      64, seed=5, max_passes=shape['max_passes'], length=shape['max_length'],
      **loaded.traffic['generator_params'])
  ref = family.reference_logits(tree, windows, shape, block=32)
  yard = family.reference_logits(tree, windows, shape, 'bfloat16', block=32)
  low = family.reference_logits(tree, windows, shape, 'fp8', block=32)
  judged = compare.judge(
      compare.numbers(ref, *compare.served_from_logits(low), yard),
      loaded.limits)
  assert judged and not all(ok for *_r, ok in judged)
  # The reference itself, and the yardstick, pass the same comparison.
  for logits in (ref, yard):
    same = compare.judge(
        compare.numbers(ref, *compare.served_from_logits(logits), yard),
        loaded.limits)
    assert all(ok for *_r, ok in same)
