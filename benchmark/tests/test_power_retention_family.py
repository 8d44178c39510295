"""Family `power_retention_encoder` and the cell `brumby_polish`: new files
only. Toy sizes on the CPU through the harness, the published sizes by
shape alone.

Run with: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, 'fixtures')
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_retention.json')
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'brumby_polish'
NEW_METRICS = ('retention_roofline', 'retention_device_share', 'ffn_roofline',
               'resident_weights_gib')

# config.json of manifestai/Brumby-14B-Base as the model-configs catalog
# gives it (the keys that say something about the model's shape).
PUBLISHED = {
    'attention_bias': False, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 5120, 'intermediate_size': 17408,
    'max_position_embeddings': 32768, 'max_window_layers': 40,
    'model_type': 'brumby', 'num_attention_heads': 40,
    'num_hidden_layers': 40, 'num_key_value_heads': 8, 'rms_norm_eps': 1e-06,
    'rope_scaling': None, 'rope_theta': 1000000, 'sliding_window': None,
    'tie_word_embeddings': False, 'use_sliding_window': False,
    'vocab_size': 151936}


def load(bench, cell):
  from benchmark import run
  return run.load_cell(bench, cell)


@pytest.fixture(scope='module')
def toy(no_cache):
  loaded = load(TOY, 'toy_retention_polish')
  return loaded, loaded.family, loaded.family.shape_of(loaded.config)


@pytest.fixture(scope='module')
def real():
  loaded = load(BENCH, CELL)
  return loaded, loaded.family, loaded.family.shape_of(loaded.config)


@pytest.fixture(scope='module')
def toy_windows(toy):
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  tree = family.make_params(shape, 2**31 + 5)
  windows = gen.make(shape, loaded.traffic, 2**31 + 5)[:48]
  return tree, windows, family.reference_logits(tree, windows, shape)


# ----------------------------------------------------- the files of the cell

def test_cell_configuration_traffic_and_metrics_are_appended_entries(real):
  loaded, family, _shape = real
  bench = loaded.bench
  assert family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'power_retention_encoder.py')
  assert loaded.cell == bench['workloads'][-1]
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw8'
  entry = bench['configs'][-1]
  assert entry['name'] == loaded.cell['config'] == 'brumby14b_8of40_L100'
  assert entry['reduced'] == loaded.config['reduced'] == ['num_hidden_layers']
  assert entry['source'] == (
      'https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/'
      'config.json')
  assert [m['name'] for m in bench['per_layer'][-4:]] == list(NEW_METRICS)
  for metric in bench['per_layer'][-4:]:
    assert metric['workloads'] == [CELL]
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
  # The 14 metrics the benchmark had carry no list: all apply to the cell.
  assert len(loaded.per_layer) == 18
  assert [m['name'] for m in loaded.per_layer[-4:]] == list(NEW_METRICS)
  assert set(loaded.limits) == {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}


def test_configuration_file_holds_the_published_config_but_the_depth(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    if key in config['reduced']:
      assert config[key] == 8 and config[key + '_published'] == value
    else:
      assert key in config and config[key] == value, key
  assert config['batch_size'] == 256 and config['batch_size_why']
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assert any('two directions' in text for text in config['departures'])


def test_traffic_is_the_window_stream_cut_to_eight_zmws(real):
  traffic = real[0].traffic
  with open(os.path.join(ROOT, 'benchmark', 'traffic',
                         'window_stream.json')) as f:
    stream = json.load(f)
  for key in ('entry', 'generator', 'generator_params', 'windows_per_zmw',
              'options', 'loop'):
    assert traffic[key] == stream[key], key
  assert traffic['pool_windows'] == 1200 == 8 * traffic['windows_per_zmw']
  assert traffic['compare_windows'] == 128


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'power_retention_encoder.py')) as f:
    text = f.read().split('"""', 2)[2]
  assert 'deepconsensus_tpu' not in text
  assert 'benchmark.reference' not in text and 'lib.weights' not in text


# ------------------------------------------------------ sizes, file and preset

def test_file_and_preset_agree_at_the_published_sizes(real):
  from benchmark import run
  loaded, family, shape = real
  params = run.program_params(loaded.config, family)
  stated = family.stated(params)
  assert {k: loaded.config[k] for k in stated} == stated
  assert (shape['hidden_size'], shape['num_heads'], shape['num_kv_heads'],
          shape['head_dim'], shape['filter_size'],
          shape['num_hidden_layers']) == (5120, 40, 8, 128, 17408, 8)
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in loaded.config.items()
                     if k != 'num_kv_heads'})


@pytest.mark.parametrize('key,value', [
    ('num_kv_heads', 4), ('head_dim', 64), ('num_key_value_heads', 40),
    ('intermediate_size', 13824), ('rope_theta', 10000),
    ('block_kind', 'banded_softmax_relu'), ('retention_degree', 4)])
def test_file_and_preset_disagreeing_in_a_size_exits(real, key, value):
  from benchmark import run
  loaded, family, _shape = real
  config = dict(loaded.config, **{key: value})
  with pytest.raises(SystemExit, match='configuration file and program '
                     f"disagree: .*'{key}'"):
    run.program_params(config, family)


# ------------------------------------------------------------------- the work

def test_work_at_the_published_widths_is_the_hand_count(real):
  from benchmark.lib import peaks
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  layer_matmuls = 2 * 100 * 5120 * (5120 + 1024 + 1024 + 8 + 5120)
  assert flops['qkvgo'] == 8 * layer_matmuls == 100_728_832_000
  assert flops['ffn'] == 8 * 2 * 100 * 3 * 5120 * 17408 == 427_819_008_000
  assert flops['retention_scores'] == flops['retention_values'] == (
      8 * 2 * 100 * 100 * 40 * 128)
  assert flops['condense'] == 2 * 100 * 560 * 5120
  assert flops['head'] == 2 * 100 * 5120 * 5
  assert flops['total'] == sum(v for k, v in flops.items() if k != 'total')
  assert flops['total'] == 530_764_800_000
  layer = (5120 * 5120 * 2 + 2 * 5120 * 1024 + 5120 * 8 + 8
           + 3 * 5120 * 17408 + 2 * 5120 + 2 * 128)
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 560 * 5120
             + 5120 * 5 + 5 + 5120)
  assert family.param_count(shape) == 8 * layer + outside == 2_645_729_307
  assert family.param_count(shape) == real[0].config['param_count']
  assert layer == real[0].config['param_count_per_layer']
  moved = family.bytes_per_pack(shape, 256)
  assert moved['weights'] == 2 * 2_645_729_307
  assert moved['rows_in'] == 256 * 81 * 100
  least = family.least_seconds_per_pack(shape, 256, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'
  assert least['seconds'] == pytest.approx(0.68972, abs=1e-5)


def test_work_of_the_parts_a_pack(real):
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  retention = family.retention_work(shape, 256)
  assert retention == family.part_work(shape, 256, 'retention')
  assert retention['flops'] == 256 * (flops['retention_scores']
                                      + flops['retention_values'])
  # q and y [25600, 40, 128] bf16, k and v [25600, 8, 128] bf16, log g
  # [25600, 8] float32, for each of 8 layers.
  assert retention['bytes'] == 8 * 25600 * (2 * 10240 + 2 * 2048 + 32)
  ffn = family.ffn_work(shape, 256)
  assert ffn == family.part_work(shape, 256, 'ffn')
  assert ffn['flops'] == 256 * flops['ffn']
  assert ffn['bytes'] == 8 * 2 * (3 * 5120 * 17408 + 2 * 25600 * 5120)
  with pytest.raises(KeyError):
    family.part_work(shape, 256, 'attention')


def test_work_at_toy_widths_is_the_hand_count(toy):
  _loaded, family, shape = toy
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 20 * 170 * 64
  assert flops['qkvgo'] == 2 * 2 * 20 * 64 * (32 + 16 + 16 + 2 + 32)
  assert flops['retention_scores'] == 2 * 2 * 20 * 20 * 4 * 8
  assert flops['ffn'] == 2 * 2 * 20 * 3 * 64 * 96
  assert flops['head'] == 2 * 20 * 64 * 5
  layer = (64 * 32 * 2 + 2 * 64 * 16 + 64 * 2 + 2 + 3 * 64 * 96 + 2 * 64
           + 2 * 8)
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 170 * 64 + 64 * 5
             + 5 + 64)
  assert family.param_count(shape) == 2 * layer + outside


# ------------------------------------------------------------------- the tree

def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  """Abstractly: no array of the 5.3 GB is made."""
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, shape = real
  tree = jax.eval_shape(lambda: family.make_params(shape, 2**31 + 5))
  model = model_lib.get_model(run.program_params(loaded.config, family))
  want = jax.eval_shape(
      lambda k: model.init(k, jnp.zeros((1, 85, 100, 1))),
      jax.random.PRNGKey(0))['params']
  shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
  assert shapes(tree) == shapes(want)
  leaves = jax.tree_util.tree_leaves(tree)
  assert len(leaves) == 9 + 8 * 13
  assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
  assert sum(leaf.size for leaf in leaves) == family.param_count(shape)


def test_tree_from_the_seed(toy):
  import jax
  import jax.numpy as jnp
  _loaded, family, shape = toy
  a, b, c = (family.make_params(shape, s) for s in (7, 7, 2**31 + 7))
  flat = lambda t: [np.asarray(x, np.float32)
                    for x in jax.tree_util.tree_leaves(t)]
  assert all(np.array_equal(x, y) for x, y in zip(flat(a), flat(b)))
  assert not any(np.array_equal(x, y) for x, y in zip(flat(a), flat(c)))
  att = a['encoder']['self_attention_1']
  scale = np.asarray(att['query_norm']['scale'], np.float32)
  assert 0.5 <= scale.min() and scale.max() <= 1.5 and scale.std() > 0.1
  bias = np.asarray(att['gate']['bias'], np.float32)
  assert 1.5 <= bias.min() and bias.max() <= 2.5
  kernel = np.asarray(a['encoder']['ffn_0']['output_layer']['kernel'],
                      np.float32)
  assert kernel.std() == pytest.approx(96 ** -0.5, rel=0.05)
  assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(a))


# -------------------------------------------------------------- the reference

def test_program_agrees_with_the_familys_reference(toy, toy_windows):
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from benchmark.lib import compare
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, _shape = toy
  tree, windows, ref = toy_windows
  model = model_lib.get_model(run.program_params(loaded.config, family))
  upcast = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
  with jax.default_matmul_precision('highest'):
    got = model.apply({'params': upcast}, jnp.asarray(windows),
                      method=model.apply_with_intermediates)['logits']
  assert np.abs(np.asarray(got) - ref).max() < 1e-4
  ids, quals = compare.served_from_logits(ref)
  assert len(np.unique(quals)) > 5 and len(np.unique(ids)) == 5


@pytest.mark.parametrize('causal', [False, True],
                         ids=['two_directions', 'causal_as_published'])
@pytest.mark.parametrize('length', [12, 100])
def test_references_quadratic_form_is_its_recurrence(toy, length, causal):
  import jax.numpy as jnp
  family = toy[1]
  rng = np.random.default_rng(length)
  q = rng.normal(size=(2, length, 4, 8))
  k, v = rng.normal(size=(2, 2, length, 2, 8))
  log_g = np.log(rng.uniform(0.5, 1.0, size=(2, length, 2)))
  as32 = lambda a: jnp.asarray(a, jnp.float32)
  quadratic = family.retention_quadratic(as32(q), as32(k), as32(v),
                                         as32(log_g), causal=causal)
  recurrence = family.retention_recurrence(q, k, v, log_g, causal=causal)
  assert np.abs(np.asarray(quadratic) - recurrence).max() < 1e-5


@pytest.mark.parametrize('served', ['fp8', 'gate_ignored', 'degree_1',
                                    'causal'])
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/brumby_polish.json), by the
  rule `run_cell` judges with, on toy numbers: each of these in the
  program's place comes out not correct; the float32 reference and, by one
  of the limits at least, nothing gentler than fp8 passes."""
  from benchmark.lib import compare
  _loaded, family, shape = toy
  tree, windows, ref = toy_windows
  limits = real[0].limits
  yard = family.reference_logits(tree, windows, shape, 'bfloat16')
  kwargs = {'fp8': dict(precision='fp8'), 'gate_ignored': dict(gate=False),
            'degree_1': dict(degree=1), 'causal': dict(causal=True)}[served]
  low = family.reference_logits(tree, windows, shape, **kwargs)
  judged = compare.judge(
      compare.numbers(ref, *compare.served_from_logits(low), yard), limits)
  assert len(judged) == 2 and not all(ok for *_r, ok in judged)
  same = compare.judge(
      compare.numbers(ref, *compare.served_from_logits(ref), yard), limits)
  assert all(ok for *_r, ok in same)


# ---------------------------------------------------------- through the harness

@pytest.mark.parametrize('trace', [False, True])
def test_toy_cell_runs_through_the_harness_on_the_cpu(tmp_path, trace,
                                                      no_cache):
  from benchmark import run
  result = run.run_cell(TOY, 'toy_retention_polish', 2**31 + 28, 0.3, trace,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and result['attempted'] % 32 == 0
  assert result['compared']['id_gap_mean']['value'] <= 1e-6
  if trace:
    metrics = result['metrics']
    # The leaves stay as the family made them, bfloat16: 2 bytes each.
    family = load(TOY, 'toy_retention_polish').family
    shape = family.shape_of(load(TOY, 'toy_retention_polish').config)
    assert metrics['resident_weights_gib']['value'] == pytest.approx(
        2 * family.param_count(shape) / 2**30)
    for name in ('retention_roofline', 'ffn_roofline',
                 'retention_device_share', 'forward_mfu'):
      assert name not in metrics  # never off a chip
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


# ------------------------------------------------- the four metric files

def _reading(real, scopes, on_chip=True, spans=None):
  """A hand-made trace: one device, four operations of 2, 6, 10 and 2 ms
  in a 40 ms window, two packs."""
  from benchmark.lib import peaks, spans as spans_lib, xplane
  _loaded, family, shape = real
  ms = 1e6
  planes = xplane.Planes({
      '/device:TPU:0': {xplane.OP_LINE: [
          ('%fusion.1', 0 * ms, 2 * ms), ('%fusion.2', 5 * ms, 6 * ms),
          ('%fusion.3', 11 * ms, 10 * ms), ('%copy.4', 30 * ms, 2 * ms)]}})
  planes.scopes['/device:TPU:0'] = list(scopes)
  return types.SimpleNamespace(
      planes=planes, trace_window=(0.0, 40 * ms), xplane=xplane,
      spans_lib=spans_lib, on_chip=on_chip, chips=1, shape=shape, batch=256,
      work=family, peaks=peaks.peaks_for('TPU v5e'),
      result={'counters': {'n_packs': 2}}, span_window=(100.0, 140.0),
      spans=spans or {})


SCOPES = ('jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'self_attention_0/query/dot_general',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'self_attention_0/retention/bkglm,bmkd->blkgd/dot_general',
          'jit(forward)/M/encoder/ffn/ffn_wrapper_0/ffn_0/up_layer/'
          'dot_general',
          '')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  v5e = _reading(real, SCOPES).peaks
  reading = _reading(real, SCOPES)
  need = family.retention_work(shape, 256)
  assert need['bytes'] / v5e['hbm_bytes_per_s'] > (
      need['flops'] / v5e['bf16_flops_per_s'])  # memory-bound
  least = need['bytes'] / v5e['hbm_bytes_per_s']
  assert _read('retention_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.006)
  need = family.ffn_work(shape, 256)
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  assert _read('ffn_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.010)
  assert _read('retention_device_share', reading) == pytest.approx(
      100 * 6 / 20)


def test_scope_names_match_as_whole_steps_of_the_path(real):
  """`ffn_0`, `ffn_wrapper_0` and `self_attention_0` are module names, not
  the scopes: a program without the promised names reads nothing."""
  old = ('jit(forward)/M/encoder/attention_wrapper_0/self_attention_0/query',
         'jit(forward)/M/encoder/self_attention_0/softmax',
         'jit(forward)/M/encoder/ffn_wrapper_0/ffn_0/filter_layer/dot', '')
  reading = _reading(real, old)
  for name in ('retention_roofline', 'ffn_roofline',
               'retention_device_share'):
    assert _read(name, reading) is None, name


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'no_part_work'])
def test_roofline_metrics_return_nothing_where_there_is_nothing(real, how):
  reading = _reading(real, SCOPES, on_chip=how != 'off_chip')
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_part_work':
    reading.work = types.SimpleNamespace()
  assert _read('retention_roofline', reading) is None
  assert _read('ffn_roofline', reading) is None
  if how != 'no_part_work':
    assert _read('retention_device_share', reading) is None


def test_resident_weights_reads_the_launch_spans_inside_the_window(real):
  launch = lambda t, **args: (t, t + 0.001, args)
  spans = {'forward_launch': [
      launch(99.0, weight_bytes=7 * 2**30),  # the warm-up, before the window
      launch(101.0, weight_bytes=5 * 2**30, block_kind='x', n_positions=1),
      launch(120.0, weight_bytes=5 * 2**30)]}
  assert _read('resident_weights_gib',
               _reading(real, SCOPES, spans=spans)) == 5.0
  # A program from before the span had the argument: nothing, no error.
  old = {'forward_launch': [launch(101.0, pack=1)]}
  assert _read('resident_weights_gib',
               _reading(real, SCOPES, spans=old)) is None
  assert _read('resident_weights_gib', _reading(real, SCOPES)) is None
