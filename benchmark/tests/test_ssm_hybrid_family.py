"""Family `ssm_hybrid_encoder` and the cell `jamba_polish`: new files only.
Toy sizes on the CPU through the harness, the published sizes by shape
alone. Every entry of the cell is found by its name, never by its place in
BENCHMARK.json's lists: later PRs append behind it.

Run with: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, 'fixtures')
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_mamba_hybrid.json')
TOY_CELL = 'toy_mamba_hybrid_polish'
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'jamba_polish'
CONFIG = 'jamba2_3b_28of28_L100'
SOURCE = 'https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json'
NEW_METRICS = ('ssm_scan_roofline', 'ssm_scan_device_share',
               'mamba_device_share')

# config.json of ai21labs/AI21-Jamba2-3B as the model-configs catalog gives
# it (the keys that say something about the model's shape).
PUBLISHED = {
    'attn_layer_offset': 7, 'attn_layer_period': 14,
    'expert_layer_offset': 1, 'expert_layer_period': 2, 'hidden_act': 'silu',
    'hidden_size': 2560, 'intermediate_size': 8192, 'mamba_conv_bias': True,
    'mamba_d_conv': 4, 'mamba_d_state': 16, 'mamba_dt_rank': 160,
    'mamba_expand': 2, 'mamba_proj_bias': False,
    'max_position_embeddings': 262144, 'model_type': 'jamba',
    'num_attention_heads': 20, 'num_experts': 1, 'num_experts_per_tok': 1,
    'num_hidden_layers': 28, 'num_key_value_heads': 1,
    'num_logits_to_keep': 1, 'rms_norm_eps': 1e-06, 'sliding_window': None,
    'tie_word_embeddings': True, 'use_mamba_kernels': True,
    'vocab_size': 65536}
FAULTS = (dict(directions=1), dict(skip=False))


def load(bench, cell):
  from benchmark import run
  return run.load_cell(bench, cell)


@pytest.fixture(scope='module')
def toy(no_cache):
  loaded = load(TOY, TOY_CELL)
  return loaded, loaded.family, loaded.family.shape_of(loaded.config)


@pytest.fixture(scope='module')
def real():
  loaded = load(BENCH, CELL)
  return loaded, loaded.family, loaded.family.shape_of(loaded.config)


@pytest.fixture(scope='module')
def toy_windows(toy):
  """(tree, windows, the float32 reference, the bfloat16 yardstick)."""
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  tree = family.make_params(shape, 2**31 + 5)
  windows = gen.make(shape, loaded.traffic, 2**31 + 5)[:48]
  return (tree, windows, family.reference_logits(tree, windows, shape),
          family.reference_logits(tree, windows, shape, 'bfloat16'))


# ----------------------------------------------------- the files of the cell

def test_cell_configuration_traffic_and_metrics_are_found_by_name(real):
  loaded, family, _shape = real
  bench = loaded.bench
  assert family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'ssm_hybrid_encoder.py')
  (cell,) = [w for w in bench['workloads'] if w['name'] == CELL]
  assert cell == loaded.cell and cell['config'] == CONFIG
  assert cell['chips'] == 1 and len(cell['why']) <= 200
  assert cell['traffic'] == 'window_stream_zmw8'
  (entry,) = [c for c in bench['configs'] if c['name'] == CONFIG]
  assert entry['reduced'] == loaded.config['reduced'] == []
  assert entry['source'] == SOURCE
  assert loaded.config['source'].startswith(SOURCE)
  assert entry['file'] == f'benchmark/configs/{CONFIG}.json'
  assert len(entry['why']) <= 200
  mine = [m for m in bench['per_layer'] if m['name'] in NEW_METRICS]
  assert [m['name'] for m in mine] == list(NEW_METRICS)
  for metric in mine:
    assert metric['workloads'] == [CELL]
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
    assert metric['source'] == 'device_trace' and metric['unit'] == '%'
    assert set(metric) == {'name', 'unit', 'better', 'source', 'layer',
                           'moves', 'workloads'}
    assert os.path.exists(os.path.join(ROOT, 'benchmark', 'metrics',
                                       metric['name'] + '.py'))
  # The cell reads its own three and every metric that carries no list.
  shared = [m['name'] for m in bench['per_layer'] if 'workloads' not in m]
  assert [m['name'] for m in loaded.per_layer] == [
      m['name'] for m in bench['per_layer']
      if m['name'] in shared or m['name'] in NEW_METRICS]
  assert set(loaded.limits) == {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}


def test_configuration_file_holds_the_published_config_uncut(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    assert key in config and config[key] == value, key
  assert config['overrides'] == {} and config['reduced'] == []
  assert config['batch_size'] == 256
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assumed = ' '.join(config['assumed'])
  for said in ('n % attn_layer_period == attn_layer_offset', '7:1',
               'log(1..16)', '[0.001, 0.1]', 'random from --seed'):
    assert said in assumed, said
  departures = ' '.join(config['departures'])
  for said in ('two directions', 'its own D term', 'no causal mask',
               'vocabulary'):
    assert said in departures, said


def test_traffic_is_the_window_stream_of_eight_zmws(real):
  traffic = real[0].traffic
  assert traffic['pool_windows'] == 1200 == 8 * traffic['windows_per_zmw']
  assert traffic['compare_windows'] == 128


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'ssm_hybrid_encoder.py')) as f:
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
  assert shape['layer_pattern'] == 'MMMMMMMFMMMMMM' * 2
  assert (shape['hidden_size'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['mamba_d_state'], shape['mamba_dt_rank']) == (
              2560, 20, 1, 128, 16, 160)


@pytest.mark.parametrize('key,value', [
    ('hidden_size', 2048), ('num_attention_heads', 16),
    ('num_key_value_heads', 2), ('attn_layer_offset', 6),
    ('attn_layer_period', 8), ('layer_pattern', 'M' * 28),
    ('mamba_d_state', 32), ('mamba_dt_rank', 128), ('mamba_expand', 1),
    ('mamba_d_conv', 3), ('intermediate_size', 14336), ('num_experts', 16),
    ('block_kind', 'window_moe')])
def test_file_and_preset_disagreeing_in_a_size_exits(real, key, value):
  from benchmark import run
  loaded, family, _shape = real
  config = dict(loaded.config, **{key: value})
  with pytest.raises(SystemExit, match='configuration file and program '
                     f"disagree: .*'{key}'"):
    run.program_params(config, family)


# ------------------------------------------------------------------- the work

def test_parameters_at_the_published_widths_are_the_hand_count(real):
  _loaded, family, shape = real
  mixer = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
           + 5120 * 16 + 5120 + 5120 * 2560 + 160 + 16 + 16)
  assert mixer == 41_241_792
  swiglu = 3 * 2560 * 8192
  attention = 2 * 2560 * 2560 + 2 * 2560 * 128
  assert family.layer_counts(shape) == {
      'mamba_mixer': mixer, 'mamba_layer': mixer + swiglu + 2 * 2560,
      'attention_layer': attention + swiglu + 2 * 2560}
  assert mixer + swiglu + 2 * 2560 == 104_161_472
  assert attention + swiglu + 2 * 2560 == 76_682_240
  block = 26 * 104_161_472 + 2 * 76_682_240
  assert block == 2_861_562_752 == real[0].config['param_count_block']
  # The embedding, the condenser, the final norm and the head beside them.
  outside = (5 * 8 + 256 * 8 * 2 + 3 * 2 + 501 * 8 + 560 * 2560 + 2560 * 5
             + 5 + 2560)
  assert family.param_count(shape) == block + outside == real[0].config[
      'param_count']


def test_work_at_the_published_widths_is_the_hand_count(real):
  from benchmark.lib import peaks
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  per_position = lambda part: flops[part] / 100
  # A Mamba layer 211.7 MFLOP a position beside its SwiGLU of 125.8, an
  # attention layer 154.4: 5.81 GFLOP a position in all.
  mamba = (per_position('mamba_in') + per_position('mamba_x_dt')
           + per_position('mamba_out')) / 26
  assert mamba == 2 * 2560 * 10240 + 2 * 2 * 5120 * (160 + 32 + 160) + (
      2 * 5120 * 2560) == 85_852_160
  ffn = per_position('ffn') / 28
  assert ffn == 2 * 3 * 2560 * 8192 == 125_829_120
  assert (mamba + ffn) == pytest.approx(211.7e6, rel=1e-3)
  attention = (per_position('attention_projections')
               + per_position('gqa_scores') + per_position('gqa_values')) / 2
  assert attention + ffn == pytest.approx(154.4e6, rel=1e-3)
  assert flops['total'] / 100 == pytest.approx(5.815e9, rel=1e-3)
  least = family.least_seconds_per_pack(shape, 256,
                                        peaks.peaks_for('TPU v5e'))
  # 148.9 TFLOP a pack: 0.756 s at the bf16 peak, against 7 ms for the
  # weights once.
  assert least['seconds'] == pytest.approx(0.756, rel=0.01)
  assert least['bound'] == 'compute'


def test_work_of_the_parts_a_pack(real):
  _loaded, family, shape = real
  scan = family.part_work(shape, 256, 'ssm_scan')
  steps = 256 * 100 * 26 * 2 * 5120 * 16
  assert steps == 1.0905190400e11
  assert scan['flops'] == 6 * steps
  # x and dt of both directions, z and y: six [25600, 5120] bfloat16; B
  # and C of both directions [25600, 64] float32; 26 layers: 41 GB.
  assert scan['bytes'] == 26 * (6 * 25600 * 5120 * 2 + 25600 * 64 * 4)
  assert scan['bytes'] == pytest.approx(41.06e9, rel=1e-3)
  mamba = family.part_work(shape, 256, 'mamba')
  assert mamba['flops'] == 256 * 100 * 26 * 85_852_160 + 6 * steps
  assert mamba['bytes'] == 26 * 2 * (41_241_792 + 2 * 25600 * 2560)
  with pytest.raises(KeyError):
    family.part_work(shape, 256, 'gqa')


def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  import jax
  import jax.numpy as jnp

  from benchmark import run
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, shape = real
  params = run.program_params(loaded.config, family)
  model = model_lib.get_model(params)
  tree = jax.eval_shape(lambda k: model.init(k, jnp.zeros(
      (1, params.total_rows, params.max_length, 1))), jax.random.PRNGKey(0))
  want = {'/'.join(path): shp for path, shp, _k, _f in family.leaf_specs(
      shape)}
  got = {'/'.join(str(getattr(k, 'key', k)) for k in path): leaf.shape
         for path, leaf in jax.tree_util.tree_flatten_with_path(
             tree['params'])[0]}
  assert got == want


# -------------------------------------------------------------- the reference

def test_tree_from_the_seed(toy, toy_windows):
  _loaded, family, shape = toy
  tree = toy_windows[0]
  mixer = tree['encoder']['mamba_0']
  a_log = np.asarray(mixer['A_log'], np.float32)
  assert np.allclose(np.exp(a_log), np.arange(1, 17)[None, :], rtol=1e-2)
  dt = np.log1p(np.exp(np.asarray(mixer['dt_proj']['bias'], np.float32)))
  assert 1e-3 * 0.99 < dt.min() and dt.max() < 0.1 * 1.01
  assert str(mixer['in_proj']['kernel'].dtype) == 'bfloat16'
  again = family.make_params(shape, 2**31 + 5)
  assert np.array_equal(np.asarray(again['encoder']['ffn_2']['up_layer'][
      'kernel'], np.float32), np.asarray(tree['encoder']['ffn_2'][
          'up_layer']['kernel'], np.float32))


def test_program_agrees_with_the_familys_reference(toy, toy_windows):
  import jax
  import jax.numpy as jnp

  from benchmark import run
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, _shape = toy
  tree, windows, ref, _yard = toy_windows
  params = run.program_params(loaded.config, family)
  model = model_lib.get_model(params)
  f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)
  with jax.default_matmul_precision('highest'):
    got = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates)['logits'])(
            {'params': f32}, jnp.asarray(windows))
  # float32 on both sides: the sums' order alone.
  np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4)


@pytest.mark.parametrize('served', ['fp8', 'one_direction', 'no_skip'])
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/jamba_polish.json), by the rule
  `run_cell` judges with, on toy numbers: the fp8 control, the published
  causal mixer alone and the D term left out, each in the program's place,
  come out not correct; the float32 reference and the bfloat16 yardstick
  pass."""
  from benchmark.lib import compare
  _loaded, family, shape = toy
  tree, windows, ref, yard = toy_windows
  kwargs = {'fp8': dict(precision='fp8'), 'one_direction': FAULTS[0],
            'no_skip': FAULTS[1]}[served]
  verdicts = lambda logits: compare.judge(
      compare.numbers(ref, *compare.served_from_logits(logits), yard),
      real[0].limits)
  low = verdicts(family.reference_logits(tree, windows, shape, **kwargs))
  assert low and not all(ok for *_r, ok in low)
  assert all(ok for *_r, ok in verdicts(ref))
  assert all(ok for *_r, ok in verdicts(yard))


# ---------------------------------------------------------- through the harness

@pytest.mark.parametrize('trace', [False, True])
def test_toy_cell_runs_through_the_harness_on_the_cpu(tmp_path, trace,
                                                      no_cache):
  from benchmark import run
  result = run.run_cell(TOY, TOY_CELL, 2**31 + 44, 0.3, trace,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and result['attempted'] % 32 == 0
  assert result['compared']['id_gap_mean']['value'] <= 1e-6
  if trace:
    metrics = result['metrics']
    for name in NEW_METRICS + ('forward_mfu',):
      assert name not in metrics  # never off a chip
    from benchmark.lib import spans as spans_lib
    spans = spans_lib.read_spans(
        os.path.join(str(tmp_path), f'spans.{TOY_CELL}.jsonl'))
    args = spans['forward_launch'][0][2]
    assert args['block_kind'] == 'mamba_hybrid'
    assert args['layer_pattern'] == 'MMFM' and args['ffn_pattern'] == 'DDDD'
    assert args['ssm_scan_path'] == 'plain'  # the CPU
    assert args['ssm_state'] == [128, 16]
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


# --------------------------------------------------- the three metric files

def _reading(real, scopes, on_chip=True):
  """A hand-made trace: one device, five operations of 2, 6, 10, 2 and 4
  ms in a 40 ms window, two packs."""
  from benchmark.lib import peaks, spans as spans_lib, xplane
  _loaded, family, shape = real
  ms = 1e6
  planes = xplane.Planes({
      '/device:TPU:0': {xplane.OP_LINE: [
          ('%fusion.1', 0 * ms, 2 * ms), ('%fusion.2', 5 * ms, 6 * ms),
          ('%ssm_scan_window.3', 11 * ms, 10 * ms),
          ('%copy.4', 30 * ms, 2 * ms), ('%fusion.5', 34 * ms, 4 * ms)]}})
  planes.scopes['/device:TPU:0'] = list(scopes)
  return types.SimpleNamespace(
      planes=planes, trace_window=(0.0, 40 * ms), xplane=xplane,
      spans_lib=spans_lib, on_chip=on_chip, chips=1, shape=shape, batch=256,
      work=family, peaks=peaks.peaks_for('TPU v5e'),
      result={'counters': {'n_packs': 2}}, span_window=(100.0, 140.0),
      spans={})


MIXER = ('jit(forward)/M/encoder/attention/attention_wrapper_0/mamba_0/'
         'mamba/')
SCOPES = (MIXER + 'in_proj/dot_general', MIXER + 'conv/add',
          MIXER + 'ssm_scan/ssm_scan_window',
          'jit(forward)/M/encoder/attention/attention_wrapper_7/'
          'self_attention_7/softmax/grouped_window_tile',
          'jit(forward)/M/encoder/ffn/ffn_wrapper_0/ffn_0/up_layer/dot')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  reading = _reading(real, SCOPES)
  v5e = reading.peaks
  busy = 24
  need = family.part_work(shape, 256, 'ssm_scan')
  least = need['bytes'] / v5e['hbm_bytes_per_s']  # the bytes bound it
  assert least > need['flops'] / v5e['bf16_flops_per_s']
  # 10 ms under `ssm_scan`, two packs.
  assert _read('ssm_scan_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.010)
  assert _read('ssm_scan_device_share', reading) == pytest.approx(
      100 * 10 / busy)
  # 2, 6 and 10 ms under `mamba`.
  assert _read('mamba_device_share', reading) == pytest.approx(
      100 * 18 / busy)


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'old_names',
                                 'no_work'])
def test_metrics_return_nothing_where_there_is_nothing(real, how):
  """Among them the parent commit, whose program has neither the kind nor
  these scopes: nothing, and no error."""
  scopes = SCOPES
  if how == 'old_names':  # the module's name is not the scope
    scopes = tuple(s.replace('/mamba/', '/').replace('ssm_scan/', '')
                   for s in SCOPES)
  reading = _reading(real, scopes, on_chip=how != 'off_chip')
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_work':
    reading.work = types.SimpleNamespace()
    assert _read('ssm_scan_roofline', reading) is None
    return
  for name in NEW_METRICS:
    assert _read(name, reading) is None, name
