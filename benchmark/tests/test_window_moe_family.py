"""Family `window_moe_encoder` and the cell `mellum_polish`: new files only.
Toy sizes on the CPU through the harness, the published sizes by shape
alone.

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
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_window_moe.json')
TOY_CELL = 'toy_window_moe_polish'
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'mellum_polish'
CONFIG = 'mellum2_12b_8of28_L100'
SOURCE = ('https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/'
          'main/config.json')
NEW_METRICS = ('moe64_roofline', 'moe64_device_share',
               'moe64_load_max_over_mean', 'gqa32_roofline',
               'rotary_device_share')
METRIC_SOURCES = {
    'moe64_roofline': 'device_trace', 'moe64_device_share': 'device_trace',
    'moe64_load_max_over_mean': 'program_counter',
    'gqa32_roofline': 'device_trace', 'rotary_device_share': 'device_trace'}
PERIOD = ['sliding_attention'] * 3 + ['full_attention']

# config.json of JetBrains/Mellum2-12B-A2.5B-Instruct as the model-configs
# catalog gives it (the keys that say something about the model's shape).
PUBLISHED = {
    'attention_bias': False, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 2304, 'intermediate_size': 7168,
    'layer_types': PERIOD * 7, 'mlp_layer_types': ['sparse'] * 28,
    'max_position_embeddings': 131072, 'max_window_layers': 0,
    'model_type': 'mellum', 'moe_intermediate_size': 896,
    'norm_topk_prob': True, 'num_attention_heads': 32, 'num_experts': 64,
    'num_experts_per_tok': 8, 'num_hidden_layers': 28,
    'num_key_value_heads': 4, 'rms_norm_eps': 1e-06,
    'rope_parameters': {
        'full_attention': {
            'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
            'original_max_position_embeddings': 8192, 'beta_fast': 32,
            'beta_slow': 1, 'attention_factor': 1.2772588722239782},
        'sliding_attention': {'rope_type': 'default', 'rope_theta': 500000}},
    'sliding_window': 1024, 'tie_word_embeddings': False,
    'vocab_size': 98304, 'use_sliding_window': True}
AS_RUN = {'num_hidden_layers': 8}
FAULTS = ('parallel', 'no_attention_factor', 'full_default_rope',
          'not_renormalised', 'no_interpolation')


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

def test_cell_configuration_traffic_and_metrics_are_entries_of_their_own(
    real):
  """The cell's entries found BY NAME, wherever they lie in
  BENCHMARK.json's lists: a later PR appends behind them."""
  loaded, family, _shape = real
  bench = loaded.bench
  assert family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'window_moe_encoder.py')
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw32'
  assert loaded.cell['config'] == CONFIG
  assert [w['name'] for w in bench['workloads']].count(CELL) == 1
  (entry,) = [c for c in bench['configs'] if c['name'] == CONFIG]
  assert entry['reduced'] == loaded.config['reduced'] == ['num_hidden_layers']
  assert entry['source'] == SOURCE
  assert loaded.config['source'].startswith(entry['source'])
  assert len(entry['why']) <= 200
  mine = [m for m in bench['per_layer'] if m.get('workloads') == [CELL]]
  assert [m['name'] for m in mine] == list(NEW_METRICS)
  for metric in mine:
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
    assert set(metric) == {'name', 'unit', 'better', 'source', 'layer',
                           'moves', 'workloads'}
    assert os.path.exists(os.path.join(ROOT, 'benchmark', 'metrics',
                                       metric['name'] + '.py'))
  assert {m['name']: m['source'] for m in mine} == METRIC_SOURCES
  # The metrics that carry no list apply to the cell as they are.
  shared = [m for m in bench['per_layer'] if 'workloads' not in m]
  assert len(loaded.per_layer) == len(shared) + len(NEW_METRICS)
  assert set(loaded.limits) == {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}


def test_the_cells_the_benchmark_had_are_as_they_were():
  with open(BENCH) as f:
    bench = json.load(f)
  assert [w['name'] for w in bench['workloads']][:6] == [
      'teacher_polish', 'student_polish', 'brumby_polish', 'qwen3next_polish',
      'kanana_polish', 'commanda_polish']
  assert bench['run_seconds'] == 30
  assert [m['name'] for m in bench['end_to_end']] == ['windows_per_s',
                                                      'setup_s']
  assert all(w['chips'] == 1 for w in bench['workloads'])
  # The traffic file of the other packs of 512 is shared, not copied.
  assert [w['name'] for w in bench['workloads']
          if w['traffic'] == 'window_stream_zmw32'] == [
              'qwen3next_polish', 'kanana_polish', CELL]


def test_configuration_file_holds_the_published_config_but_the_cut(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    if key in config['reduced']:
      assert config[key] == AS_RUN[key]
      assert config[key + '_published'] == value
    else:
      assert key in config and config[key] == value, key
  # The program runs the first stage's layers of the published lists.
  assert config['layer_types_as_run'] == PUBLISHED['layer_types'][:8]
  assert config['mlp_layer_types_as_run'] == ['sparse'] * 8
  assert config['overrides']['layer_types'] == config['layer_types_as_run']
  assert config['experts_held'] == [0, 64]
  assert config['batch_size'] == 512 and '8.48 of 15.75 GiB' in config[
      'batch_size_why']
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assert '4-stage pipeline' in config['deployment']
  assert '8, 8, 8 and 4' in config['deployment']
  assert any('MASKS NOTHING' in text for text in config['departures'])
  assert any('no MTP head' in text for text in config['departures'])
  assumed = ' '.join(config['assumed'])
  for said in ('no q/k norm', 'rotate_half', 'random from --seed',
               'balanced on 32 calibration windows', 'folds nothing'):
    assert said in assumed, said
  # No width is among the cuts.
  assert not [k for k in config['reduced']
              if k.endswith(('_dim', '_rank', '_size')) or 'head' in k]


def test_traffic_is_the_window_stream_of_thirty_two_zmws(real):
  traffic = real[0].traffic
  assert traffic['pool_windows'] == 4800 == 32 * traffic['windows_per_zmw']
  # 256 windows x 100 positions x 8 / 64: 3,200 rows an expert.
  assert traffic['compare_windows'] == 256
  assert traffic['generator_params'] == real[1].CALIBRATION_TRAFFIC


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'window_moe_encoder.py')) as f:
    text = f.read().split('"""', 2)[2]
  assert 'deepconsensus_tpu' not in text
  assert 'benchmark.reference' not in text and 'lib.weights' not in text
  assert 'families.mla_moe' not in text and 'families.gdn_moe' not in text


# ------------------------------------------------------ sizes, file and preset

def test_file_and_preset_agree_at_the_published_sizes(real):
  from benchmark import run
  loaded, family, shape = real
  params = run.program_params(loaded.config, family)
  stated = family.stated(params)
  assert {k: loaded.config[k] for k in stated} == stated
  assert (shape['hidden_size'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['rms_norm_eps'], shape['sliding_window']) == (
              2304, 32, 4, 128, 1e-6, 1024)
  assert (shape['layer_pattern'], shape['ffn_pattern']) == (
      'WWWFWWWF', 'EEEEEEEE')
  assert family.pattern_of(shape['layer_types']) == 'WWWF' * 7
  assert shape['rope_parameters'] == PUBLISHED['rope_parameters']
  assert (shape['num_experts_published'], shape['num_experts'],
          shape['num_experts_per_tok'], shape['moe_intermediate_size'],
          stated['num_shared_experts'], stated['router_scoring'],
          stated['router_selection_bias']) == (
              64, 64, 8, 896, 0, 'softmax', False)
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in loaded.config.items()
                     if k != 'rope_parameters'})


@pytest.mark.parametrize('key,value', [
    ('hidden_size', 2048), ('num_attention_heads', 16),
    ('num_key_value_heads', 8), ('head_dim', 64), ('rms_norm_eps', 1e-5),
    ('sliding_window', 4096), ('layer_pattern', 'WWWWWWWF'),
    ('layer_types_as_run', PERIOD[::-1] * 2),
    ('rope_parameters', dict(PUBLISHED['rope_parameters'], full_attention={
        'rope_type': 'default', 'rope_theta': 500000})),
    ('num_experts', 32), ('num_experts_published', 128),
    ('num_experts_per_tok', 6), ('moe_intermediate_size', 768),
    ('num_shared_experts', 1), ('router_scoring', 'sigmoid'),
    ('norm_topk_prob', False), ('experts_held', [0, 32]),
    ('block_kind', 'parallel_window_moe')])
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
  attention = 2 * 9_437_184 + 2 * 1_179_648
  assert attention == 21_233_664
  assert family.layer_counts(shape) == {
      'attention': attention, 'norms': 4608, 'router': 147_456,
      'expert': 6_193_152}
  config = real[0].config
  assert config['param_count_by_part'] == {
      'attention': attention, 'two_norms': 4608, 'router': 147_456,
      'one_routed_expert': 6_193_152}
  outside_experts = attention + 4608 + 147_456
  assert outside_experts == 21_385_728
  layer = outside_experts + 64 * 6_193_152
  assert layer == 417_747_456
  block = 8 * layer
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 560 * 2304
             + 2304 * 5 + 5 + 2304)
  assert block == 3_341_979_648 == config['param_count_block']
  assert family.param_count(shape) == block + outside == config['param_count']
  assert family.expert_layers(shape) == 8
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 100 * 560 * 2304
  assert flops['attention_projections'] == 8 * 2 * 100 * 2304 * 128 * (
      32 + 32 + 4 + 4)
  assert flops['gqa_scores'] == flops['gqa_values'] == (
      8 * 2 * 100 * 100 * 32 * 128)
  assert flops['router'] == 8 * 2 * 100 * 2304 * 64
  # Every expert held: 8 assignments a token.
  assert flops['experts'] == 8 * 2 * 100 * 8 * 3 * 2304 * 896
  assert flops['head'] == 2 * 100 * 2304 * 5
  assert flops['total'] == sum(v for k, v in flops.items() if k != 'total')
  assert flops['total'] == 115_053_209_600  # "115.05 GFLOP a window"
  # A token of a layer: 99.1 M in the experts, 42.5 M in the projections.
  assert flops['experts'] / 800 == pytest.approx(99.09e6, rel=1e-3)
  assert flops['attention_projections'] / 800 == pytest.approx(42.47e6,
                                                               rel=1e-3)
  share = lambda *names: sum(flops[n] for n in names) / flops['total']
  assert round(100 * share('experts'), 1) == 68.9
  assert round(100 * share('attention_projections'), 1) == 29.5
  assert round(100 * share('gqa_scores', 'gqa_values'), 2) == 1.14
  moved = family.bytes_per_pack(shape, 512)
  assert moved['weights'] == 2 * family.param_count(shape)
  least = family.least_seconds_per_pack(shape, 512, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'
  assert least['seconds'] == pytest.approx(0.29902, abs=1e-5)


def test_work_of_the_parts_a_pack(real):
  from benchmark.lib import peaks
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  v5e = peaks.peaks_for('TPU v5e')
  positions = 51_200
  gqa = family.part_work(shape, 512, 'gqa')
  assert gqa['flops'] == 512 * (flops['gqa_scores'] + flops['gqa_values'])
  # q and o [32 x 128], k and v [4 x 128] bfloat16 a position, 8 layers.
  assert gqa['bytes'] == 8 * positions * 2 * 128 * (32 + 32 + 4 + 4)
  assert gqa['bytes'] / v5e['hbm_bytes_per_s'] > (
      gqa['flops'] / v5e['bf16_flops_per_s'])  # memory-bound
  rotary = family.part_work(shape, 512, 'rotary')
  assert rotary == {'flops': 0,
                    'bytes': 8 * positions * 2 * 2 * 128 * (32 + 4)}
  held = 8 * positions * 8  # every assignment on a held expert
  moe = family.moe_work(shape, positions, held, 1)
  assert moe == family.part_work(shape, 512, 'moe')
  assert moe['flops'] == 512 * (flops['router'] + flops['experts'])
  # 409,600 assignments a pack of rows of 4.6 kB are two turns of 1 GiB:
  # the experts' weights twice, the router's once.
  assert family.turns_a_pack(shape, positions * 8) == 2
  assert moe['bytes'] == 2 * 8 * (
      2 * 64 * 6_193_152 + 2304 * 64 + 2 * positions * 2304)
  assert moe['flops'] / v5e['bf16_flops_per_s'] > (
      moe['bytes'] / v5e['hbm_bytes_per_s'])  # compute-bound
  fewer = family.moe_work(shape, positions, held - 1000, 1)
  assert moe['flops'] - fewer['flops'] == 1000 * 3 * 2 * 2304 * 896
  assert fewer['bytes'] == moe['bytes']
  with pytest.raises(KeyError):
    family.part_work(shape, 512, 'shared_expert')


def test_work_at_toy_widths_is_the_hand_count(toy):
  _loaded, family, shape = toy
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 20 * 170 * 64
  assert flops['attention_projections'] == 4 * 2 * 20 * 64 * 16 * (
      4 + 4 + 2 + 2)
  assert flops['gqa_scores'] == 4 * 2 * 20 * 20 * 4 * 16
  assert flops['router'] == 4 * 2 * 20 * 64 * 16
  # Half the experts held: 2 of a token's 4 assignments on average.
  assert flops['experts'] == 4 * 2 * 20 * 2 * 3 * 64 * 24
  layer = 2 * 64 + 64 * 16 * (4 + 4 + 2 + 2) + 64 * 16 + 8 * 3 * 64 * 24
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 170 * 64 + 64 * 5
             + 5 + 64)
  assert family.param_count(shape) == 4 * layer + outside


# ------------------------------------------------------------------- the tree

def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  """Abstractly: no array of the 6.68 GB is made."""
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, shape = real
  tree = jax.eval_shape(lambda: family.draw_params(shape, 2**31 + 5))
  model = model_lib.get_model(run.program_params(loaded.config, family))
  want = jax.eval_shape(
      lambda k: model.init(k, jnp.zeros((1, 85, 100, 1))),
      jax.random.PRNGKey(0))['params']
  shapes = lambda t: jax.tree_util.tree_map(lambda x: x.shape, t)
  assert shapes(tree) == shapes(want)
  leaves = jax.tree_util.tree_leaves(tree)
  assert len(leaves) == 9 + 8 * 10
  assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves)
  assert sum(leaf.size for leaf in leaves) == family.param_count(shape)


def test_tree_from_the_seed_and_its_balanced_routers(toy):
  """The same seed, the same tree; as drawn a router loads some expert
  with more of every pack than the tokens' spread explains; balanced, no
  held expert takes three times the mean, on windows the balancing never
  saw."""
  import jax
  import jax.numpy as jnp
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  seed = 2**31 + 9
  a = family.make_params(shape, seed)
  drawn, again = (family.draw_params(shape, seed) for _ in range(2))
  flat = lambda t: [np.asarray(x, np.float32)
                    for x in jax.tree_util.tree_leaves(t)]
  assert all(np.array_equal(x, y) for x, y in zip(flat(drawn), flat(again)))
  assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(a))
  scale = np.asarray(a['encoder']['ffn_wrapper_1']['rms_norm']['scale'],
                     np.float32)
  assert 0.5 <= scale.min() and scale.max() <= 1.5 and scale.std() > 0.1
  assert set(a['encoder']['moe_0']) == {
      'router', 'experts_gate', 'experts_up', 'experts_down'}
  windows = gen.make(shape, loaded.traffic, seed + 1)[:96]
  worst = {}
  for name, tree in (('drawn', drawn), ('balanced', a)):
    _logits, counts, _same = family.reference_forward(tree, windows, shape)
    assert counts.shape == (4, 8)
    # Experts 8-15 of 16 are held: about half of 4 assignments a token.
    assert 0.3 < counts.sum() / (96 * 20 * 4 * 4) < 0.7
    worst[name] = (counts.max(axis=1) / counts.mean(axis=1)).max()
  assert worst['balanced'] < 3.0
  assert worst['balanced'] <= worst['drawn']


# -------------------------------------------------------------- the reference

def test_program_agrees_with_the_familys_reference(toy, toy_windows):
  import jax
  import jax.numpy as jnp
  from benchmark import run
  from benchmark.lib import compare
  from deepconsensus_tpu.models import model as model_lib
  loaded, family, shape = toy
  tree, windows, ref, _yard = toy_windows
  model = model_lib.get_model(run.program_params(loaded.config, family))
  upcast = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))({'params': upcast}, jnp.asarray(windows))
  # The window of 8 binds at L=20, and the toy rope's YaRN ramp turns
  # within it: the program's mask and tables against the reference's.
  assert np.abs(np.asarray(got['logits']) - ref).max() < 1e-4
  counts = family.reference_forward(tree, windows, shape)[1]
  assert np.array_equal(
      np.asarray(model_lib.expert_assignments(sown['moe_counts'])), counts)
  ids, quals = compare.served_from_logits(ref)
  assert len(np.unique(quals)) > 5 and len(np.unique(ids)) == 5


def test_references_yarn_is_the_published_formula(real):
  """At the published sizes: correction range [18, 35], frequencies kept
  below it and divided by 16 above it, cos and sin at the attention
  factor; the window layers' tables the default law at magnitude 1."""
  _loaded, family, shape = real
  ropes = shape['rope_parameters']
  assert family.yarn_range(128, ropes['full_attention']) == (18, 35)
  cos, sin = family.rope_tables(ropes['full_attention'], 100, 128)
  plain_cos, plain_sin = family.rope_tables(ropes['sliding_attention'], 100,
                                            128)
  a = 1.2772588722239782
  np.testing.assert_allclose(cos[:, :19], a * plain_cos[:, :19], rtol=1e-6)
  np.testing.assert_allclose(cos[0], np.full(128, a, np.float32), rtol=1e-7)
  inv = 500000.0 ** (-2 * 40 / 128) / 16
  assert sin[99, 40] == pytest.approx(a * np.sin(99 * inv), rel=1e-5)
  assert sin[99, 40 + 64] == sin[99, 40]  # rotate-half repeats the half
  assert np.abs(plain_cos).max() <= 1.0


def test_reference_builds_the_window_mask_always(toy):
  """Window 8 at L=20 binds; a window that covers the length changes
  nothing, mask and all."""
  import jax
  import jax.numpy as jnp
  family = toy[1]
  rng = np.random.default_rng(4)
  draw = lambda *s: jnp.asarray(rng.normal(0, s[0] ** -0.5, s), jnp.float32)
  w = {'query': {'kernel': draw(16, 4, 4)}, 'key': {'kernel': draw(16, 2, 4)},
       'value': {'kernel': draw(16, 2, 4)},
       'output_transform': {'kernel': draw(4, 4, 16)}}
  u = jnp.asarray(rng.normal(size=(2, 20, 16)), jnp.float32)
  tables = family.rope_tables({'rope_type': 'default', 'rope_theta': 1e4},
                              20, 4)
  run = lambda window: np.asarray(family.grouped_attention(
      w, u, tables=tables, window=window, rd=lambda a: a))
  with jax.default_matmul_precision('highest'):
    assert np.array_equal(run(20), run(1024))
    np.testing.assert_allclose(run(None), run(1024), atol=1e-6)
    assert np.abs(run(8) - run(None)).max() > 1e-3


@pytest.mark.parametrize('served', ('fp8',) + FAULTS)
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/mellum_polish.json), by the
  rule `run_cell` judges with, on toy numbers: the fp8 control, (a) YaRN's
  attention factor left out, (b) the full layers rotated with the window
  layers' rope, (c) a parallel block in place of the sequential one, (d)
  the top-8 weights not renormalised and (e) YaRN's factor without its
  interpolation, each in the program's place, come out not correct; the
  float32 reference and the bfloat16 yardstick pass. The toy's rope turns
  its YaRN ramp within its window; at the cell's size (e) is what PERF.md
  says of it, and tests/test_window_moe_block.py holds its tables exactly."""
  from benchmark.lib import compare
  _loaded, family, shape = toy
  tree, windows, ref, yard = toy_windows
  kwargs = (dict(precision='fp8') if served == 'fp8' else {served: True})
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
  result = run.run_cell(TOY, TOY_CELL, 2**31 + 28, 0.3, trace,
                        require_chip=False, out_dir=str(tmp_path))
  assert result['correct'] is True and result['failed'] == 0
  assert result['attempted'] > 0 and result['attempted'] % 32 == 0
  assert result['compared']['id_gap_mean']['value'] <= 1e-6
  if trace:
    metrics = result['metrics']
    loaded = load(TOY, TOY_CELL)
    shape = loaded.family.shape_of(loaded.config)
    assert metrics['resident_weights_gib']['value'] == pytest.approx(
        2 * loaded.family.param_count(shape) / 2**30)
    # From the program's own counts, so it reads on the CPU too.
    assert 1.0 <= metrics['moe64_load_max_over_mean']['value'] < 3.0
    for name in ('moe64_roofline', 'moe64_device_share', 'gqa32_roofline',
                 'rotary_device_share', 'forward_mfu'):
      assert name not in metrics  # never off a chip
    from benchmark.lib import spans as spans_lib
    spans = spans_lib.read_spans(
        os.path.join(str(tmp_path), f'spans.{TOY_CELL}.jsonl'))
    args = spans['forward_launch'][0][2]
    assert args['block_form'] == 'sequential'
    assert args['layer_pattern'] == 'WWWF' and args['ffn_pattern'] == 'EEEE'
    assert args['rope'] == {'W': 'default', 'F': 'yarn×4'}
    assert args['attention_window'] == 8
    assert args['experts_held'] == [8, 16]
    assert args['router_scoring'] == 'softmax'
    assert 'shared_experts' not in args
    drains = [a for _s, _e, a in spans['finalize_drain']]
    # Four expert layers, four assignments a position, about half held.
    assert all(a['moe_assignments_total'] == 32 * 20 * 4 * 4 for a in drains)
    assert all(0 < a['moe_assignments_held'] < a['moe_assignments_total']
               for a in drains)
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


# --------------------------------------------------- the five metric files

def _reading(real, scopes, on_chip=True, spans=None):
  """A hand-made trace: one device, five operations of 2, 6, 10, 2 and 4
  ms in a 40 ms window, two packs."""
  from benchmark.lib import peaks, spans as spans_lib, xplane
  _loaded, family, shape = real
  ms = 1e6
  planes = xplane.Planes({
      '/device:TPU:0': {xplane.OP_LINE: [
          ('%fusion.1', 0 * ms, 2 * ms), ('%fusion.2', 5 * ms, 6 * ms),
          ('%custom-call.3', 11 * ms, 10 * ms), ('%copy.4', 30 * ms, 2 * ms),
          ('%fusion.5', 34 * ms, 4 * ms)]}})
  planes.scopes['/device:TPU:0'] = list(scopes)
  return types.SimpleNamespace(
      planes=planes, trace_window=(0.0, 40 * ms), xplane=xplane,
      spans_lib=spans_lib, on_chip=on_chip, chips=1, shape=shape, batch=512,
      work=family, peaks=peaks.peaks_for('TPU v5e'),
      result={'counters': {'n_packs': 2}}, span_window=(100.0, 140.0),
      spans=spans if spans is not None else {'finalize_drain': DRAINS})


drain = lambda t, **args: (t, t + 0.001, args)
POSITIONS = 51200
ALL = 8 * 8 * POSITIONS  # eight expert layers, eight a position, all held
DRAINS = [
    drain(99.0, pack=1, moe_assignments_total=ALL,  # the warm-up
          moe_assignments_held=ALL, moe_expert_load_max=99000),
    drain(101.0, pack=2, moe_assignments_total=ALL,
          moe_assignments_held=ALL, moe_expert_load_max=7000),
    drain(120.0, pack=3, moe_assignments_total=ALL,
          moe_assignments_held=ALL, moe_expert_load_max=8000)]

SCOPES = ('jit(forward)/M/encoder/ffn/ffn_wrapper_1/moe_1/moe/while/body/'
          'closed_call/combine/moe_combine',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'self_attention_0/softmax/bkglm,bmkd->blkgd/dot_general',
          'jit(forward)/M/encoder/ffn/ffn_wrapper_1/moe_1/moe/while/body/'
          'closed_call/experts/grouped_gated_up',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'self_attention_0/rotary/mul',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'self_attention_0/query/dot_general')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  reading = _reading(real, SCOPES)
  v5e = reading.peaks
  busy = 24
  need = family.part_work(shape, 512, 'gqa')
  least = need['bytes'] / v5e['hbm_bytes_per_s']  # memory-bound
  # 6 ms under `softmax`; the query's product is under `attention` alone.
  assert _read('gqa32_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.006)
  # 2 ms under `rotary`.
  assert _read('rotary_device_share', reading) == pytest.approx(
      100 * 2 / busy)
  # The experts' work is what the two packs inside the window counted.
  need = family.moe_work(shape, 2 * POSITIONS, 2 * ALL, 2)
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  # 2 ms and 10 ms under `moe`: 23 TFLOP in 12 ms is over the peak, which
  # is what the driver refuses: the readers hide nothing under a min.
  assert _read('moe64_roofline', reading) == pytest.approx(
      100 * least / 0.012)
  assert _read('moe64_roofline', reading) > 100
  assert _read('moe64_device_share', reading) == pytest.approx(
      100 * 12 / busy)
  # The fullest group of a layer over the mean group: 8000 / (ALL / 512).
  assert _read('moe64_load_max_over_mean', reading) == pytest.approx(
      8000 * 512 / ALL)


def test_scope_names_match_as_whole_steps_of_the_path(real):
  """`moe_1`, `self_attention_0` and `rotary_emb` are not the scopes: a
  program without the promised names reads nothing."""
  old = ('jit(forward)/M/encoder/self_attention_0/query',
         'jit(forward)/M/encoder/self_attention_0/softmax_of/dot_general',
         'jit(forward)/M/encoder/moe_1/experts/ragged_dot', '',
         'jit(forward)/M/encoder/self_attention_0/rotary_emb/mul')
  reading = _reading(real, old)
  for name in NEW_METRICS[:2] + NEW_METRICS[3:]:
    assert _read(name, reading) is None, name


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'no_work',
                                 'no_counts'])
def test_metrics_return_nothing_where_there_is_nothing(real, how):
  """Among them the parent commit, whose program has neither the kind, nor
  scope `rotary`, nor this family: nothing, and no error."""
  reading = _reading(real, SCOPES, on_chip=how != 'off_chip',
                     spans={'finalize_drain': [drain(101.0, pack=2, bytes=9)]}
                     if how == 'no_counts' else None)
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_work':
    reading.work = types.SimpleNamespace()
  assert _read('moe64_roofline', reading) is None
  if how != 'no_counts':
    assert _read('gqa32_roofline', reading) is None
  if how in ('off_chip', 'no_scopes'):
    assert _read('moe64_device_share', reading) is None
    assert _read('rotary_device_share', reading) is None
  if how in ('no_counts', 'no_work'):
    assert _read('moe64_load_max_over_mean', reading) is None
    reading.spans = {}
    assert _read('moe64_load_max_over_mean', reading) is None
  else:
    assert _read('moe64_load_max_over_mean', reading) == pytest.approx(
        8000 * 512 / ALL)
