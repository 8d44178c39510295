"""Family `parallel_moe_encoder` and the cell `commanda_polish`: new files
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
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_parallel_moe.json')
TOY_CELL = 'toy_parallel_moe_polish'
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'commanda_polish'
CONFIG = 'commandaplus_4of32_e16_L100'
SOURCE = ('https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/'
          'main/config.json')
NEW_METRICS = ('gqa_roofline', 'gqa_device_share', 'moe16_roofline',
               'moe16_device_share', 'moe16_load_max_over_mean',
               'shared_experts_roofline')
METRIC_SOURCES = {
    'gqa_roofline': 'device_trace', 'gqa_device_share': 'device_trace',
    'moe16_roofline': 'device_trace', 'moe16_device_share': 'device_trace',
    'moe16_load_max_over_mean': 'program_counter',
    'shared_experts_roofline': 'device_trace'}

# config.json of CohereLabs/command-a-plus-05-2026 as the model-configs
# catalog gives it (the keys that say something about the model's shape).
PUBLISHED = {
    'attention_bias': False, 'expert_selection_fn': 'sigmoid',
    'first_k_dense_replace': 0, 'head_dim': 128, 'hidden_act': 'silu',
    'hidden_size': 4096, 'intermediate_size': 4096, 'layer_norm_eps': 1e-05,
    'layer_switch': 4,
    'layer_types': ['sliding_attention', 'sliding_attention',
                    'sliding_attention', 'full_attention'] * 8,
    'logit_scale': 1, 'max_position_embeddings': 200000,
    'model_type': 'cohere2_moe', 'norm_topk_prob': True,
    'num_attention_heads': 128, 'num_experts': 128, 'num_experts_per_tok': 8,
    'num_hidden_layers': 32, 'num_key_value_heads': 8,
    'num_shared_experts': 4,
    'order_of_interleaved_layers': 'local_attn_first',
    'position_embedding_type': 'rope_gptj',
    'prefix_dense_intermediate_size': 16384,
    'prefix_dense_sliding_window_pattern': 1, 'rms_norm_eps': None,
    'rope_parameters': {'rope_theta': 50000, 'rope_type': 'default'},
    'rope_theta': 50000, 'rotary_pct': 1,
    'shared_expert_combination_strategy': 'average', 'sliding_window': 4096,
    'tf_legacy_loss': False, 'tie_word_embeddings': True,
    'use_embedding_sharing': True, 'use_gated_activation': True,
    'use_parallel_block': True, 'use_parallel_embedding': False,
    'use_qk_norm': False, 'vocab_size': 262144}
AS_RUN = {'num_hidden_layers': 4, 'num_experts': 16}


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
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  tree = family.make_params(shape, 2**31 + 5)
  windows = gen.make(shape, loaded.traffic, 2**31 + 5)[:48]
  return tree, windows, family.reference_logits(tree, windows, shape)


# ----------------------------------------------------- the files of the cell

def test_cell_configuration_traffic_and_metrics_are_entries_of_their_own(
    real):
  """The cell's entries found BY NAME, wherever they lie in
  BENCHMARK.json's lists: a later PR appends behind them."""
  loaded, family, _shape = real
  bench = loaded.bench
  assert family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'parallel_moe_encoder.py')
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw8'
  assert loaded.cell['config'] == CONFIG
  assert [w['name'] for w in bench['workloads']].count(CELL) == 1
  (entry,) = [c for c in bench['configs'] if c['name'] == CONFIG]
  assert entry['reduced'] == loaded.config['reduced'] == [
      'num_hidden_layers', 'num_experts']
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
  assert [m['name'] for m in loaded.per_layer if 'workloads' in m] == list(
      NEW_METRICS)
  assert set(loaded.limits) <= {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}
  assert loaded.limits


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
  # not copied.
  assert all(w['chips'] == 1 for w in bench['workloads'])
  assert [w['name'] for w in bench['workloads']
          if w['traffic'] == 'window_stream_zmw8'] == ['brumby_polish', CELL]


def test_configuration_file_holds_the_published_config_but_the_cut(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    if key in config['reduced']:
      assert config[key] == AS_RUN[key]
      assert config[key + '_published'] == value
    else:
      assert key in config and config[key] == value, key
  assert config['experts_held'] == [0, 16]
  assert config['batch_size'] == 256 and config['batch_size_why']
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assert '8-stage pipeline' in config['deployment']
  assert 'divided over 8 v5e chips' in config['deployment']
  assert len(config['departures']) == 4
  assert any('MASKS NOTHING' in text for text in config['departures'])
  assert any('no vision tower' in text for text in config['departures'])
  assumed = ' '.join(config['assumed'])
  for said in ('intermediate_size 4096', 'MEAN of the four',
               'halves_from_pairs', 'random from --seed'):
    assert said in assumed, said
  # No width is among the cuts.
  assert not [k for k in config['reduced']
              if k.endswith(('_dim', '_rank', '_size')) or 'head' in k]


def test_traffic_is_the_window_stream_of_eight_zmws(real):
  traffic = real[0].traffic
  assert traffic['pool_windows'] == 1200 == 8 * traffic['windows_per_zmw']
  # 128 windows x 100 positions x 8 / 128: 800 rows a held expert.
  assert traffic['compare_windows'] == 128
  assert traffic['generator_params'] == real[1].CALIBRATION_TRAFFIC


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'parallel_moe_encoder.py')) as f:
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
          shape['rope_theta'], shape['layer_norm_eps'],
          shape['sliding_window'], shape['layer_switch']) == (
              4096, 128, 8, 128, 50000, 1e-5, 4096, 4)
  assert (shape['layer_pattern'], shape['ffn_pattern']) == ('WWWF', 'EEEE')
  # The published list against the derived rule, at the published depth.
  assert stated['layer_types'] == PUBLISHED['layer_types']
  assert family.pattern_of(32, 4) == 'WWWF' * 8
  assert (shape['num_experts_published'], shape['num_experts'],
          shape['num_experts_per_tok'], shape['intermediate_size'],
          shape['num_shared_experts'],
          stated['shared_expert_intermediate_size'],
          shape['expert_selection_fn'], stated['router_selection_bias'],
          stated['routed_scaling_factor']) == (
              128, 16, 8, 4096, 4, 16384, 'sigmoid', False, 1.0)
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in loaded.config.items()
                     if k != 'sliding_window'})


@pytest.mark.parametrize('key,value', [
    ('hidden_size', 2048), ('num_attention_heads', 64),
    ('num_key_value_heads', 16), ('head_dim', 64), ('rope_theta', 10000),
    ('layer_norm_eps', 1e-6), ('sliding_window', 1024), ('layer_switch', 2),
    ('layer_pattern', 'WFWF'), ('ffn_pattern', 'DEEE'),
    ('layer_types', ['full_attention'] * 32), ('first_k_dense_replace', 1),
    ('num_experts', 128), ('num_experts_published', 64),
    ('num_experts_per_tok', 6), ('intermediate_size', 2048),
    ('num_shared_experts', 2),
    ('shared_expert_combination_strategy', 'sum'),
    ('shared_expert_intermediate_size', 4096), ('shared_expert_gated', True),
    ('expert_selection_fn', 'softmax'), ('router_selection_bias', True),
    ('routed_scaling_factor', 2.5), ('norm_topk_prob', False),
    ('experts_held', [16, 32]), ('use_parallel_block', False),
    ('block_kind', 'latent_attention_moe')])
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
  attention = 2 * 67_108_864 + 2 * 4_194_304
  assert attention == 142_606_336
  assert family.layer_counts(shape) == {
      'attention': attention, 'norm': 4096, 'router': 524_288,
      'shared_experts': 201_326_592, 'expert': 50_331_648}
  config = real[0].config
  assert config['param_count_by_part'] == {
      'attention': attention, 'the_one_norm': 4096, 'router': 524_288,
      'four_shared_experts': 201_326_592, 'one_routed_expert': 50_331_648}
  outside_experts = attention + 4096 + 524_288 + 201_326_592
  assert outside_experts == 344_461_312
  layer = outside_experts + 16 * 50_331_648
  assert layer == 1_149_767_680
  block = 4 * layer
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 560 * 4096
             + 4096 * 5 + 5 + 4096)
  assert block == 4_599_070_720 == config['param_count_block']
  assert family.param_count(shape) == block + outside == config['param_count']
  assert family.expert_layers(shape) == 4
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 100 * 560 * 4096
  # W_q and W_o 13.42 GFLOP each, W_k and W_v 1.68 together, a layer.
  assert flops['attention_projections'] == 4 * 2 * 100 * 4096 * 128 * (
      128 + 128 + 8 + 8)
  assert 2 * 100 * 4096 * 16384 == 13_421_772_800
  assert flops['gqa_scores'] == flops['gqa_values'] == (
      4 * 2 * 100 * 100 * 128 * 128)
  assert flops['gqa_scores'] // 4 == 327_680_000
  assert flops['router'] == 4 * 2 * 100 * 4096 * 128
  assert flops['shared_experts'] == 4 * 2 * 100 * 3 * 4096 * 16384
  # The mean of 1.0 held assignment a token: 8 x 16 / 128.
  assert flops['experts'] == 4 * 2 * 100 * 1 * 3 * 4096 * 4096
  assert flops['head'] == 2 * 100 * 4096 * 5
  assert flops['total'] == sum(v for k, v in flops.items() if k != 'total')
  assert flops['total'] == 318_915_379_200  # "318.9 GFLOP a window"
  a_layer = (flops['total'] - flops['condense'] - flops['head']) / 4
  assert round(a_layer / 1e9, 2) == 79.61
  share = lambda *names: sum(flops[n] for n in names) / flops['total']
  assert round(100 * share('shared_experts'), 1) == 50.5
  assert round(100 * share('attention_projections'), 1) == 35.8
  assert round(100 * share('experts'), 1) == 12.6
  assert round(100 * share('gqa_scores', 'gqa_values'), 1) == 0.8
  moved = family.bytes_per_pack(shape, 256)
  assert moved['weights'] == 2 * family.param_count(shape)
  assert moved['rows_in'] == 256 * 81 * 100
  least = family.least_seconds_per_pack(shape, 256, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'
  assert least['seconds'] == pytest.approx(0.41443, abs=1e-5)
  assert least['bytes_seconds'] == pytest.approx(0.01124, abs=1e-5)


def test_work_of_the_parts_a_pack(real):
  from benchmark.lib import peaks
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  v5e = peaks.peaks_for('TPU v5e')
  positions = 25_600
  gqa = family.part_work(shape, 256, 'gqa')
  assert gqa['flops'] == 256 * (flops['gqa_scores'] + flops['gqa_values'])
  assert gqa['flops'] // (256 * 4) == 655_360_000  # 0.655 GFLOP a layer
  # q and o [128 x 128], k and v [8 x 128] bfloat16 a position, four layers.
  assert gqa['bytes'] == 4 * positions * 2 * (16384 + 1024 + 1024 + 16384)
  assert gqa['bytes'] / v5e['hbm_bytes_per_s'] > (
      gqa['flops'] / v5e['bf16_flops_per_s'])  # memory-bound
  shared = family.part_work(shape, 256, 'shared_expert')
  assert shared['flops'] == 256 * flops['shared_experts']
  assert shared['bytes'] == 2 * 4 * (201_326_592 + 2 * positions * 4096)
  assert shared['flops'] / v5e['bf16_flops_per_s'] > (
      shared['bytes'] / v5e['hbm_bytes_per_s'])  # compute-bound
  held = 4 * positions  # 1.0 held assignment a position a layer
  moe = family.moe_work(shape, positions, held, 1)
  assert moe == family.part_work(shape, 256, 'moe')
  assert moe['flops'] == 256 * (flops['router'] + flops['experts'])
  # 204,800 assignments a pack of rows of 8 kB are two turns of 1 GiB: the
  # held experts' weights twice, the router's once.
  assert family.turns_a_pack(shape, positions * 8) == 2
  assert moe['bytes'] == 2 * 4 * (
      2 * 16 * 50_331_648 + 4096 * 128 + 2 * positions * 4096)
  assert moe['flops'] / v5e['bf16_flops_per_s'] > (
      moe['bytes'] / v5e['hbm_bytes_per_s'])  # compute-bound
  # An uneven window: fewer assignments are less work, the same bytes; two
  # packs read the weights twice as often.
  fewer = family.moe_work(shape, positions, held - 1000, 1)
  assert moe['flops'] - fewer['flops'] == 1000 * 3 * 2 * 4096 * 4096
  assert fewer['bytes'] == moe['bytes']
  two = family.moe_work(shape, 2 * positions, 2 * held, 2)
  assert two == {'flops': 2 * moe['flops'], 'bytes': 2 * moe['bytes']}
  # At hidden 2048 a turn would hold twice the rows.
  assert family.turns_a_pack(dict(shape, hidden_size=2048),
                             positions * 8) == 1
  with pytest.raises(KeyError):
    family.part_work(shape, 256, 'attention')


def test_work_at_toy_widths_is_the_hand_count(toy):
  _loaded, family, shape = toy
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 20 * 170 * 64
  assert flops['attention_projections'] == 4 * 2 * 20 * 64 * 8 * (
      32 + 32 + 2 + 2)
  assert flops['gqa_scores'] == 4 * 2 * 20 * 20 * 32 * 8
  assert flops['router'] == 4 * 2 * 20 * 64 * 16
  assert flops['shared_experts'] == 4 * 2 * 20 * 3 * 64 * 96
  # Half the experts held: 2 of a token's 4 assignments on average.
  assert flops['experts'] == 4 * 2 * 20 * 2 * 3 * 64 * 24
  layer = (64 * 8 * (32 + 32 + 2 + 2) + 64 + 64 * 16 + 8 * 3 * 64 * 24
           + 3 * 64 * 96)
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 170 * 64 + 64 * 5
             + 5 + 64)
  assert family.param_count(shape) == 4 * layer + outside


# ------------------------------------------------------------------- the tree

def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  """Abstractly: no array of the 9.20 GB is made."""
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
  assert len(leaves) == 9 + 4 * (1 + 4 + 4 + 3)
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
  assert all(x.dtype == jnp.bfloat16 for x in jax.tree_util.tree_leaves(a))
  scale = np.asarray(a['encoder']['block_norm_1']['scale'], np.float32)
  assert 0.5 <= scale.min() and scale.max() <= 1.5 and scale.std() > 0.1
  kernel = np.asarray(a['encoder']['moe_1']['experts_down'], np.float32)
  assert kernel.shape == (8, 24, 64)
  assert kernel.std() == pytest.approx(24 ** -0.5, rel=0.05)
  # A shared expert's down kernel is drawn at its own width's fan-in, not
  # the stack's: the mean of the four has a branch's usual size.
  down = np.asarray(
      a['encoder']['moe_1']['shared_expert']['output_layer']['kernel'],
      np.float32)
  assert down.shape == (96, 64)
  assert down.std() == pytest.approx(24 ** -0.5, rel=0.05)
  assert set(a['encoder']['moe_0']) == {
      'router', 'experts_gate', 'experts_up', 'experts_down', 'shared_expert'}
  assert 'attention_wrapper_0' not in a['encoder']


def test_routers_are_balanced_on_windows_from_the_seed(toy):
  """As drawn a router loads some expert with more of every pack than the
  tokens' spread explains; balanced (every column orthogonal to the mean
  token, no bias: the model has none), no held expert takes three times
  the mean, on windows the balancing never saw."""
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  seed = 2**31 + 9
  windows = gen.make(shape, loaded.traffic, seed + 1)[:96]
  worst = {}
  for name, make in (('drawn', family.draw_params),
                     ('balanced', family.make_params)):
    tree = make(shape, seed)
    _logits, counts, same = family.reference_forward(tree, windows, shape)
    assert same['encoder']['moe_1']['router'] is (
        tree['encoder']['moe_1']['router'])
    assert counts.shape == (4, 8)
    # Experts 8-15 of 16 are held: about half of 4 assignments a token.
    assert 0.3 < counts.sum() / (96 * 20 * 4 * 4) < 0.7
    worst[name] = (counts.max(axis=1) / counts.mean(axis=1)).max()
    assert 'router_selection_bias' not in tree['encoder']['moe_1']
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
  tree, windows, ref = toy_windows
  model = model_lib.get_model(run.program_params(loaded.config, family))
  upcast = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))({'params': upcast}, jnp.asarray(windows))
  # The window of 8 binds at L=20: the program's mask against the
  # reference's, which is built always.
  assert np.abs(np.asarray(got['logits']) - ref).max() < 1e-4
  counts = family.reference_forward(tree, windows, shape)[1]
  assert np.array_equal(
      np.asarray(model_lib.expert_assignments(sown['moe_counts'])), counts)
  ids, quals = compare.served_from_logits(ref)
  assert len(np.unique(quals)) > 5 and len(np.unique(ids)) == 5


def test_references_rotation_is_the_published_one_on_published_columns(toy):
  """The family's reference un-permutes the columns of a head and rotates
  interleaved pairs: on a kernel whose columns are in the published order
  that is the published q . k, which the halves rotation gives on the
  permuted columns."""
  import jax.numpy as jnp
  family = toy[1]
  rng = np.random.default_rng(3)
  d = 8
  columns = jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
  perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
  np.testing.assert_array_equal(
      np.asarray(family.published_order(columns[:, perm])),
      np.asarray(columns))
  x = jnp.asarray(rng.normal(size=(1, 6, 2, d)), jnp.float32)
  got = np.asarray(family.rotary_pairs(x, 5.0e4))
  angle = 3 * 5.0e4 ** (-2 / d)  # position 3, pair (2, 3)
  want = (np.asarray(x)[0, 3, 1, 2] * np.cos(angle)
          - np.asarray(x)[0, 3, 1, 3] * np.sin(angle))
  assert got[0, 3, 1, 2] == pytest.approx(want, abs=1e-5)


def test_reference_builds_the_window_mask_always(toy):
  """Window 8 at L=20 binds; a window that covers the length changes
  nothing, mask and all."""
  import jax
  import jax.numpy as jnp
  _loaded, family, shape = toy
  rng = np.random.default_rng(4)
  draw = lambda *s: jnp.asarray(rng.normal(0, s[0] ** -0.5, s), jnp.float32)
  w = {'query': {'kernel': draw(16, 4, 4)}, 'key': {'kernel': draw(16, 2, 4)},
       'value': {'kernel': draw(16, 2, 4)},
       'output_transform': {'kernel': draw(4, 4, 16)}}
  u = jnp.asarray(rng.normal(size=(2, 20, 16)), jnp.float32)
  run = lambda window: np.asarray(family.grouped_attention(
      w, u, rotated=True, window=window, theta=5e4, rd=lambda a: a))
  with jax.default_matmul_precision('highest'):
    assert np.array_equal(run(20), run(4096))
    np.testing.assert_allclose(run(None), run(4096), atol=1e-6)
    assert np.abs(run(8) - run(None)).max() > 1e-3


def _judged(family, shape, tree, windows, limits, **kwargs):
  """The limits' verdicts on the reference with `kwargs` in the program's
  place, and on the float32 reference and the bfloat16 yardstick
  themselves."""
  from benchmark.lib import compare
  ref = family.reference_logits(tree, windows, shape)
  yard = family.reference_logits(tree, windows, shape, 'bfloat16')
  verdicts = lambda logits: compare.judge(
      compare.numbers(ref, *compare.served_from_logits(logits), yard), limits)
  return (verdicts(family.reference_logits(tree, windows, shape, **kwargs)),
          verdicts(ref), verdicts(yard))


@pytest.mark.parametrize('served', ['fp8', 'sequential', 'rotate_full',
                                    'shared_summed'])
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/commanda_polish.json), by the
  rule `run_cell` judges with, on toy numbers: the fp8 control, a
  sequential block in place of the parallel one, the full layer rotated and
  the shared experts summed and not averaged, each in the program's place,
  come out not correct; the float32 reference and the bfloat16 yardstick
  pass."""
  _loaded, family, shape = toy
  tree, windows, _ref = toy_windows
  kwargs = (dict(precision='fp8') if served == 'fp8' else {served: True})
  low, same, yard = _judged(family, shape, tree, windows, real[0].limits,
                            **kwargs)
  assert low and not all(ok for *_r, ok in low)
  assert all(ok for *_r, ok in same) and all(ok for *_r, ok in yard)


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
    assert 1.0 <= metrics['moe16_load_max_over_mean']['value'] < 3.0
    for name in ('gqa_roofline', 'gqa_device_share', 'moe16_roofline',
                 'moe16_device_share', 'shared_experts_roofline',
                 'forward_mfu'):
      assert name not in metrics  # never off a chip
    from benchmark.lib import spans as spans_lib
    spans = spans_lib.read_spans(
        os.path.join(str(tmp_path), f'spans.{TOY_CELL}.jsonl'))
    args = spans['forward_launch'][0][2]
    assert args['block_form'] == 'parallel'
    assert args['layer_pattern'] == 'WWWF' and args['ffn_pattern'] == 'EEEE'
    assert args['attention_window'] == 8
    assert args['experts_held'] == [8, 16]
    assert args['experts_published'] == 16
    assert args['router_scoring'] == 'sigmoid'
    assert args['shared_experts'] == 4
    drains = [a for _s, _e, a in spans['finalize_drain']]
    # Four expert layers, four assignments a position, about half held.
    assert all(a['moe_assignments_total'] == 32 * 20 * 4 * 4 for a in drains)
    assert all(0 < a['moe_assignments_held'] < a['moe_assignments_total']
               for a in drains)
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


# --------------------------------------------------- the six metric files

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
      spans_lib=spans_lib, on_chip=on_chip, chips=1, shape=shape, batch=256,
      work=family, peaks=peaks.peaks_for('TPU v5e'),
      result={'counters': {'n_packs': 2}}, span_window=(100.0, 140.0),
      spans=spans if spans is not None else {'finalize_drain': DRAINS})


drain = lambda t, **args: (t, t + 0.001, args)
POSITIONS = 25600
ALL = 4 * 8 * POSITIONS  # four expert layers, eight a position
HELD = 4 * POSITIONS  # an eighth of them on the 16 held experts
DRAINS = [
    drain(99.0, pack=1, moe_assignments_total=ALL,  # the warm-up
          moe_assignments_held=HELD, moe_expert_load_max=9000),
    drain(101.0, pack=2, moe_assignments_total=ALL,
          moe_assignments_held=HELD, moe_expert_load_max=2000),
    drain(120.0, pack=3, moe_assignments_total=ALL,
          moe_assignments_held=HELD + 6400, moe_expert_load_max=2460)]

SCOPES = ('jit(forward)/M/encoder/ffn/moe_1/moe/while/body/closed_call/'
          'combine/moe_combine',
          'jit(forward)/M/encoder/attention/self_attention_0/softmax/'
          'bkglm,bmkd->blkgd/dot_general',
          'jit(forward)/M/encoder/ffn/moe_1/moe/while/body/closed_call/'
          'experts/grouped_gated_up',
          'jit(forward)/M/encoder/attention/self_attention_0/query/'
          'dot_general',
          'jit(forward)/M/encoder/ffn/moe_1/shared_expert/shared_expert/'
          'up_layer/dot_general')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  reading = _reading(real, SCOPES)
  v5e = reading.peaks
  busy = 24
  need = family.part_work(shape, 256, 'gqa')
  least = need['bytes'] / v5e['hbm_bytes_per_s']  # memory-bound
  # 6 ms under `softmax`; the query's product is under `attention` alone.
  assert _read('gqa_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.006)
  assert _read('gqa_device_share', reading) == pytest.approx(100 * 6 / busy)
  # The experts' work is what the two packs inside the window counted.
  need = family.moe_work(shape, 2 * POSITIONS, 2 * HELD + 6400, 2)
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  # 2 ms and 10 ms under `moe`.
  assert _read('moe16_roofline', reading) == pytest.approx(
      100 * least / 0.012)
  assert _read('moe16_device_share', reading) == pytest.approx(
      100 * 12 / busy)
  # The fullest group of a layer over the mean group: 2460 / (mean of
  # (HELD + 6400) / 64 = 1700).
  assert _read('moe16_load_max_over_mean', reading) == pytest.approx(
      2460 * 64 / (HELD + 6400))
  need = family.part_work(shape, 256, 'shared_expert')
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  assert _read('shared_experts_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.004)
  # A pack at the chip's peak in 4 ms would read over 100%; these 2 x
  # 0.204 s of work in 4 ms do, which is what the driver refuses: the
  # readers hide nothing under a min(..., 100).
  assert _read('shared_experts_roofline', reading) > 100


def test_scope_names_match_as_whole_steps_of_the_path(real):
  """`moe_1`, `self_attention_0` and `shared_expert_gate` are module
  names, not the scopes: a program without the promised names reads
  nothing."""
  old = ('jit(forward)/M/encoder/self_attention_0/query',
         'jit(forward)/M/encoder/self_attention_0/softmax_of/dot_general',
         'jit(forward)/M/encoder/moe_1/experts/ragged_dot', '',
         'jit(forward)/M/encoder/moe_1/shared_expert_gate/dot_general')
  reading = _reading(real, old)
  for name in ('gqa_roofline', 'gqa_device_share', 'moe16_roofline',
               'moe16_device_share', 'shared_experts_roofline'):
    assert _read(name, reading) is None, name


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'no_work',
                                 'no_counts'])
def test_metrics_return_nothing_where_there_is_nothing(real, how):
  """Among them the parent commit, whose program has neither the kind nor
  this family: nothing, and no error."""
  reading = _reading(real, SCOPES, on_chip=how != 'off_chip',
                     spans={'finalize_drain': [drain(101.0, pack=2, bytes=9)]}
                     if how == 'no_counts' else None)
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_work':
    reading.work = types.SimpleNamespace()
  assert _read('moe16_roofline', reading) is None
  if how != 'no_counts':
    assert _read('gqa_roofline', reading) is None
    assert _read('shared_experts_roofline', reading) is None
  if how in ('off_chip', 'no_scopes'):
    assert _read('gqa_device_share', reading) is None
    assert _read('moe16_device_share', reading) is None
  if how in ('no_counts', 'no_work'):
    assert _read('moe16_load_max_over_mean', reading) is None
    reading.spans = {}
    assert _read('moe16_load_max_over_mean', reading) is None
  else:
    assert _read('moe16_load_max_over_mean', reading) == pytest.approx(
        2460 * 64 / (HELD + 6400))
