"""Family `gdn_moe_encoder` and the cell `qwen3next_polish`: new files
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
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_gdn_moe.json')
TOY_CELL = 'toy_gdn_moe_polish'
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'qwen3next_polish'
CONFIG = 'qwen3next80b_4of48_e256_L100'
NEW_METRICS = ('moe_roofline', 'gdn_roofline', 'moe_device_share',
               'expert_load_max_over_mean')

# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct as the model-configs
# catalog gives it (the keys that say something about the model's shape).
PUBLISHED = {
    'decoder_sparse_step': 1, 'full_attention_interval': 4, 'head_dim': 256,
    'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 5120,
    'linear_conv_kernel_dim': 4, 'linear_key_head_dim': 128,
    'linear_num_key_heads': 16, 'linear_num_value_heads': 32,
    'linear_value_head_dim': 128, 'max_position_embeddings': 262144,
    'mlp_only_layers': [], 'model_type': 'qwen3_next',
    'moe_intermediate_size': 512, 'norm_topk_prob': True,
    'num_attention_heads': 16, 'num_experts': 512, 'num_experts_per_tok': 10,
    'num_hidden_layers': 48, 'num_key_value_heads': 2,
    'partial_rotary_factor': 0.25, 'rms_norm_eps': 1e-06,
    'rope_scaling': None, 'rope_theta': 10000000,
    'shared_expert_intermediate_size': 512, 'tie_word_embeddings': False,
    'use_sliding_window': False, 'vocab_size': 151936}
AS_RUN = {'num_hidden_layers': 4, 'num_experts': 256}


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
  loaded, family, _shape = real
  bench = loaded.bench
  assert family.__file__ == os.path.join(
      ROOT, 'benchmark', 'families', 'gdn_moe_encoder.py')
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw32'
  assert loaded.cell['config'] == CONFIG
  assert [w['name'] for w in bench['workloads']].count(CELL) == 1
  (entry,) = [c for c in bench['configs'] if c['name'] == CONFIG]
  assert entry['reduced'] == loaded.config['reduced'] == [
      'num_hidden_layers', 'num_experts']
  assert entry['source'] == (
      'https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/'
      'config.json')
  assert loaded.config['source'].startswith(entry['source'])
  assert len(entry['why']) <= 200
  mine = [m for m in bench['per_layer'] if m.get('workloads') == [CELL]]
  assert [m['name'] for m in mine] == list(NEW_METRICS)
  for metric in mine:
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
  assert {m['name']: m['source'] for m in mine} == {
      'moe_roofline': 'device_trace', 'gdn_roofline': 'device_trace',
      'moe_device_share': 'device_trace',
      'expert_load_max_over_mean': 'program_counter'}
  # The 14 metrics that carry no list apply to the cell as they are.
  assert len(loaded.per_layer) == 18
  assert [m['name'] for m in loaded.per_layer[-4:]] == list(NEW_METRICS)
  assert set(loaded.limits) <= {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}
  assert loaded.limits


def test_the_cells_the_benchmark_had_are_as_they_were():
  with open(BENCH) as f:
    bench = json.load(f)
  assert [w['name'] for w in bench['workloads']][:3] == [
      'teacher_polish', 'student_polish', 'brumby_polish']
  assert [c['name'] for c in bench['configs']][:3] == [
      'teacher_6x280_L100', 'student_5x280_L100', 'brumby14b_8of40_L100']
  assert bench['run_seconds'] == 30
  assert [m['name'] for m in bench['end_to_end']] == ['windows_per_s',
                                                      'setup_s']
  brumby = [m['name'] for m in bench['per_layer']
            if m.get('workloads') == ['brumby_polish']]
  assert brumby == ['retention_roofline', 'retention_device_share',
                    'ffn_roofline', 'resident_weights_gib']


def test_configuration_file_holds_the_published_config_but_the_cut(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    if key in config['reduced']:
      assert config[key] == AS_RUN[key]
      assert config[key + '_published'] == value
    else:
      assert key in config and config[key] == value, key
  assert config['experts_held'] == [0, 256]
  assert config['batch_size'] == 512 and config['batch_size_why']
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assert '2 chips a layer x 12 stages = 24 chips' in config['deployment']
  assert len(config['departures']) == 4
  assert any('two directions' in text for text in config['departures'])


def test_traffic_is_the_window_stream_cut_to_thirty_two_zmws(real):
  traffic = real[0].traffic
  with open(os.path.join(ROOT, 'benchmark', 'traffic',
                         'window_stream.json')) as f:
    stream = json.load(f)
  for key in ('entry', 'generator', 'generator_params', 'windows_per_zmw',
              'options', 'loop'):
    assert traffic[key] == stream[key], key
  assert traffic['pool_windows'] == 4800 == 32 * traffic['windows_per_zmw']
  assert traffic['compare_windows'] == 256
  assert traffic['generator_params'] == real[1].CALIBRATION_TRAFFIC


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'gdn_moe_encoder.py')) as f:
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
  assert (shape['hidden_size'], shape['linear_num_key_heads'],
          shape['linear_num_value_heads'], shape['linear_key_head_dim'],
          shape['linear_conv_kernel_dim']) == (2048, 16, 32, 128, 4)
  assert (shape['num_attention_heads'], shape['num_key_value_heads'],
          shape['head_dim'], shape['partial_rotary_factor'],
          shape['rope_theta']) == (16, 2, 256, 0.25, 1e7)
  assert (shape['num_experts_published'], shape['num_experts_per_tok'],
          shape['moe_intermediate_size'],
          shape['shared_expert_intermediate_size'],
          shape['full_attention_interval'], shape['layer_pattern']) == (
              512, 10, 512, 512, 4, 'GGGS')
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in loaded.config.items()
                     if k != 'linear_num_value_heads'})


@pytest.mark.parametrize('key,value', [
    ('hidden_size', 1024), ('linear_num_key_heads', 32),
    ('linear_num_value_heads', 16), ('linear_key_head_dim', 64),
    ('linear_conv_kernel_dim', 3), ('num_attention_heads', 8),
    ('num_key_value_heads', 4), ('head_dim', 128),
    ('partial_rotary_factor', 0.5), ('rope_theta', 10000),
    ('num_experts_published', 256), ('num_experts_per_tok', 8),
    ('moe_intermediate_size', 768), ('shared_expert_intermediate_size', 0),
    ('full_attention_interval', 2), ('layer_pattern', 'GSGS'),
    ('experts_held', [256, 512]), ('norm_topk_prob', False),
    ('block_kind', 'power_retention_swiglu')])
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
  assert family.layer_counts(shape) == {
      'delta': 33_722_560, 'softmax': 27_267_584,
      'beside_experts': 4_196_352, 'expert': 3_145_728}
  config = real[0].config
  assert config['param_count_by_part'] == {
      'delta_layer_outside_experts': 33_722_560,
      'softmax_layer_outside_experts': 27_267_584,
      'router_shared_expert_and_gate': 4_196_352, 'one_expert': 3_145_728}
  block = 3 * 33_722_560 + 27_267_584 + 4 * (4_196_352 + 256 * 3_145_728)
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 560 * 2048
             + 2048 * 5 + 5 + 2048)
  assert block == 3_366_446_144 == config['param_count_block']
  assert family.param_count(shape) == block + outside == config['param_count']
  assert family.held_mean(shape) == 5.0
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 100 * 560 * 2048
  # [q | k | v | z], [b | a] and the output product of three layers.
  assert flops['delta_projections'] == 3 * 2 * 100 * 2048 * (
      2048 + 2048 + 4096 + 4096 + 64 + 4096)
  # Pairs j < t and j <= t of 100 positions, two directions.
  assert flops['delta_rule'] == 3 * 2 * 2 * (4950 + 5050) * (
      16 * 128 + 32 * 128)
  assert flops['softmax_projections'] == 2 * 100 * 2048 * (
      16 * 512 + 2 * 2 * 256 + 4096)
  assert flops['softmax_scores'] == flops['softmax_values'] == (
      2 * 100 * 100 * 16 * 256)
  assert flops['router'] == 4 * 2 * 100 * 2048 * 512
  assert flops['shared_expert'] == 4 * 2 * 100 * (3 * 2048 * 512 + 2048)
  assert flops['experts'] == 4 * 2 * 100 * 5 * 3 * 2048 * 512
  assert flops['head'] == 2 * 100 * 2048 * 5
  assert flops['total'] == sum(v for k, v in flops.items() if k != 'total')
  assert flops['total'] == 42_736_435_200  # "about 42.6 GFLOP a window"
  assert 0.36 < (flops['experts'] + flops['router']
                 + flops['shared_expert']) / flops['total'] < 0.38
  moved = family.bytes_per_pack(shape, 512)
  assert moved['weights'] == 2 * family.param_count(shape)
  assert moved['rows_in'] == 512 * 81 * 100
  least = family.least_seconds_per_pack(shape, 512, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'
  assert least['seconds'] == pytest.approx(0.11107, abs=1e-5)


def test_work_of_the_parts_a_pack(real):
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  gdn = family.part_work(shape, 512, 'gdn')
  assert gdn['flops'] == 512 * flops['delta_rule']
  # q, k [16 x 128] and v [32 x 128] bfloat16 in each direction, g and
  # beta [32] float32 in, o [32 x 128] float32 out, three layers.
  assert gdn['bytes'] == 3 * 51200 * (2 * 8192 * 2 + 2 * 32 * 4 + 4096 * 4)
  positions = 51200
  moe = family.moe_work(shape, positions, 4 * positions * 5, 1)
  assert moe == family.part_work(shape, 512, 'moe')
  assert moe['flops'] == 512 * (flops['router'] + flops['experts'])
  assert moe['bytes'] == 2 * 4 * (
      256 * 3 * 2048 * 512 + 2048 * 512 + 2 * positions * 2048)
  # An uneven window: more assignments are more work, the same bytes; two
  # packs read the weights twice.
  more = family.moe_work(shape, positions, 4 * positions * 7, 1)
  assert more['flops'] - moe['flops'] == 4 * positions * 2 * 3 * 2 * 2048 * 512
  assert more['bytes'] == moe['bytes']
  two = family.moe_work(shape, 2 * positions, 2 * 4 * positions * 5, 2)
  assert two == {'flops': 2 * moe['flops'], 'bytes': 2 * moe['bytes']}
  with pytest.raises(KeyError):
    family.part_work(shape, 512, 'attention')


def test_work_at_toy_widths_is_the_hand_count(toy):
  _loaded, family, shape = toy
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 20 * 170 * 64
  assert flops['delta_projections'] == 3 * 2 * 20 * 64 * (
      16 + 16 + 32 + 32 + 8 + 32)
  assert flops['delta_rule'] == 3 * 2 * 2 * (190 + 210) * (2 * 8 + 4 * 8)
  assert flops['softmax_projections'] == 2 * 20 * 64 * (4 * 32 + 64 + 64)
  assert flops['router'] == 4 * 2 * 20 * 64 * 16
  assert family.held_mean(shape) == 2.0
  assert flops['experts'] == 4 * 2 * 20 * 2 * 3 * 64 * 24
  delta = (64 * 96 + 64 * 8 + 4 * 64 + 4 + 4 + 8 + 32 * 64 + 2 * 64)
  softmax = 64 * 4 * 32 + 2 * 64 * 2 * 16 + 2 * 16 + 64 * 64 + 2 * 64
  experts = 64 * 16 + 8 * 3 * 64 * 24 + 3 * 64 * 24 + 64
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 170 * 64 + 64 * 5
             + 5 + 64)
  assert family.param_count(shape) == (3 * delta + softmax + 4 * experts
                                       + outside)


# ------------------------------------------------------------------- the tree

def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  """Abstractly: no array of the 6.7 GB is made."""
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
  assert len(leaves) == 9 + 3 * (2 + 7 + 8) + (2 + 6 + 8)
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
  gdn = a['encoder']['gdn_1']
  decay = np.exp(-np.exp(np.asarray(gdn['A_log'], np.float32)))
  assert 0.8 <= decay.min() and decay.max() <= 0.91  # at softplus = 1
  dt = np.asarray(gdn['dt_bias'], np.float32)
  assert -1.0 <= dt.min() and dt.max() <= 0.5
  plain = np.asarray(gdn['norm_scale'], np.float32)
  assert 0.5 <= plain.min() and plain.max() <= 1.5
  scale = np.asarray(
      a['encoder']['gated_attention_3']['query_norm']['scale'], np.float32)
  assert -0.5 <= scale.min() and scale.max() <= 0.5 and scale.std() > 0.1
  kernel = np.asarray(a['encoder']['moe_0']['experts_down'], np.float32)
  assert kernel.shape == (8, 24, 64)
  assert kernel.std() == pytest.approx(24 ** -0.5, rel=0.05)


def test_decay_and_beta_spread_as_the_file_says(toy, toy_windows):
  """exp(g) over about (0.6, 1) and beta over about (0.1, 0.9) on the
  generator's windows, from the family's own mixer arithmetic."""
  import jax
  import jax.numpy as jnp
  _loaded, family, shape = toy
  tree, windows, _ref = toy_windows
  f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
  x = family.embed_fn(tree, jnp.asarray(windows[..., 0]), max_passes=5,
                      precision='float32')
  enc = f32(tree['encoder'])
  u = family.norm(x, enc['attention_wrapper_0']['rms_norm']['scale'], 1e-6)
  b, a = jnp.split(u @ enc['gdn_0']['in_proj_ba']['kernel'], 2, axis=-1)
  decay = np.exp(np.asarray(
      -jnp.exp(enc['gdn_0']['A_log']) * jax.nn.softplus(
          a + enc['gdn_0']['dt_bias'])))
  beta = np.asarray(jax.nn.sigmoid(b))
  assert 0.55 < np.quantile(decay, 0.01) and np.quantile(decay, 0.99) < 1.0
  assert decay.std() > 0.02
  assert 0.1 < np.quantile(beta, 0.05) and np.quantile(beta, 0.95) < 0.9
  assert beta.std() > 0.1


def test_routers_are_balanced_on_windows_from_the_seed(toy):
  """As drawn a router loads some expert with more of every pack than the
  tokens' spread explains (they share a direction); balanced, none gets
  three times the mean, on windows the balancing never saw."""
  from benchmark.generators import pileup_windows as gen
  loaded, family, shape = toy
  seed = 2**31 + 9
  windows = gen.make(shape, loaded.traffic, seed + 1)[:96]
  worst = {}
  for name, make in (('drawn', family.draw_params),
                     ('balanced', family.make_params)):
    tree = make(shape, seed)
    _logits, counts, same = family.reference_forward(tree, windows, shape)
    assert same['encoder']['moe_0']['router'] is (
        tree['encoder']['moe_0']['router'])
    assert counts.shape == (4, 8)
    assert 0.4 < counts.sum() / (96 * 20 * 4 * 4) < 0.6  # 8 of 16 held
    worst[name] = (counts.max(axis=1) / counts.mean(axis=1)).max()
  assert worst['balanced'] < 3.0
  assert worst['balanced'] < worst['drawn']


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
  assert np.abs(np.asarray(got['logits']) - ref).max() < 1e-4
  counts = family.reference_forward(tree, windows, shape)[1]
  assert np.array_equal(
      np.asarray(model_lib.expert_assignments(sown['moe_counts'])), counts)
  ids, quals = compare.served_from_logits(ref)
  assert len(np.unique(quals)) > 5 and len(np.unique(ids)) == 5


@pytest.mark.parametrize('length', [1, 12, 100])
def test_references_recurrence_is_the_chunked_rule(toy, length):
  """The family's token-by-token rule against the rule written over one
  chunk (the triangular system solved outright in float64)."""
  import jax.numpy as jnp
  family = toy[1]
  rng = np.random.default_rng(length)
  unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
  q = unit(rng.normal(size=(2, length, 2, 8))) * 8 ** -0.5
  k = unit(rng.normal(size=(2, length, 2, 8)))
  v = rng.normal(size=(2, length, 4, 8))
  g = np.log(rng.uniform(0.6, 1.0, size=(2, length, 4)))
  beta = rng.uniform(0.1, 0.9, size=(2, length, 4))
  as32 = lambda a: jnp.asarray(a, jnp.float32)
  got = np.asarray(family.delta_recurrence(
      as32(q), as32(k), as32(v), as32(g), as32(beta)))
  cum = np.cumsum(g, axis=1)
  for b in range(2):
    for h in range(4):
      kh, qh = k[b, :, h // 2], q[b, :, h // 2]
      decay = np.exp(cum[b, :, None, h] - cum[b, None, :, h])
      system = np.tril(beta[b, :, None, h] * decay * (kh @ kh.T), -1)
      d = np.linalg.solve(np.eye(length) + system,
                          beta[b, :, None, h] * v[b, :, h])
      want = np.tril(decay * (qh @ kh.T)) @ d
      assert np.abs(got[b, :, h] - want).max() < 1e-5


@pytest.mark.parametrize('served', ['fp8', 'correction_dropped',
                                    'not_renormalised', 'one_direction'])
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/qwen3next_polish.json), by the
  rule `run_cell` judges with, on toy numbers: each of these in the
  program's place comes out not correct; the float32 reference and the
  bfloat16 yardstick pass."""
  from benchmark.lib import compare
  _loaded, family, shape = toy
  tree, windows, ref = toy_windows
  limits = real[0].limits
  yard = family.reference_logits(tree, windows, shape, 'bfloat16')
  kwargs = {'fp8': dict(precision='fp8'),
            'correction_dropped': dict(correct=False),
            'not_renormalised': dict(renormalise=False),
            'one_direction': dict(directions=(1,))}[served]
  low = family.reference_logits(tree, windows, shape, **kwargs)
  judged = compare.judge(
      compare.numbers(ref, *compare.served_from_logits(low), yard), limits)
  assert judged and not all(ok for *_r, ok in judged)
  for logits in (ref, yard):
    same = compare.judge(
        compare.numbers(ref, *compare.served_from_logits(logits), yard),
        limits)
    assert all(ok for *_r, ok in same)


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
    assert 1.0 <= metrics['expert_load_max_over_mean']['value'] < 4.0
    for name in ('moe_roofline', 'gdn_roofline', 'moe_device_share',
                 'forward_mfu'):
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
          ('%fusion.1', 0 * ms, 2 * ms), ('%custom-call.2', 5 * ms, 6 * ms),
          ('%custom-call.3', 11 * ms, 10 * ms), ('%copy.4', 30 * ms, 2 * ms)]}})
  planes.scopes['/device:TPU:0'] = list(scopes)
  return types.SimpleNamespace(
      planes=planes, trace_window=(0.0, 40 * ms), xplane=xplane,
      spans_lib=spans_lib, on_chip=on_chip, chips=1, shape=shape, batch=512,
      work=family, peaks=peaks.peaks_for('TPU v5e'),
      result={'counters': {'n_packs': 2}}, span_window=(100.0, 140.0),
      spans=spans if spans is not None else {'finalize_drain': DRAINS})


drain = lambda t, **args: (t, t + 0.001, args)
POSITIONS = 51200
DRAINS = [
    drain(99.0, pack=1, moe_assignments_total=40 * POSITIONS,  # the warm-up
          moe_assignments_held=20 * POSITIONS, moe_expert_load_max=9000),
    drain(101.0, pack=2, moe_assignments_total=40 * POSITIONS,
          moe_assignments_held=1_024_000, moe_expert_load_max=1500),
    drain(120.0, pack=3, moe_assignments_total=40 * POSITIONS,
          moe_assignments_held=1_000_000, moe_expert_load_max=2500)]

SCOPES = ('jit(forward)/M/encoder/ffn/ffn_wrapper_0/moe_0/moe/while/body/'
          'closed_call/combine/reduce_sum',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/gdn_0/gdn/'
          'gated_delta_window/pallas_call',
          # The compiler's grouped product: its own name, no scope.
          'ragged-dot-none',
          'jit(forward)/M/encoder/ffn/ffn_wrapper_0/moe_0/shared_expert/'
          'shared_expert/up_layer/dot_general')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  reading = _reading(real, SCOPES)
  v5e = reading.peaks
  need = family.part_work(shape, 512, 'gdn')
  least = max(need['flops'] / v5e['bf16_flops_per_s'],
              need['bytes'] / v5e['hbm_bytes_per_s'])
  assert need['bytes'] / v5e['hbm_bytes_per_s'] > (
      need['flops'] / v5e['bf16_flops_per_s'])  # memory-bound
  assert _read('gdn_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.006)
  # The experts' work is what the two packs inside the window counted.
  need = family.moe_work(shape, 2 * POSITIONS, 2_024_000, 2)
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  assert least > need['bytes'] / v5e['hbm_bytes_per_s']
  # 2 ms under the scope and 10 ms of grouped products without one.
  assert _read('moe_roofline', reading) == pytest.approx(100 * least / 0.012)
  assert _read('moe_device_share', reading) == pytest.approx(100 * 12 / 20)
  # The fullest group of a layer over the mean group: 2500 / (1e6 / 1024).
  assert _read('expert_load_max_over_mean', reading) == pytest.approx(2.56)


def test_scope_names_match_as_whole_steps_of_the_path(real):
  """`moe_0` and `gdn_0` are module names, not the scopes: a program
  without the promised names reads nothing."""
  old = ('jit(forward)/M/encoder/attention_wrapper_0/gdn_0/in_proj_qkvz',
         'jit(forward)/M/encoder/gdn_0/gated_delta_window',
         'jit(forward)/M/encoder/ffn_wrapper_0/moe_0/experts/ragged_dot', '')
  reading = _reading(real, old)
  for name in ('gdn_roofline', 'moe_roofline', 'moe_device_share'):
    assert _read(name, reading) is None, name


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'no_work',
                                 'no_counts'])
def test_metrics_return_nothing_where_there_is_nothing(real, how):
  """Among them the parent commit, whose program has neither the scopes
  nor the counts: nothing, and no error."""
  reading = _reading(real, SCOPES, on_chip=how != 'off_chip',
                     spans={'finalize_drain': [drain(101.0, pack=2, bytes=9)]}
                     if how == 'no_counts' else None)
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_work':
    reading.work = types.SimpleNamespace()
  assert _read('moe_roofline', reading) is None
  if how != 'no_counts':
    assert _read('gdn_roofline', reading) is None
  if how in ('off_chip', 'no_scopes'):
    assert _read('moe_device_share', reading) is None
  if how == 'no_counts':
    assert _read('expert_load_max_over_mean', reading) is None
    reading.spans = {}
    assert _read('expert_load_max_over_mean', reading) is None
  else:
    assert _read('expert_load_max_over_mean', reading) == pytest.approx(2.56)
