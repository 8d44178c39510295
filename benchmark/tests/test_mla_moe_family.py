"""Family `mla_moe_encoder` and the cell `kanana_polish`: new files only.
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
TOY = os.path.join(FIXTURES, 'BENCHMARK.toy_mla_moe.json')
TOY_CELL = 'toy_mla_moe_polish'
BENCH = os.path.join(ROOT, 'BENCHMARK.json')
CELL = 'kanana_polish'
CONFIG = 'kanana2_30b_8of48_L100'
NEW_METRICS = ('latent_roofline', 'latent_device_share', 'moe128_roofline',
               'moe128_device_share', 'moe128_load_max_over_mean')

# config.json of kakaocorp/kanana-2-30b-a3b-instruct-2601 as the
# model-configs catalog gives it (the keys that say something about the
# model's shape).
PUBLISHED = {
    'attention_bias': False, 'first_k_dense_replace': 1, 'head_dim': 64,
    'hidden_act': 'silu', 'hidden_size': 2048, 'intermediate_size': 6144,
    'kv_lora_rank': 512, 'max_position_embeddings': 32768,
    'model_type': 'deepseek_v3', 'moe_intermediate_size': 768,
    'moe_layer_freq': 1, 'n_group': 1, 'n_routed_experts': 128,
    'n_shared_experts': 2, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 6, 'num_hidden_layers': 48,
    'num_key_value_heads': 32, 'q_lora_rank': None, 'qk_head_dim': 192,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_interleave': True, 'rope_scaling': None, 'rope_theta': 1000000,
    'routed_scaling_factor': 2.448, 'scoring_func': 'sigmoid',
    'tie_word_embeddings': False, 'topk_group': 1, 'topk_method': 'noaux_tc',
    'v_head_dim': 128, 'vocab_size': 128256}
AS_RUN = {'num_hidden_layers': 8}


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
      ROOT, 'benchmark', 'families', 'mla_moe_encoder.py')
  assert loaded.cell['chips'] == 1 and len(loaded.cell['why']) <= 200
  assert loaded.cell['traffic'] == 'window_stream_zmw32'
  assert loaded.cell['config'] == CONFIG
  assert [w['name'] for w in bench['workloads']].count(CELL) == 1
  (entry,) = [c for c in bench['configs'] if c['name'] == CONFIG]
  assert entry['reduced'] == loaded.config['reduced'] == ['num_hidden_layers']
  assert entry['source'] == (
      'https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/'
      'main/config.json')
  assert loaded.config['source'].startswith(entry['source'])
  assert len(entry['why']) <= 200
  mine = [m for m in bench['per_layer'] if m.get('workloads') == [CELL]]
  assert [m['name'] for m in mine] == list(NEW_METRICS)
  assert [m['name'] for m in bench['per_layer'][-5:]] == list(NEW_METRICS)
  for metric in mine:
    assert metric['moves'] == 'windows_per_s' and metric['layer'] == 'forward'
  assert {m['name']: m['source'] for m in mine} == {
      'latent_roofline': 'device_trace', 'latent_device_share': 'device_trace',
      'moe128_roofline': 'device_trace', 'moe128_device_share': 'device_trace',
      'moe128_load_max_over_mean': 'program_counter'}
  # The 14 metrics that carry no list apply to the cell as they are.
  assert len(loaded.per_layer) == 19
  assert [m['name'] for m in loaded.per_layer[-5:]] == list(NEW_METRICS)
  assert set(loaded.limits) <= {'id_gap_mean_vs_bf16',
                                'qual_diff_mean_vs_bf16'}
  assert loaded.limits


def test_the_cells_the_benchmark_had_are_as_they_were():
  with open(BENCH) as f:
    bench = json.load(f)
  assert [w['name'] for w in bench['workloads']] == [
      'teacher_polish', 'student_polish', 'brumby_polish', 'qwen3next_polish',
      CELL]
  assert [c['name'] for c in bench['configs']][:4] == [
      'teacher_6x280_L100', 'student_5x280_L100', 'brumby14b_8of40_L100',
      'qwen3next80b_4of48_e256_L100']
  assert bench['run_seconds'] == 30
  assert [m['name'] for m in bench['end_to_end']] == ['windows_per_s',
                                                      'setup_s']
  qwen = [m['name'] for m in bench['per_layer']
          if m.get('workloads') == ['qwen3next_polish']]
  assert qwen == ['moe_roofline', 'gdn_roofline', 'moe_device_share',
                  'expert_load_max_over_mean']


def test_configuration_file_holds_the_published_config_but_the_cut(real):
  config = real[0].config
  for key, value in PUBLISHED.items():
    if key in config['reduced']:
      assert config[key] == AS_RUN[key]
      assert config[key + '_published'] == value
    else:
      assert key in config and config[key] == value, key
  assert config['experts_held'] == [0, 128]
  assert config['batch_size'] == 512 and config['batch_size_why']
  for key in ('assumed', 'departures', 'deployment', 'reduced_why'):
    assert config[key], key
  assert '6-stage pipeline of 8 layers a stage' in config['deployment']
  assert len(config['departures']) == 4
  assert any('no causal mask' in text for text in config['departures'])
  assert any('halves_from_pairs' in text for text in config['assumed'])


def test_traffic_is_the_window_stream_of_thirty_two_zmws(real):
  traffic = real[0].traffic
  assert traffic['pool_windows'] == 4800 == 32 * traffic['windows_per_zmw']
  assert traffic['compare_windows'] == 256
  assert traffic['generator_params'] == real[1].CALIBRATION_TRAFFIC


def test_family_names_nothing_of_the_program():
  with open(os.path.join(ROOT, 'benchmark', 'families',
                         'mla_moe_encoder.py')) as f:
    text = f.read().split('"""', 2)[2]
  assert 'deepconsensus_tpu' not in text
  assert 'benchmark.reference' not in text and 'lib.weights' not in text
  assert 'families.gdn_moe' not in text


# ------------------------------------------------------ sizes, file and preset

def test_file_and_preset_agree_at_the_published_sizes(real):
  from benchmark import run
  loaded, family, shape = real
  params = run.program_params(loaded.config, family)
  stated = family.stated(params)
  assert {k: loaded.config[k] for k in stated} == stated
  assert (shape['hidden_size'], shape['num_attention_heads'],
          shape['qk_nope_head_dim'], shape['qk_rope_head_dim'],
          shape['v_head_dim'], shape['kv_lora_rank'], shape['rope_theta'],
          shape['rms_norm_eps']) == (2048, 32, 128, 64, 128, 512, 1e6, 1e-6)
  assert stated['q_lora_rank'] is None and stated['qk_head_dim'] == 192
  assert (shape['intermediate_size'], shape['first_k_dense_replace'],
          shape['layer_pattern'], shape['ffn_pattern']) == (
              6144, 1, 'LLLLLLLL', 'DEEEEEEE')
  assert (shape['n_routed_experts'], shape['num_experts_per_tok'],
          shape['moe_intermediate_size'], shape['n_shared_experts'],
          stated['shared_expert_intermediate_size'], shape['scoring_func'],
          shape['topk_method'], shape['routed_scaling_factor']) == (
              128, 6, 768, 2, 1536, 'sigmoid', 'noaux_tc', 2.448)
  with pytest.raises(KeyError):
    family.shape_of({k: v for k, v in loaded.config.items()
                     if k != 'kv_lora_rank'})


@pytest.mark.parametrize('key,value', [
    ('hidden_size', 1024), ('num_attention_heads', 16),
    ('qk_nope_head_dim', 64), ('qk_rope_head_dim', 32), ('qk_head_dim', 128),
    ('v_head_dim', 192), ('kv_lora_rank', 256), ('q_lora_rank', 1536),
    ('rope_theta', 10000), ('rms_norm_eps', 1e-5),
    ('intermediate_size', 8192), ('first_k_dense_replace', 3),
    ('ffn_pattern', 'EEEEEEEE'), ('n_routed_experts', 256),
    ('num_experts_per_tok', 8), ('moe_intermediate_size', 512),
    ('n_shared_experts', 1), ('shared_expert_intermediate_size', 768),
    ('shared_expert_gated', True),
    ('scoring_func', 'softmax'), ('topk_method', 'greedy'),
    ('routed_scaling_factor', 1.0), ('norm_topk_prob', False),
    ('experts_held', [0, 64]), ('block_kind', 'gated_delta_hybrid_moe')])
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
  attention = 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
  assert attention == 26_345_984
  assert family.layer_counts(shape) == {
      'attention': attention + 4096, 'dense_ffn': 37_748_736,
      'beside_experts': 262_144 + 128 + 9_437_184, 'expert': 4_718_592}
  config = real[0].config
  assert config['param_count_by_part'] == {
      'attention_with_the_layers_two_norms': 26_350_080,
      'dense_feed_forward': 37_748_736,
      'router_bias_and_shared_expert': 9_699_456, 'one_expert': 4_718_592}
  dense_layer = 26_350_080 + 37_748_736
  expert_layer = 26_350_080 + 9_699_456 + 128 * 4_718_592
  assert (dense_layer, expert_layer) == (64_098_816, 640_029_312)
  block = dense_layer + 7 * expert_layer
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 560 * 2048
             + 2048 * 5 + 5 + 2048)
  assert block == 4_544_304_000 == config['param_count_block']
  assert family.param_count(shape) == block + outside == config['param_count']
  assert family.expert_layers(shape) == 7
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 100 * 560 * 2048
  # W_q, W_kva, W_kvb and W_o of eight layers: 5.269 GFLOP a layer.
  assert flops['attention_projections'] == 8 * 2 * 100 * (
      2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048)
  assert flops['attention_projections'] // 8 == 5_269_094_400
  assert flops['latent_scores'] == 8 * 2 * 100 * 100 * 32 * 192
  assert flops['latent_values'] == 8 * 2 * 100 * 100 * 32 * 128
  assert flops['dense_ffn'] == 2 * 100 * 3 * 2048 * 6144 == 7_549_747_200
  assert flops['router'] == 7 * 2 * 100 * 2048 * 128
  assert flops['shared_expert'] == 7 * 2 * 100 * 3 * 2048 * 1536
  assert flops['experts'] == 7 * 2 * 100 * 6 * 3 * 2048 * 768
  assert flops['head'] == 2 * 100 * 2048 * 5
  assert flops['total'] == sum(v for k, v in flops.items() if k != 'total')
  assert flops['total'] == 104_787_558_400  # "104.8 GFLOP a window"
  share = lambda *names: sum(flops[n] for n in names) / flops['total']
  assert round(100 * share('experts'), 1) == 37.8
  assert round(100 * share('attention_projections'), 1) == 40.2
  assert round(100 * share('dense_ffn', 'shared_expert'), 1) == 19.8
  assert round(100 * share('latent_scores', 'latent_values'), 1) == 1.6
  moved = family.bytes_per_pack(shape, 512)
  assert moved['weights'] == 2 * family.param_count(shape)
  assert moved['rows_in'] == 512 * 81 * 100
  least = family.least_seconds_per_pack(shape, 512, peaks.peaks_for('TPU v5e'))
  assert least['bound'] == 'compute'
  assert least['seconds'] == pytest.approx(0.27234, abs=1e-5)
  # Held short of all the experts the work would be another's too.
  with pytest.raises(ValueError, match='every expert held'):
    family.flops_per_window(dict(shape, experts_held=[0, 64]))


def test_work_of_the_parts_a_pack(real):
  from benchmark.lib import peaks
  _loaded, family, shape = real
  flops = family.flops_per_window(shape)
  latent = family.part_work(shape, 512, 'latent')
  assert latent['flops'] == 512 * (flops['latent_scores']
                                   + flops['latent_values'])
  assert latent['flops'] // 512 == 1_638_400_000  # 1.64 GFLOP a window
  # q [32 x 192], k_nope [32 x 128], the one k_rope [64], v and o
  # [32 x 128] bfloat16 a position, eight layers.
  assert latent['bytes'] == 8 * 51200 * 2 * (6144 + 4096 + 64 + 4096 + 4096)
  v5e = peaks.peaks_for('TPU v5e')
  assert latent['bytes'] / v5e['hbm_bytes_per_s'] > (
      latent['flops'] / v5e['bf16_flops_per_s'])  # memory-bound
  positions = 51200
  moe = family.moe_work(shape, positions, 7 * positions * 6, 1)
  assert moe == family.part_work(shape, 512, 'moe')
  assert moe['flops'] == 512 * (flops['router'] + flops['experts'])
  # 307,200 assignments a pack are two turns of the program's 262,144: the
  # experts' weights twice, the router's once.
  assert moe['bytes'] == 2 * 7 * (
      2 * 128 * 3 * 2048 * 768 + 2048 * 128 + 2 * positions * 2048)
  assert moe['flops'] / v5e['bf16_flops_per_s'] > (
      moe['bytes'] / v5e['hbm_bytes_per_s'])  # compute-bound
  # An uneven window: fewer assignments are less work, the same bytes; two
  # packs read the weights twice as often.
  fewer = family.moe_work(shape, positions, 7 * positions * 5, 1)
  assert moe['flops'] - fewer['flops'] == 7 * positions * 3 * 2 * 2048 * 768
  assert fewer['bytes'] == moe['bytes']
  two = family.moe_work(shape, 2 * positions, 2 * 7 * positions * 6, 2)
  assert two == {'flops': 2 * moe['flops'], 'bytes': 2 * moe['bytes']}
  with pytest.raises(KeyError):
    family.part_work(shape, 512, 'attention')


def test_work_at_toy_widths_is_the_hand_count(toy):
  _loaded, family, shape = toy
  flops = family.flops_per_window(shape)
  assert flops['condense'] == 2 * 20 * 170 * 64
  assert flops['attention_projections'] == 3 * 2 * 20 * (
      64 * 4 * 24 + 64 * 32 + 24 * 4 * 28 + 48 * 64)
  assert flops['latent_scores'] == 3 * 2 * 20 * 20 * 4 * 24
  assert flops['latent_values'] == 3 * 2 * 20 * 20 * 4 * 12
  assert flops['dense_ffn'] == 2 * 20 * 3 * 64 * 96
  assert flops['router'] == 2 * 2 * 20 * 64 * 16
  assert flops['experts'] == 2 * 2 * 20 * 4 * 3 * 64 * 24
  attention = 64 * 4 * 24 + 64 * 32 + 24 + 24 * 4 * 28 + 48 * 64 + 2 * 64
  experts = 64 * 16 + 16 + 16 * 3 * 64 * 24 + 3 * 64 * 48
  outside = (5 * 8 + 256 * 8 + 256 * 8 + 3 * 2 + 501 * 8 + 170 * 64 + 64 * 5
             + 5 + 64)
  assert family.param_count(shape) == (3 * attention + 3 * 64 * 96
                                       + 2 * experts + outside)


# ------------------------------------------------------------------- the tree

def test_tree_is_the_programs_at_the_published_sizes_by_shape(real):
  """Abstractly: no array of the 9.09 GB is made."""
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
  assert len(leaves) == 9 + 8 * (2 + 5) + 3 + 7 * 8
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
  scale = np.asarray(
      a['encoder']['latent_attention_1']['kv_a_norm']['scale'], np.float32)
  assert 0.5 <= scale.min() and scale.max() <= 1.5 and scale.std() > 0.1
  kernel = np.asarray(a['encoder']['moe_1']['experts_down'], np.float32)
  assert kernel.shape == (16, 24, 64)
  assert kernel.std() == pytest.approx(24 ** -0.5, rel=0.05)
  assert 'moe_0' not in a['encoder'] and 'ffn_1' not in a['encoder']
  assert set(a['encoder']['ffn_0']) == {'gate_layer', 'up_layer',
                                        'output_layer'}


def test_routers_are_balanced_by_the_published_rule_on_windows_from_the_seed(
    toy):
  """As drawn the selection bias is zero and a router loads some expert
  with more of every pack than the tokens' spread explains; balanced, b is
  not zero, moves by whole steps of the rule, changes which experts are
  chosen for a measurable share of tokens, and no expert gets three times
  the mean, on windows the balancing never saw."""
  import jax
  import jax.numpy as jnp
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
    assert counts.shape == (2, 16)
    assert counts.sum() == 96 * 20 * 2 * 4  # every expert is held
    worst[name] = (counts.max(axis=1) / counts.mean(axis=1)).max()
    bias = np.asarray(tree['encoder']['moe_1']['router_selection_bias'],
                      np.float32)
    if name == 'drawn':
      assert not bias.any()
  assert worst['balanced'] < 3.0
  assert worst['balanced'] < worst['drawn']
  assert bias.any() and np.abs(bias).max() <= (
      family.BIAS_ROUNDS * family.BIAS_STEP * 1.01)
  # Who is chosen with b and without it, on the layer's own tokens.
  rng = np.random.default_rng(0)
  scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(2000, 16)),
                                      jnp.float32))
  route = lambda b: np.sort(np.asarray(family.weights_fn(
      scores, jnp.asarray(b), top_k=4, n_group=1, topk_group=1,
      renormalise=True, factor=2.448)[1]))
  moved = (route(bias) != route(np.zeros(16, np.float32))).any(axis=1)
  assert 0.02 < moved.mean() < 0.9


def test_balancing_bias_evens_the_load_of_a_skewed_router():
  from benchmark.families import mla_moe_encoder as family
  rng = np.random.default_rng(1)
  logits = rng.normal(size=(4000, 16)) + np.linspace(-0.3, 0.3, 16)
  scores = (1 / (1 + np.exp(-logits))).astype(np.float32)
  load = lambda b: np.bincount(
      np.argsort(-(scores + b), axis=1)[:, :4].ravel(), minlength=16)
  bias = family.balancing_bias(scores, 4)
  before, after = load(np.zeros(16, np.float32)), load(bias)
  assert after.max() / after.mean() < before.max() / before.mean()
  assert after.max() / after.mean() < 1.25
  # The rule moves every b_e by one step a round, towards the mean load.
  steps = bias / np.float32(family.BIAS_STEP)
  assert np.abs(steps - np.round(steps)).max() < 1e-3
  assert np.abs(steps).max() <= family.BIAS_ROUNDS
  assert bias[before.argmax()] < 0 < bias[before.argmin()]


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


def test_references_rotation_is_the_published_one_on_published_columns(toy):
  """The family's reference un-permutes the rotary columns and rotates
  interleaved pairs: on a kernel whose columns are in the published order
  that is the published q_rope . k_rope, which the halves rotation gives
  on the permuted columns."""
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
  got = np.asarray(family.rotary_pairs(x, 1.0e6))
  angle = 3 * 1.0e6 ** (-2 / d)  # position 3, pair (2, 3)
  want = (np.asarray(x)[0, 3, 1, 2] * np.cos(angle)
          - np.asarray(x)[0, 3, 1, 3] * np.sin(angle))
  assert got[0, 3, 1, 2] == pytest.approx(want, abs=1e-5)


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


@pytest.mark.parametrize('served', ['fp8', 'factor_dropped',
                                    'rotary_left_out'])
def test_control_and_faults_fail_the_committed_limits(toy, toy_windows, real,
                                                      served):
  """The cell's own limits (benchmark/limits/kanana_polish.json), by the
  rule `run_cell` judges with, on toy numbers: each of these in the
  program's place comes out not correct; the float32 reference and the
  bfloat16 yardstick pass."""
  _loaded, family, shape = toy
  tree, windows, _ref = toy_windows
  kwargs = {'fp8': dict(precision='fp8'),
            'factor_dropped': dict(factor=1.0),
            'rotary_left_out': dict(rotary=False)}[served]
  low, same, yard = _judged(family, shape, tree, windows, real[0].limits,
                            **kwargs)
  assert low and not all(ok for *_r, ok in low)
  assert all(ok for *_r, ok in same) and all(ok for *_r, ok in yard)


def test_bias_in_the_weights_fails_the_limits_where_the_bias_is_sizable(
    toy, toy_windows, real):
  """The third injected fault, the selection bias added to the weights
  too (p from s + b). A bias of the size of the scores themselves (uniform
  +-0.5, a router that leans on its bias) comes out not correct under the
  committed limits. The bias that `balance_routers` leaves is a few
  hundredths against scores of 0.6-0.9: there the fault moves the weights
  by a few percent, which is of the order of what bfloat16 operands move
  the logits by (under three times the yardstick on both numbers), and the
  comparison cannot be counted on to see it (PERF.md section 7 says so)."""
  import jax
  import jax.numpy as jnp
  _loaded, family, shape = toy
  tree, windows, _ref = toy_windows
  limits = real[0].limits
  calibrated = np.asarray(
      tree['encoder']['moe_1']['router_selection_bias'], np.float32)
  assert 0 < np.abs(calibrated).max() <= (
      family.BIAS_ROUNDS * family.BIAS_STEP * 1.01)
  low, _same, _yard = _judged(family, shape, tree, windows, limits,
                              bias_in_weights=True)
  assert all(value < 3.0 for _name, value, _limit, _ok in low)
  rng = np.random.default_rng(11)
  leaning = jax.tree_util.tree_map_with_path(
      lambda path, leaf: jnp.asarray(rng.uniform(-0.5, 0.5, leaf.shape),
                                     leaf.dtype)
      if 'router_selection_bias' in str(path) else leaf, tree)
  low, same, yard = _judged(family, shape, leaning, windows, limits,
                            bias_in_weights=True)
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
    assert 1.0 <= metrics['moe128_load_max_over_mean']['value'] < 3.0
    for name in ('latent_roofline', 'latent_device_share', 'moe128_roofline',
                 'moe128_device_share', 'forward_mfu'):
      assert name not in metrics  # never off a chip
    from benchmark.lib import spans as spans_lib
    spans = spans_lib.read_spans(
        os.path.join(str(tmp_path), f'spans.{TOY_CELL}.jsonl'))
    args = spans['forward_launch'][0][2]
    assert args['layer_pattern'] == 'LLL' and args['ffn_pattern'] == 'DEE'
    assert args['experts_held'] == [0, 16]
    assert args['router_scoring'] == 'sigmoid_bias'
    drains = [a for _s, _e, a in spans['finalize_drain']]
    # Every expert is held: every assignment of two expert layers counted.
    assert all(a['moe_assignments_held'] == a['moe_assignments_total'] ==
               32 * 20 * 2 * 4 for a in drains)
  else:
    assert set(result['metrics']) == {'windows_per_s', 'setup_s'}


# ------------------------------------------------- the five metric files

def _reading(real, scopes, on_chip=True, spans=None):
  """A hand-made trace: one device, four operations of 2, 6, 10 and 2 ms
  in a 40 ms window, two packs."""
  from benchmark.lib import peaks, spans as spans_lib, xplane
  _loaded, family, shape = real
  ms = 1e6
  planes = xplane.Planes({
      '/device:TPU:0': {xplane.OP_LINE: [
          ('%fusion.1', 0 * ms, 2 * ms), ('%fusion.2', 5 * ms, 6 * ms),
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
ALL = 7 * 6 * POSITIONS  # seven expert layers, six a position
DRAINS = [
    drain(99.0, pack=1, moe_assignments_total=ALL,  # the warm-up
          moe_assignments_held=ALL, moe_expert_load_max=9000),
    drain(101.0, pack=2, moe_assignments_total=ALL,
          moe_assignments_held=ALL, moe_expert_load_max=3000),
    drain(120.0, pack=3, moe_assignments_total=ALL,
          moe_assignments_held=ALL, moe_expert_load_max=3600)]

SCOPES = ('jit(forward)/M/encoder/ffn/ffn_wrapper_1/moe_1/moe/while/body/'
          'closed_call/combine/reduce_sum',
          'jit(forward)/M/encoder/attention/attention_wrapper_0/'
          'latent_attention_0/latent/bnlm,bmnd->blnd/dot_general',
          # The compiler's grouped product: its own name, no scope.
          'ragged-dot-none',
          'jit(forward)/M/encoder/ffn/ffn_wrapper_1/moe_1/shared_expert/'
          'shared_expert/up_layer/dot_general')


def _read(name, reading):
  from benchmark import run
  return run.load_by_name(os.path.join(ROOT, 'benchmark'), 'metrics',
                          name).read(reading)


def test_roofline_and_share_metrics_read_their_scope_alone(real):
  _loaded, family, shape = real
  reading = _reading(real, SCOPES)
  v5e = reading.peaks
  need = family.part_work(shape, 512, 'latent')
  least = need['bytes'] / v5e['hbm_bytes_per_s']  # memory-bound
  assert _read('latent_roofline', reading) == pytest.approx(
      100 * least * 2 / 0.006)
  assert _read('latent_device_share', reading) == pytest.approx(100 * 6 / 20)
  # The experts' work is what the two packs inside the window counted,
  # over SEVEN layers (the stack has eight).
  need = family.moe_work(shape, 2 * POSITIONS, 2 * ALL, 2)
  least = need['flops'] / v5e['bf16_flops_per_s']  # compute-bound
  # 2 ms under the scope and 10 ms of grouped products without one.
  assert _read('moe128_roofline', reading) == pytest.approx(
      100 * least / 0.012)
  assert _read('moe128_device_share', reading) == pytest.approx(100 * 12 / 20)
  # The fullest group of a layer over the mean group: 3600 / 2400.
  assert _read('moe128_load_max_over_mean', reading) == pytest.approx(1.5)


def test_scope_names_match_as_whole_steps_of_the_path(real):
  """`moe_1` and `latent_attention_0` are module names, not the scopes: a
  program without the promised names reads nothing."""
  old = ('jit(forward)/M/encoder/attention_wrapper_0/latent_attention_0/query',
         'jit(forward)/M/encoder/latent_attention_0/dot_general',
         'jit(forward)/M/encoder/ffn_wrapper_1/moe_1/experts/ragged_dot', '')
  reading = _reading(real, old)
  for name in ('latent_roofline', 'latent_device_share', 'moe128_roofline',
               'moe128_device_share'):
    assert _read(name, reading) is None, name


@pytest.mark.parametrize('how', ['off_chip', 'no_scopes', 'no_work',
                                 'no_counts'])
def test_metrics_return_nothing_where_there_is_nothing(real, how):
  """Among them the parent commit, whose program has neither the scope
  nor this family: nothing, and no error."""
  reading = _reading(real, SCOPES, on_chip=how != 'off_chip',
                     spans={'finalize_drain': [drain(101.0, pack=2, bytes=9)]}
                     if how == 'no_counts' else None)
  if how == 'no_scopes':
    reading.planes.scopes.clear()
  if how == 'no_work':
    reading.work = types.SimpleNamespace()
  assert _read('moe128_roofline', reading) is None
  if how != 'no_counts':
    assert _read('latent_roofline', reading) is None
  if how in ('off_chip', 'no_scopes'):
    assert _read('latent_device_share', reading) is None
    assert _read('moe128_device_share', reading) is None
  if how in ('no_counts', 'no_work'):
    assert _read('moe128_load_max_over_mean', reading) is None
    reading.spans = {}
    assert _read('moe128_load_max_over_mean', reading) is None
  else:
    assert _read('moe128_load_max_over_mean', reading) == pytest.approx(1.5)
