"""The sixth encoder block kind (config.BLOCK_WINDOW_MOE): a sequential
pre-RMSNorm stack whose layer types are listed (`layer_types`), each type
with its own rotation (`rope_parameters`: the default law on the window
layers, YaRN with its magnitude on the full layers), grouped heads without
q/k norm or gate, routed experts scored by a softmax and renormalised, no
shared expert.

Toy sizes on the CPU (hidden 64; 4 query heads over 2 key-value heads of 16;
16 experts of width 24, 4 a token, 8 or all 16 held; 4 layers `WWWF`; window
8 at L 24, which binds, and at L 8, which does not). The published rope
parameters rotate nothing that a toy window can tell apart below the YaRN
ramp, so one case runs a toy rope (base 1,000, 64 original positions,
factor 4) whose interpolation turns within 24 positions. What is held here:
the program's model, through get_model and through ModelRunner, against a
test-local plain reference (tests/window_moe_reference.py: YaRN from the
published formula, rotate-half, k and v repeated to the query heads, the
window mask built always, the experts one by one) on seeded weights; the
YaRN tables against hand values; the listed patterns; no shared-expert leaf;
what the kind refuses by name.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from tests import window_moe_reference as ref
from tests.test_mla_moe_block import _runner
from tests.test_parallel_moe_block import seeded_variables
from tests.test_power_retention import pileup_rows

PRESET = 'transformer_learn_values_window_moe+custom'
KIND = config_lib.BLOCK_WINDOW_MOE
TOP_K = 4
PERIOD = ['sliding_attention'] * 3 + ['full_attention']
# A rope whose interpolation turns within a toy window: at D 16, base 1,000
# and 64 original positions the ramp runs over dims 2-4 of 8.
TOY_ROPE = {
    'sliding_attention': {'rope_type': 'default', 'rope_theta': 1000},
    'full_attention': {
        'rope_type': 'yarn', 'rope_theta': 1000, 'factor': 4,
        'original_max_position_embeddings': 64, 'beta_fast': 32,
        'beta_slow': 1, 'attention_factor': 1.2772588722239782}}


def tiny_params(length=24, held=(8, 8), window=8, layer_types=PERIOD,
                **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_hidden_layers = len(layer_types)
    p.layer_types = list(layer_types)
    p.mlp_layer_types = ['sparse'] * len(layer_types)
    p.sliding_window = window
    p.num_heads, p.num_kv_heads, p.head_dim = 4, 2, 16
    p.num_experts, p.num_experts_per_tok = 16, TOP_K
    p.moe_intermediate_size = p.filter_size = 24
    p.experts_held_first, p.experts_held_count = held
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def reference(variables, rows, p, **faults):
  """(logits, assignments [layers, held]) of the plain reference."""
  with jax.default_matmul_precision('highest'):
    logits, counts = ref.logits(
        variables['params'], jnp.asarray(rows[..., 0]),
        max_passes=p.max_passes, layer_types=list(p.layer_types),
        rope_parameters=p.rope_parameters.to_dict(),
        window=p.sliding_window, eps=p.rms_norm_eps,
        top_k=p.num_experts_per_tok, renormalise=p.norm_topk_prob,
        first=p.experts_held_first, **faults)
  return np.asarray(logits), counts


# ------------------------------------------------ the program and the reference

@pytest.mark.parametrize('length,held,window,rope', [
    (24, (8, 8), 8, None), (24, (0, 16), 8, TOY_ROPE), (8, (8, 8), 16, None)],
                         ids=['L24_half_held', 'L24_all_held_toy_rope',
                              'L8_window_covers_it'])
def test_model_agrees_with_the_plain_reference_in_float32(length, held,
                                                          window, rope):
  p = tiny_params(length, held, window,
                  **({'rope_parameters': rope} if rope else {}))
  assert config_lib.layer_pattern(p) == 'WWWF'
  assert config_lib.ffn_pattern(p) == 'EEEE'
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=length)
  rows = pileup_rows(p, 3, seed=length)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))(variables, jnp.asarray(rows))
  want, want_counts = reference(variables, rows, p)
  assert got['logits'].shape == (3, length, 5)
  # float32 throughout, two orders of summation (grouped heads against
  # repeated keys, grouped products against a loop, the rope's magnitude in
  # the tables of each side): rounding of sums of a few hundred terms.
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  counts = np.asarray(model_lib.expert_assignments(sown['moe_counts']))
  assert counts.shape == (4, held[1])
  assert np.array_equal(counts, want_counts)
  if held[1] == 16:
    assert counts.sum() == 4 * 3 * length * TOP_K
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.fixture(scope='module')
def faults_baseline():
  """(params, variables, rows, the reference's logits) the faults are
  turned against: the toy rope, whose YaRN ramp turns within the window."""
  p = tiny_params(24, (0, 16), rope_parameters=TOY_ROPE)
  variables = seeded_variables(model_lib.get_model(p), p, seed=3)
  rows = pileup_rows(p, 2, seed=3)
  return p, variables, rows, reference(variables, rows, p)[0]


@pytest.mark.parametrize('fault', [
    'parallel', 'no_attention_factor', 'full_default_rope', 'not_renormalised',
    'no_interpolation', 'window_left_out'])
def test_reference_faults_are_seen_at_this_tolerance(fault, faults_baseline):
  """What the float32 tolerance above catches: a parallel block in place of
  the sequential one, YaRN's attention factor left out, the full layer
  rotated with the window layers' rope, the top-k weights not renormalised,
  YaRN's magnitude without its interpolation (under the toy rope, whose
  ramp turns within the window) and the window left out where it binds:
  each moves the reference's own logits by far more than 1e-4."""
  p, variables, rows, a = faults_baseline
  p = p.copy_and_resolve_references()  # the faults below turn sizes
  if fault == 'window_left_out':
    with p.unlocked():
      p.sliding_window = 24
    b, _ = reference(variables, rows, p)
  elif fault == 'not_renormalised':
    with p.unlocked():
      p.norm_topk_prob = False
    b, _ = reference(variables, rows, p)
  else:
    b, _ = reference(variables, rows, p, **{fault: True})
  assert np.abs(a - b).max() > 0.01


# --------------------------------------------------------------- the rotation

def test_yarn_tables_are_the_published_formula_at_the_published_sizes():
  """Head 128, base 500,000, factor 16 over 8,192 positions, beta 32 / 1:
  the correction range is [18, 35]; below it the frequencies are the
  default law's, above it a sixteenth of them, a linear ramp between; cos
  and sin carry the attention factor. The program's tables are the
  reference's, which compute YaRN on their own."""
  p = config_lib.get_config(PRESET)
  rope = p.rope_parameters.full_attention.to_dict()
  assert ref.yarn_range(128, 5e5, 8192, 32, 1) == (18, 35)
  inv, magnitude = model_lib.rope_frequencies(model_lib.Rope.of(rope), 128)
  base, unit = model_lib.rope_frequencies(5e5, 128)
  assert magnitude == 1.2772588722239782 and unit == 1.0
  ratio = inv / base
  assert np.array_equal(ratio[:19], np.ones(19))  # i <= 18 unchanged
  np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-12)  # i >= 35
  # The ramp between: i = 26 lies 8 / 17 of the way.
  assert ratio[26] == pytest.approx((1 - 8 / 17) + 8 / 17 / 16, rel=1e-12)
  cos, sin = model_lib.rotary_tables(100, 128, model_lib.Rope.of(rope))
  want_cos, want_sin = ref.rope_tables(rope, 100, 128)
  np.testing.assert_allclose(cos, want_cos, atol=1e-6)
  np.testing.assert_allclose(sin, want_sin, atol=1e-6)
  assert np.abs(cos[0]).max() == pytest.approx(1.2772588722239782)
  # Fault (e), held here exactly: YaRN's magnitude without its
  # interpolation moves the dims from 19 on alone, and little under 100
  # positions: the fastest of them turns 2 rad over 99 positions, and the
  # ramp's first steps move that by a few percent.
  _, plain_sin = ref.rope_tables(rope, 100, 128, interpolate=False)
  differs = np.abs(plain_sin - want_sin).max(axis=0)[:64] > 1e-7
  assert not differs[:19].any() and differs[19:].all()
  assert np.abs(plain_sin - want_sin).max() < 0.3


@pytest.mark.parametrize('theta', [1.0e6, 1.0e7, 5.0e4, 1000.0])
@pytest.mark.parametrize('head_dim', [64, 128])
def test_the_default_law_builds_the_tables_it_built_before(theta, head_dim):
  """The bases the other kinds rotate at (power retention 1e6, gated
  delta 1e7, parallel window 5e4): a bare base and its default Rope give
  to the bit what the one-law builder gave."""
  inv = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
  angles = np.arange(100, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)
  want = (np.cos(angles).astype(np.float32),
          np.sin(angles).astype(np.float32))
  for rope in (theta, model_lib.Rope(theta),
               model_lib.Rope.of({'rope_type': 'default',
                                  'rope_theta': theta})):
    got = model_lib.rotary_tables(100, head_dim, rope)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


def test_an_unserved_rope_type_is_refused_by_name():
  with pytest.raises(ValueError, match="rope_type 'longrope' is not served"):
    model_lib.Rope.of({'rope_type': 'longrope', 'rope_theta': 1e4})


# ---------------------------------------------------- listed, not derived

@pytest.mark.parametrize('layer_types,want', [
    (PERIOD * 7, 'WWWF' * 7), (PERIOD * 2, 'WWWFWWWF'),
    (['full_attention', 'sliding_attention'], 'FW')])
def test_the_layer_pattern_is_read_from_the_listed_types(layer_types, want):
  p = tiny_params(8, layer_types=layer_types)
  assert config_lib.layer_pattern(p) == want
  assert config_lib.ffn_pattern(p) == 'E' * len(want)
  # The published list repeats its period: three window layers to one full.
  published = config_lib.get_config(PRESET)
  assert list(published.layer_types) == PERIOD * 7
  assert config_lib.layer_pattern(published) == 'WWWF' * 7


@pytest.mark.parametrize('key,value,match', [
    ('layer_types', PERIOD * 2, 'layer_types lists 8 layers and '
     'num_hidden_layers is 4'),
    ('layer_types', PERIOD[:3] + ['chunked_attention'],
     r"layer_types \['chunked_attention'\] are not served"),
    ('mlp_layer_types', ['sparse', 'dense', 'sparse', 'sparse'],
     r"mlp_layer_types \['dense'\] are not served")])
def test_a_list_the_kind_does_not_run_is_refused_by_name(key, value, match):
  p = tiny_params(8)
  with p.unlocked():
    p[key] = value
  with pytest.raises(ValueError, match=match):
    jax.eval_shape(lambda k: model_lib.get_model(p).init(
        k, jnp.zeros((1, p.total_rows, 8, 1))), jax.random.PRNGKey(0))


# ------------------------------------------------------------------- the tree

def test_the_tree_holds_no_shared_expert_and_a_norm_a_sublayer():
  p = tiny_params(8)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, 8, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  for n in range(4):
    assert set(tree[f'moe_{n}']) == {'router', 'experts_gate', 'experts_up',
                                     'experts_down'}
    assert set(tree[f'self_attention_{n}']) == {
        'query', 'key', 'value', 'output_transform'}
    assert tree[f'self_attention_{n}']['query']['kernel'].shape == (64, 4, 16)
    for wrapper in ('attention_wrapper', 'ffn_wrapper'):
      assert jax.tree_util.tree_map(
          lambda x: x.shape, tree[f'{wrapper}_{n}']) == {
              'rms_norm': {'scale': (64,)}}
  assert set(tree['output_normalization']) == {'scale'}
  # And nothing is added where there is no shared expert: the module's
  # output is the routed experts' alone.
  moe = model_lib._sparse_experts(p, 0, jnp.float32)
  assert moe.shared_width == 0 and moe.shared_scale == 1.0
  x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 64)),
                  jnp.float32)
  variables = moe.init(jax.random.PRNGKey(1), x, deterministic=True)
  with_shared = moe.clone(shared_width=8, shared_gate=False)
  shared_leaves = with_shared.init(jax.random.PRNGKey(2), x,
                                   deterministic=True)['params']
  zeroed = dict(variables['params'], shared_expert=jax.tree_util.tree_map(
      jnp.zeros_like, shared_leaves['shared_expert']))
  np.testing.assert_array_equal(
      np.asarray(moe.apply(variables, x, deterministic=True)),
      np.asarray(with_shared.apply({'params': zeroed}, x,
                                   deterministic=True)))


def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.block_kind == KIND and config_lib.block_form(p) == 'sequential'
  assert (p.hidden_size, p.num_hidden_layers, p.rms_norm_eps) == (
      2304, 28, 1e-6)
  assert (p.num_heads, p.num_kv_heads, p.head_dim, p.sliding_window) == (
      32, 4, 128, 1024)
  assert p.rope_parameters.to_dict() == {
      'sliding_attention': {'rope_type': 'default', 'rope_theta': 500000},
      'full_attention': {
          'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
          'original_max_position_embeddings': 8192, 'beta_fast': 32,
          'beta_slow': 1, 'attention_factor': 1.2772588722239782}}
  assert (p.num_experts, p.num_experts_per_tok, p.moe_intermediate_size,
          p.num_shared_experts, p.shared_expert_intermediate_size,
          p.norm_topk_prob, p.router_scoring, p.router_selection_bias) == (
              64, 8, 896, 0, 0, True, 'softmax', False)
  assert (p.experts_held_first, p.experts_held_count) == (0, 64)
  assert (p.dtype, p.inference_dtype, p.rezero, p.add_pos_encoding) == (
      'bfloat16', 'bfloat16', False, False)
  assert list(p.mlp_layer_types) == ['sparse'] * 28


def test_two_periods_at_the_published_widths_have_the_hand_counted_parameters():
  """By shape alone: no array of the 6.68 GB is made."""
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 8
    p.layer_types = PERIOD * 2
    p.mlp_layer_types = ['sparse'] * 8
  config_lib.finalize_params(p, is_training=False)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, p.max_length, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda node: sum(
      leaf.size for leaf in jax.tree_util.tree_leaves(node))
  attention = tree['self_attention_3']
  assert attention['query']['kernel'].shape == (2304, 32, 128)
  assert attention['key']['kernel'].shape == (2304, 4, 128)
  assert attention['output_transform']['kernel'].shape == (32, 128, 2304)
  assert count(attention) == 2 * 9_437_184 + 2 * 1_179_648
  moe = tree['moe_0']
  assert moe['router']['kernel'].shape == (2304, 64)
  assert moe['experts_gate'].shape == (64, 2304, 896)
  assert moe['experts_down'].shape == (64, 896, 2304)
  outside = (count(attention) + count(tree['attention_wrapper_0'])
             + count(tree['ffn_wrapper_0']) + count(moe['router']))
  assert outside == 21_385_728
  assert count(moe) - count(moe['router']) == 396_361_728
  layer = outside + 396_361_728
  assert layer == 417_747_456
  assert count(tree) - 2304 == 8 * layer == 3_341_979_648


# ------------------------------------------------- through the normal path

def test_model_runner_serves_the_reference_and_says_each_layer_types_rope(
    tmp_path, capsys):
  from deepconsensus_tpu import cli

  p = tiny_params(24)
  variables = seeded_variables(model_lib.get_model(p), p, seed=1)
  runner, _ = _runner(p, variables)
  rows = pileup_rows(p, 8, seed=2)
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.clear_early()
  trace_lib.configure(path, tier='run')
  try:
    ids, quals = runner.predict(rows)
  finally:
    trace_lib.configure(None)
  want, want_counts = reference(variables, rows, p)
  # Where the reference's top two logits are not a rounding apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 1e-3
  assert clear.mean() > 0.95
  assert np.array_equal(np.asarray(ids)[clear], want.argmax(-1)[clear])
  assert np.asarray(quals).min() >= 0
  stats = runner.dispatch_stats()
  assert stats['block_kind'] == KIND
  # One pack of 8, four expert layers, 4 experts a position.
  assert stats['moe_assignments_total'] == 8 * 24 * 4 * TOP_K
  assert stats['moe_assignments_held'] == want_counts.sum()
  assert stats['moe_expert_load_max'] == want_counts.max()
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  (launch,) = [e['args'] for e in events if e['name'] == 'forward_launch']
  assert launch['block_kind'] == KIND and launch['block_form'] == 'sequential'
  assert launch['layer_pattern'] == 'WWWF' and launch['ffn_pattern'] == 'EEEE'
  assert launch['rope'] == {'W': 'default', 'F': 'yarn×16'}
  assert launch['attention_window'] == 8
  assert launch['experts_held'] == [8, 16]
  assert launch['router_scoring'] == 'softmax'
  assert launch['moe_turns'] == 1
  assert 'shared_experts' not in launch
  assert cli.main(['trace', path, '--json']) == 0
  forward = json.loads(capsys.readouterr().out)['forward']
  assert forward['ropes'] == ['W default, F yarn×16']
  assert forward['shared_experts'] == []
  assert forward['moe_turns'] == [1]
  assert cli.main(['trace', path]) == 0
  assert ('layers: WWWF (window: 8) (rope: W default, F yarn×16) '
          '(grouped-head attention: plain); experts '
          '8-15 of 16 held (router: softmax; grouped products: ragged_dot; '
          'combine: gather; turns a pack: 1); feed-forward: EEEE'
          in capsys.readouterr().out)


def test_the_rotation_runs_in_scope_rotary():
  p = tiny_params(8)
  model = model_lib.get_model(p)
  rows = jnp.zeros((1, p.total_rows, 8, 1))
  variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), rows)
  text = jax.jit(lambda v, r: model.apply(v, r)).lower(
      variables, rows).as_text(debug_info=True)
  assert '/rotary/' in text and '/softmax/' in text


# ------------------------------------------------- what the kind declines

def test_tp_and_int8_are_refused_by_name():
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  p = tiny_params(8)
  variables = seeded_variables(model_lib.get_model(p), p, seed=7)
  with pytest.raises(ValueError, match=rf"'{KIND}' is not served with --tp"):
    _runner(p, variables, mesh=mesh_lib.make_mesh(
        dp=2, tp=2, devices=jax.devices()[:4]))
  with pytest.raises(ValueError, match=rf"'{KIND}' is not served with "
                     r"quantize_matmuls='int8'"):
    _runner(tiny_params(8, quantize_matmuls='int8'), variables)


@pytest.mark.parametrize('command', ['train', 'distill', 'export'])
def test_training_and_export_of_the_kind_are_refused_by_name(command,
                                                             tmp_path):
  from deepconsensus_tpu.models import distill as distill_lib
  from deepconsensus_tpu.models import export as export_lib
  from deepconsensus_tpu.models import train as train_lib

  p = tiny_params(8)
  match = rf"'{KIND}' is not served by `dctpu {command}`"
  with pytest.raises(ValueError, match=match):
    if command == 'train':
      train_lib.Trainer(params=p, out_dir=str(tmp_path))
    elif command == 'distill':
      student = config_lib.get_config('transformer_learn_values_distill+test')
      config_lib.finalize_params(student, is_training=False)
      distill_lib.run_distillation(student, p, {}, str(tmp_path),
                                   train_patterns=['x'], eval_patterns=['x'])
    else:
      export_lib.export_model('unused', str(tmp_path), params=p,
                              variables={'params': {}})
