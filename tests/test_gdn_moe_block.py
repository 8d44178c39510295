"""The third encoder block kind (config.BLOCK_GATED_DELTA_MOE): a stack
whose layers are not alike (three Gated DeltaNet mixers to one gated
softmax attention), every layer followed by sparse experts of which this
process holds a share, zero-centred RMSNorm.

Toy sizes on the CPU (hidden 64; DeltaNet 2 key / 4 value heads of 8,
convolution of 4; attention 4 / 2 heads of 16, rotary on 4 of them; 16
experts of width 24, 4 a token, 8 held; one period of the pattern of 4;
L 12 and 100). What is held here: the program's model, through get_model
and through ModelRunner, against a test-local plain reference
(tests/gdn_moe_reference.py: the token-by-token recurrence, the experts as
a plain loop) on seeded weights; the per-layer pattern in the parameter
tree. What the normal path says of the kind (spans, counters), counts of
it and refuses by name: tests/test_gdn_moe_runner.py, on these sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from tests import gdn_moe_reference as ref
from tests.test_power_retention import pileup_rows

PRESET = 'transformer_learn_values_gdn_moe+custom'
KIND = config_lib.BLOCK_GATED_DELTA_MOE
LENGTHS = (12, 100)
TOP_K, HELD = 4, (8, 8)  # experts 8 ... 15 of 16


def tiny_params(length=12, **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_hidden_layers = 4
    p.linear_num_key_heads, p.linear_num_value_heads = 2, 4
    p.linear_key_head_dim = p.linear_value_head_dim = 8
    p.num_heads, p.num_kv_heads, p.head_dim = 4, 2, 16
    p.num_experts, p.num_experts_per_tok = 16, TOP_K
    p.moe_intermediate_size = p.filter_size = 24
    p.shared_expert_intermediate_size = 24
    p.experts_held_first, p.experts_held_count = HELD
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def seeded_variables(model, p, seed=0):
  """model.init's tree with every leaf drawn anew, away from its init, so
  that each one counts: norm weights, A_log, dt_bias, the convolution."""
  rows = jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)
  tree = jax.jit(model.init)(jax.random.PRNGKey(seed), rows)['params']
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  rng = np.random.default_rng(seed)
  leaves = []
  for path, leaf in flat:
    name = '/'.join(str(getattr(k, 'key', k)) for k in path)
    if name.endswith('norm_scale'):
      value = rng.uniform(0.5, 1.5, leaf.shape)
    elif name.endswith('scale'):  # zero-centred: multiplies as 1 + w
      value = rng.uniform(-0.5, 0.5, leaf.shape)
    elif name.endswith('A_log'):
      value = rng.uniform(np.log(0.1), np.log(0.4), leaf.shape)
    elif name.endswith('dt_bias'):
      value = rng.uniform(-1.0, 0.5, leaf.shape)
    elif name.endswith('bias'):
      value = rng.normal(0, 0.02, leaf.shape)
    elif 'embedding' in name:
      value = np.asarray(leaf)
    else:
      fan_in = (np.prod(leaf.shape[:-1])
                if name.endswith('output_transform/kernel')
                else leaf.shape[-2])
      value = rng.normal(0, fan_in ** -0.5, leaf.shape)
      if name.endswith('router/kernel'):
        value = value * 3.0  # clear winners: few near-ties to round apart
    leaves.append(jnp.asarray(value, jnp.float32))
  return {'params': jax.tree_util.tree_unflatten(
      jax.tree_util.tree_structure(tree), leaves)}


def reference(variables, rows, p):
  """(logits, assignments [layers, held]) of the plain reference."""
  with jax.default_matmul_precision('highest'):
    logits, counts = ref.logits(
        variables['params'], jnp.asarray(rows[..., 0]),
        max_passes=p.max_passes, pattern=config_lib.layer_pattern(p),
        hk=p.linear_num_key_heads, hv=p.linear_num_value_heads,
        dk=p.linear_key_head_dim, dv=p.linear_value_head_dim,
        rotary_dim=int(p.head_dim * p.partial_rotary_factor),
        theta=p.rope_theta, eps=p.rms_norm_eps, top_k=p.num_experts_per_tok,
        renormalise=p.norm_topk_prob, first=p.experts_held_first)
  return np.asarray(logits), counts


def _runner(p, variables, batch_size=8, mesh=None):
  options = runner_lib.InferenceOptions(batch_size=batch_size)
  options.max_passes = p.max_passes
  options.max_length = p.max_length
  options.use_ccs_bq = p.use_ccs_bq
  return runner_lib.ModelRunner(p, variables, options, mesh=mesh), options


# ------------------------------------------------ the program and the reference

@pytest.mark.parametrize('length', LENGTHS)
def test_model_agrees_with_the_plain_reference_in_float32(length):
  p = tiny_params(length)
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=length)
  rows = pileup_rows(p, 3, seed=length)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))(variables, jnp.asarray(rows))
  want, want_counts = reference(variables, rows, p)
  assert got['logits'].shape == (3, length, 5)
  # float32 throughout, two orders of summation (chunked against token by
  # token, grouped against looped): rounding of sums of a few hundred terms.
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  assert np.array_equal(
      np.asarray(model_lib.expert_assignments(sown['moe_counts'])),
      want_counts)
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.mark.parametrize('fault', ['one_direction', 'no_correction',
                                   'not_renormalised'])
def test_reference_faults_are_seen_at_this_tolerance(fault):
  """What the float32 tolerance above would catch: each of these moves the
  reference's own logits by far more than 1e-4."""
  p = tiny_params(12)
  model = model_lib.get_model(p)
  params = seeded_variables(model, p, seed=3)['params']
  rows = jnp.asarray(pileup_rows(p, 2, seed=3)[..., 0])
  sizes = dict(hk=2, hv=4, dk=8, dv=8, eps=p.rms_norm_eps)
  u = jnp.asarray(np.random.default_rng(0).normal(size=(2, 12, 64)),
                  jnp.float32)
  with jax.default_matmul_precision('highest'):
    if fault == 'not_renormalised':
      kwargs = dict(max_passes=5, pattern='GGGS', rotary_dim=4,
                    theta=p.rope_theta, top_k=TOP_K, first=8, **sizes)
      a = ref.logits(params, rows, **kwargs)[0]
      b = ref.logits(params, rows, renormalise=False, **kwargs)[0]
    else:
      faulty = (dict(directions=(1,)) if fault == 'one_direction'
                else dict(correct=False))
      a = ref.gdn_mixer(params['encoder']['gdn_0'], u, **sizes)
      b = ref.gdn_mixer(params['encoder']['gdn_0'], u, **sizes, **faulty)
  assert np.abs(np.asarray(a - b)).max() > 0.01


def test_predict_path_runs_the_kind_in_bfloat16():
  """The preset as shipped (bfloat16 leaves and stream) at the toy widths:
  ModelRunner.predict stays near the float32 reference of the rounded
  weights."""
  p = tiny_params(dtype='bfloat16', inference_dtype='bfloat16')
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=4)
  runner, _ = _runner(p, variables)
  assert all(leaf.dtype == jnp.bfloat16 for leaf in
             jax.tree_util.tree_leaves(runner.variables['params']))
  rows = pileup_rows(p, 8, seed=5)
  ids, _quals = runner.predict(rows)
  rounded = jax.tree_util.tree_map(
      lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), variables)
  want, _ = reference(rounded, rows, p)
  # bfloat16 keeps 8 bits of every product's operands through four layers
  # and may route a near-tie to another expert: the served base is held to
  # the reference's where its top two logits stand a quarter apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 0.25
  assert clear.mean() > 0.5
  assert (np.asarray(ids)[clear] == want.argmax(-1)[clear]).mean() > 0.97


# ------------------------------------------------------- the per-layer pattern

def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.block_kind == KIND
  assert (p.hidden_size, p.num_hidden_layers, p.full_attention_interval) == (
      2048, 48, 4)
  assert (p.linear_num_key_heads, p.linear_num_value_heads,
          p.linear_key_head_dim, p.linear_value_head_dim,
          p.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
  assert (p.num_heads, p.num_kv_heads, p.head_dim, p.partial_rotary_factor,
          p.rope_theta, p.rms_norm_eps) == (16, 2, 256, 0.25, 1e7, 1e-6)
  assert (p.num_experts, p.num_experts_per_tok, p.moe_intermediate_size,
          p.shared_expert_intermediate_size, p.norm_topk_prob) == (
              512, 10, 512, 512, True)
  assert (p.router_scoring, p.router_selection_bias,
          p.routed_scaling_factor, p.shared_expert_gated) == (
              'softmax', False, 1.0, True)
  # As published a process holds every expert; a chip's share is a size.
  assert (p.experts_held_first, p.experts_held_count) == (0, 512)
  assert (p.dtype, p.inference_dtype, p.rezero, p.add_pos_encoding) == (
      'bfloat16', 'bfloat16', False, False)
  pattern = config_lib.layer_pattern(p)
  assert pattern == 'GGGS' * 12 and len(pattern) == 48


@pytest.mark.parametrize('layers,interval,want', [
    (4, 4, 'GGGS'), (8, 4, 'GGGSGGGS'), (6, 4, 'GGGSGG'), (4, 2, 'GSGS'),
    (3, 1, 'SSS')])
def test_layer_n_is_softmax_where_n_plus_1_divides_by_the_interval(
    layers, interval, want):
  p = tiny_params(num_hidden_layers=layers, full_attention_interval=interval)
  assert config_lib.layer_pattern(p) == want
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, 12, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  for n, letter in enumerate(want):
    softmax = letter == config_lib.LAYER_GATED_SOFTMAX
    assert (f'gated_attention_{n}' in tree) is softmax
    assert (f'gdn_{n}' in tree) is not softmax
    assert f'moe_{n}' in tree and f'attention_wrapper_{n}' in tree
  assert set(tree['gated_attention_%d' % want.index('S')]) == {
      'query', 'key', 'value', 'query_norm', 'key_norm', 'output_transform'}
  if 'G' in want:
    assert set(tree['gdn_%d' % want.index('G')]) == {
        'in_proj_qkvz', 'in_proj_ba', 'conv_kernel', 'A_log', 'dt_bias',
        'norm_scale', 'out_proj'}
  assert set(tree['moe_0']) == {
      'router', 'experts_gate', 'experts_up', 'experts_down',
      'shared_expert', 'shared_expert_gate'}


@pytest.mark.parametrize('preset,letter', [
    ('transformer_learn_values+test', 'B'),
    ('transformer_learn_values_retention+custom', 'R')])
def test_kinds_whose_layers_are_alike_repeat_one_letter(preset, letter):
  p = config_lib.get_config(preset)
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.layer_pattern(p) == letter * p.num_hidden_layers


def test_a_period_at_the_published_widths_has_the_hand_counted_parameters():
  """By shape alone: no array of the 6.7 GB is made."""
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 4
    p.experts_held_count = 256
  config_lib.finalize_params(p, is_training=False)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, p.max_length, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda *names: sum(
      leaf.size for name in names
      for leaf in jax.tree_util.tree_leaves(tree[name]))
  assert count('gdn_0', 'attention_wrapper_0', 'ffn_wrapper_0') == 33_722_560
  assert count('gated_attention_3', 'attention_wrapper_3',
               'ffn_wrapper_3') == 27_267_584
  moe = tree['moe_0']
  assert moe['experts_gate'].shape == (256, 2048, 512)
  assert moe['experts_down'].shape == (256, 512, 2048)
  assert moe['router']['kernel'].shape == (2048, 512)  # full width
  experts = sum(moe[name].size for name in (
      'experts_gate', 'experts_up', 'experts_down'))
  assert experts == 256 * 3_145_728 == 805_306_368
  assert count('moe_0') - experts == 4_196_352
  block = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree)) - 2048
  assert block == 3_366_446_144


@pytest.mark.parametrize('length,group', [(20, 2), (100, 1)])
def test_mixer_through_the_window_kernel_is_the_mixer_through_the_modules(
    length, group, monkeypatch):
  """GatedDeltaNetMixer at heads of 128, float32: where the code takes the
  window kernel (one TPU device at inference; here the interpreter stands
  in for the chip) the flat stream goes in and comes out with the two
  norms inside the call, and the mixer's output is what the modules around
  the plain form give, and what the reference's mixer gives."""
  from deepconsensus_tpu.ops import gated_delta, pallas_util
  hk, hv, d = 1, group, 128
  mixer = model_lib.GatedDeltaNetMixer(
      hidden_size=64, num_key_heads=hk, num_value_heads=hv, key_head_dim=d,
      value_head_dim=d, conv_kernel=4, rms_norm_eps=1e-6)
  rng = np.random.default_rng(length)
  x = jnp.asarray(rng.normal(size=(2, length, 64)), jnp.float32)
  variables = jax.jit(lambda k: mixer.init(k, x, True))(jax.random.PRNGKey(0))
  params = dict(variables['params'])
  params['A_log'] = jnp.asarray(rng.uniform(np.log(0.1), np.log(0.4), hv),
                                jnp.float32)
  params['norm_scale'] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
  apply = lambda: jax.jit(lambda v: mixer.apply(v, x, True))(
      {'params': params})
  with jax.default_matmul_precision('highest'):
    modules = apply()
    want = ref.gdn_mixer(params, x, hk=hk, hv=hv, dk=d, dv=d, eps=1e-6)
    taken = []
    kernel_call = gated_delta._window_kernel_call
    monkeypatch.setattr(
        gated_delta, '_window_kernel_call',
        lambda *args, **sizes: taken.append(args[0][0].shape) or kernel_call(
            *args, **sizes))
    monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
    monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: True)
    with pallas_util.single_device_inference():
      kernel = apply()
  assert taken == [(2, length, (2 * hk + hv) * d)]
  np.testing.assert_allclose(np.asarray(kernel), np.asarray(modules),
                             atol=2e-5)
  np.testing.assert_allclose(np.asarray(kernel), np.asarray(want), atol=1e-4)
  assert np.abs(np.asarray(want)).max() > 0.05
