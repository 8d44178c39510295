"""The second encoder block kind (config.BLOCK_POWER_RETENTION): gated
power retention over grouped heads in its two-direction quadratic form,
SwiGLU feed-forward, RMSNorm, rotary positions.

Tiny sizes on the CPU (hidden 64, 4 query / 2 key-value heads of 8, filter
96, 2 layers, L 12 and 100). What is held here: the program's model
against a test-local plain reference (tests/power_retention_reference.py)
on seeded weights; the reference's quadratic form against its
token-by-token recurrence, in both directions and in the published causal
half; grouped heads against explicitly repeated key-value heads; the
normal path (get_model, ModelRunner, ConsensusEngine.submit -> deliver) on
the tiny preset; and that the published block is what it was before the
kind existed: the same parameter tree and the same outputs, bit for bit,
as the old module path builds them.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import ml_collections
import numpy as np
import pytest

from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.models import quantize as quantize_lib
from deepconsensus_tpu.ops import power_retention
from tests import power_retention_reference as ref

PRESET = 'transformer_learn_values_retention+custom'
LENGTHS = (12, 100)


def tiny_params(length=12, **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_heads = 4
    p.num_kv_heads = 2
    p.head_dim = 8
    p.filter_size = 96
    p.num_hidden_layers = 2
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def seeded_variables(model, p, seed=0):
  """model.init's tree with every leaf drawn anew: norm weights and gate
  bias away from their init of 1 and 0, so that each one counts."""
  rows = jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)
  tree = model.init(jax.random.PRNGKey(seed), rows)['params']
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  rng = np.random.default_rng(seed)
  leaves = []
  for path, leaf in flat:
    name = '/'.join(str(getattr(k, 'key', k)) for k in path)
    if name.endswith('scale'):
      value = rng.uniform(0.5, 1.5, leaf.shape)
    elif name.endswith('gate/bias'):
      value = rng.uniform(0.5, 2.5, leaf.shape)
    elif name.endswith('bias'):
      value = rng.normal(0, 0.02, leaf.shape)
    elif 'embedding' in name:
      value = np.asarray(leaf)
    else:
      fan_in = np.prod(leaf.shape[:-1]) if name.endswith(
          'output_transform/kernel') else leaf.shape[0]
      value = rng.normal(0, fan_in ** -0.5, leaf.shape)
    leaves.append(jnp.asarray(value, jnp.float32))
  return {'params': jax.tree_util.tree_unflatten(
      jax.tree_util.tree_structure(tree), leaves)}


def pileup_rows(p, n, seed=0):
  """[n, R, L, 1] float32 in the ranges the featurizer leaves."""
  rng = np.random.default_rng(seed)
  mp, length = p.max_passes, p.max_length
  rows = np.zeros((n, p.total_rows, length, 1), np.float32)
  rows[:, :mp] = rng.integers(0, 5, (n, mp, length, 1))
  rows[:, mp:3 * mp] = rng.integers(0, 256, (n, 2 * mp, length, 1))
  rows[:, 3 * mp:4 * mp] = rng.integers(0, 3, (n, mp, length, 1))
  rows[:, 4 * mp] = rng.integers(0, 5, (n, length, 1))
  rows[:, 4 * mp + 1:] = rng.integers(4, 21, (n, 4, 1, 1))
  return rows


def reference_logits(variables, rows, p):
  with jax.default_matmul_precision('highest'):
    return np.asarray(ref.logits(
        variables['params'], jnp.asarray(rows[..., 0]),
        max_passes=p.max_passes, num_layers=p.num_hidden_layers,
        rope_theta=p.rope_theta, eps=p.rms_norm_eps))


def operands(length, n_q=4, n_kv=2, d=8, batch=2, seed=0):
  rng = np.random.default_rng(seed)
  q = rng.normal(size=(batch, length, n_q, d))
  k = rng.normal(size=(batch, length, n_kv, d))
  v = rng.normal(size=(batch, length, n_kv, d))
  log_g = np.log(rng.uniform(0.5, 1.0, size=(batch, length, n_kv)))
  return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, log_g))


# ------------------------------------------------ the program and the reference

@pytest.mark.parametrize('length', LENGTHS)
def test_model_agrees_with_the_plain_reference_in_float32(length):
  p = tiny_params(length)
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=length)
  rows = pileup_rows(p, 3, seed=length)
  with jax.default_matmul_precision('highest'):
    got = model.apply(variables, jnp.asarray(rows),
                      method=model.apply_with_intermediates)
  want = reference_logits(variables, rows, p)
  assert got['logits'].shape == (3, length, 5)
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.mark.parametrize('length', LENGTHS)
@pytest.mark.parametrize('causal', [False, True],
                         ids=['two_directions', 'causal_as_published'])
def test_quadratic_form_is_the_token_by_token_recurrence(length, causal):
  q, k, v, log_g = operands(length, seed=3 + length)
  quadratic = np.asarray(ref.retention_quadratic(q, k, v, log_g, causal))
  recurrence = ref.retention_recurrence(q, k, v, log_g, causal)
  np.testing.assert_allclose(quadratic, recurrence, atol=1e-5)


def test_power_features_multiply_to_the_squared_dot_product():
  rng = np.random.default_rng(0)
  a, b = rng.normal(size=(2, 7, 8))
  assert ref.power_features(a).shape == (7, 36)
  np.testing.assert_allclose(
      np.sum(ref.power_features(a) * ref.power_features(b), axis=-1),
      np.sum(a * b, axis=-1) ** 2, rtol=1e-12)


def test_two_directions_are_both_runs_with_the_diagonal_once():
  """forward run + backward run - the j = i term, numerators and
  normalisers apart, one division: written out on the quadratic weights."""
  q, k, v, log_g = operands(12, seed=5)
  k_rep, v_rep = (np.repeat(np.asarray(a, np.float64), 2, axis=2)
                  for a in (k, v))
  cum = np.repeat(np.cumsum(np.asarray(log_g, np.float64), axis=1), 2, axis=2)
  s = np.einsum('bihd,bjhd->bhij', np.asarray(q, np.float64), k_rep) ** 2 / 8
  diff = cum.transpose(0, 2, 1)[:, :, :, None] - cum.transpose(
      0, 2, 1)[:, :, None, :]
  i = np.arange(12)
  lower = s * np.where(i[:, None] >= i[None, :], np.exp(diff), 0.0)
  upper = s * np.where(i[:, None] <= i[None, :], np.exp(-diff), 0.0)
  both = lower + upper - s * np.eye(12)
  want = np.einsum('bhij,bjhd->bihd', both, v_rep) / (
      both.sum(-1).transpose(0, 2, 1)[..., None] + ref.EPS)
  np.testing.assert_allclose(
      np.asarray(ref.retention_quadratic(q, k, v, log_g)), want, atol=1e-5)


# ------------------------------------------------------------- the operator

@pytest.mark.parametrize('length', LENGTHS)
def test_operator_agrees_with_the_reference_quadratic_form(length):
  q, k, v, log_g = operands(length, seed=length)
  with jax.default_matmul_precision('highest'):
    got = power_retention.power_retention_bidirectional(q, k, v, log_g)
  assert got.shape == q.shape and got.dtype == q.dtype
  np.testing.assert_allclose(
      np.asarray(got), np.asarray(ref.retention_quadratic(q, k, v, log_g)),
      atol=1e-5)


@pytest.mark.parametrize('n_q,n_kv', [(4, 2), (10, 2), (4, 4), (6, 1)])
def test_grouped_heads_are_explicitly_repeated_key_value_heads(n_q, n_kv):
  """Query head h reads key-value head h // (n_q // n_kv)."""
  q, k, v, log_g = operands(12, n_q=n_q, n_kv=n_kv, seed=n_q)
  group = n_q // n_kv
  with jax.default_matmul_precision('highest'):
    grouped = power_retention.power_retention_bidirectional(q, k, v, log_g)
    repeated = power_retention.power_retention_bidirectional(
        q, *(jnp.repeat(a, group, axis=2) for a in (k, v, log_g)))
  np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                             atol=1e-6)
  # And it is that head, not another: swapping the key-value heads moves
  # the answer where there is more than one.
  if n_kv > 1:
    swapped = power_retention.power_retention_bidirectional(
        q, k[:, :, ::-1], v[:, :, ::-1], log_g[:, :, ::-1])
    assert np.abs(np.asarray(swapped) - np.asarray(grouped)).max() > 1e-2


def test_operator_refuses_heads_that_do_not_group():
  q, k, v, log_g = operands(12, n_q=4, n_kv=3)
  with pytest.raises(ValueError, match='do not group'):
    power_retention.power_retention_bidirectional(q, k, v, log_g)


def test_operator_keeps_scores_in_float32_under_bfloat16_operands():
  q, k, v, log_g = operands(100, seed=9)
  want = np.asarray(ref.retention_quadratic(q, k, v, log_g))
  got = power_retention.power_retention_bidirectional(
      *(a.astype(jnp.bfloat16) for a in (q, k, v)), log_g)
  assert got.dtype == jnp.bfloat16
  # bfloat16 operands, float32 scores and normaliser: a few 1e-2 on
  # values of order 1, not the 1e-1 of a bfloat16 normaliser.
  assert np.abs(np.asarray(got, np.float32) - want).mean() < 2e-2


def test_rotary_scores_depend_on_the_distance_alone():
  rng = np.random.default_rng(1)
  q = jnp.asarray(np.tile(rng.normal(size=(1, 1, 1, 8)), (1, 12, 1, 1)),
                  jnp.float32)
  k = jnp.asarray(np.tile(rng.normal(size=(1, 1, 1, 8)), (1, 12, 1, 1)),
                  jnp.float32)
  rq, rk = (np.asarray(model_lib.apply_rotary(a, 1.0e6))[0, :, 0]
            for a in (q, k))
  scores = rq @ rk.T
  for offset in (0, 1, 5):
    diagonal = np.diagonal(scores, offset)
    np.testing.assert_allclose(diagonal, diagonal[0], atol=1e-5)
  np.testing.assert_allclose(
      rq, np.asarray(ref.rotary(q, 1.0e6))[0, :, 0], atol=1e-6)
  assert abs(scores[0, 5] - scores[0, 0]) > 1e-3


def test_rms_norm_is_reckoned_in_float32_and_returned_in_its_dtype():
  x = jnp.asarray(np.random.default_rng(2).normal(size=(3, 5, 64)) * 7,
                  jnp.bfloat16)
  norm = model_lib.RMSNorm(1e-6, dtype=jnp.bfloat16)
  variables = {'params': {'scale': jnp.full((64,), 0.75, jnp.float32)}}
  got = norm.apply(variables, x)
  x32 = np.asarray(x, np.float32)
  want = x32 / np.sqrt((x32 ** 2).mean(-1, keepdims=True) + 1e-6) * 0.75
  assert got.dtype == jnp.bfloat16
  np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2)


# ---------------------------------------------------- the preset and the tree

def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.model_name == 'transformer_learn_values_retention'
  assert p.block_kind == config_lib.BLOCK_POWER_RETENTION
  assert (p.hidden_size, p.num_hidden_layers, p.num_heads, p.num_kv_heads,
          p.head_dim, p.filter_size) == (5120, 40, 40, 8, 128, 17408)
  assert (p.rope_theta, p.rms_norm_eps, p.retention_degree) == (1e6, 1e-6, 2)
  assert not p.add_pos_encoding and not p.rezero
  assert not p.use_fused_hotpath
  assert p.dtype == p.inference_dtype == 'bfloat16'
  assert p.total_rows == 85 and p.max_length == 100


def test_a_layer_at_the_published_widths_has_330_million_parameters():
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 1
  config_lib.finalize_params(p, is_training=False)
  model = model_lib.get_model(p)
  tree = jax.eval_shape(
      lambda key: model.init(key, jnp.zeros((1, 85, 100, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda t: sum(int(np.prod(x.shape))
                        for x in jax.tree_util.tree_leaves(t))
  layer = count(tree) - count(tree['output_normalization'])
  hand = (5120 * 5120 * 2 + 2 * 5120 * 1024 + 5120 * 8 + 8
          + 3 * 5120 * 17408 + 2 * 5120 + 2 * 128)
  assert layer == hand == 330_352_904
  att = tree['self_attention_0']
  assert att['query']['kernel'].shape == (5120, 40, 128)
  assert att['key']['kernel'].shape == att['value']['kernel'].shape == (
      5120, 8, 128)
  assert att['gate']['kernel'].shape == (5120, 8)
  assert att['output_transform']['kernel'].shape == (40, 128, 5120)
  assert tree['ffn_0']['gate_layer']['kernel'].shape == (5120, 17408)


@pytest.mark.parametrize('preset', ['transformer_learn_values+custom',
                                    'transformer_learn_values_distill+custom',
                                    'transformer+custom'])
def test_presets_that_existed_name_the_block_there_was(preset):
  p = config_lib.get_config(preset)
  assert p.block_kind == config_lib.BLOCK_BANDED_SOFTMAX
  assert p.rezero and p.num_heads == 2 and p.attn_win_size == 12
  for key in ('num_kv_heads', 'head_dim', 'rope_theta', 'rms_norm_eps'):
    assert key not in p


def test_a_params_json_from_before_the_key_means_the_old_block():
  p = config_lib.get_config('transformer_learn_values+custom')
  with p.unlocked():
    del p['block_kind']
  assert config_lib.block_kind_of(p) == config_lib.BLOCK_BANDED_SOFTMAX


@pytest.mark.parametrize('key,value,match', [
    ('block_kind', 'no_such_block', 'unknown block_kind'),
    ('retention_degree', 3, 'retention_degree 3 is not served')])
def test_a_kind_or_degree_that_is_not_served_is_refused(key, value, match):
  p = tiny_params(**{key: value})
  model = model_lib.get_model(p)
  with pytest.raises(ValueError, match=match):
    model.init(jax.random.PRNGKey(0), jnp.zeros((1, p.total_rows, 12, 1)))


# ---------------------------------- the published block is what it was before

class _ParentEncoderStack(nn.Module):
  """EncoderStack's XLA path as it stood before block kinds (PR 27)."""

  params: ml_collections.FrozenConfigDict
  dtype: object = jnp.float32

  @nn.compact
  def __call__(self, x, deterministic):
    p = self.params
    for n in range(p.num_hidden_layers):
      attn = model_lib.BandedSelfAttention(
          hidden_size=p.hidden_size, num_heads=p.num_heads,
          dropout_rate=p.attention_dropout, attn_win_size=p.attn_win_size,
          dtype=self.dtype, use_pallas=p.get('use_pallas_attention', False),
          softmax_dtype=jnp.dtype(
              p.get('attn_softmax_dtype', None) or 'float32'),
          name=f'self_attention_{n}')
      x = model_lib.ResidualWrapper(
          attn, rezero=p.rezero, dropout_rate=p.layer_postprocess_dropout,
          name=f'attention_wrapper_{n}')(x, deterministic=deterministic)
      ffn = model_lib.FeedForward(
          hidden_size=p.hidden_size, filter_size=p.filter_size,
          dropout_rate=p.relu_dropout, dtype=self.dtype, name=f'ffn_{n}')
      x = model_lib.ResidualWrapper(
          ffn, rezero=p.rezero, dropout_rate=p.layer_postprocess_dropout,
          name=f'ffn_wrapper_{n}')(x, deterministic=deterministic)
    return nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                        name='output_normalization')(x)


@pytest.mark.parametrize('preset,rezero,dtype', [
    ('transformer_learn_values+custom', True, 'bfloat16'),
    ('transformer_learn_values+custom', True, 'float32'),
    ('transformer_learn_values_distill+custom', True, 'bfloat16'),
    ('transformer_learn_values+custom', False, 'float32')])
def test_old_presets_build_the_same_tree_and_outputs_bit_for_bit(
    preset, rezero, dtype):
  p = config_lib.get_config(preset)
  with p.unlocked():
    p.rezero = rezero
    p.dtype = dtype
  config_lib.finalize_params(p, is_training=False)
  frozen = ml_collections.FrozenConfigDict(p)
  new = model_lib.EncoderStack(frozen, dtype=jnp.dtype(dtype))
  old = _ParentEncoderStack(frozen, dtype=jnp.dtype(dtype))
  x = jax.random.normal(jax.random.PRNGKey(1), (4, 100, p.hidden_size),
                        jnp.dtype(dtype))
  v_new = new.init(jax.random.PRNGKey(7), x, deterministic=True)
  v_old = old.init(jax.random.PRNGKey(7), x, deterministic=True)
  flat_new, tree_new = jax.tree_util.tree_flatten(v_new['params'])
  flat_old, tree_old = jax.tree_util.tree_flatten(v_old['params'])
  assert tree_new == tree_old
  assert len(flat_new) == p.num_hidden_layers * (10 if rezero else 12) + 2
  for a, b in zip(flat_new, flat_old):
    assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
  # ReZero's alphas start at 0, where every block is a no-op.
  lively = jax.tree_util.tree_map(
      lambda a: a + 0.5 if a.ndim == 0 else a, v_new)
  out_new = jax.jit(lambda v: new.apply(v, x, deterministic=True))(lively)
  out_old = jax.jit(lambda v: old.apply(v, x, deterministic=True))(lively)
  assert np.array_equal(np.asarray(out_new), np.asarray(out_old))
  assert np.asarray(out_new).std() > 0.1


# ------------------------------------------------------------ the normal path

@pytest.mark.parametrize('flag', ['fused', 'ragged'])
def test_fused_and_ragged_hot_paths_decline_the_kind(flag):
  """From the kind itself: with the flag on and every other condition the
  banded block would meet."""
  rows = jnp.zeros((2, 25, 12))
  for kind, want in ((config_lib.BLOCK_POWER_RETENTION, False),
                     (config_lib.BLOCK_BANDED_SOFTMAX, True)):
    p = tiny_params(use_fused_hotpath=True, rezero=True, block_kind=kind,
                    attention_dropout=0.0)
    model = model_lib.get_model(p)

    def eligible(m):
      # Inside apply, so is_initializing() is False as at serving time.
      if flag == 'fused':
        return m._fused_hotpath_eligible(rows, False)
      return m._ragged_hotpath_eligible(rows)

    got = nn.apply(eligible, model)({'params': {}})
    assert got is want, (kind, flag)


def _runner(p, variables, batch_size=8):
  options = runner_lib.InferenceOptions(batch_size=batch_size)
  options.max_passes = p.max_passes
  options.max_length = p.max_length
  options.use_ccs_bq = p.use_ccs_bq
  return runner_lib.ModelRunner(p, variables, options), options


@pytest.mark.parametrize('length', LENGTHS)
def test_engine_submit_to_delivery_serves_the_reference_bases(length):
  p = tiny_params(length)
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=1)
  runner, options = _runner(p, variables)
  delivered = {}
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(
          t, (ids.copy(), quals.copy())))
  rows = pileup_rows(p, 19, seed=2)
  engine.submit(list(rows), list(range(len(rows))))
  engine.flush()
  assert sorted(delivered) == list(range(19))
  want = reference_logits(variables, rows, p)
  ids = np.stack([delivered[t][0] for t in range(19)])
  quals = np.stack([delivered[t][1] for t in range(19)])
  # Where the reference's top two logits are not a rounding apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 1e-3
  assert clear.mean() > 0.95
  assert np.array_equal(ids[clear], want.argmax(-1)[clear])
  assert quals.min() >= 0 and len(np.unique(quals)) > 3
  stats = engine.stats()
  assert stats['block_kind'] == config_lib.BLOCK_POWER_RETENTION
  assert stats['n_forward_positions'] == 3 * 8 * length
  assert stats['n_forward_shapes'] == 1


def test_predict_windows_path_runs_the_kind_in_bfloat16():
  """The preset as shipped (bfloat16 weights and stream) at the tiny
  widths: ModelRunner.predict stays near the float32 reference."""
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
  want = reference_logits(rounded, rows, p)
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 0.25
  assert clear.mean() > 0.5
  assert (np.asarray(ids)[clear] == want.argmax(-1)[clear]).mean() > 0.97


def test_device_resident_bfloat16_leaves_stay_the_one_copy():
  """No host round trip, upcast or second copy on the way into the
  runner: the leaves it serves are the buffers it was handed."""
  p = tiny_params(dtype='bfloat16', inference_dtype='bfloat16')
  model = model_lib.get_model(p)
  tree = jax.tree_util.tree_map(
      lambda a: jax.device_put(a.astype(jnp.bfloat16)),
      seeded_variables(model, p)['params'])
  prepared, n_quantized = quantize_lib.prepare_inference_variables(
      {'params': tree}, p)
  assert n_quantized == 0
  for a, b in zip(jax.tree_util.tree_leaves(prepared['params']),
                  jax.tree_util.tree_leaves(tree)):
    assert a is b
  runner, _ = _runner(p, {'params': tree})
  handed = jax.tree_util.tree_leaves(tree)
  served = jax.tree_util.tree_leaves(runner.variables['params'])
  assert len(served) == len(handed) > 20
  for a, b in zip(served, handed):
    assert a.dtype == jnp.bfloat16
    assert a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
  assert runner.dispatch_stats()['model_weight_bytes'] == sum(
      2 * leaf.size for leaf in handed)


def test_cast_params_still_casts_what_is_not_there_yet():
  tree = {'a': np.ones((3, 2), np.float32), 'n': np.arange(3, dtype=np.int8),
          'b': jnp.ones((2,), jnp.bfloat16)}
  out = quantize_lib.cast_params({'params': tree, 'quant': {'s': 1.0}},
                                 'bfloat16')
  assert out['params']['a'].dtype == jnp.bfloat16
  assert out['params']['n'].dtype == np.int8
  assert out['params']['b'] is tree['b']
  assert out['quant'] == {'s': 1.0}
