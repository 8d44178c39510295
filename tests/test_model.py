"""Model construction, shapes, and forward-pass invariants
(modeled on reference networks_test.py coverage)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import model as model_lib


def make_params(name='transformer_learn_values+test', **overrides):
  params = config_lib.get_config(name)
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'  # deterministic numerics on CPU tests
    for k, v in overrides.items():
      params[k] = v
  return params


def fake_rows(params, batch=2, seed=0):
  rng = np.random.default_rng(seed)
  rows = np.zeros(
      (batch, params.total_rows, params.max_length, 1), dtype=np.float32
  )
  mp = params.max_passes
  rows[:, :mp] = rng.integers(0, 5, size=rows[:, :mp].shape)
  rows[:, mp : 2 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 2 * mp : 3 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 3 * mp : 4 * mp] = rng.integers(0, 3, size=rows[:, :mp].shape)
  rows[:, 4 * mp] = rng.integers(0, 5, size=rows[:, 4 * mp].shape)
  rows[:, 4 * mp + 1 :] = rng.integers(0, 501, size=rows[:, 4 * mp + 1 :].shape)
  return jnp.asarray(rows)


def test_hidden_size_derivation():
  params = make_params()
  # 20 passes * (8+8+8+2) + ccs 8 + sn 4*8 = 560, condensed to 280.
  assert params.total_rows == 85
  assert params.hidden_size == 280
  assert params.transformer_input_size == 280


def test_forward_shapes_and_softmax():
  params = make_params()
  model = model_lib.get_model(params)
  rows = fake_rows(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  preds = model.apply(variables, rows)
  assert preds.shape == (2, params.max_length, 5)
  np.testing.assert_allclose(
      np.asarray(preds.sum(-1)), np.ones((2, params.max_length)), atol=1e-5
  )


def _take_embed(table, ids, dtype):
  """MaskedEmbed as a gather: the form the contraction replaces."""
  emb = jnp.take(table.astype(dtype), ids, axis=0, mode='clip')
  emb = emb * jnp.asarray(table.shape[1] ** 0.5, dtype)
  return emb * (ids != 0).astype(dtype)[..., None]


def _edge_ids(vocab, shape=(3, 7, 11), seed=0):
  """Ids over the whole vocabulary, with 0, the maximum and values beyond
  the vocabulary (which clip to its last row) certain to occur."""
  ids = np.random.default_rng(seed).integers(0, vocab + 40, size=shape)
  ids.flat[:4] = [0, vocab - 1, vocab, vocab + 1000]
  return jnp.asarray(ids, jnp.int32)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('vocab', [3, 5, 256, 501])
def test_masked_embed_matches_take(vocab, dtype):
  """The one-hot contraction selects single table values: bit-equal to
  take * sqrt * mask at every vocabulary the model has."""
  dtype = jnp.dtype(dtype)
  emb = model_lib.MaskedEmbed(vocab_size=vocab, features=8, dtype=dtype)
  ids = _edge_ids(vocab, seed=vocab)
  variables = emb.init(jax.random.PRNGKey(vocab), ids)
  got = emb.apply(variables, ids)
  want = _take_embed(variables['params']['embedding'], ids, dtype)
  assert got.dtype == dtype and got.shape == ids.shape + (8,)
  np.testing.assert_array_equal(
      np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_masked_embed_table_gradient_matches_take():
  """Training: the table's gradient through the contraction (a product
  with the one-hot) is the gather form's scatter-add, row 0 included."""
  emb = model_lib.MaskedEmbed(vocab_size=256, features=8)
  ids = _edge_ids(256, shape=(4, 20, 50), seed=1)
  table = emb.init(jax.random.PRNGKey(1), ids)['params']['embedding']
  weights = jax.random.normal(jax.random.PRNGKey(2), ids.shape + (8,))
  got = jax.grad(lambda t: jnp.sum(
      emb.apply({'params': {'embedding': t}}, ids) * weights))(table)
  want = jax.grad(lambda t: jnp.sum(
      _take_embed(t, ids, jnp.float32) * weights))(table)
  assert not np.asarray(got[0]).any()
  np.testing.assert_allclose(
      np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_embed_rows_concat_order_is_the_per_row_takes():
  """_embed_rows' [B, L, 560] is, bit for bit, the per-row lookups in the
  order the condenser's weight and every checkpoint were trained on:
  bases, pw, ip, strand rows (pass by pass), then ccs, then the SN rows."""
  params = make_params()
  rows = fake_rows(params, batch=3, seed=5)
  model = model_lib.get_model(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  got = model.apply(
      variables, rows[..., 0], method=lambda m, r: m._embed_rows(r))
  tables = {k[:-len('_embedding')]: v['embedding']
            for k, v in variables['params'].items() if 'embedding' in k}
  mp = params.max_passes
  families = (['bases'] * mp + ['pw'] * mp + ['ip'] * mp + ['strand'] * mp
              + ['bases'] + ['sn'] * 4)
  assert len(families) == params.total_rows
  want = jnp.concatenate([
      _take_embed(tables[family], rows[:, i, :, 0].astype(jnp.int32),
                  jnp.float32)
      for i, family in enumerate(families)], axis=-1)
  assert got.shape == (3, params.max_length, 560)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_attn_softmax_dtype_lever():
  """bf16 softmax accumulation runs and stays close to the f32 path
  (banded logits are bounded); argmax calls must agree everywhere on
  this scale of input."""
  params = make_params()
  rows = fake_rows(params, batch=2, seed=3)
  model = model_lib.get_model(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  base = np.asarray(model.apply(variables, rows))
  params_bf = make_params(attn_softmax_dtype='bfloat16')
  got = np.asarray(model_lib.get_model(params_bf).apply(variables, rows))
  np.testing.assert_allclose(got, base, atol=0.02)
  assert (got.argmax(-1) == base.argmax(-1)).mean() > 0.999


def test_intermediates_exposed():
  params = make_params()
  model = model_lib.get_model(params)
  rows = fake_rows(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  out = model.apply(
      variables, rows, method=model.apply_with_intermediates
  )
  assert out['logits'].shape == (2, params.max_length, 5)
  assert out['final_output'].shape == (2, params.max_length, 280)


@pytest.mark.parametrize('win', [0, 6, 12, None])
def test_attention_window_sweep(win):
  params = make_params()
  with params.unlocked():
    params.attn_win_size = win
  model = model_lib.get_model(params)
  rows = fake_rows(params, batch=1)
  variables = model.init(jax.random.PRNGKey(0), rows)
  preds = model.apply(variables, rows)
  assert np.isfinite(np.asarray(preds)).all()


def test_rezero_starts_as_identity_plus_embedding():
  """With ReZero alphas at 0, the encoder stack is the identity, so two
  different inits differ only through embeddings/condenser/logits."""
  params = make_params()
  model = model_lib.get_model(params)
  rows = fake_rows(params, batch=1)
  variables = model.init(jax.random.PRNGKey(0), rows)
  alphas = [
      np.asarray(v)
      for k, v in jax.tree_util.tree_flatten_with_path(variables)[0]
      if 'alpha' in str(k)
  ]
  assert len(alphas) == 2 * params.num_hidden_layers
  assert all(a == 0.0 for a in alphas)


def test_masked_embedding_zero_id():
  emb = model_lib.MaskedEmbed(vocab_size=5, features=8)
  variables = emb.init(jax.random.PRNGKey(0), jnp.array([[0, 1]]))
  out = emb.apply(variables, jnp.array([[0, 1]]))
  np.testing.assert_array_equal(np.asarray(out[0, 0]), np.zeros(8))
  assert np.abs(np.asarray(out[0, 1])).sum() > 0


def test_bq_variant_builds():
  params = make_params('transformer_learn_values+test_bq')
  assert params.total_rows == 86
  model = model_lib.get_model(params)
  rows = jnp.zeros((1, params.total_rows, params.max_length, 1))
  variables = model.init(jax.random.PRNGKey(0), rows)
  preds = model.apply(variables, rows)
  assert preds.shape == (1, 100, 5)


def test_fc_model():
  params = make_params('fc+test')
  model = model_lib.get_model(params)
  rows = fake_rows(params, batch=2)
  variables = model.init(jax.random.PRNGKey(0), rows)
  preds = model.apply(variables, rows)
  assert preds.shape == (2, 100, 5)


def test_dataset_iterator_from_reference_shards(testdata_dir):
  params = make_params()
  ds = data_lib.DatasetIterator(
      patterns=str(testdata_dir / 'human_1m/tf_examples/train/*'),
      params=params,
      batch_size=8,
  )
  assert len(ds) == 1239
  batch = next(iter(ds))
  assert batch['rows'].shape == (8, 85, 100, 1)
  assert batch['label'].shape == (8, 100)
  # PW/IP clipped into vocab range.
  assert batch['rows'][:, 20:60].max() <= 255
  assert batch['rows'][:, 61:].max() <= 500


def test_model_runs_on_real_examples(testdata_dir):
  params = make_params()
  ds = data_lib.DatasetIterator(
      patterns=str(testdata_dir / 'human_1m/tf_examples/train/*'),
      params=params,
      batch_size=4,
      limit=4,
  )
  model = model_lib.get_model(params)
  batch = next(iter(ds))
  variables = model.init(jax.random.PRNGKey(0), jnp.asarray(batch['rows']))
  preds = model.apply(variables, jnp.asarray(batch['rows']))
  assert np.isfinite(np.asarray(preds)).all()


def test_params_json_roundtrip(tmp_path):
  params = make_params()
  config_lib.save_params_as_json(str(tmp_path), params)
  back = config_lib.read_params_from_json(str(tmp_path))
  assert back.hidden_size == params.hidden_size
  assert back.max_passes == params.max_passes
  assert back.model_name == params.model_name


def test_remat_encoder_matches_baseline():
  """params.remat must not change values or gradients — only the
  memory/recompute schedule."""
  import jax

  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib

  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
    params.num_hidden_layers = 2
    params.filter_size = 32
  rng = np.random.default_rng(0)
  rows = jnp.asarray(
      rng.uniform(0, 4, size=(4, params.total_rows, params.max_length,
                              1)).astype(np.float32))
  model = model_lib.get_model(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  with params.unlocked():
    params.remat = True
  model_r = model_lib.get_model(params)

  def loss(m):
    return lambda v: jnp.sum(m.apply(v, rows) ** 2)

  base_val, base_grad = jax.value_and_grad(loss(model))(variables)
  remat_val, remat_grad = jax.value_and_grad(loss(model_r))(variables)
  np.testing.assert_allclose(
      float(remat_val), float(base_val), rtol=1e-6
  )
  flat_b = jax.tree_util.tree_leaves(base_grad)
  flat_r = jax.tree_util.tree_leaves(remat_grad)
  for gb, gr in zip(flat_b, flat_r):
    np.testing.assert_allclose(
        np.asarray(gr), np.asarray(gb), atol=1e-5, rtol=1e-4
    )


def test_unknown_model_name_raises():
  """(reference model_utils_test: test_invalid_model_name_throws_error)"""
  import ml_collections
  import pytest as _pytest

  from deepconsensus_tpu.models import model as model_lib

  params = ml_collections.ConfigDict({'model_name': 'nonexistent_net'})
  with _pytest.raises(ValueError, match='Unknown model name'):
    model_lib.get_model(params)
