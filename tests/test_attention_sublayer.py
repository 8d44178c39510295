"""The banded attention sublayer as one kernel
(ops/fused_encoder_block.py::fused_attention_sublayer) and the rule by
which a forward takes it (`attention_path` of
models/model.py::kernel_paths).

The kernel runs in interpret mode here, against the modules it replaces,
ResidualWrapper(BandedSelfAttention), on the same parameters: float32
compute at atol 1e-5; bfloat16 compute no further from the float32
reference than the modules' own bfloat16 arithmetic is. The rule is
tested on a platform monkeypatched to be a TPU: the CPU itself never
takes the kernel, so every byte-identity test elsewhere keeps running
the modules.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import export as export_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from deepconsensus_tpu.ops import fused_encoder_block as feb
from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.parallel import mesh as mesh_lib
from test_fused_hotpath import nonzero_alphas
from test_power_retention import tiny_params

BAND = 12


def _modules(hidden, heads, dtype):
  attn = model_lib.BandedSelfAttention(
      hidden_size=hidden, num_heads=heads, dropout_rate=0.0,
      attn_win_size=BAND, dtype=dtype)
  return model_lib.ResidualWrapper(attn, rezero=True, dropout_rate=0.0)


def _sublayer_case(batch, length, hidden, heads, dtype, seed=0):
  """(x, params with a non-zero alpha, the modules' output in dtype)."""
  wrap = _modules(hidden, heads, dtype)
  x = jax.random.normal(
      jax.random.PRNGKey(seed + 1), (batch, length, hidden), jnp.float32)
  params = dict(wrap.init(jax.random.PRNGKey(seed), x, deterministic=True)
                ['params'])
  params['alpha'] = jnp.asarray(0.37, jnp.float32)
  # dclint: allow=dtype-downcast (the stream enters in the compute dtype)
  x = x.astype(dtype)
  want = wrap.apply({'params': params}, x, deterministic=True)
  return x, params, want


def _kernel(x, params, heads, tile):
  batch, length, hidden = x.shape
  attn = params['sublayer']
  flat = lambda name: attn[name]['kernel'].reshape(hidden, hidden)
  got = feb.fused_attention_sublayer(
      x.reshape(batch * length, hidden), flat('query'), flat('key'),
      flat('value'), flat('output_transform'), params['alpha'],
      length=length, num_heads=heads, attn_win_size=BAND,
      tile_windows=tile, interpret=True)
  return np.asarray(got.reshape(x.shape), np.float32)


SIZES = {
    # batch, length, hidden, heads, tile: a toy, and two heads of 140 at
    # L=100 as served; neither batch is a multiple of its tile.
    'toy': (5, 32, 32, 4, 2),
    'heads_2x140': (3, 100, 280, 2, 2),
}


@pytest.mark.parametrize('size', sorted(SIZES))
def test_float32_sublayer_matches_the_modules(size):
  batch, length, hidden, heads, tile = SIZES[size]
  x, params, want = _sublayer_case(batch, length, hidden, heads, jnp.float32)
  got = _kernel(x, params, heads, tile)
  np.testing.assert_allclose(got, np.asarray(want), atol=1e-5)
  # The band's edges, where a row sees fewer than 2*BAND+1 keys.
  edge = np.r_[0:BAND, length - BAND:length]
  np.testing.assert_allclose(got[:, edge], np.asarray(want)[:, edge],
                             atol=1e-5)


@pytest.mark.parametrize('size', sorted(SIZES))
def test_bfloat16_sublayer_is_as_near_the_float32_reference_as_the_modules(
    size):
  batch, length, hidden, heads, tile = SIZES[size]
  x, params, modules16 = _sublayer_case(
      batch, length, hidden, heads, jnp.bfloat16)
  reference = np.asarray(_modules(hidden, heads, jnp.float32).apply(
      {'params': params}, x.astype(jnp.float32), deterministic=True))
  got = _kernel(x, params, heads, tile)
  assert got.dtype == np.float32 and got.shape == reference.shape
  yardstick = np.abs(np.asarray(modules16, np.float32) - reference)
  distance = np.abs(got - reference)
  # Same operands, same roundings, float32 logits where the modules round
  # them: the kernel stands no further off than the modules do.
  assert distance.mean() <= 1.05 * yardstick.mean()
  assert distance.max() <= 1.5 * yardstick.max()
  edge = np.r_[0:BAND, length - BAND:length]
  assert distance[:, edge].mean() <= 1.05 * yardstick[:, edge].mean()
  # And it is the modules' arithmetic: one bfloat16 step apart at most.
  step = np.maximum(np.abs(reference), 1.0) * 2.0 ** -7
  assert np.all(np.abs(got - np.asarray(modules16, np.float32)) <= step)


def test_band_is_attn_win_size_wide_and_masked_keys_weigh_nothing():
  """A key outside |i-j| <= BAND cannot move a row: perturbing window
  rows 40.. changes no output row before 40 - BAND."""
  x, params, _ = _sublayer_case(2, 100, 280, 2, jnp.float32)
  base = _kernel(x, params, 2, 2)
  moved = _kernel(x.at[:, 40:].add(1.0), params, 2, 2)
  np.testing.assert_array_equal(moved[:, :40 - BAND], base[:, :40 - BAND])
  assert np.abs(moved[:, 40 - BAND] - base[:, 40 - BAND]).max() > 0


# ---------------------------------------------------------------------------
# The choice.
# ---------------------------------------------------------------------------


def _params(**overrides):
  p = config_lib.get_config('transformer_learn_values+test')
  with p.unlocked():
    p.dtype = 'bfloat16'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def _retention_params():
  return tiny_params(dtype='bfloat16')


@pytest.fixture
def on_a_tpu(monkeypatch):
  """The platform reads as a TPU; the kernels still run interpreted."""
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: True)


@pytest.fixture
def sublayer_calls(monkeypatch):
  calls = []
  real = feb.fused_attention_sublayer

  def spy(x2, *args, **kwargs):
    calls.append(x2.shape)
    return real(x2, *args, **kwargs)

  monkeypatch.setattr(feb, 'fused_attention_sublayer', spy)
  return calls


def _rows(p, batch=2, length=None, seed=0):
  rng = np.random.default_rng(seed)
  rows = rng.integers(
      0, 4, size=(batch, p.total_rows, length or p.max_length, 1))
  return jnp.asarray(rows, jnp.float32)


def _init(p):
  return model_lib.get_model(p).init(jax.random.PRNGKey(0), _rows(p))


def _apply_inference(p, variables, rows, tmp_path=None):
  with pallas_util.single_device_inference():
    return model_lib.get_model(p).apply(variables, rows)


def _apply_training(p, variables, rows, tmp_path=None):
  with pallas_util.single_device_inference():
    return model_lib.get_model(p).apply(
        variables, rows, train=True, rngs={'dropout': jax.random.PRNGKey(1)})


def _apply_capturing(p, variables, rows, tmp_path=None):
  with pallas_util.single_device_inference():
    return model_lib.get_model(p).apply(
        variables, rows, capture_intermediates=True)


def _apply_ragged(p, variables, rows, tmp_path=None):
  lengths = jnp.full((rows.shape[0], 1), rows.shape[2], jnp.int32)
  with pallas_util.single_device_inference():
    return model_lib.get_model(p).apply(
        variables, rows, window_lengths=lengths)


def _apply_undeclared(p, variables, rows, tmp_path=None):
  return model_lib.get_model(p).apply(variables, rows)


def _apply_under_a_mesh(p, variables, rows, tmp_path=None):
  with pallas_util.single_device_inference(False):
    return model_lib.get_model(p).apply(variables, rows)


def _init_again(p, variables, rows, tmp_path=None):
  with pallas_util.single_device_inference():
    return model_lib.get_model(p).init(jax.random.PRNGKey(0), rows)


def _export(p, variables, rows, tmp_path):
  export_lib.export_model(
      '', str(tmp_path / 'exported'), batch_size=rows.shape[0], params=p,
      variables={'params': variables['params']}, polymorphic_batch=False)


CHOICES = {
    # name: (params, how the forward is asked for, length, taken)
    'inference_l100_bfloat16': (_params, _apply_inference, None, True),
    'training': (_params, _apply_training, None, False),
    'init': (_params, _init_again, None, False),
    'l200': (_params, _apply_inference, 200, False),
    'ragged': (_params, _apply_ragged, None, False),
    'float32': (lambda: _params(dtype='float32'), _apply_inference, None,
                False),
    'power_retention': (_retention_params, _apply_inference, None, False),
    'capture_intermediates': (_params, _apply_capturing, None, False),
    'pre_ln_residual': (lambda: _params(rezero=False), _apply_inference,
                        None, False),
    'use_pallas_attention': (lambda: _params(use_pallas_attention=True),
                             _apply_inference, None, False),
    'nobody_declared_one_device': (_params, _apply_undeclared, None, False),
    'mesh': (_params, _apply_under_a_mesh, None, False),
    'export': (_params, _export, None, False),
}


@pytest.mark.parametrize('case', sorted(CHOICES))
def test_the_forward_takes_the_kernel_only_where_the_rule_says(
    case, on_a_tpu, sublayer_calls, tmp_path):
  make_params, ask, length, taken = CHOICES[case]
  p = make_params()
  variables = _init(p)
  assert not sublayer_calls  # init runs the modules
  rows = _rows(p, length=length)
  ask(p, variables, rows, tmp_path)
  if taken:
    assert sublayer_calls == [
        (rows.shape[0] * rows.shape[2], p.hidden_size)
    ] * p.num_hidden_layers
  else:
    assert not sublayer_calls


def test_a_cpu_never_takes_the_kernel(sublayer_calls):
  p = _params()
  _apply_inference(p, _init(p), _rows(p))
  assert not sublayer_calls
  with pallas_util.single_device_inference():
    assert model_lib.kernel_paths(p, batch=8, length=p.max_length)[
        'attention_path'] == 'xla'


def test_parameter_tree_is_the_modules_own_either_way(on_a_tpu):
  p = _params()
  plain = _init(p)
  with pallas_util.single_device_inference():
    declared = model_lib.get_model(p).init(jax.random.PRNGKey(0), _rows(p))
  assert (jax.tree_util.tree_structure(plain)
          == jax.tree_util.tree_structure(declared))
  for a, b in zip(jax.tree_util.tree_leaves(plain),
                  jax.tree_util.tree_leaves(declared)):
    assert a.shape == b.shape and a.dtype == b.dtype
  encoder = plain['params']['encoder']
  for n in range(p.num_hidden_layers):
    assert set(encoder[f'self_attention_{n}']) == {
        'query', 'key', 'value', 'output_transform'}
    assert set(encoder[f'attention_wrapper_{n}']) == {'alpha'}


def test_whole_forward_through_the_kernel_matches_the_modules(
    on_a_tpu, sublayer_calls):
  """Six places of the stream change hands, flat, and come back as
  windows: predictions agree with the modules' to bfloat16 noise."""
  p = _params()
  variables = nonzero_alphas(_init(p))
  rows = _rows(p, batch=3)
  model = model_lib.get_model(p)
  want = model.apply(variables, rows, method=model.apply_with_intermediates)
  assert not sublayer_calls
  with pallas_util.single_device_inference():
    got = model.apply(variables, rows, method=model.apply_with_intermediates)
  assert len(sublayer_calls) == p.num_hidden_layers
  for key in ('final_output', 'logits', 'preds'):
    assert got[key].shape == want[key].shape
    assert got[key].dtype == want[key].dtype
  np.testing.assert_allclose(np.asarray(got['preds']),
                             np.asarray(want['preds']), atol=2e-2)
  assert np.mean(np.argmax(got['preds'], -1)
                 == np.argmax(want['preds'], -1)) > 0.99


@pytest.mark.parametrize('mesh', [False, True], ids=['one_device', 'mesh'])
def test_forward_launch_names_the_attention_path(
    on_a_tpu, sublayer_calls, tmp_path, mesh):
  """A runner without a mesh declares its forward inference for one
  device and the span says which path the rule took; with a mesh (XLA
  cannot partition a Mosaic call) nobody declares it."""
  p = _params()
  options = runner_lib.InferenceOptions(batch_size=4)
  runner = runner_lib.ModelRunner(
      p, _init(p), options,
      mesh=mesh_lib.make_mesh(dp=2, devices=jax.devices()[:2])
      if mesh else None)
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    runner.predict(np.asarray(_rows(p, batch=4)))
  finally:
    trace_lib.configure(None)
  events = summarize_lib.load_trace(path)
  launches = [e for e in events
              if e.get('ph') == 'X' and e.get('name') == 'forward_launch']
  want = 'xla' if mesh else 'fused_sublayer'
  assert launches and all(
      e['args']['attention_path'] == want for e in launches)
  assert bool(sublayer_calls) == (not mesh)
  assert summarize_lib.summarize(events)['forward']['attention_paths'] == [
      want]
