"""Golden-window parity for the batch-major fused hot path
(ops/fused_window_attention.py) vs the XLA model path.

All tests run the kernel in Pallas interpret mode on CPU
(pallas_util.resolve_interpret), so the fused path's correctness is
provable without TPU hardware. The full-model goldens use the
production window shape (L=100, condensed input, ReZero) with the
float32 dtype override that every CPU numerics test in this repo uses.
ReZero alphas init to zero — which would let a broken attention fusion
pass trivially — so parity tests overwrite every alpha with a nonzero
value first.
"""
import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import fused_window_attention as fwa


def make_params(name='transformer_learn_values+test', pre=None, **overrides):
  params = config_lib.get_config(name)
  if pre:
    with params.unlocked():
      for k, v in pre.items():
        params[k] = v
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
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
  rows[:, mp:2 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 2 * mp:3 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 3 * mp:4 * mp] = rng.integers(0, 3, size=rows[:, :mp].shape)
  rows[:, 4 * mp] = rng.integers(0, 5, size=rows[:, 4 * mp].shape)
  if params.use_ccs_bq:
    # ccs_bq stores gap as -1 (embedded with shift +1).
    rows[:, 4 * mp + 1] = rng.integers(
        -1, params.CCS_BQ_MAX - 1, size=rows[:, 4 * mp + 1].shape)
    sn_lo = 4 * mp + 2
  else:
    sn_lo = 4 * mp + 1
  rows[:, sn_lo:] = rng.integers(0, 501, size=rows[:, sn_lo:].shape)
  return jnp.asarray(rows)


def nonzero_alphas(variables, seed=3):
  """ReZero alphas init to 0, which zeroes every residual branch; give
  each a distinct nonzero value so parity actually exercises them."""
  flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(variables))
  rng = np.random.default_rng(seed)
  for key in flat:
    if key[-1] == 'alpha':
      flat[key] = jnp.asarray(rng.uniform(0.3, 1.0), jnp.float32)
  return flax.traverse_util.unflatten_dict(flat)


def init_pair(params, batch=3, seed=0):
  rows = fake_rows(params, batch=batch, seed=seed)
  model = model_lib.get_model(params)
  variables = model.init(jax.random.PRNGKey(0), rows)
  return model, nonzero_alphas(variables), rows


def kernel_args(params, variables, rows):
  specs, keys, _ = fwa.build_family_specs(params)
  p = variables['params']
  tables = {k: p[f'{k}_embedding']['embedding'] for k in keys}
  h = params.hidden_size
  a0 = p['encoder']['self_attention_0']
  args = (
      jnp.squeeze(rows, -1), tables, p['condenser']['kernel'],
      a0['query']['kernel'].reshape(h, h),
      a0['key']['kernel'].reshape(h, h),
      a0['value']['kernel'].reshape(h, h),
      a0['output_transform']['kernel'].reshape(h, h),
      jnp.asarray(model_lib.sinusoidal_position_encoding(rows.shape[2], h)),
  )
  kwargs = dict(specs=specs, table_keys=keys, num_heads=params.num_heads,
                attn_win_size=params.attn_win_size or None)
  return args, kwargs


# ---------------------------------------------------------------------------
# Full-model goldens: production window shape, fused vs XLA.
# ---------------------------------------------------------------------------


def test_fused_matches_xla_on_golden_production_windows():
  """L=100, condensed, ReZero: the acceptance-criteria golden. Batch 11
  with the default tile of 8 also exercises the batch-padding path."""
  params = make_params()
  assert params.max_length == 100 and params.condense_transformer_input
  model, variables, rows = init_pair(params, batch=11, seed=7)
  ref = model.apply(variables, rows, False,
                    method='apply_with_intermediates')

  params_f = make_params(use_fused_hotpath=True)
  model_f = model_lib.get_model(params_f)
  got = model_f.apply(variables, rows, False,
                      method='apply_with_intermediates')
  # Acceptance bar: atol 1e-5 on the model output (preds). Logits get
  # a small rtol on top — six f32 encoder layers amplify the kernel's
  # different-but-valid summation order to ~2e-5 on O(10) logits.
  np.testing.assert_allclose(
      np.asarray(got['logits']), np.asarray(ref['logits']),
      rtol=2e-3, atol=1e-5)
  np.testing.assert_allclose(
      np.asarray(got['preds']), np.asarray(ref['preds']), atol=1e-5)


def test_fused_matches_xla_with_ccs_bq():
  """The ccs_bq family has a +1 id shift and its own vocab; make sure
  the family-spec table covers it."""
  params = make_params(pre={'use_ccs_bq': True})
  model, variables, rows = init_pair(params, batch=4, seed=11)
  ref = model.apply(variables, rows)
  params_f = make_params(pre={'use_ccs_bq': True}, use_fused_hotpath=True)
  got = model_lib.get_model(params_f).apply(variables, rows)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_fused_path_is_actually_taken(monkeypatch):
  """Guard against eligibility silently routing to XLA (which would
  make every parity test vacuous)."""
  calls = []
  real = fwa.fused_embed_condense_attention

  def spy(*args, **kwargs):
    calls.append(1)
    return real(*args, **kwargs)

  monkeypatch.setattr(fwa, 'fused_embed_condense_attention', spy)
  params = make_params(use_fused_hotpath=True)
  model, variables, rows = init_pair(params, batch=2)
  assert not calls  # init must create params via the XLA path
  model.apply(variables, rows)
  assert calls


def test_fused_softmax_dtype_lever():
  """attn_softmax_dtype=bfloat16 mirrors the XLA cast chain; bf16
  accumulation legitimately perturbs weights at ~1e-2, so the check is
  loose tolerance + argmax agreement (same bar as the XLA lever test)."""
  params = make_params(attn_softmax_dtype='bfloat16')
  model, variables, rows = init_pair(params, batch=3, seed=5)
  ref = model.apply(variables, rows)
  params_f = make_params(attn_softmax_dtype='bfloat16',
                         use_fused_hotpath=True)
  got = model_lib.get_model(params_f).apply(variables, rows)
  np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-2)
  # bf16 rounding order differs between the two paths, so near-tie
  # positions can legitimately flip; require near-total agreement.
  agree = np.mean(
      np.asarray(got.argmax(-1)) == np.asarray(ref.argmax(-1)))
  assert agree >= 0.98, f'argmax agreement {agree:.3f}'


# ---------------------------------------------------------------------------
# Fallback routing: configs the kernel doesn't serve must be bitwise
# identical to the flag-off run (both land on the XLA path).
# ---------------------------------------------------------------------------


def test_training_falls_back_to_xla():
  params = make_params()
  model, variables, rows = init_pair(params, batch=2)
  rngs = {'dropout': jax.random.PRNGKey(42)}
  ref = model.apply(variables, rows, train=True, rngs=rngs)
  params_f = make_params(use_fused_hotpath=True)
  got = model_lib.get_model(params_f).apply(
      variables, rows, train=True, rngs=rngs)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_long_window_falls_back_to_xla():
  pre = {'max_length': fwa.MAX_WINDOW_LEN + 32}
  params = make_params(pre=pre)
  model, variables, rows = init_pair(params, batch=2)
  ref = model.apply(variables, rows)
  params_f = make_params(pre=pre, use_fused_hotpath=True)
  got = model_lib.get_model(params_f).apply(variables, rows)
  np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_init_param_tree_identical():
  params = make_params()
  params_f = make_params(use_fused_hotpath=True)
  rows = fake_rows(params, batch=2)
  v0 = model_lib.get_model(params).init(jax.random.PRNGKey(0), rows)
  v1 = model_lib.get_model(params_f).init(jax.random.PRNGKey(0), rows)
  assert jax.tree_util.tree_structure(v0) == jax.tree_util.tree_structure(v1)
  for a, b in zip(jax.tree_util.tree_leaves(v0),
                  jax.tree_util.tree_leaves(v1)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Kernel-level unit tests vs the pure-jnp reference.
# ---------------------------------------------------------------------------


def test_family_specs_cover_condenser_input():
  for pre in (None, {'use_ccs_bq': True}):
    params = make_params(pre=pre)
    specs, keys, width = fwa.build_family_specs(params)
    variables = model_lib.get_model(params).init(
        jax.random.PRNGKey(0), fake_rows(params, batch=1))
    assert width == variables['params']['condenser']['kernel'].shape[0]
    assert sorted({s.name for s in specs}) == sorted(
        ['bases', 'pw', 'ip', 'strand', 'ccs', 'sn']
        + (['ccs_bq'] if params.use_ccs_bq else []))
    # ccs rows must share the bases table.
    ccs = next(s for s in specs if s.name == 'ccs')
    bases = next(s for s in specs if s.name == 'bases')
    assert ccs.table_idx == bases.table_idx


@pytest.mark.parametrize('attn_win_size', [None, 12])
@pytest.mark.parametrize('batch,tile', [(3, 4), (11, 4)])
def test_kernel_matches_jnp_reference(attn_win_size, batch, tile):
  """Direct kernel-vs-reference parity, including batch==tile-remainder
  padding (11 % 4 != 0) and unbanded attention."""
  params = make_params()
  with params.unlocked():
    params.attn_win_size = attn_win_size or 0
  model, variables, rows = init_pair(params, batch=batch, seed=batch)
  args, kwargs = kernel_args(params, variables, rows)
  xb_k, at_k = fwa.fused_embed_condense_attention(
      *args, tile_windows=tile, **kwargs)
  xb_r, at_r = fwa.reference_fused_forward(*args, **kwargs)
  assert xb_k.shape == (batch, params.max_length, params.hidden_size)
  # When batch != tile the reference chunks differently than the
  # kernel, so f32 summation order differs at the ~1e-6 level.
  np.testing.assert_allclose(np.asarray(xb_k), np.asarray(xb_r), atol=1e-5)
  np.testing.assert_allclose(np.asarray(at_k), np.asarray(at_r), atol=1e-5)


def test_kernel_rejects_mismatched_condenser():
  params = make_params()
  model, variables, rows = init_pair(params, batch=2)
  args, kwargs = kernel_args(params, variables, rows)
  bad = list(args)
  bad[2] = jnp.zeros((args[2].shape[0] + 8, args[2].shape[1]))
  with pytest.raises(ValueError, match='condenser'):
    fwa.fused_embed_condense_attention(*bad, **kwargs)
