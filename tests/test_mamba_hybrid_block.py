"""The seventh encoder block kind (config.BLOCK_MAMBA_HYBRID): a sequential
pre-RMSNorm stack of Mamba-1 mixers with softmax attention where
n % attn_layer_period == attn_layer_offset, a SwiGLU in every layer.

Toy sizes on the CPU (hidden 64; inner width 128, a state of 16, dt rank
8, 4 taps; 4 query heads over one key-value head of 16; SwiGLU 96; 4
layers `MMFM`, period 4 and offset 2; L 16). What is held here: the
selective scan in both forms (plain jnp, and the Pallas call interpreted)
against the test-local plain reference (tests/mamba_hybrid_reference.py,
the scan a loop over positions), each direction and their sum; a scan held
in bfloat16 against the same tolerance; the mixer through the kernel; the
program's model through get_model and through ModelRunner against the
reference on seeded weights; the pattern rule; a window reversed; what the
kind refuses by name.
"""
import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.ops import selective_scan
from tests import mamba_hybrid_reference as ref
from tests.test_mla_moe_block import _runner
from tests.test_power_retention import pileup_rows

PRESET = 'transformer_learn_values_mamba_hybrid+custom'
KIND = config_lib.BLOCK_MAMBA_HYBRID
INNER, STATE, RANK = 128, 16, 8


def tiny_params(length=16, layers=4, period=4, offset=2, **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_hidden_layers = layers
    p.attn_layer_period, p.attn_layer_offset = period, offset
    p.num_heads, p.num_kv_heads, p.head_dim = 4, 1, 16
    p.mamba_dt_rank = RANK
    p.filter_size = 96
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def mamba_leaves(rng, inner=INNER, state=STATE):
  """A_log = log(1..S) a channel, as published; b_dt such that
  softplus(b_dt) spreads over Mamba's [0.001, 0.1]; D about 1."""
  dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), inner))
  return {
      'A_log': jnp.asarray(np.log(np.tile(np.arange(1, state + 1.0),
                                          (inner, 1))), jnp.float32),
      'D': jnp.asarray(rng.uniform(0.5, 1.5, inner), jnp.float32),
      'dt_bias': jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32)}


def seeded_variables(model, p, seed=0):
  """model.init's tree with every leaf drawn anew, away from its init, so
  that each one counts: norms' weights uniform [0.5, 1.5), biases of std
  0.02, embeddings of std E^-1/2 as initialised, kernels of variance
  1 / fan_in (the contracted axes), each mixer's A_log, D and dt_proj bias
  as `mamba_leaves` draws them. The tree's shapes alone are traced."""
  rows = jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)
  tree = jax.eval_shape(model.init, jax.random.PRNGKey(seed), rows)['params']
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  rng = np.random.default_rng(seed)
  mixers = {}
  leaves = []
  for path, leaf in flat:
    keys = [str(getattr(k, 'key', k)) for k in path]
    name = '/'.join(keys)
    mixer = next((k for k in keys if k.startswith('mamba_')), None)
    special = mixers.setdefault(mixer, mamba_leaves(rng)) if mixer else {}
    if keys[-1] in ('A_log', 'D'):
      value = special[keys[-1]]
    elif name.endswith('dt_proj/bias'):
      value = special['dt_bias']
    elif name.endswith('scale'):
      value = rng.uniform(0.5, 1.5, leaf.shape)
    elif name.endswith('bias'):
      value = rng.normal(0, 0.02, leaf.shape)
    elif 'embedding' in name:
      value = rng.normal(0, leaf.shape[-1] ** -0.5, leaf.shape)
    else:
      fan_in = (np.prod(leaf.shape[:-1])
                if name.endswith('output_transform/kernel')
                else leaf.shape[0])
      value = rng.normal(0, fan_in ** -0.5, leaf.shape)
    leaves.append(jnp.asarray(value, jnp.float32))
  return {'params': jax.tree_util.tree_unflatten(
      jax.tree_util.tree_structure(tree), leaves)}


def reference(variables, rows, p, **faults):
  """The reference's logits, jitted whole (its loops unrolled in the
  trace)."""
  logits = jax.jit(functools.partial(
      ref.logits, max_passes=p.max_passes,
      layers=config_lib.layer_pattern(p), eps=p.rms_norm_eps, **faults))
  with jax.default_matmul_precision('highest'):
    return np.asarray(logits(variables['params'], jnp.asarray(rows[..., 0])))


# -------------------------------------------------------------- the scan

def scan_operands(seed, batch=8, length=12, dtype=jnp.float32):
  """(streams, dts, bcs, xz, a_log, d, dt_bias) at the toy widths on the
  flat stream [B*L, ...]: x and z of unit scale, dt about a trained
  model's, B and C normed-like; xz holds z in its second half, as in_proj
  leaves it."""
  rng = np.random.default_rng(seed)
  rows = batch * length
  normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
  leaves = mamba_leaves(rng)
  two = lambda make: tuple(jnp.asarray(make(), dtype) for _ in range(2))
  return (two(lambda: normal(rows, INNER)), two(lambda: normal(rows, INNER)),
          tuple(jnp.asarray(normal(rows, 2 * STATE)) for _ in range(2)),
          jnp.asarray(normal(rows, 2 * INNER), dtype),
          leaves['A_log'], leaves['D'], leaves['dt_bias'])


def reference_scan(streams, dts, bcs, xz, a_log, d, dt_bias, *, length,
                   state_dtype=jnp.float32):
  """The operator from the reference's own scan: each direction as the
  published causal scan over each window (the second over the window
  reversed), added, gated."""
  windows = lambda t: jnp.asarray(t, jnp.float32).reshape(
      -1, length, t.shape[-1])
  flip = lambda t: jnp.flip(t, axis=1)
  a = -jnp.exp(a_log)
  out = 0.0
  for direction, (x, dt, bc) in enumerate(zip(streams, dts, bcs)):
    turned = flip if direction else (lambda t: t)
    delta = jax.nn.softplus(windows(dt) + dt_bias)
    bc = windows(bc)
    out = out + turned(ref.scan(
        turned(windows(x)), turned(delta), a, turned(bc[..., :STATE]),
        turned(bc[..., STATE:]), d, state_dtype=state_dtype))
  z = windows(xz)[..., INNER:]
  return np.asarray(out * jax.nn.silu(z)).reshape(-1, INNER)


FORMS = {
    'plain': lambda *args, length: selective_scan._plain(
        *args[:4], -jnp.exp(args[4]), *args[5:], length=length),
    'window_kernel': lambda *args, length: selective_scan._window_kernel_call(
        *args[:4], -jnp.exp(args[4]), *args[5:], length=length,
        interpret=True),
}
# Both forms and the reference compute in float32, each state step the
# same products in another order (the kernel's Delta x before B, the
# reference's Delta x B in one): a few ulps of the state, summed over 16
# states and carried along 12 positions. Measured: 2e-6 of an output
# whose largest value is about 10.
SCAN_TOLERANCE = 2e-5


@pytest.mark.parametrize('form', sorted(FORMS))
@pytest.mark.parametrize('direction', ['ahead', 'back', 'both'])
def test_the_scan_in_both_forms_is_the_reference_scan(form, direction):
  """Each direction alone (the other's x zero: its state stays at zero
  and its D term adds nothing) and the two summed, over 8 windows of 12
  rows: two grid steps of the kernel, four windows each, whose runs cross
  the rows of a step as one sequence and start afresh at each window."""
  streams, dts, bcs, xz, a_log, d, dt_bias = scan_operands(7)
  if direction != 'both':
    quiet = 1 if direction == 'ahead' else 0
    streams = tuple(jnp.zeros_like(x) if i == quiet else x
                    for i, x in enumerate(streams))
  args = (streams, dts, bcs, xz, a_log, d, dt_bias)
  got = np.asarray(FORMS[form](*args, length=12))
  want = reference_scan(*args, length=12)
  assert got.shape == (96, INNER) and got.dtype == np.float32
  assert np.abs(got - want).max() < SCAN_TOLERANCE
  # The outputs are not small: the tolerance is a part in a million.
  assert np.abs(want).max() > 2.0


def test_the_kernel_lays_a_whole_array_of_channels_a_state():
  """At 1,024 channels a state is one [8, 128] array: a row's lane tiles
  are laid into its eight rows and back by strided stores and loads, which
  the toy width of one lane tile never strides."""
  rng = np.random.default_rng(9)
  inner, rows, length = 1024, 48, 12
  normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
  dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), inner))
  args = ((normal(rows, inner), normal(rows, inner)),
          (normal(rows, inner), normal(rows, inner)),
          (normal(rows, 2 * STATE), normal(rows, 2 * STATE)),
          normal(rows, 2 * inner),
          -jnp.tile(jnp.arange(1, STATE + 1.0), (inner, 1)),
          normal(inner), jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32))
  assert selective_scan.channel_tiles(inner) == 8
  got = selective_scan._window_kernel_call(*args, length=length,
                                           interpret=True)
  want = selective_scan._plain(*args, length=length)
  assert np.abs(np.asarray(got) - np.asarray(want)).max() < SCAN_TOLERANCE


def test_a_scan_held_in_bfloat16_reads_outside_the_tolerance():
  """The precision the configuration states for the scan is float32: the
  same reference with its decay and state rounded to bfloat16 at every
  step lies two orders of magnitude outside the tolerance the forms meet."""
  args = scan_operands(7)
  want = reference_scan(*args, length=12)
  low = reference_scan(*args, length=12, state_dtype=jnp.bfloat16)
  assert np.abs(low - want).max() > 100 * SCAN_TOLERANCE


def test_the_rule_takes_the_kernel_on_one_tpu_at_whole_tiles(monkeypatch):
  path = lambda **sizes: selective_scan.ssm_scan_path(
      **dict(dict(inner=5120, state=16, batch=256, length=100), **sizes))
  assert path() == 'plain'  # the CPU
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  assert path() == 'plain'  # not declared single-device inference
  with pallas_util.single_device_inference():
    assert path() == path(length=500) == path(state=12) == 'window_kernel'
    assert path(inner=5000) == path(state=15) == 'plain'
    # A pack of whole grid steps: 4 windows of 100 rows or of 12, 16 of 13.
    assert path(batch=250) == path(batch=6, length=12) == 'plain'
    assert path(batch=8, length=12) == path(batch=16, length=13) == (
        'window_kernel')
  assert [selective_scan.windows_a_step(n) for n in (100, 12, 13)] == [
      4, 4, 16]
  # Channels a grid step: a whole [8, 128] array where the width allows.
  assert [selective_scan.channel_tiles(n) for n in (5120, 128, 768)] == [
      8, 1, 6]


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_the_mixer_through_the_kernel_is_the_mixer_through_the_plain_form(
    dtype, monkeypatch):
  """MambaMixer at the toy widths: where the code takes the kernel (one
  TPU device at inference; here the interpreter stands in for the chip)
  the mixer's output is what the plain form gives, and in float32 what the
  reference's mixer gives."""
  mixer = model_lib.MambaMixer(
      hidden_size=64, inner=INNER, state_size=STATE, dt_rank=RANK,
      conv_kernel=4, conv_bias=True, rms_norm_eps=1e-6, dtype=dtype)
  rng = np.random.default_rng(3)
  x = jnp.asarray(rng.normal(size=(8, 12, 64)), dtype)
  variables = jax.jit(lambda k: mixer.init(k, x, True))(jax.random.PRNGKey(0))
  params = dict(variables['params'])
  leaves = mamba_leaves(rng)
  params.update(A_log=leaves['A_log'], D=leaves['D'])
  params['dt_proj'] = dict(params['dt_proj'], bias=leaves['dt_bias'])
  params['conv1d'] = dict(params['conv1d'], bias=jnp.asarray(
      rng.normal(0, 0.1, INNER), jnp.float32))
  apply = lambda: jax.jit(lambda v: mixer.apply(v, x, True))(
      {'params': params})
  taken = []
  call = selective_scan._window_kernel_call
  monkeypatch.setattr(selective_scan, '_window_kernel_call',
                      lambda *a, **k: taken.append(1) or call(*a, **k))
  with jax.default_matmul_precision('highest'):
    plain = apply()
    monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
    monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: True)
    with pallas_util.single_device_inference():
      kernel = apply()
    want = ref.mamba(params, x.astype(jnp.float32), eps=1e-6)
  assert taken == [1] and kernel.dtype == dtype
  # float32: the scan's few ulps (above); bfloat16: the forms round y
  # once, at the same place, and may differ by one rounding of it.
  atol = 1e-4 if dtype == jnp.float32 else 0.05
  np.testing.assert_allclose(np.asarray(kernel, np.float32),
                             np.asarray(plain, np.float32), atol=atol)
  if dtype == jnp.float32:
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want),
                               atol=1e-4)
    assert np.abs(np.asarray(want)).max() > 0.1


@pytest.mark.parametrize('flat', [False, True], ids=['windows', 'flat'])
@pytest.mark.parametrize('bias', [False, True])
def test_the_two_direction_convolution_is_the_published_one_each_way(
    bias, flat):
  """The helper both mixers use (the Gated DeltaNet mixer on [B, L, C]
  without a bias, the Mamba mixer on the flat stream with one): the run
  from the start is the published causal convolution, the run from the end
  the same over the window reversed; on the flat stream no tap reaches into
  the next window."""
  rng = np.random.default_rng(5)
  x = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
  kernel = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
  b = jnp.asarray(rng.normal(size=32), jnp.float32) if bias else None
  if flat:
    ahead, back = (t.reshape(x.shape) for t in model_lib.two_direction_conv(
        x.reshape(18, 32), kernel, b, length=9))
  else:
    ahead, back = model_lib.two_direction_conv(x, kernel, b)
  flip = lambda t: jnp.flip(t, axis=1)
  zero = jnp.zeros(32) if b is None else b
  np.testing.assert_allclose(
      np.asarray(ahead), np.asarray(jax.nn.silu(ref.causal_conv(
          x, kernel, zero))), atol=1e-5)
  np.testing.assert_allclose(
      np.asarray(back), np.asarray(flip(jax.nn.silu(ref.causal_conv(
          flip(x), kernel, zero)))), atol=1e-5)


# ------------------------------------------------ the program and the reference

@pytest.fixture(scope='module')
def toy():
  """(params, variables, 8 windows of 16 rows, the reference's logits) at
  the toy widths: what the model, the faults, the reversal and the runner
  are held against."""
  p = tiny_params(16)
  variables = seeded_variables(model_lib.get_model(p), p, seed=1)
  rows = pileup_rows(p, 8, seed=2)
  return p, variables, rows, reference(variables, rows, p)


def test_model_agrees_with_the_plain_reference_in_float32(toy):
  p, variables, rows, want = toy
  assert config_lib.layer_pattern(p) == 'MMFM'
  assert config_lib.ffn_pattern(p) == 'DDDD'
  model = model_lib.get_model(p)
  with jax.default_matmul_precision('highest'):
    got = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates))(
            variables, jnp.asarray(rows))
  assert got['logits'].shape == (8, 16, 5)
  # float32 throughout, sums in other orders (the scan's products, heads
  # grouped against keys repeated): rounding of sums of a few hundred
  # terms, through four layers.
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.mark.parametrize('fault', [
    dict(directions=1), dict(skip=False), dict(state_dtype=jnp.bfloat16)],
                         ids=['one_direction', 'no_skip', 'bf16_state'])
def test_reference_faults_are_seen_at_this_tolerance(fault, toy):
  """What the float32 tolerance above catches: the published causal mixer
  alone, the D term left out, and the scan held in bfloat16 each move the
  reference's own logits by far more than 1e-4."""
  p, variables, rows, a = toy
  assert np.abs(a - reference(variables, rows, p, **fault)).max() > 1e-3


def test_a_window_reversed_gives_its_output_reversed(toy):
  """Nothing in the block knows a direction: the embedding and the
  feed-forward are position-wise, the attention has no positions, and the
  mixer runs both ways with the same leaves."""
  p, variables, rows, _want = toy
  model = model_lib.get_model(p)
  forward = jax.jit(lambda v, r: model.apply(
      v, r, method=model.apply_with_intermediates)['logits'])
  with jax.default_matmul_precision('highest'):
    ahead = np.asarray(forward(variables, jnp.asarray(rows)))
    back = np.asarray(forward(variables, jnp.asarray(rows[:, :, ::-1])))
  np.testing.assert_allclose(back[:, ::-1], ahead, atol=1e-4)
  assert np.abs(ahead - ahead[:, ::-1]).max() > 0.1


# ------------------------------------------------------- the pattern rule

def test_attention_lies_at_the_offset_of_each_period():
  published = config_lib.get_config(PRESET)
  assert config_lib.layer_pattern(published) == 'MMMMMMMFMMMMMM' * 2
  assert config_lib.ffn_pattern(published) == 'D' * 28
  assert ref.pattern(28, 14, 7) == 'MMMMMMMFMMMMMM' * 2
  assert config_lib.layer_pattern(tiny_params(layers=6, period=3,
                                              offset=0)) == 'FMMFMM'
  # A stage of the first 14 layers holds one attention layer.
  assert config_lib.layer_pattern(tiny_params(
      layers=14, period=14, offset=7)) == 'MMMMMMMFMMMMMM'


@pytest.mark.parametrize('period,offset', [(14, 14), (14, -1), (0, 0)])
def test_an_offset_outside_its_period_is_refused_by_name(period, offset):
  p = tiny_params(8)
  with p.unlocked():
    p.attn_layer_period, p.attn_layer_offset = period, offset
  with pytest.raises(ValueError, match=rf'attn_layer_offset {offset} of '
                     rf'attn_layer_period {period} places no layer'):
    config_lib.layer_pattern(p)


def test_more_than_one_expert_is_refused_by_name():
  p = tiny_params(8, num_experts=16)
  with pytest.raises(ValueError, match='num_experts 16 is not served'):
    jax.eval_shape(lambda k: model_lib.get_model(p).init(
        k, jnp.zeros((1, p.total_rows, 8, 1))), jax.random.PRNGKey(0))


def test_projection_biases_are_refused_by_name():
  p = tiny_params(8, mamba_proj_bias=True)
  with pytest.raises(ValueError, match='mamba_proj_bias true is not served'):
    jax.eval_shape(lambda k: model_lib.get_model(p).init(
        k, jnp.zeros((1, p.total_rows, 8, 1))), jax.random.PRNGKey(0))


# ------------------------------------------------------------------- the tree

def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.block_kind == KIND and config_lib.block_form(p) == 'sequential'
  assert (p.hidden_size, p.num_hidden_layers, p.rms_norm_eps,
          p.filter_size) == (2560, 28, 1e-6, 8192)
  assert (p.attn_layer_period, p.attn_layer_offset, p.expert_layer_period,
          p.expert_layer_offset, p.num_experts, p.num_experts_per_tok) == (
              14, 7, 2, 1, 1, 1)
  assert (p.num_heads, p.num_kv_heads, p.head_dim) == (20, 1, 128)
  assert (p.mamba_d_state, p.mamba_d_conv, p.mamba_expand, p.mamba_dt_rank,
          p.mamba_conv_bias, p.mamba_proj_bias) == (16, 4, 2, 160, True,
                                                    False)
  assert (p.dtype, p.inference_dtype, p.rezero, p.add_pos_encoding) == (
      'bfloat16', 'bfloat16', False, False)
  assert not config_lib.holds_experts(p) and config_lib.inference_only(p)


def test_the_whole_stack_at_the_published_widths_has_the_hand_counted_parameters():
  """By shape alone: no array of the 5.7 GB is made. A Mamba layer
  104,161,472 (its mixer 41,241,792), an attention layer 76,682,240, each
  counted on a stack of one of each at the published widths (`MF`:
  attention at offset 1 of 2); the published stack holds 26 and 2."""
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 2
    p.attn_layer_period, p.attn_layer_offset = 2, 1
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.layer_pattern(p) == 'MF'
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, p.max_length, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda node: sum(
      leaf.size for leaf in jax.tree_util.tree_leaves(node))
  shapes = jax.tree_util.tree_map(lambda leaf: leaf.shape, tree['mamba_0'])
  assert shapes == {
      'in_proj': {'kernel': (2560, 10240)},
      'conv1d': {'kernel': (4, 5120), 'bias': (5120,)},
      'x_proj': {'kernel': (5120, 192)},
      'dt_layernorm': {'scale': (160,)}, 'b_layernorm': {'scale': (16,)},
      'c_layernorm': {'scale': (16,)},
      'dt_proj': {'kernel': (160, 5120), 'bias': (5120,)},
      'A_log': (5120, 16), 'D': (5120,),
      'out_proj': {'kernel': (5120, 2560)}}
  assert count(tree['mamba_0']) == 41_241_792
  attention = tree['self_attention_1']
  assert attention['query']['kernel'].shape == (2560, 20, 128)
  assert attention['key']['kernel'].shape == (2560, 1, 128)
  layer = lambda n: sum(count(tree[f'{part}_{n}']) for part in (
      'attention_wrapper', 'ffn_wrapper', 'ffn')) + count(
          tree.get(f'mamba_{n}', tree.get(f'self_attention_{n}')))
  assert layer(0) == 104_161_472 and layer(1) == 76_682_240
  assert count(tree) - 2560 == layer(0) + layer(1)
  published = config_lib.get_config(PRESET)
  pattern = config_lib.layer_pattern(published)
  assert (pattern.count('M') * layer(0) + pattern.count('F') * layer(1)
          == 2_861_562_752)


# ------------------------------------------------- through the normal path

def test_model_runner_serves_the_reference_and_says_the_scan_path(
    toy, tmp_path, capsys):
  from deepconsensus_tpu import cli

  p, variables, rows, want = toy
  runner, _ = _runner(p, variables)
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.clear_early()
  trace_lib.configure(path, tier='run')
  try:
    ids, quals = runner.predict(rows)
  finally:
    trace_lib.configure(None)
  # Where the reference's top two logits are not a rounding apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 1e-3
  assert clear.mean() > 0.95
  assert np.array_equal(np.asarray(ids)[clear], want.argmax(-1)[clear])
  assert np.asarray(quals).min() >= 0
  assert runner.dispatch_stats()['block_kind'] == KIND
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  (launch,) = [e['args'] for e in events if e['name'] == 'forward_launch']
  assert launch['block_kind'] == KIND and launch['block_form'] == 'sequential'
  assert launch['layer_pattern'] == 'MMFM' and launch['ffn_pattern'] == 'DDDD'
  assert launch['ssm_scan_path'] == 'plain'
  assert launch['ssm_state'] == [INNER, STATE]
  assert launch['grouped_attention_path'] == 'plain'
  assert 'rope' not in launch and 'experts_held' not in launch
  assert cli.main(['trace', path, '--json']) == 0
  forward = json.loads(capsys.readouterr().out)['forward']
  assert forward['ssm_scan_paths'] == ['plain']
  assert forward['ssm_states'] == ['128x16']
  assert cli.main(['trace', path]) == 0
  assert ('layers: MMFM (selective scan: plain; state 128x16) '
          '(grouped-head attention: plain); feed-forward: DDDD'
          in capsys.readouterr().out)


def test_the_mixer_runs_in_scopes_mamba_conv_and_ssm_scan():
  p = tiny_params(8)
  model = model_lib.get_model(p)
  rows = jnp.zeros((1, p.total_rows, 8, 1))
  variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), rows)
  text = jax.jit(lambda v, r: model.apply(v, r)).lower(
      variables, rows).as_text(debug_info=True)
  # Inside `attention`, where Flax puts the modules' own names between.
  for scope in ('/mamba/conv/', '/mamba/ssm_scan/', '/softmax/'):
    assert re.search(rf'/attention/[^"]*{scope}', text)


# ------------------------------------------------- what the kind declines

def test_tp_and_int8_are_refused_by_name():
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  p = tiny_params(8)
  variables = seeded_variables(model_lib.get_model(p), p, seed=7)
  with pytest.raises(ValueError, match=rf"'{KIND}' is not served with --tp: "
                     r"parallel/partition_rules.py has no rule for the Mamba"):
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
  match = (rf"'{KIND}' is not served by `dctpu {command}`: the selective "
           'scan has no backward pass')
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
