"""The fourth encoder block kind (config.BLOCK_LATENT_MOE): multi-head
latent attention in every layer and a feed-forward chosen per layer, one
leading dense SwiGLU and sparse experts behind it (sigmoid router with a
selection bias, a scaling factor, an ungated shared expert).

Toy sizes on the CPU (hidden 64; 4 heads of 16 + 8 / 12 out of a latent of
24; dense width 96; 16 experts of width 24, 4 a token, 8 or all 16 held; 3
layers: 1 dense, 2 with experts; L 12 and 100). What is held here: the
program's model, through get_model and through ModelRunner, against a
test-local plain reference (tests/mla_moe_reference.py: the attention as
published, one product over concatenated keys and the rotation over
interleaved pairs; the published top-k routine; the experts as a plain
loop) on seeded weights; both per-layer patterns in the parameter tree and
in the spans; what the kind refuses by name.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from deepconsensus_tpu.ops import latent_attention
from tests import mla_moe_reference as ref
from tests.test_grouped_product import kernel_taken as as_on_one_tpu
from tests.test_power_retention import pileup_rows

PRESET = 'transformer_learn_values_mla_moe+custom'
KIND = config_lib.BLOCK_LATENT_MOE
LENGTHS = (12, 100)
TOP_K = 4
HELD = ((8, 8), (0, 16))  # experts 8 ... 15 of 16, and all of them


def tiny_params(length=12, held=(8, 8), **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_hidden_layers = 3
    p.num_heads = 4
    p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim = 16, 8, 12
    p.kv_lora_rank = 24
    p.filter_size = 96
    p.num_experts, p.num_experts_per_tok = 16, TOP_K
    p.moe_intermediate_size = 24
    p.shared_expert_intermediate_size = 48
    p.experts_held_first, p.experts_held_count = held
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def seeded_variables(model, p, seed=0):
  """model.init's tree with every leaf drawn anew, away from its init, so
  that each one counts: norm weights, the selection bias."""
  rows = jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)
  tree = jax.jit(model.init)(jax.random.PRNGKey(seed), rows)['params']
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  rng = np.random.default_rng(seed)
  leaves = []
  for path, leaf in flat:
    name = '/'.join(str(getattr(k, 'key', k)) for k in path)
    if name.endswith('scale'):
      value = rng.uniform(0.5, 1.5, leaf.shape)
    elif name.endswith('router_selection_bias'):
      value = rng.uniform(-0.2, 0.2, leaf.shape)
    elif name.endswith('bias'):
      value = rng.normal(0, 0.02, leaf.shape)
    elif 'embedding' in name:
      value = np.asarray(leaf)
    else:
      fan_in = (np.prod(leaf.shape[:-1])
                if name.endswith('output_transform/kernel')
                else leaf.shape[0] if name.endswith(('query/kernel',
                                                     'kv_b/kernel'))
                else leaf.shape[-2])
      value = rng.normal(0, fan_in ** -0.5, leaf.shape)
    leaves.append(jnp.asarray(value, jnp.float32))
  return {'params': jax.tree_util.tree_unflatten(
      jax.tree_util.tree_structure(tree), leaves)}


def reference(variables, rows, p, **faults):
  """(logits, assignments [expert layers, held]) of the plain reference."""
  with jax.default_matmul_precision('highest'):
    logits, counts = ref.logits(
        variables['params'], jnp.asarray(rows[..., 0]),
        max_passes=p.max_passes, ffn_pattern=config_lib.ffn_pattern(p),
        nope=p.qk_nope_head_dim, rope=p.qk_rope_head_dim,
        rank=p.kv_lora_rank, theta=p.rope_theta, eps=p.rms_norm_eps,
        top_k=p.num_experts_per_tok, factor=p.routed_scaling_factor,
        renormalise=p.norm_topk_prob, first=p.experts_held_first, **faults)
  return np.asarray(logits), counts


def _runner(p, variables, batch_size=8, mesh=None):
  options = runner_lib.InferenceOptions(batch_size=batch_size)
  options.max_passes = p.max_passes
  options.max_length = p.max_length
  options.use_ccs_bq = p.use_ccs_bq
  return runner_lib.ModelRunner(p, variables, options, mesh=mesh), options


# ------------------------------------------------ the program and the reference

@pytest.mark.parametrize('held', HELD, ids=['half_held', 'all_held'])
@pytest.mark.parametrize('length', LENGTHS)
def test_model_agrees_with_the_plain_reference_in_float32(length, held):
  p = tiny_params(length, held)
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=length)
  rows = pileup_rows(p, 3, seed=length)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))(variables, jnp.asarray(rows))
  want, want_counts = reference(variables, rows, p)
  assert got['logits'].shape == (3, length, 5)
  # float32 throughout, two orders of summation (two score products
  # against one over concatenated keys, halves against pairs, grouped
  # against looped): rounding of sums of a few hundred terms.
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  counts = np.asarray(model_lib.expert_assignments(sown['moe_counts']))
  # One row an EXPERT layer: the leading dense layer has none.
  assert counts.shape == (2, held[1])
  assert np.array_equal(counts, want_counts)
  if held[1] == 16:
    assert counts.sum() == 2 * 3 * length * TOP_K
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.mark.parametrize('fault', ['bias_in_weights', 'factor_dropped',
                                   'rotary_left_out', 'no_bias'])
def test_reference_faults_are_seen_at_this_tolerance(fault):
  """What the float32 tolerance above would catch: each of these moves the
  reference's own logits by far more than 1e-4."""
  p = tiny_params(12, (0, 16))
  variables = seeded_variables(model_lib.get_model(p), p, seed=3)
  rows = pileup_rows(p, 2, seed=3)
  a, counts = reference(variables, rows, p)
  if fault == 'factor_dropped':
    with p.unlocked():
      p.routed_scaling_factor = 1.0
    b, _ = reference(variables, rows, p)
  elif fault == 'no_bias':
    moved = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if 'router_selection_bias'
        in str(path) else leaf, variables)
    b, other_counts = reference(moved, rows, p)
    assert not np.array_equal(counts, other_counts)  # who is chosen moves
  else:
    b, _ = reference(variables, rows, p, **(
        dict(bias_in_weights=True) if fault == 'bias_in_weights'
        else dict(rotary=False)))
  assert np.abs(a - b).max() > 0.01


@pytest.mark.parametrize('length', LENGTHS)
def test_model_runner_serves_the_reference_bases_in_float32(length, tmp_path):
  p = tiny_params(length)
  variables = seeded_variables(model_lib.get_model(p), p, seed=1)
  runner, _ = _runner(p, variables)
  rows = pileup_rows(p, 8, seed=2)
  path = str(tmp_path / 'spans.jsonl')
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

  # What the normal path says of the kind, and what it counts of it.
  stats = runner.dispatch_stats()
  assert stats['block_kind'] == KIND
  # One pack of 8, TWO expert layers of the three, 4 experts a position.
  assert stats['moe_assignments_total'] == 8 * length * 2 * TOP_K
  assert stats['moe_assignments_held'] == want_counts.sum()
  assert stats['moe_expert_load_max'] == want_counts.max()
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  (launch,) = [e['args'] for e in events if e['name'] == 'forward_launch']
  (drain,) = [e['args'] for e in events if e['name'] == 'finalize_drain']
  assert launch['block_kind'] == KIND and launch['attention_path'] == 'xla'
  assert 'delta_rule_path' not in launch
  # The toy heads are no lane tiles and the CPU is no TPU.
  assert launch['latent_attention_path'] == 'plain'
  assert launch['grouped_product_path'] == 'ragged_dot'
  assert launch['combine_path'] == 'gather'
  assert launch['moe_turns'] == 1
  assert launch['layer_pattern'] == 'LLL' and launch['ffn_pattern'] == 'DEE'
  assert launch['experts_held'] == [8, 16]
  assert launch['experts_published'] == 16
  assert launch['router_scoring'] == 'sigmoid_bias'
  assert drain['moe_assignments_total'] == stats['moe_assignments_total']
  assert drain['moe_assignments_held'] == stats['moe_assignments_held']
  assert drain['moe_expert_load_min'] == want_counts.min()


def test_predict_path_runs_the_kind_in_bfloat16():
  """The preset as shipped (bfloat16 leaves and stream) at the toy widths:
  ModelRunner.predict stays near the float32 reference of the rounded
  weights."""
  p = tiny_params(held=(0, 16), dtype='bfloat16', inference_dtype='bfloat16')
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
  # bfloat16 keeps 8 bits of every product's operands through three layers
  # and may route a near-tie to another expert: the served base is held to
  # the reference's where its top two logits stand a quarter apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 0.25
  assert clear.mean() > 0.5
  assert (np.asarray(ids)[clear] == want.argmax(-1)[clear]).mean() > 0.97
  # Every expert is held: every assignment of every position is computed.
  stats = runner.dispatch_stats()
  assert stats['moe_assignments_held'] == stats['moe_assignments_total']


def test_dctpu_trace_lists_both_patterns_and_the_router(tmp_path, capsys):
  from deepconsensus_tpu import cli

  p = tiny_params(12)
  variables = seeded_variables(model_lib.get_model(p), p, seed=6)
  runner, _ = _runner(p, variables)
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    runner.predict(pileup_rows(p, 8, seed=6))
  finally:
    trace_lib.configure(None)
  assert cli.main(['trace', path, '--json']) == 0
  forward = json.loads(capsys.readouterr().out)['forward']
  assert forward['block_kinds'] == [KIND]
  assert forward['attention_paths'] == ['xla']
  assert forward['delta_rule_paths'] == []
  # The toy heads are no lane tiles and the CPU is no TPU: the plain form.
  assert forward['latent_attention_paths'] == ['plain']
  assert forward['grouped_product_paths'] == ['ragged_dot']
  assert forward['combine_paths'] == ['gather']
  assert forward['moe_turns'] == [1]
  assert forward['layer_patterns'] == ['LLL']
  assert forward['ffn_patterns'] == ['DEE']
  assert forward['router_scorings'] == ['sigmoid_bias']
  assert forward['experts_held'] == [[8, 16, 16]]
  assert cli.main(['trace', path]) == 0
  assert ('layers: LLL (latent attention: plain); experts 8-15 of 16 held '
          '(router: sigmoid_bias; grouped products: ragged_dot; combine: '
          'gather; turns a pack: 1); feed-forward: DEE'
          in capsys.readouterr().out)


# ------------------------------------------------------ the per-layer patterns

def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.block_kind == KIND
  assert (p.hidden_size, p.num_hidden_layers, p.num_heads) == (2048, 48, 32)
  assert (p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim,
          p.kv_lora_rank, p.q_lora_rank) == (128, 64, 128, 512, None)
  assert (p.rope_theta, p.rms_norm_eps) == (1e6, 1e-6)
  assert (p.first_k_dense_replace, p.filter_size) == (1, 6144)
  assert (p.num_experts, p.num_experts_per_tok, p.moe_intermediate_size,
          p.shared_expert_intermediate_size, p.norm_topk_prob) == (
              128, 6, 768, 1536, True)
  assert (p.router_scoring, p.router_selection_bias,
          p.routed_scaling_factor, p.shared_expert_gated, p.n_group,
          p.topk_group) == ('sigmoid', True, 2.448, False, 1, 1)
  # As published a process holds every expert; a chip's share is a size.
  assert (p.experts_held_first, p.experts_held_count) == (0, 128)
  assert (p.dtype, p.inference_dtype, p.rezero, p.add_pos_encoding) == (
      'bfloat16', 'bfloat16', False, False)
  assert config_lib.layer_pattern(p) == 'L' * 48
  assert config_lib.ffn_pattern(p) == 'D' + 'E' * 47


@pytest.mark.parametrize('layers,leading,want', [
    (3, 1, 'DEE'), (8, 1, 'DEEEEEEE'), (4, 3, 'DDDE'), (2, 0, 'EE')])
def test_layer_n_is_dense_below_first_k_dense_replace(layers, leading, want):
  p = tiny_params(num_hidden_layers=layers, first_k_dense_replace=leading)
  assert config_lib.ffn_pattern(p) == want
  assert config_lib.layer_pattern(p) == 'L' * layers
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, 12, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  for n, letter in enumerate(want):
    experts = letter == config_lib.FFN_EXPERTS
    assert (f'moe_{n}' in tree) is experts
    assert (f'ffn_{n}' in tree) is not experts
    assert set(tree[f'latent_attention_{n}']) == {
        'query', 'kv_a', 'kv_a_norm', 'kv_b', 'output_transform'}
    assert set(tree[f'ffn_wrapper_{n}']) == {'rms_norm'}
  dense = tree['ffn_%d' % want.index('D')] if 'D' in want else None
  if dense is not None:
    assert set(dense) == {'gate_layer', 'up_layer', 'output_layer'}
    assert dense['gate_layer']['kernel'].shape == (64, 96)
  moe = tree['moe_%d' % want.index('E')]
  # No gate on the shared expert; the selection bias beside the router.
  assert set(moe) == {'router', 'router_selection_bias', 'experts_gate',
                      'experts_up', 'experts_down', 'shared_expert'}
  assert moe['router_selection_bias'].shape == (16,)
  assert moe['shared_expert']['up_layer']['kernel'].shape == (64, 48)


@pytest.mark.parametrize('preset,want', [
    ('transformer_learn_values+test', 'D'),
    ('transformer_learn_values_retention+custom', 'D'),
    ('transformer_learn_values_gdn_moe+custom', 'E')])
def test_kinds_whose_feed_forwards_are_alike_repeat_one_letter(preset, want):
  p = config_lib.get_config(preset)
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.ffn_pattern(p) == want * p.num_hidden_layers


def test_eight_layers_at_the_published_widths_have_the_hand_counted_parameters():
  """By shape alone: no array of the 9.09 GB is made."""
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 8
  config_lib.finalize_params(p, is_training=False)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, p.max_length, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda *names: sum(
      leaf.size for name in names
      for leaf in jax.tree_util.tree_leaves(tree[name]))
  attention = tree['latent_attention_0']
  assert attention['query']['kernel'].shape == (2048, 32, 192)
  assert attention['kv_a']['kernel'].shape == (2048, 576)
  assert attention['kv_b']['kernel'].shape == (512, 32, 256)
  assert attention['output_transform']['kernel'].shape == (32, 128, 2048)
  assert count('latent_attention_0') == 26_345_984
  assert count('latent_attention_0', 'attention_wrapper_0', 'ffn_wrapper_0',
               'ffn_0') == 64_098_816
  moe = tree['moe_1']
  assert moe['experts_gate'].shape == (128, 2048, 768)
  assert moe['router']['kernel'].shape == (2048, 128)
  experts = sum(moe[name].size for name in (
      'experts_gate', 'experts_up', 'experts_down'))
  assert experts == 128 * 4_718_592 == 603_979_776
  assert count('latent_attention_1', 'attention_wrapper_1', 'ffn_wrapper_1',
               'moe_1') == 640_029_312
  block = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree)) - 2048
  assert block == 64_098_816 + 7 * 640_029_312 == 4_544_304_000


# --------------------------------- the flat stream and the window-tile kernel

PUBLISHED_HEADS = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128)


def test_stack_on_the_flat_stream_through_the_kernel_is_the_stack_of_modules(
    monkeypatch):
  """Four heads of the published sizes on the toy stream, bfloat16: where
  `latent_attention_path` says so the stack runs flat from its first layer
  to the head, every latent attention through the Pallas call, the dense
  and the expert feed-forwards on [B*L, H]; the same leaves, the modules'
  own outputs up to the order of a float32 sum."""
  p = tiny_params(100, held=(0, 16), dtype='bfloat16',
                  inference_dtype='bfloat16', **PUBLISHED_HEADS)
  model = model_lib.get_model(p)
  variables = jax.tree_util.tree_map(
      lambda a: a.astype(jnp.bfloat16), seeded_variables(model, p, seed=21))
  rows = jnp.asarray(pileup_rows(p, 8, seed=21))
  forward = lambda v, r: model.apply(v, r, mutable=['moe_counts'])
  want, want_sown = jax.jit(forward)(variables, rows)
  traced = []
  real = latent_attention.window_tile_attention
  monkeypatch.setattr(
      latent_attention, 'window_tile_attention',
      lambda *a, **k: traced.append(k['length']) or real(*a, **k))
  init = lambda k: model.init(k, jnp.zeros((1, p.total_rows, 100, 1)))
  with as_on_one_tpu(monkeypatch):
    # (A function of its own: jit's cache does not see the declaration.)
    got, got_sown = jax.jit(lambda v, r: forward(v, r))(variables, rows)
    # And init, even so declared, runs the modules: the tree is theirs.
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
  assert traced == [100] * 3  # every layer, none through the plain form
  assert got.shape == want.shape == (8, 100, 5)
  difference = np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32))
  # A weight a unit off moves an output a unit; a routed near-tie that
  # falls the other way moves a position by more.
  assert np.median(difference) < 2e-3 and (difference < 0.05).mean() > 0.98
  counts = lambda sown: np.asarray(
      model_lib.expert_assignments(sown['moe_counts']))
  assert np.abs(counts(got_sown) - counts(want_sown)).sum() <= (
      0.01 * counts(want_sown).sum())
  shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
  assert shapes(tree['params']) == shapes(variables['params'])


def test_parameter_tree_of_latent_attention_is_what_it_was():
  p = tiny_params(100, **PUBLISHED_HEADS)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, 100, 1))),
      jax.random.PRNGKey(0))['params']['encoder']['latent_attention_1']
  assert jax.tree_util.tree_map(lambda a: a.shape, tree) == {
      'query': {'kernel': (64, 4, 192)}, 'kv_a': {'kernel': (64, 24 + 64)},
      'kv_a_norm': {'scale': (24,)}, 'kv_b': {'kernel': (24, 4, 256)},
      'output_transform': {'kernel': (4, 128, 64)}}


def test_forward_launch_says_window_tile_kernel_as_on_one_tpu(
    monkeypatch, tmp_path, capsys):
  from deepconsensus_tpu import cli

  p = tiny_params(100, held=(0, 16), dtype='bfloat16',
                  inference_dtype='bfloat16', **PUBLISHED_HEADS)
  variables = seeded_variables(model_lib.get_model(p), p, seed=22)
  path = str(tmp_path / 'spans.jsonl')
  with as_on_one_tpu(monkeypatch):
    runner, _ = _runner(p, variables)
    trace_lib.clear_early()
    trace_lib.configure(path, tier='run')
    try:
      ids, _quals = runner.predict(pileup_rows(p, 8, seed=22))
    finally:
      trace_lib.configure(None)
  assert np.asarray(ids).shape == (8, 100)
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  (launch,) = [e['args'] for e in events if e['name'] == 'forward_launch']
  assert launch['latent_attention_path'] == 'window_tile_kernel'
  assert launch['attention_path'] == 'xla'
  assert cli.main(['trace', path]) == 0
  assert 'layers: LLL (latent attention: window_tile_kernel); experts' in (
      capsys.readouterr().out)


# ------------------------------------------------- what the kind declines

def test_attention_path_declines_the_kind_even_on_a_tpu(monkeypatch):
  from deepconsensus_tpu.ops import pallas_util

  p = tiny_params(100, dtype='bfloat16')
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    paths = lambda p, batch=8, length=100: model_lib.kernel_paths(
        p, batch=batch, length=length)
    assert paths(p)['attention_path'] == model_lib.ATTENTION_XLA
    assert 'delta_rule_path' not in paths(p)
    # Its own attention declines the toy heads (16 + 8 / 12 are no lane
    # tiles) and takes the kernel at the published ones.
    assert paths(p)['latent_attention_path'] == 'plain'
    published = config_lib.get_config(PRESET)
    config_lib.finalize_params(published, is_training=False)
    assert paths(published)['latent_attention_path'] == 'window_tile_kernel'
    assert paths(published, length=130)['latent_attention_path'] == 'plain'
    # The grouped products decline the toy widths, and take the kernel at
    # whole lane tiles (the published 2048 and 768 are).
    assert paths(p)['grouped_product_path'] == 'ragged_dot'
    wide = tiny_params(100, dtype='bfloat16', transformer_input_size=128,
                       moe_intermediate_size=256)
    assert paths(wide)['grouped_product_path'] == 'group_kernel'
    # The combine likewise: rows of whole lane tiles, and a turn's tokens
    # (32 x 100) whole tiles of 128.
    assert paths(p, batch=32)['combine_path'] == 'gather'
    assert paths(wide, batch=32)['combine_path'] == 'token_tile_kernel'
    assert paths(wide, batch=5)['combine_path'] == 'gather'


@pytest.mark.parametrize('flag', ['fused', 'ragged'])
def test_fused_and_ragged_hot_paths_decline_the_kind(flag):
  import flax.linen as nn

  p = tiny_params(use_fused_hotpath=True)
  model = model_lib.get_model(p)
  rows = jnp.zeros((2, 25, 12))

  def eligible(m):
    if flag == 'fused':
      return m._fused_hotpath_eligible(rows, False)
    return m._ragged_hotpath_eligible(rows)

  assert nn.apply(eligible, model)({'params': {}}) is False


def test_tp_is_refused_by_name_and_dp_is_served():
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  p = tiny_params(12)
  variables = seeded_variables(model_lib.get_model(p), p, seed=7)
  with pytest.raises(ValueError, match=rf"'{KIND}' is not served with --tp: "
                     r'parallel/partition_rules.py has no expert axis'):
    _runner(p, variables, mesh=mesh_lib.make_mesh(
        dp=2, tp=2, devices=jax.devices()[:4]))
  rows = pileup_rows(p, 8, seed=7)
  alone, _ = _runner(p, variables)
  sharded, _ = _runner(p, variables, mesh=mesh_lib.make_mesh(
      dp=2, tp=1, devices=jax.devices()[:2]))
  ids, _quals = alone.predict(rows)
  ids_dp, _quals_dp = sharded.predict(rows)
  assert np.array_equal(np.asarray(ids), np.asarray(ids_dp))
  assert sharded.dispatch_stats()['moe_assignments_held'] == (
      alone.dispatch_stats()['moe_assignments_held'])


def test_int8_is_refused_by_name():
  p = tiny_params(12, quantize_matmuls='int8')
  variables = seeded_variables(model_lib.get_model(p), p, seed=8)
  with pytest.raises(ValueError, match=rf"'{KIND}' is not served with "
                     r"quantize_matmuls='int8': models/quantize.py has no "
                     r"per-expert scales"):
    _runner(p, variables)


@pytest.mark.parametrize('command', ['train', 'distill', 'export'])
def test_training_and_export_of_the_kind_are_refused_by_name(command,
                                                             tmp_path):
  from deepconsensus_tpu.models import distill as distill_lib
  from deepconsensus_tpu.models import export as export_lib
  from deepconsensus_tpu.models import train as train_lib

  p = tiny_params(12)
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


@pytest.mark.parametrize('key,value', [('q_lora_rank', 16), ('n_group', 2),
                                       ('topk_group', 2)])
def test_a_query_latent_and_a_group_limit_are_refused_by_name(key, value):
  p = tiny_params(12, **{key: value})
  with pytest.raises(ValueError, match=f'{key} {value}.* not served'):
    jax.eval_shape(
        lambda k: model_lib.get_model(p).init(
            k, jnp.zeros((1, p.total_rows, 12, 1))), jax.random.PRNGKey(0))
