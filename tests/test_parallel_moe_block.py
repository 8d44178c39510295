"""The fifth encoder block kind (config.BLOCK_PARALLEL_WINDOW_MOE): a
parallel block (one bias-free LayerNorm, attention and feed-forward both on
its output, one addition), window layers that rotate the whole head three
to one full layer without positions, grouped heads without q/k norm or
gate, sparse experts scored by a sigmoid without a bias, shared experts
that are averaged.

Toy sizes on the CPU (hidden 64; 32 query heads over 2 key-value heads of
8, so groups of 16 and a query projection four times the hidden size; 16
experts of width 24, 4 a token, 8 or all 16 held; 4 shared experts of 24
run as one of 96 times 1/4; 4 layers `WWWF`; window 8 at L 24 and 100,
which binds, and window 16 at L 12, which does not). What is held here: the
program's model, through get_model and through ModelRunner, against a
test-local plain reference (tests/parallel_moe_reference.py: the published
pair rotation on un-permuted columns, k and v repeated to the query heads,
the window mask built always, the shared experts one by one) on seeded
weights; the patterns and the form in the parameter tree and in the spans;
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
from tests import parallel_moe_reference as ref
from tests.test_mla_moe_block import _runner
from tests.test_power_retention import pileup_rows

PRESET = 'transformer_learn_values_parallel_moe+custom'
KIND = config_lib.BLOCK_PARALLEL_WINDOW_MOE
TOP_K = 4
N_SHARED = 4


def tiny_params(length=24, held=(8, 8), window=8, **overrides):
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.max_passes = 5
    p.max_length = length
    p.transformer_input_size = 64
    p.num_hidden_layers = 4
    p.sliding_window = window
    p.num_heads, p.num_kv_heads, p.head_dim = 32, 2, 8
    p.num_experts, p.num_experts_per_tok = 16, TOP_K
    p.moe_intermediate_size = p.filter_size = 24
    p.shared_expert_intermediate_size = N_SHARED * 24
    p.experts_held_first, p.experts_held_count = held
    p.dtype = 'float32'
    p.inference_dtype = 'float32'
    for key, value in overrides.items():
      p[key] = value
  config_lib.finalize_params(p, is_training=False)
  return p


def seeded_variables(model, p, seed=0):
  """model.init's tree with every leaf drawn anew, away from its init, so
  that each one counts: the norms' weights among them."""
  rows = jnp.zeros((1, p.total_rows, p.max_length, 1), jnp.float32)
  tree = jax.jit(model.init)(jax.random.PRNGKey(seed), rows)['params']
  flat, _ = jax.tree_util.tree_flatten_with_path(tree)
  rng = np.random.default_rng(seed)
  leaves = []
  for path, leaf in flat:
    name = '/'.join(str(getattr(k, 'key', k)) for k in path)
    if name.endswith('scale'):
      value = rng.uniform(0.5, 1.5, leaf.shape)
    elif name.endswith('bias'):
      value = rng.normal(0, 0.02, leaf.shape)
    elif 'embedding' in name:
      value = np.asarray(leaf)
    else:
      fan_in = (np.prod(leaf.shape[:-1])
                if name.endswith('output_transform/kernel')
                else leaf.shape[0] if name.endswith((
                    'query/kernel', 'key/kernel', 'value/kernel'))
                else leaf.shape[-2])
      value = rng.normal(0, fan_in ** -0.5, leaf.shape)
    leaves.append(jnp.asarray(value, jnp.float32))
  return {'params': jax.tree_util.tree_unflatten(
      jax.tree_util.tree_structure(tree), leaves)}


def reference(variables, rows, p, **faults):
  """(logits, assignments [layers, held]) of the plain reference."""
  with jax.default_matmul_precision('highest'):
    logits, counts = ref.logits(
        variables['params'], jnp.asarray(rows[..., 0]),
        max_passes=p.max_passes, layer_pattern=config_lib.layer_pattern(p),
        theta=p.rope_theta, window=p.sliding_window, eps=p.layer_norm_eps,
        top_k=p.num_experts_per_tok, n_shared=p.num_shared_experts,
        renormalise=p.norm_topk_prob, first=p.experts_held_first, **faults)
  return np.asarray(logits), counts


# ------------------------------------------------ the program and the reference

@pytest.mark.parametrize('length,held,window', [
    (24, (8, 8), 8), (24, (0, 16), 8), (100, (8, 8), 8), (12, (8, 8), 16)],
                         ids=['L24_half_held', 'L24_all_held', 'L100_half_held',
                              'L12_window_covers_it'])
def test_model_agrees_with_the_plain_reference_in_float32(length, held,
                                                          window):
  p = tiny_params(length, held, window)
  assert config_lib.layer_pattern(p) == 'WWWF'
  model = model_lib.get_model(p)
  variables = seeded_variables(model, p, seed=length)
  rows = pileup_rows(p, 3, seed=length)
  with jax.default_matmul_precision('highest'):
    got, sown = jax.jit(lambda v, r: model.apply(
        v, r, method=model.apply_with_intermediates,
        mutable=['moe_counts']))(variables, jnp.asarray(rows))
  want, want_counts = reference(variables, rows, p)
  assert got['logits'].shape == (3, length, 5)
  # float32 throughout, two orders of summation (halves against pairs,
  # grouped heads against repeated keys, one wide shared expert against
  # four, grouped products against a loop): rounding of sums of a few
  # hundred terms.
  np.testing.assert_allclose(np.asarray(got['logits']), want, atol=1e-4)
  counts = np.asarray(model_lib.expert_assignments(sown['moe_counts']))
  assert counts.shape == (4, held[1])
  assert np.array_equal(counts, want_counts)
  if held[1] == 16:
    assert counts.sum() == 4 * 3 * length * TOP_K
  # The logits spread: a saturated or dead head would compare nothing.
  assert np.asarray(got['preds']).max(axis=-1).std() > 0.01


@pytest.mark.parametrize('fault', ['sequential', 'rotate_full',
                                   'shared_summed', 'window_left_out'])
def test_reference_faults_are_seen_at_this_tolerance(fault):
  """What the float32 tolerance above would catch: a sequential block in
  place of the parallel one, the full layer rotated, the shared experts
  summed and not averaged, and the window left out where it binds, each
  moves the reference's own logits by far more than 1e-4."""
  p = tiny_params(24, (0, 16))
  variables = seeded_variables(model_lib.get_model(p), p, seed=3)
  rows = pileup_rows(p, 2, seed=3)
  a, _ = reference(variables, rows, p)
  if fault == 'window_left_out':
    with p.unlocked():
      p.sliding_window = 24
    b, _ = reference(variables, rows, p)
  else:
    b, _ = reference(variables, rows, p, **{fault: True})
  assert np.abs(a - b).max() > 0.01


def test_halves_on_permuted_columns_are_the_published_pairs():
  """The program rotates halves (i, i + D / 2); a checkpoint published for
  interleaved pairs (2i, 2i + 1) loads with the columns of every head of
  W_q and W_k in `halves_from_pairs` order, and q . k is then a sum over
  the same pairs: a permutation of columns and no other arithmetic."""
  from deepconsensus_tpu.ops import latent_attention

  rng = np.random.default_rng(0)
  length, heads, d, hidden = 12, 3, 16, 20
  u = jnp.asarray(rng.normal(size=(2, length, hidden)), jnp.float32)
  published = rng.normal(0, hidden ** -0.5, (2, hidden, heads, d)).astype(
      np.float32)
  order = latent_attention.halves_from_pairs(d)
  loaded = published[..., order]  # what the program's leaves hold
  np.testing.assert_array_equal(
      np.asarray(ref.published_order(jnp.asarray(loaded))), published)
  project = lambda w: jnp.einsum('blh,hnd->blnd', u, jnp.asarray(w))
  with jax.default_matmul_precision('highest'):
    q, k = (model_lib.apply_rotary(project(w), 5e4) for w in loaded)
    want_q, want_k = (ref.rotary_pairs(project(w), 5e4) for w in published)
    got = jnp.einsum('bihd,bjhd->bhij', q, k)
    want = jnp.einsum('bihd,bjhd->bhij', want_q, want_k)
  # The same 16 products a score, summed in another order.
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
  assert np.abs(np.asarray(want)).max() > 1.0


def test_one_wide_scaled_shared_expert_is_four_averaged():
  """The mean of m SwiGLUs is one SwiGLU of m x the width with its down
  product scaled by 1 / m: the program runs the wide one, the reference
  the four."""
  p = tiny_params(12)
  moe = model_lib._sparse_experts(p, 0, jnp.float32)
  assert moe.shared_scale == 0.25 and moe.shared_width == 4 * 24
  x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, 64)),
                  jnp.float32)
  rng = np.random.default_rng(2)
  draw = lambda a, b: jnp.asarray(rng.normal(0, a ** -0.5, (a, b)),
                                  jnp.float32)
  w = {'gate_layer': {'kernel': draw(64, 96)},
       'up_layer': {'kernel': draw(64, 96)},
       'output_layer': {'kernel': draw(96, 64)}}
  with jax.default_matmul_precision('highest'):
    wide = model_lib.GatedFeedForward(hidden_size=64, filter_size=96).apply(
        {'params': w}, x, deterministic=True) * moe.shared_scale
    four = ref.shared_experts(w, x.reshape(-1, 64), N_SHARED)
    summed = ref.shared_experts(w, x.reshape(-1, 64), N_SHARED,
                                averaged=False)
  np.testing.assert_allclose(np.asarray(wide).reshape(-1, 64),
                             np.asarray(four), atol=1e-5)
  assert np.abs(np.asarray(summed) - np.asarray(four)).max() > 0.1


def test_the_window_masks_only_where_it_is_shorter_than_the_forward():
  """A window that covers the forward's length builds no mask (at L=100
  the published 4,096 masks nothing); one that binds is the reference's
  mask, |i - j| < window both ways."""
  attention = lambda window: model_lib.GroupedSoftmaxAttention(
      hidden_size=16, num_heads=4, num_kv_heads=2, head_dim=4, rotary_dim=4,
      rope=5e4, output_gate=False, qk_norm=False, window=window)
  x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 10, 16)),
                  jnp.float32)
  variables = attention(None).init(jax.random.PRNGKey(0), x,
                                   deterministic=True)
  run = lambda window: np.asarray(attention(window).apply(
      variables, x, deterministic=True))
  assert np.array_equal(run(None), run(10)) and np.array_equal(
      run(None), run(4096))
  assert np.abs(run(None) - run(3)).max() > 1e-3
  masked = jax.make_jaxpr(lambda: attention(3).apply(
      variables, x, deterministic=True))()
  covered = jax.make_jaxpr(lambda: attention(10).apply(
      variables, x, deterministic=True))()
  assert 'select_n' in str(masked) and 'select_n' not in str(covered)
  # Window 1 attends to the position itself alone: the values projected.
  w = variables['params']
  with jax.default_matmul_precision('highest'):
    v = jnp.einsum('blh,hnd->blnd', x, w['value']['kernel'])
    alone = jnp.einsum('blnd,ndh->blh', jnp.repeat(v, 2, axis=2),
                       w['output_transform']['kernel'])
    got = attention(1).apply(variables, x, deterministic=True)
  np.testing.assert_allclose(np.asarray(got), np.asarray(alone), atol=1e-5)


@pytest.mark.parametrize('length', [24, 100])
def test_model_runner_serves_the_reference_bases_in_float32(length, tmp_path):
  p = tiny_params(length)
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

  # What the normal path says of the kind, and what it counts of it.
  stats = runner.dispatch_stats()
  assert stats['block_kind'] == KIND
  # One pack of 8, four expert layers, 4 experts a position.
  assert stats['moe_assignments_total'] == 8 * length * 4 * TOP_K
  assert stats['moe_assignments_held'] == want_counts.sum()
  assert stats['moe_expert_load_max'] == want_counts.max()
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  (launch,) = [e['args'] for e in events if e['name'] == 'forward_launch']
  (drain,) = [e['args'] for e in events if e['name'] == 'finalize_drain']
  assert launch['block_kind'] == KIND and launch['attention_path'] == 'xla'
  assert launch['block_form'] == 'parallel'
  assert 'delta_rule_path' not in launch
  assert 'latent_attention_path' not in launch
  # Heads of 8 on the CPU: the plain form.
  assert launch['grouped_attention_path'] == 'plain'
  assert launch['grouped_product_path'] == 'ragged_dot'
  assert launch['combine_path'] == 'gather'
  assert launch['moe_turns'] == 1
  assert launch['layer_pattern'] == 'WWWF' and launch['ffn_pattern'] == 'EEEE'
  assert launch['attention_window'] == 8
  assert launch['experts_held'] == [8, 16]
  assert launch['experts_published'] == 16
  assert launch['router_scoring'] == 'sigmoid'
  assert launch['shared_experts'] == N_SHARED
  assert drain['moe_assignments_total'] == stats['moe_assignments_total']
  assert drain['moe_assignments_held'] == stats['moe_assignments_held']
  assert drain['moe_expert_load_max'] == want_counts.max()
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
  # bfloat16 keeps 8 bits of every product's operands through four layers
  # and may route a near-tie to another expert: the served base is held to
  # the reference's where its top two logits stand a quarter apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 0.25
  assert clear.mean() > 0.5
  assert (np.asarray(ids)[clear] == want.argmax(-1)[clear]).mean() > 0.97
  # Every expert is held: every assignment of every position is computed.
  stats = runner.dispatch_stats()
  assert stats['moe_assignments_held'] == stats['moe_assignments_total']


def test_dctpu_trace_lists_the_form_the_patterns_and_the_router(tmp_path,
                                                                capsys):
  from deepconsensus_tpu import cli

  p = tiny_params(24)
  variables = seeded_variables(model_lib.get_model(p), p, seed=6)
  runner, _ = _runner(p, variables)
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.clear_early()
  trace_lib.configure(path, tier='run')
  try:
    runner.predict(pileup_rows(p, 8, seed=6))
  finally:
    trace_lib.configure(None)
  assert cli.main(['trace', path, '--json']) == 0
  forward = json.loads(capsys.readouterr().out)['forward']
  assert forward['block_kinds'] == [KIND]
  assert forward['block_forms'] == ['parallel']
  assert forward['attention_paths'] == ['xla']
  assert forward['delta_rule_paths'] == []
  assert forward['grouped_product_paths'] == ['ragged_dot']
  assert forward['combine_paths'] == ['gather']
  assert forward['moe_turns'] == [1]
  assert forward['layer_patterns'] == ['WWWF']
  assert forward['ffn_patterns'] == ['EEEE']
  assert forward['attention_windows'] == [8]
  assert forward['router_scorings'] == ['sigmoid']
  assert forward['shared_experts'] == [N_SHARED]
  assert forward['experts_held'] == [[8, 16, 16]]
  assert cli.main(['trace', path]) == 0
  assert ('layers: WWWF (parallel block) (window: 8) (grouped-head '
          'attention: plain); experts 8-15 of 16 '
          'held (router: sigmoid; grouped products: ragged_dot; combine: '
          'gather; turns a pack: 1; shared experts: 4 averaged); '
          'feed-forward: EEEE' in capsys.readouterr().out)


# ------------------------------------------------- the patterns and the form

def test_preset_states_the_published_sizes():
  p = config_lib.get_config(PRESET)
  config_lib.finalize_params(p, is_training=False)
  assert p.block_kind == KIND
  assert (p.hidden_size, p.num_hidden_layers, p.layer_switch) == (
      4096, 32, 4)
  assert (p.num_heads, p.num_kv_heads, p.head_dim, p.sliding_window) == (
      128, 8, 128, 4096)
  assert (p.rope_theta, p.layer_norm_eps) == (5e4, 1e-5)
  assert (p.first_k_dense_replace, p.num_experts, p.num_experts_per_tok,
          p.moe_intermediate_size, p.num_shared_experts,
          p.shared_expert_combination, p.shared_expert_intermediate_size,
          p.norm_topk_prob) == (0, 128, 8, 4096, 4, 'average', 16384, True)
  assert (p.router_scoring, p.router_selection_bias,
          p.routed_scaling_factor, p.shared_expert_gated) == (
              'sigmoid', False, 1.0, False)
  # As published a process holds every expert; a chip's share is a size.
  assert (p.experts_held_first, p.experts_held_count) == (0, 128)
  assert (p.dtype, p.inference_dtype, p.rezero, p.add_pos_encoding) == (
      'bfloat16', 'bfloat16', False, False)
  assert config_lib.layer_pattern(p) == 'WWWF' * 8
  assert config_lib.ffn_pattern(p) == 'E' * 32


@pytest.mark.parametrize('layers,switch,want', [
    (4, 4, 'WWWF'), (8, 4, 'WWWFWWWF'), (5, 2, 'WFWFW'), (3, 4, 'WWW'),
    (2, 1, 'FF')])
def test_layer_n_is_full_where_n_plus_1_divides_by_layer_switch(layers,
                                                                switch, want):
  p = tiny_params(12, num_hidden_layers=layers, layer_switch=switch)
  assert config_lib.layer_pattern(p) == want
  assert config_lib.ffn_pattern(p) == 'E' * layers
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, 12, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  for n in range(layers):
    # ONE norm a layer, and window and full layers hold the same leaves:
    # no q/k norm, no gate, no bias anywhere.
    assert set(tree[f'block_norm_{n}']) == {'scale'}
    assert f'attention_wrapper_{n}' not in tree
    assert f'ffn_wrapper_{n}' not in tree
    attention = tree[f'self_attention_{n}']
    assert set(attention) == {'query', 'key', 'value', 'output_transform'}
    assert attention['query']['kernel'].shape == (64, 32, 8)
    assert attention['key']['kernel'].shape == (64, 2, 8)
    moe = tree[f'moe_{n}']
    assert set(moe) == {'router', 'experts_gate', 'experts_up',
                        'experts_down', 'shared_expert'}
    assert moe['shared_expert']['up_layer']['kernel'].shape == (64, 96)
  assert set(tree['output_normalization']) == {'scale'}


@pytest.mark.parametrize('preset,want', [
    ('transformer_learn_values+test', 'sequential'),
    ('transformer_learn_values_retention+custom', 'sequential'),
    ('transformer_learn_values_gdn_moe+custom', 'sequential'),
    ('transformer_learn_values_mla_moe+custom', 'sequential'),
    (PRESET, 'parallel')])
def test_only_this_kind_composes_its_sublayers_in_parallel(preset, want):
  p = config_lib.get_config(preset)
  config_lib.finalize_params(p, is_training=False)
  assert config_lib.block_form(p) == want


def test_one_period_at_the_published_widths_has_the_hand_counted_parameters():
  """By shape alone: no array of the 9.20 GB is made."""
  p = config_lib.get_config(PRESET)
  with p.unlocked():
    p.num_hidden_layers = 4
    p.experts_held_count = 16
  config_lib.finalize_params(p, is_training=False)
  tree = jax.eval_shape(
      lambda k: model_lib.get_model(p).init(
          k, jnp.zeros((1, p.total_rows, p.max_length, 1))),
      jax.random.PRNGKey(0))['params']['encoder']
  count = lambda node: sum(
      leaf.size for leaf in jax.tree_util.tree_leaves(node))
  attention = tree['self_attention_3']
  assert attention['query']['kernel'].shape == (4096, 128, 128)
  assert attention['key']['kernel'].shape == (4096, 8, 128)
  assert attention['value']['kernel'].shape == (4096, 8, 128)
  assert attention['output_transform']['kernel'].shape == (128, 128, 4096)
  assert count(attention) == 142_606_336
  moe = tree['moe_0']
  assert moe['router']['kernel'].shape == (4096, 128)
  assert moe['experts_gate'].shape == (16, 4096, 4096)
  assert moe['experts_down'].shape == (16, 4096, 4096)
  assert count(moe['shared_expert']) == 4 * 50_331_648 == 201_326_592
  outside = (count(attention) + count(tree['block_norm_0'])
             + count(moe['router']) + count(moe['shared_expert']))
  assert outside == 344_461_312
  experts = sum(moe[name].size for name in (
      'experts_gate', 'experts_up', 'experts_down'))
  assert experts == 16 * 50_331_648 == 805_306_368
  layer = outside + experts
  assert layer == 1_149_767_680
  block = count(tree) - 4096  # the final norm
  assert block == 4 * layer == 4_599_070_720
  # Uncut, a layer holds all 128 experts: 13.57 GB in bfloat16.
  assert 2 * (outside + 128 * 50_331_648) == 13_573_824_512


# ------------------------------------------------- what the kind declines

def test_attention_path_declines_the_kind_even_on_a_tpu(monkeypatch):
  from deepconsensus_tpu.ops import pallas_util

  p = tiny_params(100, dtype='bfloat16')
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    paths = lambda p, batch=8: model_lib.kernel_paths(
        p, batch=batch, length=100)
    assert paths(p)['attention_path'] == model_lib.ATTENTION_XLA
    assert 'delta_rule_path' not in paths(p)
    assert 'latent_attention_path' not in paths(p)
    # The grouped products and the combine decline the toy widths, and take
    # their kernels at the published ones: a pack of 256 is two turns of
    # 12,800 tokens, a [4096, 4096] matrix passes in column blocks.
    assert paths(p)['grouped_product_path'] == 'ragged_dot'
    assert paths(p, batch=32)['combine_path'] == 'gather'
    published = config_lib.get_config(PRESET)
    with published.unlocked():
      published.num_hidden_layers, published.experts_held_count = 4, 16
    config_lib.finalize_params(published, is_training=False)
    assert paths(published, batch=256)['grouped_product_path'] == (
        'group_kernel')
    assert paths(published, batch=256)['combine_path'] == (
        'token_tile_kernel')


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


def test_leading_dense_layers_are_refused_by_name():
  p = tiny_params(12, first_k_dense_replace=1)
  with pytest.raises(ValueError,
                     match='first_k_dense_replace 1 is not served'):
    jax.eval_shape(
        lambda k: model_lib.get_model(p).init(
            k, jnp.zeros((1, p.total_rows, 12, 1))), jax.random.PRNGKey(0))
