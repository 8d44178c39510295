"""The third encoder block kind (config.BLOCK_GATED_DELTA_MOE) on the
normal path: through ModelRunner and ConsensusEngine from submit to
delivery against the test-local plain reference, what the path says of the
kind (spans, counters, `dctpu trace`) and counts of it, which hot paths
decline it, and what the kind refuses by name (--tp, int8, train, distill,
export). Sizes, seeded weights and reference are tests/test_gdn_moe_block.py's
(a file of its own so that the two halves run on two workers).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from tests.test_gdn_moe_block import (KIND, LENGTHS, TOP_K, _runner,
                                      reference, seeded_variables,
                                      tiny_params)
from tests.test_power_retention import pileup_rows

# ------------------------------------------------------------ the normal path

@pytest.mark.parametrize('length', LENGTHS)
def test_engine_submit_to_delivery_serves_the_reference_bases(length,
                                                              tmp_path):
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
  path = str(tmp_path / 'spans.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    engine.submit(list(rows), list(range(len(rows))))
    engine.flush()
  finally:
    trace_lib.configure(None)
  assert sorted(delivered) == list(range(19))
  want, want_counts = reference(variables, rows, p)
  ids = np.stack([delivered[t][0] for t in range(19)])
  quals = np.stack([delivered[t][1] for t in range(19)])
  # Where the reference's top two logits are not a rounding apart.
  top = np.sort(want, axis=-1)
  clear = (top[..., -1] - top[..., -2]) > 1e-3
  assert clear.mean() > 0.95
  assert np.array_equal(ids[clear], want.argmax(-1)[clear])
  assert quals.min() >= 0 and len(np.unique(quals)) > 3

  # What the normal path says of the kind, and what it counts of it.
  stats = engine.stats()
  assert stats['block_kind'] == KIND
  assert stats['n_forward_positions'] == 3 * 8 * length
  assert stats['n_forward_shapes'] == 1
  # Three packs of 8 (the tail's 5 padding windows are routed too), 4
  # layers, 4 experts a position.
  assert stats['moe_assignments_total'] == 3 * 8 * length * 4 * TOP_K
  counters = runner.obs.snapshot()
  assert counters['counters']['moe_assignments_total'] == (
      stats['moe_assignments_total'])
  assert counters['counters']['moe_assignments_held'] == (
      stats['moe_assignments_held'])
  assert counters['gauges']['moe_expert_load_max'] == (
      stats['moe_expert_load_max'])
  events = [e for e in summarize_lib.load_trace(path) if e.get('ph') == 'X']
  launches = [e['args'] for e in events if e['name'] == 'forward_launch']
  drains = [e['args'] for e in events if e['name'] == 'finalize_drain']
  assert len(launches) == len(drains) == 3
  for args in launches:
    assert args['block_kind'] == KIND and args['attention_path'] == 'xla'
    # The CPU takes no kernel on its own; nor do heads of 8 anywhere.
    assert args['delta_rule_path'] == 'plain'
    assert 'latent_attention_path' not in args
    # Its gated softmax layer (heads of 256, a quarter rotated) never takes
    # the grouped-head kernel.
    assert args['grouped_attention_path'] == 'plain'
    assert args['grouped_product_path'] == 'ragged_dot'
    assert args['combine_path'] == 'gather'
    # A pack of toy tokens is one turn.
    assert args['moe_turns'] == 1
    assert args['layer_pattern'] == 'GGGS'
    assert args['ffn_pattern'] == 'EEEE'
    assert args['router_scoring'] == 'softmax'
    assert args['experts_held'] == [8, 16]
    assert args['experts_published'] == 16
  assert sum(a['moe_assignments_held'] for a in drains) == (
      stats['moe_assignments_held'])
  assert max(a['moe_expert_load_max'] for a in drains) == (
      stats['moe_expert_load_max'])
  # The two full packs hold windows 0 ... 15: their counts are the
  # reference's for those windows.
  # To within a couple of near-ties of the last expert kept: the program
  # and the reference sum in two orders, and 25,600 assignments are routed.
  _, first_two = reference(variables, rows[:16], p)
  assert abs(drains[0]['moe_assignments_held']
             + drains[1]['moe_assignments_held'] - first_two.sum()) <= 2
  share = stats['moe_assignments_held'] / stats['moe_assignments_total']
  assert 0.3 < share < 0.7  # half the experts are held
  assert want_counts.sum() <= stats['moe_assignments_held']


def test_dctpu_trace_shows_the_pattern_and_the_held_share(tmp_path, capsys):
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
  assert forward['delta_rule_paths'] == ['plain']
  assert forward['grouped_product_paths'] == ['ragged_dot']
  assert forward['combine_paths'] == ['gather']
  assert forward['moe_turns'] == [1]
  assert forward['layer_patterns'] == ['GGGS']
  assert forward['experts_held'] == [[8, 16, 16]]
  assert cli.main(['trace', path]) == 0
  assert ('layers: GGGS (delta rule: plain) (grouped-head attention: '
          'plain); experts 8-15 of 16 held '
          '(router: softmax; grouped products: ragged_dot; combine: gather; '
          'turns a pack: 1); feed-forward: EEEE' in capsys.readouterr().out)


def test_attention_path_declines_the_kind_even_on_a_tpu(monkeypatch):
  from deepconsensus_tpu.ops import pallas_util

  p = tiny_params(100, dtype='bfloat16')
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    assert model_lib.kernel_paths(p, batch=8, length=100)[
        'attention_path'] == model_lib.ATTENTION_XLA
    banded = config_lib.get_config('transformer_learn_values+custom')
    with banded.unlocked():
      banded.dtype = 'bfloat16'
    config_lib.finalize_params(banded, is_training=False)
    assert model_lib.kernel_paths(banded, batch=8, length=100)[
        'attention_path'] == model_lib.ATTENTION_FUSED_SUBLAYER


@pytest.mark.parametrize('where', ['cpu', 'tpu', 'tpu_mesh', 'tpu_heads_of_8',
                                   'tpu_long_window', 'other_kind'])
def test_delta_rule_path_is_the_kernel_on_one_tpu_at_heads_of_128(
    where, monkeypatch):
  """`forward_launch`'s `delta_rule_path`: the model's rule asked as the
  runner asks it. Heads of 128 (the published size) on one TPU device at
  inference take the window kernel; the CPU, a mesh, other head sizes and
  a window over one chunk take the plain form; a kind without the layer
  says nothing."""
  from deepconsensus_tpu.ops import pallas_util

  sizes = {} if where == 'tpu_heads_of_8' else dict(
      linear_key_head_dim=128, linear_value_head_dim=128)
  p = tiny_params(100, **sizes)
  if where == 'other_kind':
    p = config_lib.get_config('transformer_learn_values+custom')
    config_lib.finalize_params(p, is_training=False)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: where != 'cpu')
  length = 600 if where == 'tpu_long_window' else 100
  with pallas_util.single_device_inference(where != 'tpu_mesh'):
    got = model_lib.kernel_paths(p, batch=8, length=length).get(
        'delta_rule_path')
  assert got == {'tpu': 'window_kernel', 'other_kind': None}.get(
      where, 'plain')


@pytest.mark.parametrize('where', ['cpu', 'tpu', 'tpu_mesh', 'tpu_float32',
                                   'tpu_toy_widths', 'tpu_odd_pack',
                                   'other_kind'])
def test_grouped_product_path_is_the_kernel_on_one_tpu_at_lane_tile_widths(
    where, monkeypatch):
  """`forward_launch`'s `grouped_product_path`: the model's rule asked as
  the runner asks it, with the rows of one turn of the pack. bfloat16 at
  widths of whole lane tiles (the published sizes) on one TPU device at
  inference takes the kernel; the CPU, a mesh, float32, the toy widths and
  a pack whose rows no tile divides take `ragged_dot`; a kind without
  sparse experts says nothing."""
  from deepconsensus_tpu.ops import pallas_util

  sizes = {} if where == 'tpu_toy_widths' else dict(
      transformer_input_size=128, moe_intermediate_size=256)
  p = tiny_params(100, dtype='float32' if where == 'tpu_float32'
                  else 'bfloat16', **sizes)
  if where == 'other_kind':
    p = config_lib.get_config('transformer_learn_values+custom')
    config_lib.finalize_params(p, is_training=False)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: where != 'cpu')
  batch = 5 if where == 'tpu_odd_pack' else 8  # 5 x 100 x 4 = 2,000 rows
  with pallas_util.single_device_inference(where != 'tpu_mesh'):
    got = model_lib.kernel_paths(p, batch=batch, length=100).get(
        'grouped_product_path')
  assert got == {'tpu': 'group_kernel', 'other_kind': None}.get(
      where, 'ragged_dot')


@pytest.mark.parametrize('where', ['cpu', 'tpu', 'tpu_mesh', 'tpu_float32',
                                   'tpu_toy_widths', 'tpu_odd_pack',
                                   'other_kind'])
def test_combine_path_is_the_kernel_on_one_tpu_at_lane_tile_widths(
    where, monkeypatch):
  """`forward_launch`'s `combine_path`: the model's rule asked as the
  runner asks it, with the tokens of one turn of the pack. bfloat16 rows of
  whole lane tiles (the published 2048 is) on one TPU device at inference,
  a turn's tokens whole tiles of 128, take the kernel a tile of tokens; the
  CPU, a mesh, float32, the toy widths and a pack whose tokens no tile
  divides take XLA's gather; a kind without sparse experts says nothing."""
  from deepconsensus_tpu.ops import pallas_util

  sizes = {} if where == 'tpu_toy_widths' else dict(
      transformer_input_size=128, moe_intermediate_size=256)
  p = tiny_params(100, dtype='float32' if where == 'tpu_float32'
                  else 'bfloat16', **sizes)
  if where == 'other_kind':
    p = config_lib.get_config('transformer_learn_values+custom')
    config_lib.finalize_params(p, is_training=False)
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: where != 'cpu')
  batch = 5 if where == 'tpu_odd_pack' else 32  # 5 x 100 = 500 tokens
  with pallas_util.single_device_inference(where != 'tpu_mesh'):
    got = model_lib.kernel_paths(p, batch=batch, length=100).get(
        'combine_path')
  assert got == {'tpu': 'token_tile_kernel', 'other_kind': None}.get(
      where, 'gather')


@pytest.mark.parametrize('kind,want', [
    ('gated_delta_moe', 2), ('latent_attention_moe', 2),
    ('parallel_window_moe', 2), ('window_moe', 4), ('banded', None)])
def test_moe_turns_are_the_turns_held_experts_takes_at_each_cell(kind, want):
  """`forward_launch`'s `moe_turns`: ops/moe.py::turns_of asked as the
  runner asks it, at the published widths and packs of the four expert
  cells (512, 512, 256, 512 windows of 100): two turns where a turn's
  tokens are 100 MiB, four at hidden 2304, where two would leave 112.5 MiB
  of tokens a turn; a kind without sparse experts says nothing."""
  presets = {
      'gated_delta_moe': ('transformer_learn_values_gdn_moe+custom', 512),
      'latent_attention_moe': ('transformer_learn_values_mla_moe+custom', 512),
      'parallel_window_moe': (
          'transformer_learn_values_parallel_moe+custom', 256),
      'window_moe': ('transformer_learn_values_window_moe+custom', 512),
      'banded': ('transformer_learn_values+custom', 512)}
  name, batch = presets[kind]
  p = config_lib.get_config(name)
  with p.unlocked():
    p.dtype = 'bfloat16'
  config_lib.finalize_params(p, is_training=False)
  assert model_lib.kernel_paths(p, batch=batch, length=100).get(
      'moe_turns') == want


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


# ------------------------------------------------- what the kind refuses

def test_tp_is_refused_by_name_and_dp_is_served():
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  p = tiny_params(12)
  variables = seeded_variables(model_lib.get_model(p), p, seed=7)
  with pytest.raises(ValueError, match=r'not served with --tp: '
                     r'parallel/partition_rules.py has no expert axis'):
    _runner(p, variables, mesh=mesh_lib.make_mesh(
        dp=2, tp=2, devices=jax.devices()[:4]))
  rows = pileup_rows(p, 8, seed=7)
  alone, _ = _runner(p, variables)
  sharded, _ = _runner(p, variables, mesh=mesh_lib.make_mesh(
      dp=2, tp=1, devices=jax.devices()[:2]))
  ids, quals = alone.predict(rows)
  ids_dp, quals_dp = sharded.predict(rows)
  assert np.array_equal(np.asarray(ids), np.asarray(ids_dp))
  assert np.abs(np.asarray(quals, np.int32)
                - np.asarray(quals_dp, np.int32)).max() <= 1
  assert sharded.dispatch_stats()['moe_assignments_held'] == (
      alone.dispatch_stats()['moe_assignments_held'])


def test_int8_is_refused_by_name():
  p = tiny_params(12, quantize_matmuls='int8')
  variables = seeded_variables(model_lib.get_model(p), p, seed=8)
  with pytest.raises(ValueError, match=r"not served with "
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
  # The kinds that train are not in the way of the check.
  for preset in ('transformer_learn_values+test', 'fc+test'):
    other = config_lib.get_config(preset)
    config_lib.finalize_params(other, is_training=False)
    model_lib.refuse_inference_only_kind(other, command)


def test_a_full_attention_interval_is_stated_not_defaulted():
  p = tiny_params(12)
  with p.unlocked():
    del p['full_attention_interval']
  with pytest.raises((AttributeError, KeyError)):
    config_lib.layer_pattern(p)
