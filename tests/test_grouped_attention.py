"""ops/grouped_attention.py: the grouped-head softmax operator, with the
rotation of q and k as its prologue, as one Pallas call a tile of windows.

Interpreted on the CPU at heads of 128 (the only width its rule takes; head
counts cut for time): a layer's `GroupedSoftmaxAttention` on the flat
stream through the call against the same module through its plain form, in
bfloat16, with groups of 8 and 16, batches that are and are not whole steps
of windows, the default rope, YaRN with its magnitude and no rotation;
the rotation inside a kernel against `apply_rotary` to the bit; windows
that reach nothing but themselves; a gate and q/k norms round the call;
whole stacks of the window_moe, parallel and gated-delta kinds on the flat
stream against their stacks of modules; the rule's answers, condition by
condition; `forward_launch`'s `grouped_attention_path`; one trace for two
layers alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import summarize as summarize_lib
from deepconsensus_tpu.obs import trace as trace_lib
from deepconsensus_tpu.ops import grouped_attention as ga
from deepconsensus_tpu.ops import pallas_util
from tests.test_grouped_product import kernel_taken as as_on_one_tpu
from tests.test_power_retention import pileup_rows

D = 128
# mellum_polish's full layers: YaRN x16, cos and sin x 1.2773.
YARN = model_lib.Rope.of({
    'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
    'original_max_position_embeddings': 8192, 'beta_fast': 32,
    'beta_slow': 1, 'attention_factor': 1.2772588722239782})


def layer(heads, kv_heads, rope, **sizes):
  return model_lib.GroupedSoftmaxAttention(
      hidden_size=64, num_heads=heads, num_kv_heads=kv_heads, head_dim=D,
      rotary_dim=0 if rope is None else D, rope=rope, rms_norm_eps=1e-6,
      **{'output_gate': False, 'qk_norm': False, **sizes},
      dtype=jnp.bfloat16)


def both_forms(module, batch, length=100, seed=0):
  """(plain, through the call) of one layer on x [B, L, 64], bfloat16
  leaves; the second as the stack hands it the flat stream."""
  rng = np.random.default_rng(seed)
  x = jnp.asarray(rng.normal(size=(batch, length, 64)), jnp.bfloat16)
  variables = jax.tree_util.tree_map(
      lambda a: jnp.asarray(rng.normal(0.5, 1.0, a.shape) if a.ndim == 1
                            else rng.normal(0, a.shape[0] ** -0.5, a.shape),
                            jnp.bfloat16),
      module.init(jax.random.PRNGKey(seed), x, False))
  plain = jax.jit(lambda v, x: module.apply(v, x, False))(variables, x)
  flat = jax.jit(lambda v, x: module.apply(
      v, x.reshape(batch * length, 64), False, window_length=length))(
          variables, x)
  return (np.asarray(plain, np.float32),
          np.asarray(flat, np.float32).reshape(plain.shape))


def bfloat16_unit(a):
  """One unit in the last place of bfloat16 at the largest of `a`."""
  return 2.0 ** (np.floor(np.log2(np.abs(a).max())) - 7)


@pytest.mark.parametrize('heads,kv_heads,batch,rope', [
    (16, 2, 6, 5e5), (8, 1, 5, YARN), (32, 2, 4, 5e4), (16, 1, 7, None)],
                         ids=['group8_default_6_windows',
                              'group8_yarn_5_windows',
                              'group16_default_4_windows',
                              'group16_no_rotation_7_windows'])
def test_the_call_is_the_plain_form_within_one_bfloat16_unit(
    heads, kv_heads, batch, rope):
  """Every rounding is where the plain form has it; what differs is the
  order of a float32 sum, which moves a weight by one unit in the last
  place here and there: few outputs differ, none by more than a unit."""
  want, got = both_forms(layer(heads, kv_heads, rope), batch)
  assert np.abs(got - want).max() <= bfloat16_unit(want)
  assert (got != want).mean() < 0.01
  assert want.std() > 0.1  # nothing saturated or dead compares nothing


def test_a_gate_and_head_norms_run_round_the_call():
  """The third kind's sizes at heads of 128: the normed q and k are
  rounded before the call rotates them and the gate multiplies o as the
  call rounded it, two roundings the plain form does not make."""
  want, got = both_forms(layer(4, 2, 1e4, output_gate=True, qk_norm=True),
                         batch=4, seed=3)
  assert np.abs(got - want).max() <= 4 * bfloat16_unit(want)
  assert np.median(np.abs(got - want)) <= bfloat16_unit(want)


@pytest.mark.parametrize('rope', [5e5, YARN, 5e4], ids=['default', 'yarn',
                                                        'commanda'])
def test_rotation_in_the_kernel_is_apply_rotary_to_the_bit(rope):
  rng = np.random.default_rng(1)
  x = jnp.asarray(rng.normal(size=(4, 100, 3, D)), jnp.bfloat16)
  # Jitted, as the forward runs it: XLA:CPU contracts x cos + r sin into a
  # fused multiply-add in a compiled program, and in the interpreted kernel
  # alike; op by op it would round the products apart.
  want = jax.jit(lambda x: model_lib.apply_rotary(
      x.astype(jnp.float32), rope).astype(jnp.bfloat16))(x)
  cos, sin_signed = ga.signed_tables(*model_lib.rotary_tables(100, D, rope))
  rows = x.transpose(0, 2, 1, 3).reshape(12 * 100, D)  # a window a head
  tables = [np.tile(t, (12, 1)) for t in (cos, sin_signed)]

  def kernel(x_ref, cos_ref, sin_ref, o_ref):
    o_ref[...] = ga.turned(x_ref[...], cos_ref[...], sin_ref[...])

  got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
      rows.shape, rows.dtype), interpret=True)(rows, *tables)
  np.testing.assert_array_equal(
      np.asarray(got, np.float32),
      np.asarray(want.transpose(0, 2, 1, 3).reshape(rows.shape), np.float32))


def test_a_window_reaches_nothing_but_itself():
  """A step takes 4 windows: 10 are two whole steps and half of one, whose
  rows behind the array's end are read and reach nothing; NaN in two
  windows changes no other window's output."""
  assert ga.KERNEL_WINDOWS_A_STEP == 4
  rng = np.random.default_rng(5)
  draw = lambda heads: jnp.asarray(rng.normal(size=(10, 100, heads * D)),
                                   jnp.bfloat16)
  q, k, v = draw(16), draw(2), draw(2)
  tables = ga.signed_tables(*model_lib.rotary_tables(100, D, 5e5))
  attend = lambda q, k, v: np.asarray(ga.window_tile_attention(
      *(a.reshape(-1, a.shape[-1]) for a in (q, k, v)), *tables, length=100,
      num_heads=16, num_kv_heads=2, scale=D ** -0.5, interpret=True),
                                      np.float32).reshape(q.shape)
  ten = attend(q, k, v)
  assert np.isfinite(ten).all()
  alone = np.concatenate([attend(q[i:i + 1], k[i:i + 1], v[i:i + 1])
                          for i in (0, 3, 4, 9)])
  np.testing.assert_array_equal(alone, ten[[0, 3, 4, 9]])
  poison = lambda a: a.at[5].set(jnp.nan).at[9].set(jnp.nan)
  got = attend(poison(q), poison(k), poison(v))
  clean = [i for i in range(10) if i not in (5, 9)]
  np.testing.assert_array_equal(got[clean], ten[clean])
  assert np.isnan(got[5]).all() and np.isnan(got[9]).all()


def test_two_layers_alike_find_one_trace_of_the_call(monkeypatch):
  traced = []
  real = ga._window_tile_kernel
  monkeypatch.setattr(ga, '_window_tile_kernel',
                      lambda *a, **k: traced.append(1) or real(*a, **k))
  ga._call.clear_cache()
  rng = np.random.default_rng(7)
  draw = lambda heads: jnp.asarray(rng.normal(size=(800, heads * D)),
                                   jnp.bfloat16)
  k, v = draw(1), draw(1)
  tables = ga.signed_tables(*model_lib.rotary_tables(100, D, 5e5))

  @jax.jit
  def two_layers(q):
    attend = lambda q: ga.window_tile_attention(
        q, k, v, *tables, length=100, num_heads=4, num_kv_heads=1,
        scale=D ** -0.5, interpret=True)
    return attend(attend(q))

  two_layers(draw(4))
  assert len(traced) == 1
  ga._call.clear_cache()


# ------------------------------------------------------------------ the rule

def test_rule_takes_the_kernel_on_one_tpu_and_each_condition_declines(
    monkeypatch):
  path = lambda **other: ga.grouped_attention_path(**{**dict(
      num_heads=32, num_kv_heads=4, head_dim=128, rotary_dim=128,
      window=1024, length=100, dtype='bfloat16'), **other})
  # The CPU takes no kernel by itself, nor a TPU outside a trace declared
  # inference for one device (a mesh, `dctpu export`, a training step).
  assert path() == ga.GROUPED_PLAIN
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  assert path() == ga.GROUPED_PLAIN
  with pallas_util.single_device_inference(False):
    assert path() == ga.GROUPED_PLAIN
  with pallas_util.single_device_inference():
    assert path() == ga.GROUPED_WINDOW_TILE_KERNEL
    # Both cells' layers: 128 / 8 heads, rotated or without positions.
    assert path(num_heads=128, num_kv_heads=8, rotary_dim=0,
                window=None) == ga.GROUPED_WINDOW_TILE_KERNEL
    assert path(length=128, window=128) == ga.GROUPED_WINDOW_TILE_KERNEL
    # Each condition alone.
    assert path(dtype='float32') == ga.GROUPED_PLAIN
    assert path(length=129) == ga.GROUPED_PLAIN
    assert path(head_dim=256, rotary_dim=256) == ga.GROUPED_PLAIN
    assert path(rotary_dim=64) == ga.GROUPED_PLAIN
    assert path(window=99) == ga.GROUPED_PLAIN  # a window that masks


def test_stacks_without_such_a_layer_say_nothing_and_qwen3next_says_plain(
    monkeypatch):
  from tests.test_gdn_moe_block import tiny_params as gdn_params

  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    for preset in ('transformer_learn_values+test',
                   'transformer_learn_values_mla_moe+custom',
                   'transformer_learn_values_retention+custom'):
      p = config_lib.get_config(preset)
      config_lib.finalize_params(p, is_training=False)
      assert 'grouped_attention_path' not in model_lib.kernel_paths(
          p, batch=8, length=100)
    # The gated softmax layer of the third kind: heads of 256 with a
    # quarter rotated as published, heads of 16 at toy size.
    p = config_lib.get_config('transformer_learn_values_gdn_moe+custom')
    config_lib.finalize_params(p, is_training=False)
    assert (p.head_dim, p.partial_rotary_factor) == (256, 0.25)
    assert model_lib.kernel_paths(p, batch=8, length=100)[
        'grouped_attention_path'] == 'plain'
    toy = gdn_params(12, dtype='bfloat16', inference_dtype='bfloat16')
    assert model_lib.kernel_paths(toy, batch=8, length=12)[
        'grouped_attention_path'] == 'plain'


# ------------------------------------------------- whole stacks, flat stream

def _window_moe():
  from tests.test_parallel_moe_block import seeded_variables
  from tests.test_window_moe_block import tiny_params
  return tiny_params(100, (0, 16), window=128, head_dim=D), seeded_variables


def _parallel():
  from tests.test_parallel_moe_block import seeded_variables, tiny_params
  # 32 query heads over 2: groups of 16.
  return tiny_params(100, (0, 16), window=128, head_dim=D), seeded_variables


def _gated_delta():
  from tests.test_gdn_moe_block import seeded_variables, tiny_params
  # Heads of 128 wholly rotated: a stack whose mixers take [B, L, H].
  return tiny_params(100, head_dim=D, partial_rotary_factor=1.0), (
      seeded_variables)


@pytest.mark.parametrize('kind,layers', [
    (_window_moe, 4), (_parallel, 4), (_gated_delta, 1)],
                         ids=['window_moe', 'parallel', 'gated_delta'])
def test_stack_on_the_flat_stream_through_the_call_is_the_stack_of_modules(
    monkeypatch, kind, layers):
  """Heads of 128 on the toy stream, bfloat16, a window that covers the
  length: where `grouped_attention_path` says so every grouped-head layer
  runs through the Pallas call (the stream flat from the first layer to the
  head, or, beside the Gated DeltaNet mixers, flat inside the layer); the
  same leaves, the modules' own outputs up to the order of a float32 sum."""
  p, seeded_variables = kind()
  with p.unlocked():
    p.dtype = p.inference_dtype = 'bfloat16'
  model = model_lib.get_model(p)
  variables = jax.tree_util.tree_map(
      lambda a: a.astype(jnp.bfloat16), seeded_variables(model, p, seed=21))
  rows = jnp.asarray(pileup_rows(p, 6, seed=21))
  forward = lambda v, r: model.apply(v, r, mutable=['moe_counts'])
  want, _ = jax.jit(forward)(variables, rows)
  traced = []
  real = ga.window_tile_attention
  monkeypatch.setattr(ga, 'window_tile_attention',
                      lambda *a, **k: traced.append(k['length']) or real(
                          *a, **k))
  init = lambda k: model.init(k, jnp.zeros((1, p.total_rows, 100, 1)))
  with as_on_one_tpu(monkeypatch):
    assert model_lib.kernel_paths(p, batch=6, length=100)[
        'grouped_attention_path'] == ga.GROUPED_WINDOW_TILE_KERNEL
    # (A function of its own: jit's cache does not see the declaration.)
    got, _ = jax.jit(lambda v, r: forward(v, r))(variables, rows)
    # And init, even so declared, runs the modules: the tree is theirs.
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
  assert traced == [100] * layers
  assert got.shape == want.shape == (6, 100, 5)
  difference = np.abs(np.asarray(got, np.float32)
                      - np.asarray(want, np.float32))
  # A weight a unit off moves an output a unit; a routed near-tie that
  # falls the other way moves a position by more.
  assert np.median(difference) < 2e-3 and (difference < 0.05).mean() > 0.98
  shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
  assert shapes(tree['params']) == shapes(variables['params'])


def test_forward_launch_says_window_tile_kernel_as_on_one_tpu(
    monkeypatch, tmp_path, capsys):
  from deepconsensus_tpu import cli
  from tests.test_mla_moe_block import _runner

  p, seeded_variables = _window_moe()
  with p.unlocked():
    p.dtype = p.inference_dtype = 'bfloat16'
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
  assert launch['grouped_attention_path'] == 'window_tile_kernel'
  assert 'latent_attention_path' not in launch
  assert cli.main(['trace', path]) == 0
  assert ('layers: WWWF (window: 128) (rope: W default, F yarn×16) '
          '(grouped-head attention: window_tile_kernel); experts'
          in capsys.readouterr().out)
