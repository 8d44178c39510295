"""ops/latent_attention.py against the published concatenated form.

The operator scores a head as the sum of two products, q_nope . k_nope and
q_rope against the ONE rotary key all heads share; the published form
(tests/mla_moe_reference.py) expands that key to every head, concatenates
keys of nope + rope and takes one product. Float32 on the CPU; value heads
narrower than query/key heads; the program's rotation over halves against
the published one over interleaved pairs under the column permutation.

The same operator as one Pallas call a tile of windows
(`window_tile_attention`), interpreted on the CPU at the published head
sizes (128 + 64 / 128, heads cut for time): against the plain form in
bfloat16 within one unit of the output, and in float32; windows reach
nothing but themselves (a last tile of fewer windows than a step takes, NaN
in a neighbour); the rotary key as `placed_rotary_keys` lays it out scores
what the concatenated form scores; the query's leaf in the flat products'
column order; the rotation on the flat halves against `apply_rotary`;
`latent_attention_path`'s answers; one trace for two layers alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import latent_attention as la
from deepconsensus_tpu.ops import pallas_util
from tests import mla_moe_reference as ref

HEADS, NOPE, ROPE, VALUE = 4, 16, 8, 12
# The published head sizes, which the kernel's rule takes.
PUBLISHED = dict(nope=128, rope=64, value=128)


def parts(length, seed, batch=2, heads=HEADS, nope=NOPE, rope=ROPE,
          value=VALUE, dtype=jnp.float32):
  rng = np.random.default_rng(seed)
  draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
  return (draw(batch, length, heads, nope), draw(batch, length, heads, rope),
          draw(batch, length, heads, nope), draw(batch, length, rope),
          draw(batch, length, heads, value))


def concatenated(q_nope, q_rope, k_nope, k_rope, value, scale):
  """One product over keys of nope + rope, the rotary key repeated a head."""
  heads = q_nope.shape[2]
  query = jnp.concatenate([q_nope, q_rope], axis=-1)
  key = jnp.concatenate(
      [k_nope, jnp.repeat(k_rope[:, :, None, :], heads, axis=2)], axis=-1)
  scores = jnp.einsum('bihd,bjhd->bhij', query, key) * scale
  return jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), value)


def flat(q_nope, q_rope, k_nope, k_rope, value):
  """[B, L, N, D] operands as the flat products write them for the kernel."""
  batch, length, _, rope = q_rope.shape
  rows = lambda a: a.reshape(batch * length, -1)
  half = rope // 2
  return (rows(q_nope), rows(q_rope[..., :half]), rows(q_rope[..., half:]),
          rows(jnp.concatenate([k_nope, value], axis=-1)),
          la.placed_rotary_keys(rows(k_rope[..., :half]),
                                rows(k_rope[..., half:])))


def through_the_kernel(q_nope, q_rope, k_nope, k_rope, value, scale):
  batch, length, heads, _ = value.shape
  out = la.window_tile_attention(
      *flat(q_nope, q_rope, k_nope, k_rope, value), length=length,
      num_heads=heads, scale=scale, interpret=True)
  return out.reshape(value.shape)


@pytest.mark.parametrize('length,sizes,form', [
    (1, {}, la.latent_attention), (7, {}, la.latent_attention),
    (100, {}, la.latent_attention),
    # The rotary key placed in a zeroed lane tile a head of the group, its
    # halves apart, scores what the key of 192 scores.
    (100, PUBLISHED, through_the_kernel)],
                         ids=['L1', 'L7', 'L100', 'L100_window_tiles'])
def test_two_score_products_are_the_concatenated_form(length, sizes, form):
  args = parts(length, seed=length, **sizes)
  nope, rope, value = args[0].shape[-1], args[1].shape[-1], args[4].shape[-1]
  scale = (nope + rope) ** -0.5
  with jax.default_matmul_precision('highest'):
    got = form(*args, scale=scale)
    want = concatenated(*args, scale)
  # Value heads of 12 under query/key heads of 24.
  assert got.shape == (2, length, HEADS, value) and got.dtype == jnp.float32
  # Two float32 sums of 16 + 8 terms against one of 24 (three, of 128 + 32
  # + 32 against one of 192).
  np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                             atol=2e-6 if not sizes else 1e-5)


def test_the_scale_is_that_of_the_whole_query_key_head():
  """(nope + rope)^-1/2, not nope^-1/2: the two differ by far more than
  rounding on scores of unit-variance parts."""
  args = parts(12, seed=3)
  with jax.default_matmul_precision('highest'):
    right = la.latent_attention(*args, scale=(NOPE + ROPE) ** -0.5)
    wrong = la.latent_attention(*args, scale=NOPE ** -0.5)
    want = concatenated(*args, (NOPE + ROPE) ** -0.5)
  np.testing.assert_allclose(np.asarray(right), np.asarray(want), atol=2e-6)
  assert np.abs(np.asarray(wrong - want)).max() > 0.01


def test_stream_in_bfloat16_keeps_scores_and_softmax_in_float32():
  args = tuple(a.astype(jnp.bfloat16) for a in parts(100, seed=5))
  got = la.latent_attention(*args, scale=(NOPE + ROPE) ** -0.5)
  assert got.dtype == jnp.bfloat16
  want = concatenated(*(a.astype(jnp.float32) for a in args),
                      (NOPE + ROPE) ** -0.5)
  # The softmax weights and the output are rounded to bfloat16 once each.
  assert np.abs(np.asarray(got, np.float32) - np.asarray(want)).max() < 0.03


@pytest.mark.parametrize('rotary_dim', [8, 64])
def test_rotated_halves_are_interleaved_pairs_under_the_permutation(
    rotary_dim):
  """A kernel published for interleaved pairs, its columns taken in
  `halves_from_pairs` order, gives under `apply_rotary` (halves) the
  scores the published kernel gives under the published rotation."""
  rng = np.random.default_rng(rotary_dim)
  hidden, length = 32, 100
  u = jnp.asarray(rng.normal(size=(2, length, hidden)), jnp.float32)
  w_q = jnp.asarray(rng.normal(size=(hidden, HEADS, rotary_dim)), jnp.float32)
  w_k = jnp.asarray(rng.normal(size=(hidden, 1, rotary_dim)), jnp.float32)
  perm = la.halves_from_pairs(rotary_dim)
  assert sorted(perm) == list(range(rotary_dim))
  assert list(perm[:3]) == [0, 2, 4] and perm[rotary_dim // 2] == 1
  theta = 1.0e6
  project = lambda w: jnp.einsum('blh,hnd->blnd', u, w)
  with jax.default_matmul_precision('highest'):
    q_pairs = ref.rotary_pairs(project(w_q), theta)
    k_pairs = ref.rotary_pairs(project(w_k), theta)
    q_halves = model_lib.apply_rotary(project(w_q[..., perm]), theta)
    k_halves = model_lib.apply_rotary(project(w_k[..., perm]), theta)
    want = jnp.einsum('binr,bjr->bnij', q_pairs, k_pairs[:, :, 0])
    got = jnp.einsum('binr,bjr->bnij', q_halves, k_halves[:, :, 0])
    # Without the permutation the two rotations pair other columns.
    other = jnp.einsum('binr,bjr->bnij',
                       model_lib.apply_rotary(project(w_q), theta),
                       model_lib.apply_rotary(project(w_k), theta)[:, :, 0])
  scale = np.abs(np.asarray(want)).max()
  assert np.abs(np.asarray(got - want)).max() < 1e-5 * scale
  assert np.abs(np.asarray(other - want)).max() > 0.01 * scale
  # The reference's `published_order` is the inverse relabelling.
  np.testing.assert_array_equal(
      np.asarray(ref.published_order(w_q[..., perm])), np.asarray(w_q))


# --------------------------------------------- the kernel a tile of windows

@pytest.mark.parametrize('batch,length,heads', [
    (8, 100, 4), (16, 100, 8), (8, 128, 4), (16, 128, 4)])
def test_window_tiles_are_the_plain_form_within_one_bfloat16_unit(
    batch, length, heads):
  args = parts(length, seed=batch + heads, batch=batch, heads=heads,
               dtype=jnp.bfloat16, **PUBLISHED)
  scale = 192 ** -0.5
  want = np.asarray(la.latent_attention(*args, scale=scale), np.float32)
  got = through_the_kernel(*args, scale=scale)
  assert got.dtype == jnp.bfloat16
  got = np.asarray(got, np.float32)
  # Every rounding is where the plain form has it; what differs is the
  # order of a float32 sum (the rotary part as two sums of 32), which moves
  # a weight by one unit in the last place here and there: few outputs
  # differ, none by more than one bfloat16 unit of the outputs' size.
  unit = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
  assert np.abs(got - want).max() <= unit
  assert (got != want).mean() < 0.005


def test_window_tiles_in_float32_are_the_plain_form():
  """Interpreted, the kernel takes float32 operands too (the rule never
  sends them): the plain form up to the order of the rotary part's sum."""
  args = parts(100, seed=11, batch=8, **PUBLISHED)
  with jax.default_matmul_precision('highest'):
    want = la.latent_attention(*args, scale=192 ** -0.5)
    got = through_the_kernel(*args, scale=192 ** -0.5)
  assert got.dtype == jnp.float32
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_a_window_reaches_nothing_but_itself():
  """A step takes 4 windows: 10 are two whole tiles and half of one, whose
  rows behind the array's end are read and reach nothing; NaN in one
  window of a tile changes no other window's output."""
  assert la.KERNEL_WINDOWS_A_STEP == 4
  args = parts(100, seed=13, batch=10, dtype=jnp.bfloat16, **PUBLISHED)
  scale = 192 ** -0.5
  ten = np.asarray(through_the_kernel(*args, scale=scale), np.float32)
  assert np.isfinite(ten).all()
  want = np.asarray(la.latent_attention(*args, scale=scale), np.float32)
  np.testing.assert_allclose(ten, want, atol=2.0 ** -6)
  # Every window apart, as a call of its own, to the bit.
  alone = np.concatenate([
      np.asarray(through_the_kernel(*(a[i:i + 1] for a in args), scale=scale),
                 np.float32) for i in (0, 3, 4, 9)])
  np.testing.assert_array_equal(alone, ten[[0, 3, 4, 9]])
  poisoned = tuple(a.at[5].set(jnp.nan).at[9].set(jnp.nan) for a in args)
  got = np.asarray(through_the_kernel(*poisoned, scale=scale), np.float32)
  clean = [i for i in range(10) if i not in (5, 9)]
  np.testing.assert_array_equal(got[clean], ten[clean])
  assert np.isnan(got[5]).all() and np.isnan(got[9]).all()


def test_query_leaf_in_the_flat_column_order_is_the_old_split_to_the_bit():
  """x times `flat_query_kernels(leaf)` against the leaf's own product
  [.., N, 192] split at 128 and, the rotary part, at its half."""
  rng = np.random.default_rng(17)
  hidden, heads, nope, rope = 64, 4, 128, 64
  x = jnp.asarray(rng.normal(size=(200, hidden)), jnp.bfloat16)
  leaf = jnp.asarray(rng.normal(size=(hidden, heads, nope + rope)),
                     jnp.bfloat16)
  whole = jnp.einsum('rh,hnd->rnd', x, leaf)  # DenseGeneral's contraction
  assert whole.dtype == jnp.bfloat16
  w_nope, w_halves = la.flat_query_kernels(leaf, nope)
  assert w_nope.shape == (hidden, heads * nope)
  assert w_halves.shape == (hidden, heads * rope)
  rows = lambda a: np.asarray(a.reshape(200, -1), np.float32)
  np.testing.assert_array_equal(np.asarray(jnp.dot(x, w_nope), np.float32),
                                rows(whole[..., :nope]))
  half = rope // 2
  np.testing.assert_array_equal(
      np.asarray(jnp.dot(x, w_halves), np.float32),
      np.concatenate([rows(whole[..., nope:nope + half]),
                      rows(whole[..., nope + half:])], axis=1))


@pytest.mark.parametrize('batch', [8, 3])
def test_rotation_of_the_flat_halves_is_apply_rotary_to_the_bit(batch):
  """8 windows of 100 are whole groups of the four that tile 16 rows, 3
  are not: the tables' rows either way."""
  rng = np.random.default_rng(batch)
  length, heads, rope, theta = 100, 4, 64, 1.0e6
  x = jnp.asarray(rng.normal(size=(batch, length, heads, rope)), jnp.bfloat16)
  want = model_lib.apply_rotary(x.astype(jnp.float32), theta)
  half = rope // 2
  rows = lambda a: a.reshape(batch * length, heads * half)
  first, second = model_lib.apply_rotary_flat(
      rows(x[..., :half]), rows(x[..., half:]), length, theta, rope)
  assert first.dtype == second.dtype == jnp.float32
  windows = lambda a: a.reshape(batch, length, heads, half)
  np.testing.assert_array_equal(
      np.asarray(jnp.concatenate([windows(first), windows(second)], axis=-1)),
      np.asarray(want))


def test_rule_takes_the_kernel_on_one_tpu_at_the_published_heads(monkeypatch):
  path = lambda **other: la.latent_attention_path(**{**dict(
      num_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
      length=100, dtype='bfloat16'), **other})
  # The CPU takes no kernel by itself, nor a TPU outside a trace declared
  # inference for one device (a mesh, `dctpu export`, a training step).
  assert path() == la.LATENT_PLAIN
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  assert path() == la.LATENT_PLAIN
  with pallas_util.single_device_inference(False):
    assert path() == la.LATENT_PLAIN
  with pallas_util.single_device_inference():
    assert path() == la.LATENT_WINDOW_TILE_KERNEL
    assert path(length=128) == la.LATENT_WINDOW_TILE_KERNEL
    assert path(length=129) == la.LATENT_PLAIN
    assert path(dtype='float32') == la.LATENT_PLAIN
    # Heads that are no whole lane tiles, either side.
    assert path(qk_nope_head_dim=96) == la.LATENT_PLAIN
    assert path(v_head_dim=64) == la.LATENT_PLAIN
    # Four heads' half rotary parts are a lane tile: an odd head count, or
    # one that is no whole groups of four, has no such tiles.
    assert path(num_heads=31) == la.LATENT_PLAIN
    assert path(num_heads=30) == la.LATENT_PLAIN
    assert path(num_heads=4) == la.LATENT_WINDOW_TILE_KERNEL
    # Nor halves that do not tile the lanes.
    assert path(qk_rope_head_dim=48) == la.LATENT_PLAIN
    assert path(qk_rope_head_dim=128, num_heads=2) == (
        la.LATENT_WINDOW_TILE_KERNEL)


def test_two_layers_alike_find_one_trace_of_the_call(monkeypatch):
  traced = []
  real = la._window_tile_kernel
  monkeypatch.setattr(
      la, '_window_tile_kernel',
      lambda *a, **k: traced.append(1) or real(*a, **k))
  la._call.clear_cache()
  args = flat(*parts(100, seed=19, batch=8, dtype=jnp.bfloat16, **PUBLISHED))

  @jax.jit
  def two_layers(q_nope, *others):
    attend = lambda q: la.window_tile_attention(
        q, *others, length=100, num_heads=HEADS, scale=192 ** -0.5,
        interpret=True)
    return attend(attend(q_nope))

  two_layers(*args)
  assert len(traced) == 1
  la._call.clear_cache()
