"""ops/latent_attention.py against the published concatenated form.

The operator scores a head as the sum of two products, q_nope . k_nope and
q_rope against the ONE rotary key all heads share; the published form
(tests/mla_moe_reference.py) expands that key to every head, concatenates
keys of nope + rope and takes one product. Float32 on the CPU; value heads
narrower than query/key heads; the program's rotation over halves against
the published one over interleaved pairs under the column permutation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import latent_attention as la
from tests import mla_moe_reference as ref

HEADS, NOPE, ROPE, VALUE = 4, 16, 8, 12


def parts(length, seed, batch=2):
  rng = np.random.default_rng(seed)
  draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
  return (draw(batch, length, HEADS, NOPE), draw(batch, length, HEADS, ROPE),
          draw(batch, length, HEADS, NOPE), draw(batch, length, ROPE),
          draw(batch, length, HEADS, VALUE))


def concatenated(q_nope, q_rope, k_nope, k_rope, value, scale):
  """One product over keys of nope + rope, the rotary key repeated a head."""
  query = jnp.concatenate([q_nope, q_rope], axis=-1)
  key = jnp.concatenate(
      [k_nope, jnp.repeat(k_rope[:, :, None, :], HEADS, axis=2)], axis=-1)
  scores = jnp.einsum('bihd,bjhd->bhij', query, key) * scale
  return jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), value)


@pytest.mark.parametrize('length', [1, 7, 100])
def test_two_score_products_are_the_concatenated_form(length):
  args = parts(length, seed=length)
  scale = (NOPE + ROPE) ** -0.5
  with jax.default_matmul_precision('highest'):
    got = la.latent_attention(*args, scale=scale)
    want = concatenated(*args, scale)
  # Value heads of 12 under query/key heads of 24.
  assert got.shape == (2, length, HEADS, VALUE) and got.dtype == jnp.float32
  # Two float32 sums of 16 + 8 terms against one of 24.
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


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
