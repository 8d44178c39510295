"""ops/gated_delta.py: the gated delta rule in chunked form against the
token-by-token recurrence of the test-local plain reference
(tests/gdn_moe_reference.py), float32 on the CPU.

What is held here: the plain form at window lengths 1, 7, 100 and lengths
that are no multiple of the block, in one direction (the published causal
rule) and in two; decays near 0 and near 1; beta = 0 as plain decay of
what was written before; grouped key heads against explicitly repeated
ones; the Pallas form (interpret mode here, heads of 128) against the same
recurrence; and what the operator refuses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.ops import gated_delta
from tests import gdn_moe_reference as ref


def operands(length, hk=2, hv=4, d=8, batch=2, seed=0, decay=(0.6, 1.0),
             two=False):
  """q scaled and k of unit length over the head, as the mixer brings
  them; g the log of a decay in `decay`, beta in (0.1, 0.9). `two`: q, k,
  v with a leading axis of 2, one draw a direction."""
  rng = np.random.default_rng(seed)
  lead = (2, batch) if two else (batch,)
  unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
  q = unit(rng.normal(size=lead + (length, hk, d))) * d ** -0.5
  k = unit(rng.normal(size=lead + (length, hk, d)))
  v = rng.normal(size=lead + (length, hv, d))
  g = np.log(rng.uniform(*decay, size=(batch, length, hv)))
  beta = rng.uniform(0.1, 0.9, size=(batch, length, hv))
  return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def both_runs(q, k, v, g, beta):
  """The recurrence from the window's start plus the recurrence from its
  end (index 1 of q, k, v holds what that run reads, at the window's
  positions), by turning the window round."""
  turn = lambda a: jnp.flip(a, axis=1)
  return ref.delta_recurrence(q[0], k[0], v[0], g, beta) + turn(
      ref.delta_recurrence(turn(q[1]), turn(k[1]), turn(v[1]), turn(g),
                           turn(beta)))


@pytest.mark.parametrize('length,block', [
    (1, 32), (7, 32), (100, 32), (40, 32), (33, 16), (100, 128), (12, 4)])
def test_chunked_causal_rule_is_the_token_by_token_recurrence(length, block):
  q, k, v, g, beta = operands(length, seed=length)
  got = gated_delta.gated_delta_causal(q, k, v, g, beta, block=block)
  want = ref.delta_recurrence(q, k, v, g, beta)
  assert got.shape == (2, length, 4, 8) and got.dtype == jnp.float32
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
  assert np.abs(np.asarray(want)).max() > 0.05


@pytest.mark.parametrize('length', [1, 7, 100, 45])
def test_two_directions_are_both_runs_added(length):
  q, k, v, g, beta = operands(length, seed=length + 1, two=True)
  got = gated_delta.gated_delta_two_directions(q, k, v, g, beta)
  np.testing.assert_allclose(np.asarray(got),
                             np.asarray(both_runs(q, k, v, g, beta)),
                             atol=2e-6)
  # Neither run alone: the second direction is a real part of the sum.
  one = ref.delta_recurrence(q[0], k[0], v[0], g, beta)
  if length > 1:
    assert np.abs(np.asarray(got - one)).max() > 0.01


@pytest.mark.parametrize('decay', [(1e-9, 1e-8), (0.999, 1.0), (1e-9, 1.0)],
                         ids=['near_0', 'near_1', 'both'])
def test_decays_near_zero_and_near_one_stay_finite_and_exact(decay):
  q, k, v, g, beta = operands(100, seed=3, decay=decay, two=True)
  got = np.asarray(gated_delta.gated_delta_two_directions(q, k, v, g, beta))
  assert np.isfinite(got).all()
  np.testing.assert_allclose(got, np.asarray(both_runs(q, k, v, g, beta)),
                             atol=5e-6)


def test_beta_zero_writes_nothing_and_the_state_only_decays():
  """One write, at position 0; every later position reads it through the
  decay alone: o_t = exp(G_t - G_0) (q_t . k_0) beta_0 v_0."""
  q, k, v, g, beta = operands(24, seed=5)
  beta = beta.at[:, 1:].set(0.0)
  got = np.asarray(gated_delta.gated_delta_causal(q, k, v, g, beta))
  group = v.shape[2] // k.shape[2]
  qh, kh = (np.repeat(np.asarray(a), group, axis=2) for a in (q, k))
  cum = np.cumsum(np.asarray(g), axis=1)
  want = (np.exp(cum - cum[:, :1]) * np.einsum('blhd,bhd->blh', qh, kh[:, 0])
          * np.asarray(beta)[:, :1])[..., None] * np.asarray(v)[:, :1]
  np.testing.assert_allclose(got, want, atol=1e-6)
  assert np.abs(gated_delta.gated_delta_causal(
      q, k, v, g, jnp.zeros_like(beta))).max() == 0.0


@pytest.mark.parametrize('hk,hv', [(1, 4), (2, 4), (4, 4)])
def test_grouped_heads_are_explicitly_repeated_key_heads(hk, hv):
  q, k, v, g, beta = operands(20, hk=hk, hv=hv, seed=hk)
  got = gated_delta.gated_delta_causal(q, k, v, g, beta)
  rep = lambda a: jnp.repeat(a, hv // hk, axis=2)
  want = gated_delta.gated_delta_causal(rep(q), rep(k), v, g, beta)
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_more_problems_than_one_go_holds_are_taken_in_turn(monkeypatch):
  q, k, v, g, beta = operands(20, batch=8, seed=9)
  whole = gated_delta.gated_delta_causal(q, k, v, g, beta)
  monkeypatch.setattr(gated_delta, 'MAX_PROBLEMS', 8)  # two windows a turn
  in_turn = gated_delta.gated_delta_causal(q, k, v, g, beta)
  np.testing.assert_allclose(np.asarray(in_turn), np.asarray(whole),
                             atol=1e-6)


def test_operands_in_bfloat16_keep_decay_and_output_in_float32():
  q, k, v, g, beta = operands(100, seed=2, two=True)
  low = lambda a: a.astype(jnp.bfloat16)
  got = gated_delta.gated_delta_two_directions(low(q), low(k), low(v), g,
                                               beta)
  assert got.dtype == jnp.float32
  want = np.asarray(both_runs(q, k, v, g, beta))
  # bfloat16 keeps 8 bits: products of rounded operands, float32 sums.
  assert np.abs(np.asarray(got) - want).max() < 0.03 * np.abs(want).max()


@pytest.mark.parametrize('length', [7, 100, 130])
def test_window_kernel_is_the_same_recurrence(length):
  """The Pallas form at heads of 128 (one call a window, both directions,
  the system inverted from its diagonal blocks outwards), in interpret
  mode."""
  q, k, v, g, beta = operands(length, hk=1, hv=2, d=128, batch=2,
                              seed=length, two=True)
  got = gated_delta._two_directions_kernel(q, k, v, g, beta, interpret=True)
  assert got.shape == (2, length, 2, 128) and got.dtype == jnp.float32
  np.testing.assert_allclose(np.asarray(got),
                             np.asarray(both_runs(q, k, v, g, beta)),
                             atol=2e-6)


@pytest.mark.parametrize('form', ['plain', 'kernel'])
def test_keys_that_are_alike_keep_their_digits_in_bfloat16(form):
  """Keys behind a silu share a direction (k_t . k_j near a half), and the
  triangular system is then far from the identity. Inverted whole by
  repeated squaring in bfloat16 it loses every digit (each squaring
  doubles a power's relative error: a median error of 66% a head was
  measured, PR 32); by blocks, as both forms do, each head's output stays
  within a few bfloat16 roundings of the recurrence on the same rounded
  operands. The error is taken head by head, as the norm that follows
  sees it, not against the largest output."""
  rng = np.random.default_rng(0)
  silu = lambda a: a / (1 + np.exp(-a))
  unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
  shared = rng.normal(size=(1, 1, 1, 1, 128))
  draw = lambda *shape: rng.normal(size=shape)
  q = unit(silu(draw(2, 2, 100, 1, 128) + shared)) * 128 ** -0.5
  k = unit(silu(draw(2, 2, 100, 1, 128) + shared))
  assert np.einsum('blhd,bmhd->bhlm', k[0], k[0]).mean() > 0.4
  v = 2 * silu(draw(2, 2, 100, 2, 128))
  g = -0.15 * np.log1p(np.exp(draw(2, 100, 2)))
  beta = 1 / (1 + np.exp(-draw(2, 100, 2)))
  rounded = lambda a: jnp.asarray(a, jnp.bfloat16)
  q, k, v = rounded(q), rounded(k), rounded(v)
  g, beta = jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)
  up = lambda a: a.astype(jnp.float32)
  want = np.asarray(both_runs(up(q), up(k), up(v), g, beta))
  if form == 'kernel':
    got = gated_delta._two_directions_kernel(q, k, v, g, beta,
                                             interpret=True)
  else:
    got = gated_delta.gated_delta_two_directions(q, k, v, g, beta)
  error = (np.linalg.norm(np.asarray(got) - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
  assert np.median(error) < 0.01 and error.max() < 0.05


def test_the_code_takes_the_kernel_only_where_it_may(monkeypatch):
  """On the CPU, under a mesh or outside an inference trace the plain form
  runs; on one TPU device at inference, heads of 128 take the kernel."""
  from deepconsensus_tpu.ops import pallas_util
  taken = []
  monkeypatch.setattr(
      gated_delta, '_two_directions_kernel',
      lambda q, *rest: taken.append(q.shape) or jnp.zeros(
          q.shape[1:3] + (rest[1].shape[3], q.shape[4])))
  wide = operands(8, hk=1, hv=2, d=128, seed=1, two=True)
  narrow = operands(8, seed=1, two=True)
  gated_delta.gated_delta_two_directions(*wide)
  with pallas_util.single_device_inference():
    gated_delta.gated_delta_two_directions(*wide)  # no TPU here
  assert not taken
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  gated_delta.gated_delta_two_directions(*wide)  # nobody declared inference
  with pallas_util.single_device_inference():
    gated_delta.gated_delta_two_directions(*narrow)  # heads of 8
    assert not taken
    gated_delta.gated_delta_two_directions(*wide)
  assert taken == [(2, 2, 8, 1, 128)]


@pytest.mark.parametrize('how,match', [
    ('heads', 'do not group'), ('length', 'more than one chunk'),
    ('block', 'not a power of two')])
def test_operator_refuses_what_it_cannot_run(how, match):
  q, k, v, g, beta = operands(8, hk=3 if how == 'heads' else 2)
  kwargs = {}
  if how == 'length':
    tile = lambda a: jnp.tile(a, (1, 65) + (1,) * (a.ndim - 2))
    q, k, v, g, beta = (tile(a) for a in (q, k, v, g, beta))
  if how == 'block':
    kwargs['block'] = 24
  with pytest.raises(ValueError, match=match):
    gated_delta.gated_delta_causal(q, k, v, g, beta, **kwargs)
