"""ops/gated_delta.py: the gated delta rule in chunked form against the
token-by-token recurrence of the test-local plain reference
(tests/gdn_moe_reference.py), float32 on the CPU.

What is held here: the plain form at window lengths 1, 7, 100 and lengths
that are no multiple of the block, in one direction (the published causal
rule) and in two; decays near 0 and near 1; beta = 0 as plain decay of
what was written before; grouped key heads against explicitly repeated
ones; the Pallas form (interpret mode here, heads of 128, the flat stream
in and out with the mixer's two norms as its prologue and epilogue)
against the same recurrence between the reference's norms; and what the
operator refuses.
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


def flat_stream(length, hk, hv, dtype, batch=2, seed=0, d=128):
  """What a mixer hands `gated_delta_window`: [q | k | v] of both
  directions behind a silu with heads along the lanes, the gate z, g,
  beta and the gated norm's weight."""
  rng = np.random.default_rng(seed)
  silu = lambda a: a / (1 + np.exp(-a))
  both = silu(rng.normal(size=(2, batch, length, (2 * hk + hv) * d)))
  z = rng.normal(size=(batch, length, hv * d))
  g = np.log(rng.uniform(0.6, 1.0, size=(batch, length, hv)))
  beta = rng.uniform(0.1, 0.9, size=(batch, length, hv))
  weight = rng.uniform(0.5, 1.5, size=(d,))
  return (jnp.asarray(both, dtype), jnp.asarray(z, dtype)) + tuple(
      jnp.asarray(a, jnp.float32) for a in (g, beta, weight))


def between_the_norms(both, z, g, beta, weight, hk, hv, eps,
                      normalise=True):
  """The reference's mixer between its convolution and its output
  projection, float32: L2 norm of q and k over the head (q times
  Dk^-1/2), both recurrences, the gated RMS norm
  (tests/gdn_moe_reference.py::gdn_mixer's lines)."""
  both, z = both.astype(jnp.float32), z.astype(jnp.float32)
  _, batch, length, _ = both.shape
  d = z.shape[-1] // hv
  heads = lambda t, n: t.reshape(t.shape[:-1] + (n, d))
  unit = lambda t: t * jax.lax.rsqrt(
      jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
  q, k = heads(both[..., :hk * d], hk), heads(both[..., hk * d:2 * hk * d], hk)
  if normalise:
    q, k = unit(q) * d ** -0.5, unit(k)
  out = both_runs(q, k, heads(both[..., 2 * hk * d:], hv), g, beta)
  out = out * jax.lax.rsqrt(
      jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
  out = out * weight * jax.nn.silu(heads(z, hv))
  return out.reshape(batch, length, hv * d)


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


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('group', [1, 2, 4])
@pytest.mark.parametrize('length', [7, 100, 130, 300])
def test_window_kernel_is_the_same_recurrence(length, group, dtype):
  """The Pallas form at heads of 128 (one call a window on the flat
  stream, both directions, the L2 norm as its prologue, the system
  inverted from its diagonal blocks outwards with the window's problems
  side by side, the gated norm as its epilogue), in interpret mode,
  against the recurrence between the reference's norms."""
  hk = 2 if group == 2 else 1
  hv = hk * group
  args = flat_stream(length, hk, hv, dtype, batch=1 if length > 130 else 2,
                     seed=length + group)
  got = gated_delta._window_kernel_call(
      tuple(args[0]), *args[1:], num_key_heads=hk, num_value_heads=hv,
      epsilon=1e-6, interpret=True)
  assert got.shape == args[1].shape and got.dtype == dtype
  want = np.asarray(between_the_norms(*args, hk, hv, 1e-6))
  got = np.asarray(got, np.float32)
  if dtype == jnp.float32:
    np.testing.assert_allclose(got, want, atol=3e-5)
  else:
    # bfloat16 keeps 8 bits: products of rounded operands, float32 sums.
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()
  assert np.abs(want).max() > 1.0


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
def test_window_kernel_prologue_rounds_q_and_k_where_the_modules_do(dtype):
  """The kernel's normalised operands (its prologue, run here as a Pallas
  call of its own in interpret mode) are the modules' to the bit: the
  same float32 sum of squares over the head's 128 lanes, rsqrt, q times
  Dk^-1/2, then one rounding to the compute dtype; behind the window they
  are zero. float32 in, so that no earlier rounding hides a difference."""
  from jax.experimental import pallas as pl
  hk, length, lp, d = 2, 100, 128, 128
  rng = np.random.default_rng(4)
  both = jnp.asarray(rng.normal(size=(2, length, 4 * hk * d)), jnp.float32)

  def prologue(stream_ref, q_ref, k_ref):
    in_rows = jax.lax.broadcasted_iota(jnp.int32, (lp, 1), 0) < length
    for h in range(hk):
      q, k = gated_delta._normalised_query_and_key(
          stream_ref, h, hk * d, in_rows, dtype)
      q_ref[0, :, h * d:(h + 1) * d] = q
      k_ref[0, :, h * d:(h + 1) * d] = k

  block = lambda width: pl.BlockSpec((1, lp, width), lambda i: (i, 0, 0))
  q, k = pl.pallas_call(
      prologue, grid=(2,), in_specs=[block(4 * hk * d)],
      out_specs=[block(hk * d)] * 2,
      out_shape=[jax.ShapeDtypeStruct((2, lp, hk * d), dtype)] * 2,
      interpret=True)(both)
  heads = lambda t: t.reshape(2, length, hk, d)
  want_q = gated_delta.unit_over_head(
      heads(both[..., :hk * d]), d ** -0.5).astype(dtype)
  want_k = gated_delta.unit_over_head(
      heads(both[..., hk * d:2 * hk * d])).astype(dtype)
  np.testing.assert_array_equal(
      np.asarray(heads(q[:, :length]), np.float32),
      np.asarray(want_q, np.float32))
  np.testing.assert_array_equal(
      np.asarray(heads(k[:, :length]), np.float32),
      np.asarray(want_k, np.float32))
  assert not np.asarray(q[:, length:], np.float32).any()
  assert not np.asarray(k[:, length:], np.float32).any()
  # Unit length and the scale, to the rounding of the compute dtype.
  norm = np.linalg.norm(np.asarray(want_k, np.float32), axis=-1)
  np.testing.assert_allclose(norm, 1.0, atol=2e-3)


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
    # Through the kernel's boundary: the same q, k, v flat (unit keys stay
    # unit keys under its prologue), a gate z and the gated norm behind
    # the rule, which keeps a head's relative error.
    flat = lambda a: a.reshape(a.shape[:3] + (-1,))
    z = rounded(draw(2, 100, 256))
    weight = jnp.ones((128,), jnp.float32)
    got = gated_delta._window_kernel_call(
        tuple(jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)), z, g,
        beta, weight, num_key_heads=1, num_value_heads=2, epsilon=1e-6,
        interpret=True).astype(jnp.float32).reshape(2, 100, 2, 128)
    want = np.asarray(between_the_norms(
        jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), z, g, beta,
        weight, 1, 2, 1e-6, normalise=False)).reshape(2, 100, 2, 128)
  else:
    got = gated_delta.gated_delta_two_directions(q, k, v, g, beta)
  error = (np.linalg.norm(np.asarray(got) - want, axis=-1)
           / np.linalg.norm(want, axis=-1))
  assert np.median(error) < 0.01 and error.max() < 0.05


def test_the_code_takes_the_kernel_only_where_it_may(monkeypatch):
  """On the CPU, under a mesh or outside an inference trace the plain form
  runs between the modules' norms; on one TPU device at inference, heads
  of 128 take the kernel. `delta_rule_path` is the one rule, and says
  what `gated_delta_window` does."""
  from deepconsensus_tpu.ops import pallas_util
  taken = []
  monkeypatch.setattr(
      gated_delta, '_window_kernel_call',
      lambda streams, z, *rest, **sizes: taken.append(
          [a.shape for a in streams]) or z)
  run = lambda args, hk, hv: gated_delta.gated_delta_window(
      tuple(args[0]), *args[1:], num_key_heads=hk, num_value_heads=hv,
      epsilon=1e-6)
  path = lambda d, length=8: gated_delta.delta_rule_path(
      key_head_dim=d, value_head_dim=d, num_key_heads=1, num_value_heads=2,
      length=length)
  wide = flat_stream(8, 1, 2, jnp.float32, seed=1)
  narrow = flat_stream(8, 1, 2, jnp.float32, seed=1, d=8)
  run(wide, 1, 2)
  with pallas_util.single_device_inference():
    run(wide, 1, 2)  # no TPU here
    assert path(128) == gated_delta.DELTA_RULE_PLAIN
  assert not taken
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  run(wide, 1, 2)  # nobody declared inference
  assert path(128) == gated_delta.DELTA_RULE_PLAIN
  with pallas_util.single_device_inference():
    run(narrow, 1, 2)  # heads of 8
    assert not taken
    assert path(8) == gated_delta.DELTA_RULE_PLAIN
    assert path(128, length=600) == gated_delta.DELTA_RULE_PLAIN
    assert path(128) == gated_delta.DELTA_RULE_WINDOW_KERNEL
    run(wide, 1, 2)
  assert taken == [[(2, 8, 512)] * 2]


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
def test_plain_form_between_the_modules_norms_is_the_reference(dtype):
  """`gated_delta_window` where the kernel may not run (here: the CPU), at
  heads of 8 and of 128."""
  for d, (hk, hv) in ((8, (2, 4)), (128, (1, 2))):
    args = flat_stream(40, hk, hv, dtype, seed=d, d=d)
    got = gated_delta.gated_delta_window(
        tuple(args[0]), *args[1:], num_key_heads=hk, num_value_heads=hv,
        epsilon=1e-6)
    assert got.shape == args[1].shape and got.dtype == dtype
    want = np.asarray(between_the_norms(*args, hk, hv, 1e-6))
    tolerance = 3e-5 if dtype == jnp.float32 else 0.03 * np.abs(want).max()
    assert np.abs(np.asarray(got, np.float32) - want).max() < tolerance


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
