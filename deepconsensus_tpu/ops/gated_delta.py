"""The gated delta rule over a window, in chunked (WY) form, two directions.

A Gated DeltaNet layer keeps, per value head, a state S [Dk, Dv] that
starts at zero and at every position decays, is corrected towards the
position's value along its key, and is read by the query:

  S <- exp(g_t) S            g_t <= 0 the log of the decay
  d_t = beta_t (v_t - S^T k_t)
  S <- S + k_t d_t^T
  o_t = S^T q_t

Unlike a plain linear-attention state this has no all-pairs form: d_t
depends on every earlier d_j through S. Over one chunk from a zero state,
with G_t = sum_{m<=t} g_m,

  S_t = sum_{j<=t} exp(G_t - G_j) k_j d_j^T

so the corrections solve a unit lower-triangular system and the outputs
are one masked product:

  (I + A) D = beta * V,   A_tj = beta_t exp(G_t - G_j) (k_t . k_j), j < t
  O = W D,                W_tj = exp(G_t - G_j) (q_t . k_j),        j <= t

The system is solved by blocks of BLOCK positions: each diagonal block of
I + A is inverted as the finite product (I + P)(I + P^2)(I + P^4)... with
P = -A_ii (A_ii is strictly lower triangular, so P^BLOCK = 0: exact, and
all matrix products), then block rows are substituted forward. A window
is one chunk: nothing here passes a state from chunk to chunk, so a
window longer than MAX_WINDOW_LEN is refused.

An encoder has no causal mask: `gated_delta_two_directions` runs the rule
over the window and over the window reversed and adds the two outputs.
Key head h serves value heads h * (Hv // Hk) ... (h + 1) * (Hv // Hk) - 1;
the repeat of q and k is never materialised.

Matrix products take their operands in q's type (bfloat16 as served,
float32 in the tests) with a float32 accumulator; G, the decay, beta and
every mask are float32.

Two forms of the same arithmetic. Plain jnp, as XLA compiles it, runs on
the CPU and under a mesh; there the [L, L] matrices of every problem
(window x direction x value head) go through device memory between
products, which is what bounds it. On one TPU device at inference
(`pallas_util.may_choose_kernels`, the rule every kernel the code chooses
by itself obeys) heads of 128 take one Pallas call a window instead: both
directions and all heads of the window in VMEM, the system inverted from
its diagonal blocks outwards (the same finite product on blocks of 16,
then pairs of inverted blocks merged up to the whole padded window: all
products of 128 x 128), and only q, k, v in and o out cross device
memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepconsensus_tpu.ops import pallas_util

BLOCK = 32
MAX_WINDOW_LEN = 512
# Problems (window x direction x value head) the plain form solves in one
# go; more are taken in turn (jax.lax.map). With blocks of 32 the TPU's
# tiles of 128 lanes hold its [.., 32, .., 32] matrices at a quarter
# full: 0.5 MB a problem at L=100.
MAX_PROBLEMS = 4096
KERNEL_HEAD_DIM = 128
KERNEL_BLOCK = 16


def _inverse_unit_lower(a: jnp.ndarray, dtype) -> jnp.ndarray:
  """(I + a)^-1 for a [..., n, n] strictly lower triangular, n a power of
  two: the product over i of (I + (-a)^(2^i)), 2^i < n."""
  n = a.shape[-1]
  power = -a
  inverse = jnp.eye(n, dtype=jnp.float32) + power
  mm = lambda x, y: jnp.einsum('...ij,...jk->...ik', x.astype(dtype),
                               y.astype(dtype),
                               preferred_element_type=jnp.float32)
  step = 2
  while step < n:
    power = mm(power, power)
    inverse = inverse + mm(inverse, power)
    step *= 2
  return inverse


def _chunk(q, k, v, g, beta, block: int) -> jnp.ndarray:
  """The rule over one chunk from a zero state, one direction.
  q, k [N, L, Hk, Dk]; v [N, L, Hv, Dv]; g, beta [N, L, Hv] float32
  -> o [N, L, Hv, Dv] float32. L is a multiple of `block`."""
  n, length, hk, _ = q.shape
  hv, dv = v.shape[2], v.shape[3]
  group = hv // hk
  dtype = q.dtype
  cum = jnp.transpose(jnp.cumsum(g, axis=1), (0, 2, 1))  # G [N, Hv, L]
  cum = cum.reshape(n, hk, group, length)
  pos = np.arange(length)
  upto = pos[:, None] >= pos[None, :]  # j <= t
  before = pos[:, None] > pos[None, :]  # j < t
  decay = jnp.exp(jnp.where(
      upto, cum[..., :, None] - cum[..., None, :], -jnp.inf))
  key_key = jnp.einsum('nlhd,nmhd->nhlm', k, k,
                       preferred_element_type=jnp.float32)
  query_key = jnp.einsum('nlhd,nmhd->nhlm', q, k,
                         preferred_element_type=jnp.float32)
  by_head = lambda x: jnp.transpose(x, (0, 2, 1)).reshape(
      n, hk, group, length)
  system = jnp.where(
      before, by_head(beta)[..., None] * decay * key_key[:, :, None], 0.0)
  read = (decay * query_key[:, :, None]).astype(dtype)
  # beta * V, by key head and value head within it: [N, Hk, G, L, Dv].
  rhs = jnp.transpose(
      (beta[..., None] * v.astype(jnp.float32)).reshape(
          n, length, hk, group, dv), (0, 2, 3, 1, 4))

  blocks = length // block
  tiles = system.reshape(n, hk, group, blocks, block, blocks, block)
  diagonal = jnp.stack([tiles[:, :, :, i, :, i, :] for i in range(blocks)],
                       axis=3)
  inverse = _inverse_unit_lower(diagonal, dtype).astype(dtype)
  solved = []
  for i in range(blocks):
    right = rhs[:, :, :, i * block:(i + 1) * block]
    if i:
      earlier = jnp.concatenate(solved, axis=3).astype(dtype)
      right = right - jnp.einsum(
          'nhgtj,nhgjd->nhgtd',
          system[:, :, :, i * block:(i + 1) * block, :i * block].astype(
              dtype), earlier, preferred_element_type=jnp.float32)
    solved.append(jnp.einsum(
        'nhgts,nhgsd->nhgtd', inverse[:, :, :, i], right.astype(dtype),
        preferred_element_type=jnp.float32))
  corrections = jnp.concatenate(solved, axis=3).astype(dtype)
  out = jnp.einsum('nhgtj,nhgjd->nhgtd', read, corrections,
                   preferred_element_type=jnp.float32)
  return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(n, length, hv, dv)


def gated_delta_causal(q, k, v, g, beta, block: int = BLOCK) -> jnp.ndarray:
  """The published causal rule over whole windows from a zero state.
  q, k [N, L, Hk, Dk]; v [N, L, Hv, Dv]; g (log decay, <= 0) and beta
  [N, L, Hv] -> o [N, L, Hv, Dv] float32. q arrives scaled."""
  n, length, hk, _ = q.shape
  hv = v.shape[2]
  if hv % hk:
    raise ValueError(f'{hv} value heads do not group over {hk} key heads')
  if length > MAX_WINDOW_LEN:
    raise ValueError(
        f'a window of {length} positions is more than one chunk of '
        f'{MAX_WINDOW_LEN}: state passing between chunks is not here')
  if block & (block - 1):
    raise ValueError(f'block {block} is not a power of two')
  g = g.astype(jnp.float32)
  beta = beta.astype(jnp.float32)
  # Positions added behind the window have beta = 0 and k = 0: they
  # correct nothing, and no position of the window reads them.
  short = -length % block
  if short:
    pad = lambda x: jnp.pad(x, [(0, 0), (0, short)] + [(0, 0)] * (x.ndim - 2))
    q, k, v, g, beta = (pad(x) for x in (q, k, v, g, beta))
  turns = 1
  while (n // turns) * hv > MAX_PROBLEMS and n % (turns * 2) == 0:
    turns *= 2
  if turns == 1:
    out = _chunk(q, k, v, g, beta, block)
  else:
    split = lambda x: x.reshape((turns, n // turns) + x.shape[1:])
    out = jax.lax.map(lambda xs: _chunk(*xs, block),
                      tuple(split(x) for x in (q, k, v, g, beta)))
    out = out.reshape((n,) + out.shape[2:])
  return out[:, :length]


def _window_kernel(q_ref, k_ref, v_ref, cum_ref, cum_t_ref, beta_ref, o_ref):
  """One window, both directions, every head, positions in the window's
  order throughout. q_ref, k_ref [2, 1, Lp, Hk * D] and v_ref
  [2, 1, Lp, Hv * D] (index 1 what the reversed run reads); cum_ref
  [2, 1, Lp, Hv] the log decay summed from the window's start up to each
  position (index 0) and from each position to its end (index 1),
  cum_t_ref [2, 1, Hv, Lp] the same with positions along the lanes;
  beta_ref [1, Lp, Hv]; o_ref [1, Lp, Hv * D] float32. The reversed run
  is the same system with `j after t` for `j before t`: its matrices are
  upper triangular, and nothing is turned round."""
  d = KERNEL_HEAD_DIM
  lp = q_ref.shape[2]
  hk, hv = q_ref.shape[3] // d, v_ref.shape[3] // d
  group = hv // hk
  dtype = q_ref.dtype
  row = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 1)
  eye = (row == col).astype(jnp.float32)
  reach = ((row >= col, row > col), (row <= col, row < col))
  # Positions in the same diagonal block of KERNEL_BLOCK, of twice that,
  # ... of the whole padded window.
  sizes = [KERNEL_BLOCK]
  while sizes[-1] < lp:
    sizes.append(sizes[-1] * 2)
  together = [row // size == col // size for size in sizes]
  between = [around & ~inside for inside, around in zip(together,
                                                         together[1:])]
  lane = jax.lax.broadcasted_iota(jnp.int32, (lp, hv), 1)
  sublane = jax.lax.broadcasted_iota(jnp.int32, (hv, lp), 0)
  dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
  pairs = lambda a, b: jax.lax.dot_general(  # a b^T
      a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  beta = beta_ref[0]

  def key_head(h, carry):
    at = pl.ds(pl.multiple_of(h * d, d), d)
    totals = [0.0] * group
    for direction, (upto, before) in enumerate(reach):
      q, k = q_ref[direction, 0, :, at], k_ref[direction, 0, :, at]
      key_key, query_key = pairs(k, k), pairs(q, k)
      cum, cum_t = cum_ref[direction, 0], cum_t_ref[direction, 0]
      for i in range(group):
        head = h * group + i
        column = lambda a: jnp.sum(jnp.where(lane == head, a, 0.0), axis=1,
                                   keepdims=True)
        cum_row = jnp.sum(jnp.where(sublane == head, cum_t, 0.0), axis=0,
                          keepdims=True)
        beta_col = column(beta)
        decay = jnp.where(
            upto, jnp.exp(jnp.minimum(column(cum) - cum_row, 0.0)), 0.0)
        system = jnp.where(before, beta_col * decay * key_key, 0.0)
        # (I + system)^-1, from the diagonal blocks of KERNEL_BLOCK
        # outwards. A block's inverse is the finite product of its powers
        # (a strictly triangular block of n has n-th power zero); two
        # inverted blocks and what lies between them,
        # [[M11, 0], [M21, M22]], give [[T11, 0], [-T22 M21 T11, T22]]
        # = T - T Off T. Squaring in bfloat16 doubles a power's relative
        # error each time, so only the small blocks are inverted by
        # powers: the whole window's would lose every digit where keys
        # are alike (k_t . k_j near 1).
        power = jnp.where(together[0], -system, 0.0)
        inverse = eye + power
        step = 2
        while step < KERNEL_BLOCK:
          low = power.astype(dtype)
          power = dot(low, low)
          inverse = inverse + dot(inverse.astype(dtype), power.astype(dtype))
          step *= 2
        for outside in between:
          low = inverse.astype(dtype)
          reach_over = jnp.where(outside, system, 0.0).astype(dtype)
          inverse = inverse - dot(low, dot(reach_over, low).astype(dtype))
        value = v_ref[direction, 0, :, pl.ds(pl.multiple_of(head * d, d), d)]
        corrections = dot(inverse.astype(dtype),
                          (beta_col * value.astype(jnp.float32)).astype(dtype))
        totals[i] = totals[i] + dot((decay * query_key).astype(dtype),
                                    corrections.astype(dtype))
    for i in range(group):
      o_ref[0, :, pl.ds(pl.multiple_of((h * group + i) * d, d), d)] = totals[i]
    return carry

  jax.lax.fori_loop(0, hk, key_head, 0)


def _two_directions_kernel(q, k, v, g, beta, interpret=None) -> jnp.ndarray:
  """`gated_delta_two_directions` as one Pallas call a window."""
  _, batch, length, hk, d = q.shape
  hv = v.shape[3]
  lp = -(-length // 128) * 128
  # Positions added behind the window have g = 0, beta = 0 and k = 0:
  # they correct nothing, and no position of the window reads them.
  pad = lambda x, axis: jnp.pad(
      x, [(0, lp - length) if a == axis else (0, 0) for a in range(x.ndim)])
  flat = lambda x: pad(x, 2).reshape(2, batch, lp, -1)
  g = pad(g.astype(jnp.float32), 1)
  from_start = jnp.cumsum(g, axis=1)
  cum = jnp.stack([from_start, from_start[:, -1:] - from_start + g])
  by_window = lambda *shape: pl.BlockSpec(
      (2, 1) + shape, lambda i: (0, i, 0, 0))
  out = pl.pallas_call(
      _window_kernel,
      grid=(batch,),
      in_specs=[by_window(lp, hk * d), by_window(lp, hk * d),
                by_window(lp, hv * d), by_window(lp, hv),
                by_window(hv, lp),
                pl.BlockSpec((1, lp, hv), lambda i: (i, 0, 0))],
      out_specs=pl.BlockSpec((1, lp, hv * d), lambda i: (i, 0, 0)),
      out_shape=jax.ShapeDtypeStruct((batch, lp, hv * d), jnp.float32),
      compiler_params=pallas_util.batch_tile_compiler_params(),
      interpret=pallas_util.resolve_interpret(interpret),
      name='gated_delta_window',
  )(flat(q), flat(k), flat(v), cum, jnp.swapaxes(cum, 2, 3),
    pad(beta.astype(jnp.float32), 1))
  return out[:, :length].reshape(batch, length, hv, d)


def gated_delta_two_directions(q, k, v, g, beta,
                               block: int = BLOCK) -> jnp.ndarray:
  """The rule over the window plus the rule over the window reversed.

  q, k [2, B, L, Hk, Dk] and v [2, B, L, Hv, Dv]: index 0 holds what the
  run from the window's start reads and index 1 what the run from its end
  reads (what comes before the rule, a causal convolution, differs by
  direction, so the caller brings both), each at the window's own
  positions. g, beta [B, L, Hv]. -> o [B, L, Hv, Dv] float32, the two
  runs' outputs added position by position."""
  if (pallas_util.may_choose_kernels()
      and q.shape[-1] == v.shape[-1] == KERNEL_HEAD_DIM
      and v.shape[3] % q.shape[3] == 0 and q.shape[2] <= MAX_WINDOW_LEN):
    return _two_directions_kernel(q, k, v, g, beta)
  # The run from the end is the causal rule over the window turned round.
  turned = lambda x: jnp.concatenate([x[0], jnp.flip(x[1], axis=1)], axis=0)
  both = lambda x: jnp.concatenate([x, jnp.flip(x, axis=1)], axis=0)
  out = gated_delta_causal(turned(q), turned(k), turned(v), both(g),
                           both(beta), block)
  forward, backward = jnp.split(out, 2, axis=0)
  return forward + jnp.flip(backward, axis=1)
