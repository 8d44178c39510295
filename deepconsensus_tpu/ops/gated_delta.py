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

Two forms of the same arithmetic, behind one entry for a mixer
(`gated_delta_window`: the flat stream [B, L, q | k | v] of each direction
as the convolution leaves it, the L2 norm of every q and k head, the rule in two
directions, the gated RMS norm of every value head, the compute dtype
out) and one rule that chooses between them (`delta_rule_path`). Plain
jnp, as XLA compiles it, runs on the CPU and under a mesh: the stream is
reshaped to heads, normalised, and the [L, L] matrices of every problem
(window x direction x value head) go through device memory between
products, which is what bounds it. On one TPU device at inference
(`pallas_util.may_choose_kernels`, the rule every kernel the code chooses
by itself obeys) heads of 128 take one Pallas call a window instead. What
crosses device memory there: the convolution's output once in (bfloat16,
a head one lane tile of it, the window's own positions: the kernel's
blocks reach past them and it zeroes what lies behind), the gate z, G and
beta, and the normed, gated output once out in the compute dtype; no
padded, re-laid or float32 copy of q, k, v or o. In VMEM, for the
problems of two key heads at a time (direction x key head x value head of
its group: eight at the published sizes), side by side so that every step
is as many independent products: the L2 norm as prologue; q k^T and k k^T
as one product with k stationary; the system inverted from its diagonal
blocks outwards (the same finite product on blocks of 16: six products;
then pairs of inverted blocks merged up to the whole padded window, two
products a doubling: six at 128 positions), one product to apply the
inverse and one to read: 14 products of 128 x 128 x 128 a problem and one
of 256 x 128 x 128 a key head and direction; the gated norm as epilogue.
Both forms take the two norms' arithmetic from `unit_over_head` and
`gated_norm_over_head`, so that q and k are rounded to the compute dtype
at the same place.
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


# Which form of the rule a mixer's forward runs (`forward_launch`'s
# `delta_rule_path`, docs/observability.md).
DELTA_RULE_WINDOW_KERNEL = 'window_kernel'
DELTA_RULE_PLAIN = 'plain'
L2_EPSILON = 1e-6


def delta_rule_path(*, key_head_dim: int, value_head_dim: int,
                    num_key_heads: int, num_value_heads: int,
                    length: int) -> str:
  """The one rule by which `gated_delta_window` takes the Pallas call in
  place of the plain form around its two norms; no option asks for it.
  Heads of KERNEL_HEAD_DIM on both sides (a head is then one lane tile of
  the flat stream), value heads grouped over key heads, a window of one
  chunk, and a TPU in a trace its caller declared inference for one
  device (pallas_util.may_choose_kernels: ModelRunner without a mesh)."""
  kernel = (
      key_head_dim == value_head_dim == KERNEL_HEAD_DIM
      and num_value_heads % num_key_heads == 0
      and length <= MAX_WINDOW_LEN
      and pallas_util.may_choose_kernels())
  return DELTA_RULE_WINDOW_KERNEL if kernel else DELTA_RULE_PLAIN


def unit_over_head(x: jnp.ndarray, scale=None) -> jnp.ndarray:
  """x [..., D] -> x / |x| over the last axis (times `scale`), float32:
  the L2 norm of q and k, in the mixer's modules and in the kernel's
  prologue alike, so that both round the same number."""
  x = x.astype(jnp.float32)
  unit = x * jax.lax.rsqrt(
      jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPSILON)
  return unit if scale is None else unit * scale


def gated_norm_over_head(out: jnp.ndarray, z: jnp.ndarray,
                         weight: jnp.ndarray, epsilon: float) -> jnp.ndarray:
  """out [..., D] float32 -> out / rms(out) * weight * silu(z) over the
  last axis, float32: the mixer's gated RMS norm, in its modules and in
  the kernel's epilogue alike."""
  out = out * jax.lax.rsqrt(
      jnp.mean(jnp.square(out), axis=-1, keepdims=True) + epsilon)
  return out * weight.astype(jnp.float32) * jax.nn.silu(
      z.astype(jnp.float32))


def _block_masks(lp: int):
  """Over an [lp, lp] matrix: the identity (float32); where row and column
  lie in the same diagonal block of KERNEL_BLOCK; and, for each doubling
  of the block up to the whole padded window, where they lie in the same
  doubled block but not in the same half of it."""
  row = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 1)
  sizes = [KERNEL_BLOCK]
  while sizes[-1] < lp:
    sizes.append(sizes[-1] * 2)
  together = [row // size == col // size for size in sizes]
  between = [around & ~inside for inside, around in zip(together,
                                                         together[1:])]
  return (row == col).astype(jnp.float32), together[0], between


def _solved_and_read(systems, reads, rhs, dtype, masks):
  """For every problem of the lists (none depends on another): D solving
  (I + system) D = rhs, then read D -> [Lp, D] float32. Products take
  their operands in `dtype`; every step is written over all the problems
  before the next, so that it issues as many independent products.

  (I + system)^-1 is built from the diagonal blocks of KERNEL_BLOCK
  outwards (`masks`: _block_masks). A block's inverse is the finite
  product of its powers (a strictly triangular block of n has n-th power
  zero); two inverted blocks and what lies between them,
  [[M11, 0], [M21, M22]], give [[T11, 0], [-T22 M21 T11, T22]]
  = T - T Off T (the transpose of it for the upper triangular systems of
  the reversed run: the same formula). Squaring in bfloat16 doubles a
  power's relative error each time, so only the small blocks are inverted
  by powers: the whole window's would lose every digit where keys are
  alike (k_t . k_j near 1)."""
  eye, in_block, between = masks
  dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
  low = lambda xs: [x.astype(dtype) for x in xs]
  powers = [jnp.where(in_block, -system, 0.0) for system in systems]
  inverses = [eye + power for power in powers]
  size = 2
  while size < KERNEL_BLOCK:
    powers = low(dot(x, x) for x in low(powers))
    inverses = [inverse + dot(x, power) for inverse, x, power in zip(
        inverses, low(inverses), powers)]
    size *= 2
  # Off T: T is block diagonal, so the product of the whole system with
  # it, kept where row and column lie in two halves of one doubled block,
  # sums the same terms.
  systems = low(systems)
  for outside in between:
    lows = low(inverses)
    overs = [jnp.where(outside, dot(system, x), 0.0)
             for system, x in zip(systems, lows)]
    inverses = [inverse - dot(x, over) for inverse, x, over in zip(
        inverses, lows, low(overs))]
  corrections = [dot(x, y) for x, y in zip(low(inverses), low(rhs))]
  return [dot(x, y) for x, y in zip(low(reads), low(corrections))]


def _normalised_query_and_key(stream_ref, key_head, key_dim, in_rows, dtype):
  """The kernel's prologue for one key head of one direction: q and k
  [Lp, D] read from that direction's flat stream [1, Lp, C],
  L2-normalised over the head in float32 (q times D^-1/2) and rounded to
  `dtype` where the mixer's modules round them; zero behind the window
  (`in_rows` [Lp, 1])."""
  d = KERNEL_HEAD_DIM
  at = lambda first: pl.ds(pl.multiple_of(first + key_head * d, d), d)
  # dclint: allow=dtype-downcast (q and k meet in the compute dtype,
  # normalised in float32)
  q = jnp.where(in_rows, unit_over_head(
      stream_ref[0, :, at(0)], d ** -0.5), 0.0).astype(dtype)
  # dclint: allow=dtype-downcast (as above)
  k = jnp.where(in_rows, unit_over_head(
      stream_ref[0, :, at(key_dim)]), 0.0).astype(dtype)
  return q, k


def _window_kernel(ahead_ref, back_ref, z_ref, cum_ref, cum_t_ref, beta_ref,
                   weight_ref, o_ref, *, length: int, hk: int, hv: int,
                   pack: int, epsilon: float):
  """One window, both directions, every head, positions in the window's
  order throughout, on the flat stream. ahead_ref and back_ref [1, Lp, C]
  hold [q | k | v] with heads along the lanes as the convolution of each
  direction left them (back_ref what the reversed run reads); z_ref
  [1, Lp, Hv * D] the output gate; cum_ref [2, 1, Lp, Hv] the log decay
  summed from the window's start up to each position (index 0) and from
  each position to its end (index 1), cum_t_ref [2, 1, Hv, Lp] the same
  with positions along the lanes; beta_ref [1, Lp, Hv]; weight_ref [1, D]
  the gated norm's weight; o_ref [1, Lp, Hv * D] in the stream's type.
  The blocks reach past the window's `length` positions to Lp, and what
  they hold there is not defined: every operand is zeroed behind the
  window as it is read (g = 0, beta = 0, k = 0 correct nothing, and no
  position of the window reads them), and the rows of o_ref behind it are
  never written back.

  The reversed run is the same system with `j after t` for `j before t`:
  its matrices are upper triangular, and nothing is turned round. The
  2 * pack * (Hv // Hk) problems of `pack` key heads (direction x key
  head x value head of its group) do not depend on each other and go
  through the inversion step by step, side by side: every step is that
  many products none of which waits for another."""
  d = KERNEL_HEAD_DIM
  lp = ahead_ref.shape[1]
  group = hv // hk
  key_dim = hk * d
  dtype = ahead_ref.dtype
  row = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (lp, lp), 1)
  reach = ((row >= col, row > col), (row <= col, row < col))
  masks = _block_masks(lp)
  lane = jax.lax.broadcasted_iota(jnp.int32, (lp, hv), 1)
  sublane = jax.lax.broadcasted_iota(jnp.int32, (hv, lp), 0)
  in_rows = jax.lax.broadcasted_iota(jnp.int32, (lp, 1), 0) < length
  in_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, lp), 1) < length
  pairs = lambda a, b: jax.lax.dot_general(  # a b^T
      a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  beta = beta_ref[0]
  weight = weight_ref[...]

  def key_heads(step, carry):
    # Per problem (direction x key head x value head of its group): the
    # direction's masks, the key head's products, the value head's decay,
    # beta and right-hand side.
    column = lambda a, head: jnp.where(in_rows, jnp.sum(
        jnp.where(lane == head, a, 0.0), axis=1, keepdims=True), 0.0)
    heads = [(step * pack + h) * group + i
             for h in range(pack) for i in range(group)]
    beta_cols = [column(beta, head) for head in heads]
    systems, reads, rhs = [], [], []
    for direction, (upto, before) in enumerate(reach):
      stream_ref = (ahead_ref, back_ref)[direction]
      cum, cum_t = cum_ref[direction, 0], cum_t_ref[direction, 0]
      for h in range(pack):
        q, k = _normalised_query_and_key(stream_ref, step * pack + h,
                                         key_dim, in_rows, dtype)
        # One product for both, k the stationary operand.
        both_key = pairs(jnp.concatenate([q, k], axis=0), k)
        query_key, key_key = both_key[:lp], both_key[lp:]
        for n in range(h * group, (h + 1) * group):
          head, beta_col = heads[n], beta_cols[n]
          cum_row = jnp.where(in_lanes, jnp.sum(
              jnp.where(sublane == head, cum_t, 0.0), axis=0, keepdims=True),
                              0.0)
          decay = jnp.where(upto, jnp.exp(jnp.minimum(
              column(cum, head) - cum_row, 0.0)), 0.0)
          value = stream_ref[0, :, pl.ds(
              pl.multiple_of(2 * key_dim + head * d, d), d)]
          systems.append(jnp.where(before, beta_col * decay * key_key, 0.0))
          reads.append(decay * query_key)
          rhs.append(jnp.where(
              in_rows, beta_col * value.astype(jnp.float32), 0.0))
    outs = _solved_and_read(systems, reads, rhs, dtype, masks)
    # The two runs' outputs added, then the gated norm over each value
    # head, written once in the stream's type.
    for head, ahead, back in zip(heads, outs, outs[len(heads):]):
      at = pl.ds(pl.multiple_of(head * d, d), d)
      # dclint: allow=dtype-downcast (the gated norm is float32; the
      # stream is the compute dtype)
      o_ref[0, :, at] = gated_norm_over_head(
          ahead + back, z_ref[0, :, at], weight, epsilon).astype(o_ref.dtype)
    return carry

  jax.lax.fori_loop(0, hk // pack, key_heads, 0)


# Problems the kernel takes through the inversion side by side at a padded
# window of 128 positions, and fewer as their [Lp, Lp] matrices grow:
# enough that no MXU waits for a product's cast, few enough that a step's
# matrices stay in VMEM (my chip runs, PR 33, 512 windows of the published
# heads: one by one 62.1 ms a layer in the model; alone 33.7 ms at 4
# abreast, 25.0 at 8, 23.2 at 16, 32 no better than 16; in the model 16.1
# at 8 and 14.7 at 16).
KERNEL_PROBLEMS_ABREAST = 16


def _window_kernel_call(streams, z, g, beta, weight, *, num_key_heads: int,
                        num_value_heads: int, epsilon: float,
                        interpret=None) -> jnp.ndarray:
  """`gated_delta_window` as one Pallas call a window."""
  batch, length, channels = streams[0].shape
  hk, hv, d = num_key_heads, num_value_heads, KERNEL_HEAD_DIM
  lp = -(-length // 128) * 128
  # Key heads a step: each brings the problems of its two directions and
  # its group of value heads.
  abreast = KERNEL_PROBLEMS_ABREAST * 128 * 128 // (lp * lp)
  pack = max(1, abreast // (2 * (hv // hk)))
  while hk % pack:
    pack -= 1
  # The window's blocks, twice each for the pipeline, are Lp x 40 KiB at
  # the published heads: past one lane tile of positions they and a
  # step's matrices need more than the batch-tiled kernels' usual scope
  # (of a v5e core's 128 MiB).
  vmem_limit = pallas_util.BATCH_TILE_VMEM_LIMIT_BYTES * (2 if lp > 128 else 1)
  g = g.astype(jnp.float32)
  from_start = jnp.cumsum(g, axis=1)
  cum = jnp.stack([from_start, from_start[:, -1:] - from_start + g])
  by_window = lambda *shape: pl.BlockSpec((1,) + shape, lambda i: (i, 0, 0))
  both_ways = lambda *shape: pl.BlockSpec(
      (2, 1) + shape, lambda i: (0, i, 0, 0))
  return pl.pallas_call(
      functools.partial(_window_kernel, length=length, hk=hk, hv=hv,
                        pack=pack, epsilon=epsilon),
      grid=(batch,),
      in_specs=[by_window(lp, channels), by_window(lp, channels),
                by_window(lp, hv * d), both_ways(lp, hv), both_ways(hv, lp),
                by_window(lp, hv), pl.BlockSpec((1, d), lambda i: (0, 0))],
      out_specs=by_window(lp, hv * d),
      out_shape=jax.ShapeDtypeStruct((batch, length, hv * d), z.dtype),
      compiler_params=pallas_util.batch_tile_compiler_params(vmem_limit),
      interpret=pallas_util.resolve_interpret(interpret),
      name='gated_delta_window',
  )(*streams, z, cum, jnp.swapaxes(cum, 2, 3), beta.astype(jnp.float32),
    weight.astype(jnp.float32).reshape(1, d))


def gated_delta_two_directions(q, k, v, g, beta,
                               block: int = BLOCK) -> jnp.ndarray:
  """The rule over the window plus the rule over the window reversed, in
  the plain form.

  q, k [2, B, L, Hk, Dk] and v [2, B, L, Hv, Dv]: index 0 holds what the
  run from the window's start reads and index 1 what the run from its end
  reads (what comes before the rule, a causal convolution, differs by
  direction, so the caller brings both), each at the window's own
  positions. g, beta [B, L, Hv]. -> o [B, L, Hv, Dv] float32, the two
  runs' outputs added position by position."""
  # The run from the end is the causal rule over the window turned round.
  turned = lambda x: jnp.concatenate([x[0], jnp.flip(x[1], axis=1)], axis=0)
  both = lambda x: jnp.concatenate([x, jnp.flip(x, axis=1)], axis=0)
  out = gated_delta_causal(turned(q), turned(k), turned(v), both(g),
                           both(beta), block)
  forward, backward = jnp.split(out, 2, axis=0)
  return forward + jnp.flip(backward, axis=1)


def gated_delta_window(streams, z, g, beta, weight, *, num_key_heads: int,
                       num_value_heads: int, epsilon: float) -> jnp.ndarray:
  """A Gated DeltaNet mixer between its convolution and its output
  projection, on the flat stream: the L2 norm of every q and k head (q
  times Dk^-1/2), the rule in two directions, the gated RMS norm of every
  value head.

  streams: two arrays [B, L, Hk * Dk + Hk * Dk + Hv * Dv] that hold
  [q | k | v] as the convolution of each direction left them (the first
  what the run from the window's start reads, the second what the run
  from its end reads, each at the window's own positions); z
  [B, L, Hv * Dv] the output gate; all in the compute dtype. g, beta
  [B, L, Hv]; weight [Dv] -> [B, L, Hv * Dv] in the compute dtype. Where
  `delta_rule_path` says so, one Pallas call a window with the two norms
  as its prologue and epilogue; elsewhere the same arithmetic by heads
  around the plain form."""
  batch, length, channels = streams[0].shape
  hk, hv = num_key_heads, num_value_heads
  value_dim = z.shape[-1]
  key_dim = (channels - value_dim) // 2
  dk, dv = key_dim // hk, value_dim // hv
  if delta_rule_path(key_head_dim=dk, value_head_dim=dv, num_key_heads=hk,
                     num_value_heads=hv,
                     length=length) == DELTA_RULE_WINDOW_KERNEL:
    return _window_kernel_call(streams, z, g, beta, weight, num_key_heads=hk,
                               num_value_heads=hv, epsilon=epsilon)
  both = jnp.stack(streams)
  heads = lambda t, n: t.reshape(t.shape[:-1] + (n, t.shape[-1] // n))
  # dclint: allow=dtype-downcast (q and k meet in the compute dtype,
  # normalised in float32)
  query = unit_over_head(heads(both[..., :key_dim], hk), dk ** -0.5).astype(
      both.dtype)
  # dclint: allow=dtype-downcast (as above)
  key = unit_over_head(heads(both[..., key_dim:2 * key_dim], hk)).astype(
      both.dtype)
  out = gated_delta_two_directions(
      query, key, heads(both[..., 2 * key_dim:], hv), g, beta)
  # dclint: allow=dtype-downcast (the gated norm is float32; the stream
  # is the compute dtype)
  return gated_norm_over_head(out, heads(z, hv), weight, epsilon).astype(
      both.dtype).reshape(batch, length, value_dim)
