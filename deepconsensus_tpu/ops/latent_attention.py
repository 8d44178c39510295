"""Multi-head latent attention over one window, in its whole-window form.

Keys and values of every head come out of one low-rank latent a position,
up-projected for the whole window at once; beside it each position has ONE
rotary key that all heads share. A head's query and key are the
concatenation of a part without position (`nope`) and the rotary part
(`rope`), so its score is the sum of two products:

  score_h[l, m] = (q_nope_h[l] . k_nope_h[m] + q_rope_h[l] . k_rope[m]) * scale
  o_h[l] = sum_m softmax_m(score_h[l, :]) v_h[m]

The second product is taken against the one shared key as it is: the rotary
key is never broadcast to the heads and no [B, L, N, nope + rope] key is
laid out. Value heads may be narrower than query/key heads. No mask: an
encoder attends over the whole window in both directions. The softmax is
float32.

Two forms of the one operator, and a rule that chooses between them
(`latent_attention_path`; no option asks for either):

* `latent_attention`, plain `jax.numpy` over [B, L, N, D] operands, for the
  CPU, float32, a mesh, `dctpu export` and windows over the rule's reach;
* `window_tile_attention`, one Pallas call over tiles of windows on the
  FLAT operands the projections write ([B*L, heads x width], heads along
  the lanes): a grid step takes a few windows and a few heads, keeps each
  head's [L, L] scores in VMEM and reads q, the keys and v once. The rotary
  query comes as all heads' first halves and all heads' second halves (so
  that XLA's rotation in front of the call moves nothing along the lanes),
  and the shared rotary key placed in a zeroed lane tile a half and a head
  of a group (`placed_rotary_keys`), so the rotary part of a score is two
  products. Every rounding is where the plain form has it: float32
  accumulators, the score products added in float32, the softmax
  normalised in float32 and then rounded to the compute dtype, the values'
  float32 sum rounded once.

The "absorbed" form, in which a decode step scores against a cache of
[latent, k_rope] without up-projecting it, has nothing to run on here: this
system has no cache and no decode step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util

# Which form of the operator a layer's forward runs (`forward_launch`'s
# `latent_attention_path`, docs/observability.md).
LATENT_WINDOW_TILE_KERNEL = 'window_tile_kernel'
LATENT_PLAIN = 'plain'
LANES = 128
# A window's scores are one [L, L] block of at most one lane tile a side.
MAX_WINDOW_LEN = LANES
# Windows a grid step of the kernel (times the four heads of a lane tile of
# half rotary parts: 16 head-windows). They are independent chains, which
# Mosaic interleaves only where they are written side by side (PERF.md, PR
# 33). At the published heads the call is bound by its operands' bytes
# either way: 4, 8 and 16 windows a step all read 2.90-2.91 ms for 512
# windows of 32 heads, 1.99 GB at 686 GB/s (my chip run, PR 39); the
# fewest that make whole tiles of rows at L=100 are the least to trace.
KERNEL_WINDOWS_A_STEP = 4


def heads_a_lane_tile(qk_rope_head_dim: int) -> int:
  """How many heads' half rotary parts fill one lane tile, the heads a
  grid step of the kernel takes (4 at the published 64); 0 where halves do
  not tile the lanes."""
  half = qk_rope_head_dim // 2
  whole = qk_rope_head_dim % 2 == 0 and half and LANES % half == 0
  return LANES // half if whole else 0


def latent_attention_path(*, num_heads: int, qk_nope_head_dim: int,
                          qk_rope_head_dim: int, v_head_dim: int,
                          length: int, dtype) -> str:
  """The one rule by which a layer takes `window_tile_attention` in place
  of the plain form; no option asks for it. bfloat16 (at float32 XLA's
  product is the stated arithmetic and a Mosaic one takes 3-6 passes), a
  window of at most one lane tile of positions, position-free and value
  heads of whole lane tiles (a head is then a block of the flat operands
  as the projections write them), the halves of a few heads' rotary parts
  a lane tile each and the heads whole groups of that many
  (`heads_a_lane_tile`), and a TPU in a trace its caller declared inference
  for one device (pallas_util.may_choose_kernels: ModelRunner without a
  mesh)."""
  group = heads_a_lane_tile(qk_rope_head_dim)
  kernel = (
      jnp.dtype(dtype) == jnp.bfloat16
      and length <= MAX_WINDOW_LEN
      and qk_nope_head_dim % LANES == 0
      and v_head_dim % LANES == 0
      and group > 0
      and num_heads % group == 0
      and pallas_util.may_choose_kernels())
  return LATENT_WINDOW_TILE_KERNEL if kernel else LATENT_PLAIN


def latent_attention(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                     k_nope: jnp.ndarray, k_rope: jnp.ndarray,
                     value: jnp.ndarray, scale: float) -> jnp.ndarray:
  """q_nope, k_nope [B, L, N, Dn]; q_rope [B, L, N, Dr] and k_rope
  [B, L, Dr], both already rotated; value [B, L, N, Dv] -> [B, L, N, Dv]
  in value's type. Products take their operands' type with a float32
  accumulator; scores, scale and softmax are float32."""
  scores = jnp.einsum('blnd,bmnd->bnlm', q_nope, k_nope,
                      preferred_element_type=jnp.float32)
  scores = scores + jnp.einsum('blnr,bmr->bnlm', q_rope, k_rope,
                               preferred_element_type=jnp.float32)
  weights = jax.nn.softmax(scores * jnp.float32(scale), axis=-1)
  out = jnp.einsum('bnlm,bmnd->blnd', weights.astype(value.dtype), value,
                   preferred_element_type=jnp.float32)
  return out.astype(value.dtype)


def flat_query_kernels(kernel: jnp.ndarray, qk_nope_head_dim: int):
  """The query's leaf [H, N, Dn + Dr] (a head's position-free columns, then
  its rotary ones) -> ([H, N Dn], [H, N Dr]) as the flat products contract
  it: every head's position-free columns, a whole lane tile a head, and
  apart from them the rotary columns, all heads' first halves in front of
  all heads' second halves. A column is a column of the leaf, so each
  element of x times these is the dot product the leaf's own product
  gives."""
  hidden, heads, width = kernel.shape
  half = (width - qk_nope_head_dim) // 2
  columns = lambda start, size: kernel[:, :, start:start + size].reshape(
      hidden, heads * size)
  return columns(0, qk_nope_head_dim), jnp.concatenate(
      [columns(qk_nope_head_dim, half), columns(qk_nope_head_dim + half, half)],
      axis=1)


def placed_rotary_keys(k_first: jnp.ndarray,
                       k_second: jnp.ndarray) -> jnp.ndarray:
  """The shared rotary key's two halves, each [B*L, Dr / 2] and rotated ->
  [B*L, heads x 2 lane tiles]: for the j-th of the `heads_a_lane_tile` heads
  whose rotary queries share a lane tile a half, `k_first` placed in the j-th
  Dr / 2 lanes of a zeroed lane tile and `k_second` likewise in the tile
  behind it. A head scores the shared tiles of first and second halves
  against its own pair of these: the zeros add exact zeros to a float32
  sum, so the score is q_rope_h . k_rope as it was. The one key is laid
  out `heads` times a layer, not once a query head."""
  half = k_first.shape[1]
  placed = lambda k, j: jnp.pad(k, ((0, 0), (j * half, LANES - (j + 1) * half)))
  return jnp.concatenate(
      [placed(k, j) for j in range(LANES // half)
       for k in (k_first, k_second)], axis=-1)


def _window_tile_kernel(q_nope_ref, q_first_ref, q_second_ref, kv_ref,
                        keys_ref, o_ref, *, windows: int, heads: int,
                        length: int, nope: int, value: int, scale: float):
  """One grid step: `windows` windows (row ranges of `length`) by `heads`
  heads (lane ranges) of the flat blocks; the heads' rotary queries are
  one lane tile of first halves and one of second halves. Written stage by
  stage over the step's head-windows, not head-window by head-window."""
  # `jax.lax` primitives where `jnp` operators would do: every operator on
  # a traced value is a jitted `jnp` function, and in a process where those
  # miss their cache 416 of them (a step of 8 windows) took 2.1 s of the
  # first launch to trace (my chip run, PR 39). The jaxpr is the same, equation for
  # equation, as `jax.nn.softmax`'s own steps written with `jnp`.
  lax, dtype = jax.lax, o_ref.dtype
  pairs = lambda a, b: lax.dot_general(  # a b^T
      a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  over_keys = lambda reduce, s: lax.expand_dims(reduce(s, (1,)), (1,))
  scale = jnp.float32(scale)
  problems = [(slice(t * length, (t + 1) * length), h)
              for t in range(windows) for h in range(heads)]
  scores = []
  for rows, h in problems:
    at, key = h * (nope + value), 2 * h * LANES
    s = pairs(q_nope_ref[rows, h * nope:(h + 1) * nope],
              kv_ref[rows, at:at + nope])
    s = lax.add(s, lax.add(
        pairs(q_first_ref[rows, :], keys_ref[rows, key:key + LANES]),
        pairs(q_second_ref[rows, :],
              keys_ref[rows, key + LANES:key + 2 * LANES])))
    scores.append(lax.mul(s, scale))
  weights = []
  for s in scores:
    # Normalised in float32, then rounded.
    unnormalised = lax.exp(lax.sub(s, over_keys(lax.reduce_max, s)))
    weights.append(lax.convert_element_type(lax.div(
        unnormalised, over_keys(lax.reduce_sum, unnormalised)), dtype))
  for (rows, h), w in zip(problems, weights):
    at = h * (nope + value) + nope
    o_ref[rows, h * value:(h + 1) * value] = lax.convert_element_type(
        lax.dot_general(w, kv_ref[rows, at:at + value],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32), dtype)


def _windows_a_step(batch: int, length: int) -> int:
  """Windows a grid step: a block's rows are whole sublane tiles of the
  compute dtype (16 rows of bfloat16), or the whole array."""
  windows = KERNEL_WINDOWS_A_STEP
  while windows * length % 16:
    windows *= 2
  return min(windows, batch)


# Traced once a shape and inlined where it is called: a stack's layers are
# alike, and a kernel traced anew for each costs the chip's host a third of
# a second a call (PERF.md, PR 35).
@functools.partial(
    jax.jit, static_argnames=('length', 'num_heads', 'scale', 'interpret'),
    inline=True)
def _call(q_nope, q_first, q_second, kv, keys, *, length: int, num_heads: int,
          scale: float, interpret: bool):
  rows, nope = q_nope.shape[0], q_nope.shape[1] // num_heads
  value = kv.shape[1] // num_heads - nope
  windows = _windows_a_step(rows // length, length)
  heads = keys.shape[1] // (2 * LANES)
  by_heads = lambda width: pl.BlockSpec(
      (windows * length, width), lambda i, j: (i, j))
  return pl.pallas_call(
      functools.partial(_window_tile_kernel, windows=windows, heads=heads,
                        length=length, nope=nope, value=value, scale=scale),
      grid=(pl.cdiv(rows, windows * length), num_heads // heads),
      in_specs=[by_heads(heads * nope), by_heads(LANES), by_heads(LANES),
                by_heads(heads * (nope + value)),
                pl.BlockSpec((windows * length, keys.shape[1]),
                             lambda i, j: (i, 0))],
      out_specs=by_heads(heads * value),
      out_shape=jax.ShapeDtypeStruct((rows, num_heads * value), kv.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'arbitrary'),
          vmem_limit_bytes=pallas_util.LATENT_ATTENTION_VMEM_LIMIT_BYTES),
      interpret=interpret,
      name='latent_window_tile',
  )(q_nope, q_first, q_second, kv, keys)


def window_tile_attention(q_nope: jnp.ndarray, q_first: jnp.ndarray,
                          q_second: jnp.ndarray, kv: jnp.ndarray,
                          keys: jnp.ndarray, *, length: int, num_heads: int,
                          scale: float,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
  """`latent_attention` on the flat stream, windows of `length` rows one
  after another and heads along the lanes, as one Pallas call:

  q_nope [B*L, N Dn]; q_first and q_second [B*L, N Dr / 2], the first and
  the second halves of every head's rotary query, rotated; kv
  [B*L, N (Dn + Dv)], a head's k_nope and then its v; `keys` the shared
  rotary key as `placed_rotary_keys` lays it out for the heads of one lane
  tile -> [B*L, N Dv] in kv's type, what `latent_attention` returns with
  its last two axes merged. A last tile of fewer windows than a step takes
  reads rows behind the array's end, which reach no window but their own
  and are not written."""
  return _call(q_nope, q_first, q_second, kv, keys, length=length,
               num_heads=num_heads, scale=float(scale),
               interpret=pallas_util.resolve_interpret(interpret))


def halves_from_pairs(rotary_dim: int) -> np.ndarray:
  """The column order that turns a rotary part published for interleaved
  pairs (2i, 2i + 1) into the one `apply_rotary` rotates, halves
  (i, i + rotary_dim / 2): column j of the program's kernel is column
  `halves_from_pairs(d)[j]` of the published one. Applied to the rotary
  columns of the query kernel (every head) and of the shared rotary key,
  it is a relabelling: q_rope . k_rope is a sum over the same pairs."""
  return np.concatenate([np.arange(0, rotary_dim, 2),
                         np.arange(1, rotary_dim, 2)])
