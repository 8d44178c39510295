"""Grouped-head softmax attention over one window, with the rotation of q
and k as its prologue, as one Pallas call a tile of windows.

Query head h reads key-value head h // group; every head is 128 wide, one
lane tile. A layer rotates the whole head of q and k (rotate-half, as
`models/model.py::apply_rotary`) or nothing at all. No mask: where this
form runs the window covers the forward's length.

  o_h[l] = sum_m softmax_m(q_h[l] . k_h//g[m] * D^-1/2) v_h//g[m]

Two forms of the one operator, and a rule that chooses between them
(`grouped_attention_path`; no option asks for either):

* the plain form, `models/model.py::GroupedSoftmaxAttention` on
  [B, L, N, D] operands, for the CPU, float32, a mesh, `dctpu export`, heads
  of another width, a partial rotation, a window that masks and windows
  over the rule's reach;
* `window_tile_attention`, one Pallas call over tiles of windows on the
  FLAT operands the projections write ([B*L, heads x 128], heads along the
  lanes): a grid step takes a few windows and ONE key-value head with its
  group of query heads, which lie side by side in the query's lanes, so k
  and v are read once a group and q once in all. In VMEM it rotates the
  step's k and each q head in float32 as x cos + roll(x, 64) sin_signed
  (sin with its first half negated: the bits of `apply_rotary`), rounds
  them to the compute dtype, scores each window's [L, L] with a float32
  accumulator, takes a float32 softmax normalised by an exact divide,
  rounds the weights and sums the values with a float32 accumulator,
  rounded once: every rounding where the plain form has it.
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
# `grouped_attention_path`, docs/observability.md).
GROUPED_WINDOW_TILE_KERNEL = 'window_tile_kernel'
GROUPED_PLAIN = 'plain'
LANES = 128
# A window's scores are one [L, L] block of at most one lane tile a side.
MAX_WINDOW_LEN = LANES
# Windows a grid step: the fewest whose rows are whole sublane tiles of
# bfloat16 at L=100 (ops/latent_attention.py found 4, 8 and 16 alike, bound
# by the operands' bytes).
KERNEL_WINDOWS_A_STEP = 4


def grouped_attention_path(*, num_heads: int, num_kv_heads: int,
                           head_dim: int, rotary_dim: int,
                           window: Optional[int], length: int, dtype) -> str:
  """The one rule by which a layer takes `window_tile_attention` in place
  of the plain form; no option asks for it. bfloat16 (at float32 XLA's
  product is the stated arithmetic and a Mosaic one takes 3-6 passes), a
  window of at most one lane tile of positions, heads of one lane tile
  whose rotation turns the whole head or nothing, a window that masks
  nothing, whole groups, and a TPU in a trace its caller declared inference
  for one device (pallas_util.may_choose_kernels: ModelRunner without a
  mesh)."""
  kernel = (
      jnp.dtype(dtype) == jnp.bfloat16
      and length <= MAX_WINDOW_LEN
      and head_dim == LANES
      and rotary_dim in (0, head_dim)
      and (window is None or window >= length)
      and num_heads % num_kv_heads == 0
      and pallas_util.may_choose_kernels())
  return GROUPED_WINDOW_TILE_KERNEL if kernel else GROUPED_PLAIN


def signed_tables(cos: np.ndarray, sin: np.ndarray):
  """`rotary_tables`' (cos, sin) [L, D] -> (cos, sin_signed): the sin with
  its first half negated, so that x cos + roll(x, D / 2) sin_signed is
  x cos + [-x2 | x1] sin to the bit (a negation is exact)."""
  half = sin.shape[1] // 2
  return cos, np.concatenate([-sin[:, :half], sin[:, half:]], axis=1)


def turned(x: jnp.ndarray, cos: jnp.ndarray,
           sin_signed: jnp.ndarray) -> jnp.ndarray:
  """Inside a kernel: x [rows, 128] in its dtype, rotated in float32 by
  tables of the same shape and rounded back, as `apply_rotary` rotates a
  head and the plain form rounds it. A half lane tile's roll swaps the
  halves, and sin_signed puts the minus where [-x2 | x1] has it."""
  lax = jax.lax
  x32 = lax.convert_element_type(x, jnp.float32)
  return lax.convert_element_type(lax.add(
      lax.mul(x32, cos), lax.mul(pltpu.roll(x32, LANES // 2, 1), sin_signed)),
                                  x.dtype)


def _window_tile_kernel(*refs, windows: int, group: int, length: int,
                        scale: float, rotated: bool):
  """One grid step: `windows` windows (row ranges of `length`) by one
  key-value head and its `group` query heads (lane tiles of the q block).
  Written stage by stage over the step's head-windows, not head-window by
  head-window, in `jax.lax` primitives (ops/latent_attention.py says
  why)."""
  if rotated:
    q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, q_turned, k_turned = refs
    # The whole block at once, its rows those of the tables; the windows'
    # row ranges are read back out of VMEM.
    cos, sin_signed = cos_ref[...], sin_ref[...]
    k_turned[...] = turned(k_ref[...], cos, sin_signed)
    for h in range(group):
      lanes = slice(h * LANES, (h + 1) * LANES)
      q_turned[:, lanes] = turned(q_ref[:, lanes], cos, sin_signed)
    q_ref, k_ref = q_turned, k_turned
  else:
    q_ref, k_ref, v_ref, o_ref = refs
  lax, dtype = jax.lax, o_ref.dtype
  pairs = lambda a, b: lax.dot_general(  # a b^T
      a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  over_keys = lambda reduce, s: lax.expand_dims(reduce(s, (1,)), (1,))
  scale = jnp.float32(scale)
  problems = [(slice(t * length, (t + 1) * length),
               slice(h * LANES, (h + 1) * LANES))
              for t in range(windows) for h in range(group)]
  scores = [lax.mul(pairs(q_ref[rows, lanes], k_ref[rows, :]), scale)
            for rows, lanes in problems]
  weights = []
  for s in scores:
    # Normalised in float32, then rounded.
    unnormalised = lax.exp(lax.sub(s, over_keys(lax.reduce_max, s)))
    weights.append(lax.convert_element_type(lax.div(
        unnormalised, over_keys(lax.reduce_sum, unnormalised)), dtype))
  for (rows, lanes), w in zip(problems, weights):
    o_ref[rows, lanes] = lax.convert_element_type(
        lax.dot_general(w, v_ref[rows, :], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32), dtype)


def windows_a_step(batch: int, length: int) -> int:
  """Windows a grid step: a block's rows are whole sublane tiles of the
  compute dtype (16 rows of bfloat16), or the whole array."""
  windows = KERNEL_WINDOWS_A_STEP
  while windows * length % 16:
    windows *= 2
  return min(windows, batch)


# Traced once a shape and inlined where it is called: a stack's layers are
# alike, and a kernel traced anew for each costs the chip's host a third of
# a second a call.
@functools.partial(
    jax.jit,
    static_argnames=('length', 'num_kv_heads', 'scale', 'interpret'),
    inline=True)
def _call(q, k, v, *tables, length: int, num_kv_heads: int, scale: float,
          interpret: bool):
  rows, dtype = q.shape[0], q.dtype
  group = q.shape[1] // (num_kv_heads * LANES)
  windows = windows_a_step(rows // length, length)
  block = windows * length
  by_head = lambda width: pl.BlockSpec((block, width), lambda i, j: (i, j))
  whole = pl.BlockSpec((block, LANES), lambda i, j: (0, 0))
  return pl.pallas_call(
      functools.partial(_window_tile_kernel, windows=windows, group=group,
                        length=length, scale=scale, rotated=bool(tables)),
      grid=(pl.cdiv(rows, block), num_kv_heads),
      in_specs=[by_head(group * LANES), by_head(LANES), by_head(LANES)]
      + [whole] * len(tables),
      out_specs=by_head(group * LANES),
      out_shape=jax.ShapeDtypeStruct(q.shape, dtype),
      scratch_shapes=[pltpu.VMEM((block, group * LANES), dtype),
                      pltpu.VMEM((block, LANES), dtype)] if tables else [],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'arbitrary'),
          vmem_limit_bytes=pallas_util.GROUPED_ATTENTION_VMEM_LIMIT_BYTES),
      interpret=interpret,
      name='grouped_window_tile',
  )(q, k, v, *tables)


def window_tile_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          cos: Optional[np.ndarray],
                          sin_signed: Optional[np.ndarray], *, length: int,
                          num_heads: int, num_kv_heads: int, scale: float,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
  """The grouped-head operator on the flat stream, windows of `length` rows
  one after another and heads along the lanes, as one Pallas call:

  q [B*L, N 128], k and v [B*L, Nkv 128], q and k NOT yet rotated; cos and
  sin_signed the layer's tables [L, 128] as numpy arrays (`signed_tables`;
  None for a layer without positions), held as constants of the program
  and read once a call -> o [B*L, N 128] in q's type, what the plain form
  returns with its heads merged. A last tile of fewer windows than a step
  takes reads rows behind the array's end, which reach no window but their
  own and are not written."""
  if q.shape[1] != num_heads * LANES:
    raise ValueError(f'q of {q.shape[1]} lanes is not {num_heads} heads of '
                     f'{LANES}')
  tables = ()
  if cos is not None:
    windows = windows_a_step(q.shape[0] // length, length)
    tables = tuple(np.tile(np.asarray(t, np.float32), (windows, 1))
                   for t in (cos, sin_signed))
  return _call(q, k, v, *tables, length=length, num_kv_heads=num_kv_heads,
               scale=float(scale),
               interpret=pallas_util.resolve_interpret(interpret))
