"""The selective scan of a Mamba-1 mixer over a window, two directions.

A Mamba mixer keeps, per channel i of its inner width, a state h[i, :] of
`d_state` values that starts at zero and at every position decays by an
amount the input chooses and takes in the input along B_t:

  Delta_t[i] = softplus(dt_t[i] + b_dt[i])          (dt_t = delta_t W_dt)
  h_t[i, s]  = exp(Delta_t[i] A[i, s]) h_{t-1}[i, s] + Delta_t[i] x_t[i] B_t[s]
  y_t[i]     = sum_s h_t[i, s] C_t[s] + D[i] x_t[i]

with A = -exp(A_log) [I, S], x the convolution's output, B_t and C_t [S]
shared by every channel. Delta, A, the state and the exponentials are
float32, as the published code keeps them.

An encoder has no causal mask: `selective_scan_two_directions` runs the
scan over the window and over the window reversed (the caller brings each
direction's own x, dt, B and C, since the convolution before the scan
differs by direction) and adds the two outputs position by position, each
with its own D term; the sum is gated by silu(z).

Two forms of the same arithmetic, behind one entry and one rule that
chooses between them (`ssm_scan_path`; no option asks for either):

* the plain form, as XLA compiles it, for the CPU and under a mesh: a
  `lax.scan` over positions whose carry is the [B, I, S] float32 state of
  one direction;
* on one TPU device at inference (`pallas_util.may_choose_kernels`, the
  rule every kernel the code chooses by itself obeys) one Pallas call a
  layer on the flat operands the projections write ([B*L, I], windows one
  after another), a grid step = a tile of windows x a group of up to 1,024
  channels. What crosses device memory: x, dt and z once in (the compute
  dtype, z read where it lies in in_proj's output), B and C of both
  directions once in (float32, a grid step's rows in scalar memory), y
  once out in the compute dtype; nothing of size [L, I, S]. In VMEM the
  prologue takes softplus(dt + b_dt) and Delta x of both directions for
  every row and lays a row's channels as one [8, 128] array; then each
  state is one such array and the scan's step for it is whole-array
  products with B_t and C_t as scalars, each run starting afresh where its
  window starts; the epilogue adds the two directions and their D terms
  and multiplies by silu(z).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util

# Which form of the scan a mixer's forward runs (`forward_launch`'s
# `ssm_scan_path`, docs/observability.md).
SSM_SCAN_WINDOW_KERNEL = 'window_kernel'
SSM_SCAN_PLAIN = 'plain'
LANES = 128
SUBLANES = 8
# A state a channel group: at most a whole vreg of channels, [8, 128].
MAX_CHANNEL_TILES = SUBLANES


def windows_a_step(length: int) -> int:
  """Windows a grid step: the fewest, from four, whose rows are whole
  sublane tiles of bfloat16 (16 rows)."""
  windows = 4
  while windows * length % 16:
    windows *= 2
  return windows


def ssm_scan_path(*, inner: int, state: int, batch: int, length: int) -> str:
  """The one rule by which `selective_scan_two_directions` takes the
  Pallas call in place of the plain form; no option asks for it. An inner
  width of whole lane tiles, an even state, a pack of whole grid steps
  (`windows_a_step`), and a TPU in a trace its caller declared inference
  for one device (pallas_util.may_choose_kernels: ModelRunner without a
  mesh)."""
  kernel = (
      inner % LANES == 0
      and state % 2 == 0
      and batch % windows_a_step(length) == 0
      and pallas_util.may_choose_kernels())
  return SSM_SCAN_WINDOW_KERNEL if kernel else SSM_SCAN_PLAIN


def _one_direction(x, dt, b, c, a, d, dt_bias, reverse: bool):
  """The scan over [B, L, ...] operands from a zero state, from the window's
  start or (`reverse`) from its end -> y [B, L, I] float32 with the D
  term."""
  f32 = lambda t: t.astype(jnp.float32)
  delta = jax.nn.softplus(f32(dt) + dt_bias)  # [B, L, I]
  x, b, c = f32(x), f32(b), f32(c)
  positions = lambda t: jnp.moveaxis(t, 1, 0)

  def step(h, inputs):
    delta_t, x_t, b_t, c_t = inputs  # [B, I], [B, I], [B, S], [B, S]
    h = (jnp.exp(delta_t[..., None] * a) * h
         + (delta_t * x_t)[..., None] * b_t[:, None, :])
    return h, jnp.sum(h * c_t[:, None, :], axis=-1)

  state = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
  _, y = jax.lax.scan(step, state, tuple(
      positions(t) for t in (delta, x, b, c)), reverse=reverse)
  return jnp.moveaxis(y, 0, 1) + d * x


def _plain(streams, dts, bcs, xz, a, d, dt_bias, *, length: int):
  inner, s = a.shape
  windows = lambda t: t.reshape(-1, length, t.shape[-1])
  y = sum(_one_direction(windows(x), windows(dt), windows(bc[:, :s]),
                         windows(bc[:, s:]), a, d, dt_bias,
                         reverse=bool(direction))
          for direction, (x, dt, bc) in enumerate(zip(streams, dts, bcs)))
  z = xz[:, inner:]
  # dclint: allow=dtype-downcast (the scan is float32; the stream is the
  # compute dtype)
  return (y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))).astype(
      z.dtype)


def channel_tiles(inner: int) -> int:
  """Lane tiles of channels a grid step: the most, up to a whole vreg of
  channels (MAX_CHANNEL_TILES), that divide the inner width's."""
  tiles = inner // LANES
  return max(n for n in range(1, MAX_CHANNEL_TILES + 1) if tiles % n == 0)


def _window_kernel(xf_ref, xb_ref, dtf_ref, dtb_ref, z_ref, bc_ref, a_ref,
                   d_ref, bias_ref, o_ref, delta_ref, dx_ref, y_ref, *,
                   length: int):
  """A tile of windows (R rows: windows of `length` rows one after another)
  by a group of T lane tiles of channels, both directions. x*, dt* and z
  [R, T 128] in the compute dtype (x and dt of the run from the window's
  start, then of the run from its end); bc_ref [R 4 S] float32 in SMEM, a
  row's B and C of the first run, then of the second; a_ref [S, 1, T, 128]
  = A, a state's channels as one [T, 128] array; d_ref and bias_ref
  [1, T 128] -> o_ref [R, T 128]. Scratch, float32, by direction:
  delta_ref and dx_ref [2, R T, 128], a row's channels as rows T r ...
  T r + T - 1 (laid so by strided stores); y_ref [2, R T, 128] the same.

  A state is one [T, 128] array of the group's channels, and B_t and C_t
  are scalars: the scan's step for a state is four products, two sums and
  one exponential over the whole group, with nothing moved across lanes or
  sublanes. The states are independent but for their sum y_t, so half of
  them are carried in registers at a time along a run, the other half in a
  second pass that adds to y. Each run starts afresh where its window
  starts: Delta there is inf for the decay (A < 0, so exp(Delta A) = 0),
  and Delta x is taken before."""
  lax = jax.lax
  f32 = lambda t: lax.convert_element_type(t, jnp.float32)
  rows = xf_ref.shape[0]
  s, _, tiles, _ = a_ref.shape
  bias = bias_ref[...]
  position = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) % length
  for direction, (x_ref, dt_ref) in enumerate(((xf_ref, dtf_ref),
                                               (xb_ref, dtb_ref))):
    delta = jax.nn.softplus(f32(dt_ref[...]) + bias)
    dx = delta * f32(x_ref[...])
    first = 0 if direction == 0 else length - 1
    for j in range(tiles):
      lanes = slice(j * LANES, (j + 1) * LANES)
      laid = pl.ds(j, rows, stride=tiles)
      delta_ref[direction, laid, :] = jnp.where(position == first, jnp.inf,
                                                delta[:, lanes])
      dx_ref[direction, laid, :] = dx[:, lanes]

  half = s // 2
  for direction in range(2):
    for part in range(2):
      a = [a_ref[part * half + k, 0] for k in range(half)]

      def step(t, h, direction=direction, part=part, a=a):
        r = t if direction == 0 else rows - 1 - t
        at = pl.ds(pl.multiple_of(r * tiles, tiles), tiles)
        delta, dx = delta_ref[direction, at, :], dx_ref[direction, at, :]
        y = (jnp.zeros((tiles, LANES), jnp.float32) if part == 0
             else y_ref[direction, at, :])
        b_at = (r * 4 + direction * 2) * s + part * half
        h = list(h)
        for k in range(half):
          h[k] = (jnp.exp(delta * a[k]) * h[k]
                  + dx * bc_ref[b_at + k])
          y = y + h[k] * bc_ref[b_at + s + k]
        y_ref[direction, at, :] = y
        return tuple(h)

      zero = jnp.zeros((tiles, LANES), jnp.float32)
      lax.fori_loop(0, rows, step, (zero,) * half)

  for j in range(tiles):
    lanes = slice(j * LANES, (j + 1) * LANES)
    laid = pl.ds(j, rows, stride=tiles)
    y = (y_ref[0, laid, :] + y_ref[1, laid, :]
         + d_ref[:, lanes] * (f32(xf_ref[:, lanes]) + f32(xb_ref[:, lanes])))
    # dclint: allow=dtype-downcast (the scan is float32; the stream is the
    # compute dtype)
    o_ref[:, lanes] = (y * jax.nn.silu(f32(z_ref[:, lanes]))).astype(
        o_ref.dtype)


# Traced once a shape and inlined where it is called: a stack's mixers are
# alike, and a kernel body traced anew for each costs the host seconds.
@functools.partial(jax.jit, static_argnames=('length', 'interpret'),
                   inline=True)
def _call(xf, xb, dtf, dtb, xz, bc, a, d, dt_bias, *, length: int,
          interpret: bool):
  s, groups, tiles, _ = a.shape
  total, width = xz.shape[0], tiles * LANES
  block = windows_a_step(length) * length
  stream = pl.BlockSpec((block, width), lambda i, j: (i, j))
  row = pl.BlockSpec((1, width), lambda i, j: (0, j))
  return pl.pallas_call(
      functools.partial(_window_kernel, length=length),
      grid=(total // block, groups),
      in_specs=[stream] * 4 + [
          # z: the second half of in_proj's output, read where it lies.
          pl.BlockSpec((block, width), lambda i, j: (i, j + groups)),
          pl.BlockSpec((block * 4 * s,), lambda i, j: (i,),
                       memory_space=pltpu.SMEM),
          pl.BlockSpec((s, 1, tiles, LANES), lambda i, j: (0, j, 0, 0)),
          row, row],
      out_specs=stream,
      out_shape=jax.ShapeDtypeStruct((total, groups * width), xz.dtype),
      scratch_shapes=[pltpu.VMEM((2, block * tiles, LANES), jnp.float32)] * 3,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel'),
          vmem_limit_bytes=pallas_util.SSM_SCAN_VMEM_LIMIT_BYTES),
      interpret=interpret,
      name='ssm_scan_window',
  )(xf, xb, dtf, dtb, xz, bc, a, d, dt_bias)


def _window_kernel_call(streams, dts, bcs, xz, a, d, dt_bias, *, length: int,
                        interpret=None) -> jnp.ndarray:
  """`selective_scan_two_directions` as one Pallas call."""
  inner, s = a.shape
  tiles = channel_tiles(inner)
  # B and C of both directions a row, flat, for the kernel's scalar reads.
  bc = jnp.concatenate([bc.astype(jnp.float32) for bc in bcs],
                       axis=-1).reshape(-1)
  # A state's channels as [groups, T, 128]. |A| under 1e-30 turns exp(Delta
  # A) into 1 in float32 for any Delta below 1e22 as it is; held at -1e-30,
  # inf x A is -inf and never nan.
  a = jnp.minimum(a, -1e-30).T.reshape(s, inner // (tiles * LANES), tiles,
                                       LANES)
  return _call(*streams, *dts, xz, bc, a, d.reshape(1, inner),
               dt_bias.reshape(1, inner), length=length,
               interpret=pallas_util.resolve_interpret(interpret))


def selective_scan_two_directions(streams, dts, bcs, xz, a_log, d, dt_bias,
                                  *, length: int):
  """A Mamba mixer between its projections x_proj / dt_proj and its output
  projection, on the flat stream (windows of `length` rows one after
  another): the scan from each window's start plus the scan from its end,
  each with its D term, gated by silu(z).

  streams: two arrays [B*L, I] in the compute dtype, the convolution's
  output x of the run from the window's start and of the run from its end
  (each at the window's own positions); dts: two [B*L, I], delta W_dt of
  each run without its bias; bcs: two [B*L, 2 S] float32, [B | C] of each
  run after their norms; xz [B*L, 2 I] in_proj's output, whose second half
  is the gate z. a_log [I, S], d and dt_bias [I] -> [B*L, I] in the
  compute dtype. Where `ssm_scan_path` says so, one Pallas call; elsewhere
  the plain form."""
  f32 = lambda t: t.astype(jnp.float32)
  a = -jnp.exp(f32(a_log))
  inner, s = a.shape
  if ssm_scan_path(inner=inner, state=s, batch=xz.shape[0] // length,
                   length=length) == SSM_SCAN_WINDOW_KERNEL:
    return _window_kernel_call(streams, dts, bcs, xz, a, f32(d),
                               f32(dt_bias), length=length)
  return _plain(streams, dts, bcs, xz, a, f32(d), f32(dt_bias), length=length)
