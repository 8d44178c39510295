"""Pallas TPU kernel: batch-major fused encoder blocks (MHA+FFN+ReZero).

Completes the L=100 fused hot path started in
ops/fused_window_attention.py (PR 5): that kernel covers
embed->condense->pos->layer-0 attention; this one covers everything
after it — for each remaining encoder block, banded multi-head
attention, the relu FFN, and both ReZero residuals run as ONE grid
program per tile of windows, with the same batch-major tiling
(DEFAULT_TILE_WINDOWS windows per program, every projection an MXU-shaped
[tile*L, K] x [K, N] matmul).

One pallas_call per encoder block, not one for the whole stack: five
layers of f32 weights (~29 MB at the distilled student's 280/2048
shape) would not fit next to the activations. A single block at
tile=8 needs ~21 MiB of scoped VMEM in f32 (double-buffered
[280, 2048] + [2048, 280] weights plus the [tile*L, filter] relu
intermediate) and ~18 MiB in bf16 — over the compiler's 16 MiB default
scope, so the call raises the limit explicitly
(pallas_util.BATCH_TILE_VMEM_LIMIT_BYTES; a v5e core has 128 MiB).

Quantization support (params.quantize_matmuls=int8): each matmul
weight arrives as a `QuantizedWeight` — either a plain f32/bf16 kernel
(scale=None) or int8 values with a per-output-channel f32 scale. The
dequant is folded into the matmul epilogue, `(x @ q) * scale`, which
is exact per column because the scale is constant along the
contraction; int8 values stay int8 in HBM and VMEM, so the weight
transfer shrinks 4x. ReZero alphas are passed as (1, 1) SMEM scalars
— NOT folded into the weights — so quantization and the residual stay
independent and the op order matches the XLA model exactly.

The attention half is also callable alone: `fused_attention_sublayer`
runs one layer's `x + alpha * attention(x)` on the flat [B*L, H] stream
in the compute dtype (bfloat16 MXU operands, float32 accumulators and
softmax), which is what the default XLA forward takes for its attention
sublayers at L<=128 bfloat16 inference on one TPU
(`attention_path` of models/model.py::kernel_paths; no option asks for
it). The block kernel hands the same `_attention` float32 activations, so
its products stay float32.

Semantics are defined by `reference_encoder_stack` (pure jnp, shares
the math helpers below); the kernel is validated against it per block
and against the full XLA model in interpret mode on CPU
(tests/test_fused_encoder_block.py). models/model.py routes through
here after the PR-5 kernel when params.use_fused_hotpath is set, with
the same bitwise-tested XLA fallback for training/init/L>128.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import fused_window_attention as fwa
from deepconsensus_tpu.ops import pallas_util

Array = jnp.ndarray

_NEG = -1e9

# Windows a grid program of the attention sublayer kernel. tile*L must be
# a multiple of the bfloat16 sublane tile (16) for the flat block to need
# no re-layout: any multiple of 4 at L=100. Picked on the chip (PERF.md,
# PR 31): 8, 16 and 32 ran within 1% of each other at a pack of 8,192,
# and the kernel unrolls over its tile, so 8 also compiles fastest.
SUBLAYER_TILE_WINDOWS = 8


class QuantizedWeight(NamedTuple):
  """One matmul weight, optionally int8-quantized.

  values: [K, N] kernel — compute-dtype floats when scale is None,
  int8 otherwise. scale: per-output-channel f32 [N] such that the
  effective weight is values * scale[None, :].
  """

  values: Array
  scale: Optional[Array] = None


class EncoderBlockWeights(NamedTuple):
  """Weights for one encoder block (banded MHA + FFN + ReZero).

  The attention half (wq..wo, attn_alpha) is None for the layer-0
  remainder block when the PR-5 kernel already applied attention_0's
  residual (skip_first_attention).
  """

  wq: Optional[QuantizedWeight]
  wk: Optional[QuantizedWeight]
  wv: Optional[QuantizedWeight]
  wo: Optional[QuantizedWeight]
  attn_alpha: Optional[Array]
  w_filter: QuantizedWeight
  b_filter: Array
  w_output: QuantizedWeight
  b_output: Array
  ffn_alpha: Array


def _dequant_matmul(x2: Array, values: Array, scale: Optional[Array]) -> Array:
  """[M, K] x QuantizedWeight -> [M, N] f32, dequant in the epilogue.

  The MXU operands are in x2's dtype (the block kernel hands float32
  activations, the attention sublayer the compute dtype), the
  accumulator is float32. The per-output-channel scale commutes with
  the contraction, so (x @ q) * scale equals x @ (q * scale) up to f32
  rounding; with scale=None (or exact ones) this is the plain matmul.
  """
  out = jax.lax.dot_general(
      x2, values.astype(x2.dtype), (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32,
  )
  if scale is not None:
    out = out * scale.astype(jnp.float32)
  return out


def _rounded_product(a: Array, scalar, dtype) -> Array:
  """a * scalar as the XLA modules compute it in `dtype`: a rounded to
  dtype, the product rounded to dtype. `scalar` is already a value of
  dtype held in float32; the arithmetic between the roundings is
  float32 (the v5e VPU has no bfloat16 multiply). All no-ops for
  float32."""
  return (a.astype(dtype).astype(jnp.float32) * scalar).astype(dtype)


def _attention(x2, wqkv, wo, *, tile, length, num_heads, attn_win_size,
               softmax_dtype, mask=None):
  """Banded MHA on `tile` windows of `length` rows, flat: x2
  [tile*L, H] -> the output product's float32 accumulator [tile*L, H].

  The compute dtype is x2's: MXU operands in it, float32 accumulation,
  and q, k, v, the softmax weights and the heads' output rounded to it
  where BandedSelfAttention rounds them (DenseGeneral(dtype=...),
  query * head_dim**-0.5, .astype(dtype) after the softmax, the value
  einsum); the logits stay float32 where XLA rounds them. With float32
  activations every rounding is a no-op. wqkv: three (values,
  scale_row_or_None) pairs of [H, H], or one fused [H, 3H] pair (one
  pass over x2); wo: one pair. mask (ragged slots): a [tile, L, L] bool
  mask that REPLACES the static band — it already ANDs the band with
  the lengths-derived same-window/valid tests
  (ragged_window_attention.ragged_attention_mask). Shared by the block
  kernel, the sublayer kernel and the jnp reference."""
  cd = x2.dtype
  hidden = x2.shape[1]
  head_dim = hidden // num_heads
  qkv = [_dequant_matmul(x2, *w) for w in wqkv]
  if len(qkv) == 1:
    qkv = [qkv[0][:, i * hidden:(i + 1) * hidden] for i in range(3)]
  # head_dim**-0.5 rounded to the compute dtype, as the weakly typed
  # Python scalar is in `query_raw * (head_dim**-0.5)`.
  qscale = float(np.asarray(head_dim ** -0.5, dtype=cd))
  q = _rounded_product(qkv[0], qscale, cd)
  k = qkv[1].astype(cd)
  v = qkv[2].astype(cd)
  band = None
  if mask is None and attn_win_size is not None:
    rows = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    band = jnp.abs(rows - cols) <= attn_win_size
  # A window is a row slice and a head a lane slice of the flat
  # projections: Mosaic has no shape cast that splits the lane
  # dimension into (heads, depth), and a [tile*L, .] -> [tile, L, .]
  # reshape at L=100 unrolls into the same shifted copies.
  heads = []
  for h in range(num_heads):
    outs = []
    for t in range(tile):
      part = lambda a: a[t * length:(t + 1) * length,
                         h * head_dim:(h + 1) * head_dim]
      s = jax.lax.dot_general(
          part(q), part(k), (((1,), (1,)), ((), ())),
          preferred_element_type=jnp.float32,
      )  # [L, L]
      keep = band if mask is None else mask[t]
      if keep is not None:
        s = jnp.where(keep, s, _NEG)
      sd = s.astype(softmax_dtype)
      m = jnp.max(sd, axis=1, keepdims=True)
      p = jnp.exp(sd - m)
      w = (p / jnp.sum(p, axis=1, keepdims=True)).astype(cd)
      outs.append(jax.lax.dot_general(
          w, part(v), (((1,), (0,)), ((), ())),
          preferred_element_type=jnp.float32,
      ).astype(cd))
    heads.append(jnp.concatenate(outs, axis=0))
  o = jnp.concatenate(heads, axis=-1)
  return _dequant_matmul(o, *wo)


def _ffn(x, w_filter, b_filter, w_output, b_output, *, length, hidden):
  """filter relu -> output on a [tile, L, H] f32 block as two
  [tile*L, K] x [K, N] matmuls. Shared with the jnp reference."""
  tile = x.shape[0]
  x2 = x.reshape(tile * length, hidden)
  h = _dequant_matmul(x2, w_filter[0], w_filter[1])
  h = jnp.maximum(h + b_filter.astype(jnp.float32), 0.0)
  out = _dequant_matmul(h, w_output[0], w_output[1])
  out = out + b_output.astype(jnp.float32)
  return out.reshape(tile, length, hidden)


def _block_body(x, attn, ffn, attn_alpha, ffn_alpha, *, num_heads,
                attn_win_size, length, hidden, softmax_dtype, mask=None):
  """One encoder block on a [tile, L, H] f32 block: optional attention
  residual, then FFN residual, both ReZero (x + alpha * y)."""
  if attn is not None:
    tile = x.shape[0]
    y = _attention(
        x.reshape(tile * length, hidden), attn[:3], attn[3], tile=tile,
        length=length, num_heads=num_heads, attn_win_size=attn_win_size,
        softmax_dtype=softmax_dtype, mask=mask,
    )
    x = x + attn_alpha * y.reshape(tile, length, hidden)
  y = _ffn(x, *ffn, length=length, hidden=hidden)
  return x + ffn_alpha * y


def _kernel(*refs, has_attn, has_lengths, num_heads, attn_win_size, length,
            hidden, softmax_dtype):
  it = iter(refs)
  x_ref = next(it)
  mask = None
  if has_lengths:
    from deepconsensus_tpu.ops import ragged_window_attention as rwa

    mask = rwa.ragged_attention_mask(next(it)[:], length, attn_win_size)
  attn = attn_alpha = None
  if has_attn:
    attn = tuple((next(it)[:], next(it)[:]) for _ in range(4))
    attn_alpha = next(it)[0, 0]
  ffn = (
      (next(it)[:], next(it)[:]), next(it)[:],
      (next(it)[:], next(it)[:]), next(it)[:],
  )
  ffn_alpha = next(it)[0, 0]
  out_ref = next(it)

  x = x_ref[:].astype(jnp.float32)
  x = _block_body(
      x, attn, ffn, attn_alpha, ffn_alpha, num_heads=num_heads,
      attn_win_size=attn_win_size, length=length,
      hidden=hidden, softmax_dtype=softmax_dtype, mask=mask,
  )
  out_ref[:] = x.astype(out_ref.dtype)


def _weight_inputs(qw: QuantizedWeight, compute_dtype) -> Tuple[Array, Array]:
  """(values, scale_row) kernel inputs for one QuantizedWeight: int8
  values ride as int8 (4x smaller VMEM/transfer); unquantized kernels
  get an exact ones scale so the kernel signature stays uniform."""
  values, scale = qw
  n = values.shape[1]
  if scale is None:
    # dclint: allow=dtype-downcast (unquantized weights ride at the
    # configured compute dtype; the ones scale keeps them exact)
    return (jnp.asarray(values, compute_dtype),
            jnp.ones((1, n), jnp.float32))
  return jnp.asarray(values), jnp.asarray(scale, jnp.float32).reshape(1, n)


def _bias_input(b: Array) -> Array:
  return jnp.asarray(b, jnp.float32).reshape(1, -1)


def _alpha_input(a: Array) -> Array:
  return jnp.asarray(a, jnp.float32).reshape(1, 1)


def _block_call(xp: Array, block: EncoderBlockWeights, *, num_heads,
                attn_win_size, softmax_dtype, compute_dtype, tile,
                interpret, lengths: Optional[Array] = None) -> Array:
  """One pallas_call over an already tile-padded [B', L, H] batch."""
  bp, length, hidden = xp.shape
  n_tiles = bp // tile
  has_attn = block.wq is not None
  has_lengths = has_attn and lengths is not None

  inputs = [xp]
  in_specs = [pl.BlockSpec((tile, length, hidden), lambda i: (i, 0, 0),
                           memory_space=pltpu.VMEM)]
  full = lambda a: pl.BlockSpec(
      a.shape, lambda i: (0,) * a.ndim, memory_space=pltpu.VMEM)
  smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)

  def add_weight(qw):
    for a in _weight_inputs(qw, compute_dtype):
      inputs.append(a)
      in_specs.append(full(a))

  def add(a, spec=None):
    inputs.append(a)
    in_specs.append(spec if spec is not None else full(a))

  if has_lengths:
    add(jnp.asarray(lengths, jnp.int32),
        pl.BlockSpec((tile, lengths.shape[1]), lambda i: (i, 0),
                     memory_space=pltpu.VMEM))
  if has_attn:
    for qw in (block.wq, block.wk, block.wv, block.wo):
      add_weight(qw)
    add(_alpha_input(block.attn_alpha), smem)
  add_weight(block.w_filter)
  add(_bias_input(block.b_filter))
  add_weight(block.w_output)
  add(_bias_input(block.b_output))
  add(_alpha_input(block.ffn_alpha), smem)

  return pl.pallas_call(
      functools.partial(
          _kernel, has_attn=has_attn, has_lengths=has_lengths,
          num_heads=num_heads, attn_win_size=attn_win_size,
          length=length, hidden=hidden,
          softmax_dtype=jnp.dtype(softmax_dtype),
      ),
      grid=(n_tiles,),
      in_specs=in_specs,
      out_specs=pl.BlockSpec((tile, length, hidden), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
      out_shape=jax.ShapeDtypeStruct((bp, length, hidden), compute_dtype),
      compiler_params=pallas_util.batch_tile_compiler_params(),
      interpret=interpret,
  )(*inputs)


def fused_encoder_block(
    x: Array,
    block: EncoderBlockWeights,
    *,
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    compute_dtype: Any = jnp.float32,
    tile_windows: Optional[int] = None,
    interpret: Optional[bool] = None,
    lengths: Optional[Array] = None,
) -> Array:
  """One fused encoder block over a [B, L, H] window batch."""
  return fused_encoder_stack(
      x, [block], num_heads=num_heads, attn_win_size=attn_win_size,
      softmax_dtype=softmax_dtype, compute_dtype=compute_dtype,
      tile_windows=tile_windows, interpret=interpret, lengths=lengths,
  )


def fused_encoder_stack(
    x: Array,
    blocks: Sequence[EncoderBlockWeights],
    *,
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    compute_dtype: Any = jnp.float32,
    tile_windows: Optional[int] = None,
    interpret: Optional[bool] = None,
    lengths: Optional[Array] = None,
) -> Array:
  """Run a sequence of fused encoder blocks over a [B, L, H] batch.

  Pads the batch to a tile multiple once (padded windows compute
  garbage-free blocks over zero activations and are sliced away),
  launches one pallas_call per block, and returns [B, L, H] in
  compute_dtype. The final output LayerNorm stays outside — it is the
  caller's (cheap, dtype-sensitive) op, matching the PR-5 split where
  checkpointed scalars live with their parameters.

  lengths (ragged slots): a [B, wps] int32 per-slot window-widths
  vector; every attention block then masks with the lengths-derived
  ragged mask (band AND same-window AND valid) instead of the static
  band alone. FFN/residual halves are position-wise and unaffected.
  """
  b, length, hidden = x.shape
  if hidden % num_heads:
    raise ValueError('hidden size must divide num_heads')
  tile = tile_windows or fwa.DEFAULT_TILE_WINDOWS
  tile = max(1, min(tile, b))
  pad = (-b) % tile
  # dclint: allow=dtype-downcast (activations enter the fused stack at
  # the configured compute dtype; accumulation stays f32 in-kernel)
  xp = jnp.asarray(x, compute_dtype)
  lp = None
  if lengths is not None:
    lp = jnp.asarray(lengths, jnp.int32)
  if pad:
    xp = jnp.pad(xp, ((0, pad), (0, 0), (0, 0)))
    if lp is not None:
      # Zero lengths: every position of a padded slot is masked invalid.
      lp = jnp.pad(lp, ((0, pad), (0, 0)))
  interpret = pallas_util.resolve_interpret(interpret)
  for block in blocks:
    xp = _block_call(
        xp, block, num_heads=num_heads, attn_win_size=attn_win_size,
        softmax_dtype=softmax_dtype, compute_dtype=compute_dtype,
        tile=tile, interpret=interpret, lengths=lp,
    )
  return xp[:b]


def _sublayer_kernel(x_ref, wqkv_ref, wo_ref, alpha_ref, out_ref, *, tile,
                     length, num_heads, attn_win_size, softmax_dtype):
  """x + alpha * attention(x) on one tile of windows, in x's dtype."""
  cd = x_ref.dtype
  x2 = x_ref[:]
  y = _attention(
      x2, ((wqkv_ref[:], None),), (wo_ref[:], None), tile=tile,
      length=length, num_heads=num_heads, attn_win_size=attn_win_size,
      softmax_dtype=softmax_dtype,
  )
  # ResidualWrapper: x + alpha.astype(dtype) * y, each step in dtype.
  y = _rounded_product(y, alpha_ref[0, 0], cd)
  out_ref[:] = (x2.astype(jnp.float32) + y.astype(jnp.float32)).astype(cd)


def fused_attention_sublayer(
    x2: Array,
    wq: Array,
    wk: Array,
    wv: Array,
    wo: Array,
    alpha: Array,
    *,
    length: int,
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    tile_windows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Array:
  """The banded attention sublayer of one encoder layer with its ReZero
  residual, `x + alpha * attention(x)`, as one pallas_call over tiles of
  windows: q, k, v, the scores and the softmax weights live in VMEM only.

  x2: the window batch flat, [B*L, H], in the compute dtype; the result
  has its shape and dtype. wq/wk/wv/wo: the [H, H] matmul forms of the
  layer's DenseGeneral kernels, alpha its ReZero scalar; all are cast to
  x2's dtype here, as the modules cast them. The arithmetic is the
  modules' own (`_attention`): operands in the compute dtype, float32
  accumulators, softmax in softmax_dtype.

  The flat form is the kernel's own: its blocks are [tile*L, H] row
  ranges that need no re-layout on the way in, where a [tile, L, H]
  block pads every window's L=100 rows to the 16-row tile.
  """
  rows, hidden = x2.shape
  if hidden % num_heads:
    raise ValueError('hidden size must divide num_heads')
  if rows % length:
    raise ValueError(f'{rows} rows are no whole number of L={length} windows')
  cd = x2.dtype
  b = rows // length
  tile = tile_windows or SUBLAYER_TILE_WINDOWS
  tile = max(1, min(tile, b))
  pad = (-b) % tile
  xp = jnp.pad(x2, ((0, pad * length), (0, 0))) if pad else x2
  block = pl.BlockSpec((tile * length, hidden), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
  full = lambda a: pl.BlockSpec(
      a.shape, lambda i: (0,) * a.ndim, memory_space=pltpu.VMEM)
  # dclint: allow=dtype-downcast (the modules cast their float32 kernels
  # and alpha to the compute dtype the same way: DenseGeneral(dtype=...),
  # alpha.astype(y.dtype))
  wqkv = jnp.concatenate([wq, wk, wv], axis=1).astype(cd)
  wo = wo.astype(cd)
  alpha = jnp.asarray(alpha, jnp.float32).astype(cd).astype(
      jnp.float32).reshape(1, 1)
  out = pl.pallas_call(
      functools.partial(
          _sublayer_kernel, tile=tile, length=length, num_heads=num_heads,
          attn_win_size=attn_win_size,
          softmax_dtype=jnp.dtype(softmax_dtype)),
      grid=((b + pad) // tile,),
      in_specs=[
          block, full(wqkv), full(wo),
          pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
      ],
      out_specs=block,
      out_shape=jax.ShapeDtypeStruct(xp.shape, cd),
      compiler_params=pallas_util.batch_tile_compiler_params(),
      interpret=pallas_util.resolve_interpret(interpret),
  )(xp, wqkv, wo, alpha)
  return out[:rows]


def _reference_pair(qw: QuantizedWeight) -> Tuple[Array, Optional[Array]]:
  values, scale = qw
  if scale is None:
    return values.astype(jnp.float32), None
  return jnp.asarray(values), jnp.asarray(scale, jnp.float32).reshape(
      1, values.shape[1])


def reference_encoder_block(
    x: Array,
    block: EncoderBlockWeights,
    *,
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    lengths: Optional[Array] = None,
) -> Array:
  """Pure-jnp semantics of one fused block (same helpers, no Pallas):
  the per-block parity oracle for unit tests."""
  _, length, hidden = x.shape
  attn = None
  mask = None
  if block.wq is not None:
    attn = tuple(_reference_pair(w)
                 for w in (block.wq, block.wk, block.wv, block.wo))
    if lengths is not None:
      from deepconsensus_tpu.ops import ragged_window_attention as rwa

      mask = rwa.ragged_attention_mask(
          jnp.asarray(lengths, jnp.int32), length, attn_win_size)
  ffn = (
      _reference_pair(block.w_filter), _bias_input(block.b_filter),
      _reference_pair(block.w_output), _bias_input(block.b_output),
  )
  return _block_body(
      x.astype(jnp.float32), attn, ffn,
      None if block.attn_alpha is None else jnp.asarray(
          block.attn_alpha, jnp.float32),
      jnp.asarray(block.ffn_alpha, jnp.float32),
      num_heads=num_heads,
      attn_win_size=attn_win_size, length=length, hidden=hidden,
      softmax_dtype=jnp.dtype(softmax_dtype), mask=mask,
  )


def reference_encoder_stack(
    x: Array,
    blocks: Sequence[EncoderBlockWeights],
    *,
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    lengths: Optional[Array] = None,
) -> Array:
  """Pure-jnp mirror of fused_encoder_stack (no pad/tile, f32)."""
  for block in blocks:
    x = reference_encoder_block(
        x, block, num_heads=num_heads, attn_win_size=attn_win_size,
        softmax_dtype=softmax_dtype, lengths=lengths,
    )
  return x


def blocks_from_params(
    encoder_params,
    quant,
    num_layers: int,
    *,
    skip_first_attention: bool = False,
) -> Tuple[EncoderBlockWeights, ...]:
  """Extract per-block kernel weights from the encoder param subtree.

  encoder_params: variables['params']['encoder']. quant: the matching
  'quant' collection subtree ({module: {sub: {values, scale}}}) or
  None; when a leaf is present there, its int8 values + per-channel
  scale replace the (already dequantized-effective) params kernel.
  DenseGeneral attention kernels are reshaped to their 2D matmul form
  ([H, heads, hd] -> [H, H]; output [heads, hd, H] -> [H, H]).
  """

  def pick(mod: str, sub: str, kernel2d: Array) -> QuantizedWeight:
    entry = None
    if quant is not None and mod in quant:
      entry = quant[mod].get(sub)
    if entry is not None:
      return QuantizedWeight(entry['values'], entry['scale'])
    return QuantizedWeight(kernel2d, None)

  blocks = []
  for n in range(num_layers):
    if n == 0 and skip_first_attention:
      wq = wk = wv = wo = attn_alpha = None
    else:
      attn_p = encoder_params[f'self_attention_{n}']
      h = attn_p['query']['kernel'].shape[0]
      wq = pick(f'self_attention_{n}', 'query',
                attn_p['query']['kernel'].reshape(h, -1))
      wk = pick(f'self_attention_{n}', 'key',
                attn_p['key']['kernel'].reshape(h, -1))
      wv = pick(f'self_attention_{n}', 'value',
                attn_p['value']['kernel'].reshape(h, -1))
      wo = pick(f'self_attention_{n}', 'output_transform',
                attn_p['output_transform']['kernel'].reshape(-1, h))
      attn_alpha = encoder_params[f'attention_wrapper_{n}']['alpha']
    ffn_p = encoder_params[f'ffn_{n}']
    blocks.append(EncoderBlockWeights(
        wq=wq, wk=wk, wv=wv, wo=wo, attn_alpha=attn_alpha,
        w_filter=pick(f'ffn_{n}', 'filter_layer',
                      ffn_p['filter_layer']['kernel']),
        b_filter=ffn_p['filter_layer']['bias'],
        w_output=pick(f'ffn_{n}', 'output_layer',
                      ffn_p['output_layer']['kernel']),
        b_output=ffn_p['output_layer']['bias'],
        ffn_alpha=encoder_params[f'ffn_wrapper_{n}']['alpha'],
    ))
  return tuple(blocks)
