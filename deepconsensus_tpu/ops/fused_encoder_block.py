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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import fused_window_attention as fwa
from deepconsensus_tpu.ops import pallas_util

Array = jnp.ndarray

_NEG = -1e9


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

  The per-output-channel scale commutes with the contraction, so
  (x @ q) * scale equals x @ (q * scale) up to f32 rounding; with
  scale=None (or exact ones) this is the plain f32 matmul.
  """
  out = jax.lax.dot_general(
      x2, values.astype(jnp.float32), (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32,
  )
  if scale is not None:
    out = out * scale.astype(jnp.float32)
  return out


def _attention(x, wq, wk, wv, wo, *, num_heads, qscale, attn_win_size,
               length, softmax_dtype, mask=None):
  """Banded MHA on a [tile, L, H] f32 block with quant-aware
  projections; mirrors fused_window_attention._attention (same band
  mask, same softmax_dtype lever, same op order). Each w is a
  (values, scale_row_or_None) pair. mask (ragged slots): a
  [tile, L, L] bool mask that REPLACES the static band — it already
  ANDs the band with the lengths-derived same-window/valid tests
  (ragged_window_attention.ragged_attention_mask). Shared with the
  jnp reference."""
  tile, _, hidden = x.shape
  head_dim = hidden // num_heads
  x2 = x.reshape(tile * length, hidden)

  def proj(w):
    return _dequant_matmul(x2, w[0], w[1]).reshape(tile, length, hidden)

  q = proj(wq) * qscale
  k = proj(wk)
  v = proj(wv)
  # Heads are lane slices of the [tile, L, H] projections: Mosaic has
  # no shape cast that splits the lane dimension into (heads, depth).
  head = lambda t, h: t[:, :, h * head_dim:(h + 1) * head_dim]
  band = mask
  if band is None and attn_win_size is not None:
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, length, length), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, length, length), 2)
    band = jnp.abs(rows - cols) <= attn_win_size
  outs = []
  for h in range(num_heads):
    s = jax.lax.dot_general(
        head(q, h), head(k, h), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [tile, L, L]
    if band is not None:
      s = jnp.where(band, s, _NEG)
    sd = s.astype(softmax_dtype)
    m = jnp.max(sd, axis=2, keepdims=True)
    p = jnp.exp(sd - m)
    w = (p / jnp.sum(p, axis=2, keepdims=True)).astype(jnp.float32)
    outs.append(jax.lax.dot_general(
        w, head(v, h), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ))
  o = jnp.concatenate(outs, axis=-1).reshape(tile * length, hidden)
  return _dequant_matmul(o, wo[0], wo[1]).reshape(tile, length, hidden)


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


def _block_body(x, attn, ffn, attn_alpha, ffn_alpha, *, num_heads, qscale,
                attn_win_size, length, hidden, softmax_dtype, mask=None):
  """One encoder block on a [tile, L, H] f32 block: optional attention
  residual, then FFN residual, both ReZero (x + alpha * y)."""
  if attn is not None:
    y = _attention(
        x, *attn, num_heads=num_heads, qscale=qscale,
        attn_win_size=attn_win_size, length=length,
        softmax_dtype=softmax_dtype, mask=mask,
    )
    x = x + attn_alpha * y
  y = _ffn(x, *ffn, length=length, hidden=hidden)
  return x + ffn_alpha * y


def _kernel(*refs, has_attn, has_lengths, num_heads, qscale, attn_win_size,
            length, hidden, softmax_dtype):
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
      qscale=qscale, attn_win_size=attn_win_size, length=length,
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
  head_dim = hidden // num_heads
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
          num_heads=num_heads,
          qscale=head_dim ** -0.5, attn_win_size=attn_win_size,
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
  head_dim = hidden // num_heads
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
      num_heads=num_heads, qscale=head_dim ** -0.5,
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
