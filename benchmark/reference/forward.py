"""Plain reference of the gap-aware encoder: float32 jax.numpy, no kernels.

Follows the published DeepConsensus model (Baid et al. 2023, networks.py
EncoderOnlyLearnedValuesTransformer): per-feature embeddings of the pile-up
rows, a bias-free condenser, sinusoidal positions, `n` x (banded multi-head
self-attention, ReLU feed-forward) with ReZero residuals, a final LayerNorm,
a 5-way softmax, and the Phred epilogue. Imports nothing of the program
under test; takes the parameter tree by its published leaf names.

Departures from the paper, none numerical: the 85 row embeddings are
gathered per feature family instead of row by row, and the forward runs in
blocks of windows so that it fits beside nothing else on one chip.

`precision` is the dial the control turns: "float32" (the reference),
or "fp8" / "bfloat16", which round every matmul operand (activations and
weights) to that type before a float32-accumulated product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VOCAB = 5  # gap, A, T, C, G
SN_ROWS = 4
MAX_BASE_QUALITY = 93
MIN_ERROR_PROB = 1e-12


def row_ranges(max_passes: int):
  """(start, end) rows of bases, pw, ip, strand, ccs, sn in a window."""
  p = max_passes
  return ((0, p), (p, 2 * p), (2 * p, 3 * p), (3 * p, 4 * p),
          (4 * p, 4 * p + 1), (4 * p + 1, 4 * p + 1 + SN_ROWS))


def positions(length: int, hidden: int) -> np.ndarray:
  """Transformer timing signal, [sin | cos] halves, timescales 1..1e4."""
  n = hidden // 2
  inc = math.log(1.0e4) / max(n - 1, 1)
  inv = np.exp(np.arange(n, dtype=np.float32) * -inc)
  scaled = np.arange(length, dtype=np.float32)[:, None] * inv[None, :]
  return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1)


def _rounder(precision: str):
  if precision == 'float32':
    return lambda a: a
  dtype = {'bfloat16': jnp.bfloat16, 'fp8': jnp.float8_e4m3fn}[precision]
  return lambda a: a.astype(dtype).astype(jnp.float32)


def _embed(table, ids):
  """Masked embedding: row 0 is the zero vector, output scaled by sqrt(E)."""
  e = table.shape[1]
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(e))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  ids = rows[:, lo:hi, :].astype(jnp.int32)  # truncation, as the paper casts
  emb = _embed(table, ids)  # [B, r, L, E]
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits_fn(params, rows, *, max_passes: int, num_layers: int,
              num_heads: int, band: int, precision: str = 'float32'):
  """rows [B, 4*max_passes+5, L] float32 -> logits [B, L, 5] float32."""
  rd = _rounder(precision)
  mm = lambda a, b: jnp.matmul(rd(a), rd(b))
  base_r, pw_r, ip_r, st_r, ccs_r, sn_r = row_ranges(max_passes)
  x = jnp.concatenate([
      _family(params['bases_embedding']['embedding'], rows, *base_r),
      _family(params['pw_embedding']['embedding'], rows, *pw_r),
      _family(params['ip_embedding']['embedding'], rows, *ip_r),
      _family(params['strand_embedding']['embedding'], rows, *st_r),
      _family(params['bases_embedding']['embedding'], rows, *ccs_r),
      _family(params['sn_embedding']['embedding'], rows, *sn_r),
  ], axis=-1)
  x = mm(x, params['condenser']['kernel'])
  b, length, hidden = x.shape
  x = x + jnp.asarray(positions(length, hidden))
  head = hidden // num_heads
  idx = np.arange(length)
  in_band = jnp.asarray(np.abs(idx[:, None] - idx[None, :]) <= band)
  enc = params['encoder']
  for n in range(num_layers):
    att = enc[f'self_attention_{n}']
    proj = lambda name: mm(
        x, att[name]['kernel'].reshape(hidden, hidden)
    ).reshape(b, length, num_heads, head)
    q = proj('query') * jnp.float32(head ** -0.5)
    k, v = proj('key'), proj('value')
    scores = jnp.einsum('bqnh,bknh->bnqk', rd(q), rd(k))
    scores = jnp.where(in_band[None, None], scores, -1e9)
    weights = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum('bnqk,bknh->bqnh', rd(weights), rd(v))
    out = mm(ctx.reshape(b, length, hidden),
             att['output_transform']['kernel'].reshape(hidden, hidden))
    x = x + enc[f'attention_wrapper_{n}']['alpha'] * out
    ffn = enc[f'ffn_{n}']
    h = jax.nn.relu(mm(x, ffn['filter_layer']['kernel'])
                    + ffn['filter_layer']['bias'])
    out = mm(h, ffn['output_layer']['kernel']) + ffn['output_layer']['bias']
    x = x + enc[f'ffn_wrapper_{n}']['alpha'] * out
  norm = enc['output_normalization']
  mean = jnp.mean(x, axis=-1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
  x = (x - mean) * jax.lax.rsqrt(var + 1e-6) * norm['scale'] + norm['bias']
  # The published head is float32 whatever the compute type, so no rounding.
  return jnp.matmul(x, params['logits']['kernel']) + params['logits']['bias']


def phred(max_prob: np.ndarray) -> np.ndarray:
  """Base quality of a position from its top probability (uncalibrated)."""
  err = np.maximum(1.0 - np.asarray(max_prob, np.float64), MIN_ERROR_PROB)
  q = np.minimum(-10.0 * np.log10(err), MAX_BASE_QUALITY)
  return np.maximum(np.round(q), 0).astype(np.int32)


def forward_blocks(params, rows: np.ndarray, *, geometry: dict,
                   precision: str = 'float32', block: int = 512):
  """Runs the reference over rows [N, R, L] in blocks; returns numpy
  logits [N, L, 5]. `geometry`: max_passes, num_layers, num_heads, band."""
  fn = jax.jit(functools.partial(logits_fn, precision=precision, **geometry))
  out = []
  with jax.default_matmul_precision('highest'):
    for lo in range(0, len(rows), block):
      chunk = np.asarray(rows[lo:lo + block], np.float32)
      n = len(chunk)
      if n < block:  # keep one compiled shape
        chunk = np.concatenate(
            [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
      out.append(np.asarray(fn(params, jnp.asarray(chunk)))[:n])
  return np.concatenate(out)
