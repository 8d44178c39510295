"""What the algorithm needs for one window, from shapes alone.

Matrix-multiply FLOPs only (2 x multiply-adds): condenser, Q/K/V/O, band
scores and band values as the band needs them (pairs |i-j| <= band inside
the window, not L x L), feed-forward, output head. Recomputation, padding
rows, softmax, LayerNorm and other elementwise work count as nothing, so a
fused kernel reads the same work as the fusions it replaces.

Bytes are the algorithm's too: the uint8 rows and float32 SN scalars in,
two uint8 planes out, and the float32 weights once a pack.
"""
from __future__ import annotations


def band_pairs(length: int, band: int) -> int:
  """Number of (query, key) pairs with |i - j| <= band in one window."""
  return sum(min(length - 1, i + band) - max(0, i - band) + 1
             for i in range(length))


def flops_per_window(shape: dict) -> dict:
  """FLOPs one window needs, by part; 'total' sums them."""
  length, h, f = shape['max_length'], shape['hidden_size'], shape['filter_size']
  layers = shape['num_hidden_layers']
  pairs = band_pairs(length, shape['attn_win_size'])
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'qkvo': layers * 4 * 2 * length * h * h,
      'band_scores': layers * 2 * pairs * h,
      'band_values': layers * 2 * pairs * h,
      'ffn': layers * 2 * 2 * length * h * f,
      'head': 2 * length * h * 5,
  }
  parts['total'] = sum(parts.values())
  return parts


def param_count(shape: dict) -> int:
  h, f = shape['hidden_size'], shape['filter_size']
  emb = shape['embedding']
  n = (5 * emb['bases'] + (shape['PW_MAX'] + 1) * emb['pw']
       + (shape['IP_MAX'] + 1) * emb['ip']
       + (shape['STRAND_MAX'] + 1) * emb['strand']
       + (shape['SN_MAX'] + 1) * emb['sn'])
  n += shape['condense_input_size'] * h
  n += shape['num_hidden_layers'] * (4 * h * h + 2 * h * f + f + h + 2)
  n += 2 * h + h * 5 + 5
  return n


def bytes_per_pack(shape: dict, batch: int) -> dict:
  """Bytes the algorithm has to move for one pack of `batch` windows."""
  rows_u8 = shape['total_rows'] - 4
  length = shape['max_length']
  parts = {
      'rows_in': batch * rows_u8 * length,
      'sn_in': batch * 4 * 4,
      'planes_out': batch * length * 2,
      'weights': param_count(shape) * 4,
  }
  parts['total'] = sum(parts.values())
  return parts


def least_seconds(flops: float, n_bytes: float, peaks: dict) -> dict:
  """The roofline: the larger of FLOPs/peak and bytes/HBM rate, and which."""
  t_flops = flops / peaks['bf16_flops_per_s']
  t_bytes = n_bytes / peaks['hbm_bytes_per_s']
  return {'seconds': max(t_flops, t_bytes),
          'bound': 'compute' if t_flops >= t_bytes else 'memory',
          'flops_seconds': t_flops, 'bytes_seconds': t_bytes}


def least_seconds_per_pack(shape: dict, batch: int, peaks: dict) -> dict:
  return least_seconds(flops_per_window(shape)['total'] * batch,
                       bytes_per_pack(shape, batch)['total'], peaks)
