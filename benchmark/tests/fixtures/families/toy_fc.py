"""Fixture family: the program's published fully connected baseline
(`models/config.py::_set_base_fc_hparams`, `FullyConnectedModel`), which
`ModelRunner` serves on its normal path. It shows that a second model
family is new files only: a tree, a work count and a plain reference that
are not the encoder's, and nothing under benchmark/ that names them.

The window's raw rows, flattened, go through Dense + ReLU layers of the
published widths and a last Dense to L x 5 logits: no embedding, no
attention. Weights from the seed: Glorot-uniform kernels, the first one
halved because the rows are raw counts up to 255, biases normal
with std 0.02.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = 5
FIRST_KERNEL_SCALE = 0.5


def shape_of(config: dict) -> dict:
  keys = ('fc_size', 'max_passes', 'max_length', 'total_rows', 'PW_MAX',
          'IP_MAX', 'SN_MAX')
  return {k: config[k] for k in keys}


def stated(params) -> dict:
  return {
      'model_name': params.model_name,
      'fc_size': list(params.fc_size),
      'max_passes': params.max_passes,
      'max_length': params.max_length,
      'total_rows': params.total_rows,
      'use_ccs_bq': params.use_ccs_bq,
      'PW_MAX': params.PW_MAX, 'IP_MAX': params.IP_MAX,
      'SN_MAX': params.SN_MAX,
  }


def widths(shape: dict):
  """(fan_in, fan_out) of every Dense layer, in order."""
  sizes = ([shape['total_rows'] * shape['max_length']] + list(shape['fc_size'])
           + [shape['max_length'] * VOCAB])
  return list(zip(sizes[:-1], sizes[1:]))


def make_params(shape: dict, seed: int):
  """{'Dense_<n>': {'kernel', 'bias'}} on the device, float32 as served."""
  layers = widths(shape)

  def build(key):
    tree = {}
    for n, (fan_in, fan_out) in enumerate(layers):
      k_w, k_b, key = jax.random.split(key, 3)
      lim = math.sqrt(6.0 / (fan_in + fan_out))
      kernel = jax.random.uniform(k_w, (fan_in, fan_out), jnp.float32,
                                  -lim, lim)
      if n == 0:
        kernel = kernel * FIRST_KERNEL_SCALE
      tree[f'Dense_{n}'] = {
          'kernel': kernel,
          'bias': 0.02 * jax.random.normal(k_b, (fan_out,), jnp.float32)}
    return tree

  return jax.jit(build)(key_from_seed(seed))


def flops_per_window(shape: dict) -> dict:
  parts = {f'dense_{n}': 2 * fan_in * fan_out
           for n, (fan_in, fan_out) in enumerate(widths(shape))}
  parts['total'] = sum(parts.values())
  return parts


def param_count(shape: dict) -> int:
  return sum(fan_in * fan_out + fan_out for fan_in, fan_out in widths(shape))


def bytes_per_pack(shape: dict, batch: int) -> dict:
  length = shape['max_length']
  parts = {
      'rows_in': batch * (shape['total_rows'] - 4) * length,
      'sn_in': batch * 4 * 4,
      'planes_out': batch * length * 2,
      'weights': param_count(shape) * 4,
  }
  parts['total'] = sum(parts.values())
  return parts


def least_seconds_per_pack(shape: dict, batch: int, peaks: dict) -> dict:
  return least_seconds(flops_per_window(shape)['total'] * batch,
                       bytes_per_pack(shape, batch)['total'], peaks)


def _rounder(precision: str):
  if precision == 'float32':
    return lambda a: a
  dtype = {'bfloat16': jnp.bfloat16, 'fp8': jnp.float8_e4m3fn}[precision]
  return lambda a: a.astype(dtype).astype(jnp.float32)


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 256):
  """windows [S, R, L, 1] as generated -> logits [S, L, 5], plain float32;
  `precision` rounds every matmul operand, as the encoder's reference does."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  rd = _rounder(precision)
  n_layers = len(widths(shape))

  @jax.jit
  def forward(tree, x):
    for n in range(n_layers):
      layer = tree[f'Dense_{n}']
      x = jnp.matmul(rd(x), rd(layer['kernel'])) + layer['bias']
      if n < n_layers - 1:
        x = jax.nn.relu(x)
    return x

  out = []
  with jax.default_matmul_precision('highest'):
    for lo in range(0, len(rows), block):
      chunk = rows[lo:lo + block].reshape(len(rows[lo:lo + block]), -1)
      out.append(np.asarray(forward(params, jnp.asarray(chunk))))
  return np.concatenate(out).reshape(len(rows), shape['max_length'], VOCAB)
