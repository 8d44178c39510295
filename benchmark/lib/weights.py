"""Weights from the seed: one jitted call on the device, float32 leaves.

The benchmark makes the weights itself, so the plain reference takes
nothing that the program made. The tree has the leaf names and shapes of
the published model (what `model.init` of the program would return;
`tests/` checks that), filled from documented distributions:

  embeddings            normal, std E**-0.5 (as published)
  matmul kernels        Glorot uniform on the flattened 2-D matrix
  Dense biases          normal, std 0.02 (published init is 0: a zero
                        bias would hide a dropped bias add)
  ReZero alphas         uniform [0.5, 1.0) (published init is 0, where
                        every encoder block is a no-op; trained values
                        are non-zero, and the comparison has to see
                        every block)
  final LayerNorm       scale 1 + normal*0.1, bias normal*0.1
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.lib.seeds import key_from_seed


def leaf_specs(shape: dict):
  """(path, shape, kind, fan) for every leaf, in a fixed order."""
  h, f = shape['hidden_size'], shape['filter_size']
  heads = shape['num_heads']
  hd = h // heads
  emb = shape['embedding']
  condense_in = shape['condense_input_size']
  specs = [
      (('bases_embedding', 'embedding'), (5, emb['bases']), 'embed', None),
      (('pw_embedding', 'embedding'), (shape['PW_MAX'] + 1, emb['pw']),
       'embed', None),
      (('ip_embedding', 'embedding'), (shape['IP_MAX'] + 1, emb['ip']),
       'embed', None),
      (('strand_embedding', 'embedding'), (shape['STRAND_MAX'] + 1,
                                           emb['strand']), 'embed', None),
      (('sn_embedding', 'embedding'), (shape['SN_MAX'] + 1, emb['sn']),
       'embed', None),
      (('condenser', 'kernel'), (condense_in, h), 'glorot', (condense_in, h)),
      (('logits', 'kernel'), (h, 5), 'glorot', (h, 5)),
      (('logits', 'bias'), (5,), 'bias', None),
      (('encoder', 'output_normalization', 'scale'), (h,), 'ln_scale', None),
      (('encoder', 'output_normalization', 'bias'), (h,), 'ln_bias', None),
  ]
  for n in range(shape['num_hidden_layers']):
    att = ('encoder', f'self_attention_{n}')
    for name in ('query', 'key', 'value'):
      specs.append((att + (name, 'kernel'), (h, heads, hd), 'glorot', (h, h)))
    specs.append((att + ('output_transform', 'kernel'), (heads, hd, h),
                  'glorot', (h, h)))
    ffn = ('encoder', f'ffn_{n}')
    specs += [
        (ffn + ('filter_layer', 'kernel'), (h, f), 'glorot', (h, f)),
        (ffn + ('filter_layer', 'bias'), (f,), 'bias', None),
        (ffn + ('output_layer', 'kernel'), (f, h), 'glorot', (f, h)),
        (ffn + ('output_layer', 'bias'), (h,), 'bias', None),
        (('encoder', f'attention_wrapper_{n}', 'alpha'), (), 'alpha', None),
        (('encoder', f'ffn_wrapper_{n}', 'alpha'), (), 'alpha', None),
    ]
  return specs


_UNIFORM = ('glorot', 'alpha')


def _shape_leaf(x, shape, kind, fan):
  """x: standard normal draws, or uniform [0, 1) for the _UNIFORM kinds."""
  x = x.reshape(shape)
  if kind == 'embed':
    return x * shape[1] ** -0.5
  if kind == 'glorot':
    lim = math.sqrt(6.0 / (fan[0] + fan[1]))
    return (2.0 * x - 1.0) * lim
  if kind == 'bias':
    return x * 0.02
  if kind == 'alpha':
    return 0.5 + 0.5 * x
  if kind == 'ln_scale':
    return 1.0 + x * 0.1
  if kind == 'ln_bias':
    return x * 0.1
  raise ValueError(kind)


def make_params(shape: dict, seed: int):
  """The parameter tree {'bases_embedding': {...}, ...} on the device.
  Two draws (one uniform, one normal vector) cut into the leaves: eighty
  separate draws cost seconds to trace and load on every run."""
  specs = leaf_specs(shape)
  size = lambda shp: math.prod(shp)
  n_uniform = sum(size(s) for _p, s, k, _f in specs if k in _UNIFORM)
  n_normal = sum(size(s) for _p, s, k, _f in specs if k not in _UNIFORM)

  def build(key):
    k_u, k_n = jax.random.split(key)
    pools = {True: jax.random.uniform(k_u, (n_uniform,), jnp.float32),
             False: jax.random.normal(k_n, (n_normal,), jnp.float32)}
    offsets = {True: 0, False: 0}
    tree: dict = {}
    for path, shp, kind, fan in specs:
      which = kind in _UNIFORM
      lo = offsets[which]
      offsets[which] = lo + size(shp)
      node = tree
      for part in path[:-1]:
        node = node.setdefault(part, {})
      node[path[-1]] = _shape_leaf(pools[which][lo:lo + size(shp)], shp,
                                   kind, fan)
    return tree

  return jax.jit(build)(key_from_seed(seed))
