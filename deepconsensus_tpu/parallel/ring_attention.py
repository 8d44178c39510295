"""Ring attention: sequence-parallel exact attention over a mesh axis.

The production window model attends over fixed 100-bp windows, but the
framework treats long-context as first-class: this module computes
exact (optionally banded) attention for sequences sharded across
devices. Queries stay resident; key/value blocks rotate around the ring
via ppermute while a flash-style online softmax accumulates partial
results, so memory per device is O(L/N) and the collectives ride ICI.

Usage is via shard_map with the sequence axis sharded on a mesh axis;
ring_attention_sharded wraps that plumbing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

Array = jnp.ndarray

_NEG_INF = -1e30

# Count of ring_attention_blockwise *traces* (the Python body runs only
# when jit traces a new shape). Training surfaces this as
# n_ring_attention_traces in the faults sidecar, and the long-insert
# tests use it to prove the L=500 forward really routed through the
# blockwise scan rather than the fused/XLA logits path.
n_blockwise_traces = 0


def _mark_varying(x: Array, axis_name: str) -> Array:
  """Marks x device-varying over axis_name so the scan carry types line
  up with the ppermuted K/V blocks."""
  return jax.lax.pcast(x, axis_name, to='varying')


def _block_attention(
    q: Array,
    k: Array,
    v: Array,
    q_offset: Array,
    k_offset: Array,
    attn_win_size: Optional[int],
):
  """Scores of one (q_block, k_block) pair with optional band mask.

  q: [B, Lq, H, D]; k, v: [B, Lk, H, D]. Returns (scores [B, H, Lq, Lk],
  value tensor) with masked logits at -inf.
  """
  depth = q.shape[-1]
  s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * (depth**-0.5)
  if attn_win_size is not None:
    qi = q_offset + jnp.arange(q.shape[1])
    ki = k_offset + jnp.arange(k.shape[1])
    band = jnp.abs(qi[:, None] - ki[None, :]) <= attn_win_size
    s = jnp.where(band[None, None], s, _NEG_INF)
  return s


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    axis_name: str,
    attn_win_size: Optional[int] = None,
) -> Array:
  """Exact attention with K/V rotating around `axis_name`.

  Inside shard_map: q/k/v are the local shards [B, L_local, H, D]; the
  global sequence is the concatenation over the axis in index order.
  Returns the local output shard [B, L_local, H, D].
  """
  axis_size = jax.lax.psum(1, axis_name)
  my_index = jax.lax.axis_index(axis_name)
  l_local = q.shape[1]
  b, _, h, d = q.shape

  q_offset = my_index * l_local

  # Online softmax state, marked device-varying (see _mark_varying).
  m = _mark_varying(
      jnp.full((b, h, l_local), _NEG_INF, q.dtype), axis_name
  )  # running max
  l_sum = _mark_varying(
      jnp.zeros((b, h, l_local), q.dtype), axis_name
  )  # running denominator
  o = _mark_varying(
      jnp.zeros((b, l_local, h, d), q.dtype), axis_name
  )  # running numerator

  perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

  def step(carry, block_idx):
    k_cur, v_cur, m, l_sum, o = carry
    # K/V block `block_idx` steps behind this device's shard.
    k_owner = (my_index - block_idx) % axis_size
    k_offset = k_owner * l_local
    s = _block_attention(q, k_cur, v_cur, q_offset, k_offset, attn_win_size)
    m_block = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_block)
    # Renormalize previous accumulators.
    scale = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_sum * scale + jnp.sum(p, axis=-1)
    o_new = (
        o * jnp.transpose(scale, (0, 2, 1))[..., None]
        + jnp.einsum('bhqk,bkhd->bqhd', p, v_cur)
    )
    k_next = jax.lax.ppermute(k_cur, axis_name, perm)
    v_next = jax.lax.ppermute(v_cur, axis_name, perm)
    return (k_next, v_next, m_new, l_new, o_new), None

  (k, v, m, l_sum, o), _ = jax.lax.scan(
      step, (k, v, m, l_sum, o), jnp.arange(axis_size)
  )
  denom = jnp.transpose(l_sum, (0, 2, 1))[..., None]
  return o / jnp.maximum(denom, 1e-30)


def ring_attention_blockwise(
    q: Array,
    k: Array,
    v: Array,
    attn_win_size: Optional[int] = None,
    block_size: int = 128,
) -> Array:
  """Single-device ring attention: K/V stream through the online
  softmax in blocks instead of rotating over a mesh axis.

  The degenerate ring (axis_size = ceil(L / block_size), identity
  permutation) keeps queries resident and accumulates flash-style
  partial softmaxes per K/V block, so the [B, H, L, L] logits tensor is
  never materialized — peak activation memory is O(L * block_size) per
  head. This is the training forward for windows past the fused
  kernel's VMEM limit (the L=500 long-insert bucket): a plain lax.scan
  of differentiable ops, so gradients flow through it with no custom
  VJP.

  Fully-banded-out (q, k-block) rows self-heal exactly as in
  ring_attention: their running max stays _NEG_INF, and the first real
  block rescales the junk accumulator by exp(_NEG_INF - m_real) == 0.

  q, k, v: [B, L, H, D] -> [B, L, H, D]. Like ring_attention, scores
  are scaled by D**-0.5 internally — pass the unscaled query.
  """
  global n_blockwise_traces
  n_blockwise_traces += 1
  b, l, h, d = q.shape
  block = int(min(block_size, l))
  n_blocks = -(-l // block)
  pad = n_blocks * block - l
  k_p = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
  v_p = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
  k_blocks = jnp.moveaxis(k_p.reshape(b, n_blocks, block, h, d), 1, 0)
  v_blocks = jnp.moveaxis(v_p.reshape(b, n_blocks, block, h, d), 1, 0)
  k_offsets = jnp.arange(n_blocks) * block

  m0 = jnp.full((b, h, l), _NEG_INF, q.dtype)
  l0 = jnp.zeros((b, h, l), q.dtype)
  o0 = jnp.zeros((b, l, h, d), q.dtype)

  def step(carry, xs):
    m, l_sum, o = carry
    k_cur, v_cur, k_off = xs
    s = _block_attention(q, k_cur, v_cur, jnp.asarray(0), k_off,
                         attn_win_size)
    # Padded key slots (global index >= L) are masked out regardless of
    # the band so the pad never enters any softmax.
    valid = (k_off + jnp.arange(block)) < l
    s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
    m_block = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_block)
    scale = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l_sum * scale + jnp.sum(p, axis=-1)
    o_new = (
        o * jnp.transpose(scale, (0, 2, 1))[..., None]
        + jnp.einsum('bhqk,bkhd->bqhd', p, v_cur)
    )
    return (m_new, l_new, o_new), None

  (_, l_sum, o), _ = jax.lax.scan(
      step, (m0, l0, o0), (k_blocks, v_blocks, k_offsets)
  )
  denom = jnp.transpose(l_sum, (0, 2, 1))[..., None]
  return o / jnp.maximum(denom, 1e-30)


def ring_attention_sharded(
    q: Array,
    k: Array,
    v: Array,
    mesh: Mesh,
    seq_axis: str,
    attn_win_size: Optional[int] = None,
) -> Array:
  """Global-view wrapper: shards [B, L, H, D] on L over `seq_axis`."""
  spec = P(None, seq_axis, None, None)
  fn = functools.partial(
      ring_attention, axis_name=seq_axis, attn_win_size=attn_win_size
  )
  return shard_map(
      fn,
      mesh=mesh,
      in_specs=(spec, spec, spec),
      out_specs=spec,
  )(q, k, v)


def full_attention_reference(
    q: Array, k: Array, v: Array, attn_win_size: Optional[int] = None
) -> Array:
  """Single-device reference for testing."""
  s = _block_attention(q, k, v, jnp.asarray(0), jnp.asarray(0),
                       attn_win_size)
  w = jax.nn.softmax(s, axis=-1)
  return jnp.einsum('bhqk,bkhd->bqhd', w, v)
