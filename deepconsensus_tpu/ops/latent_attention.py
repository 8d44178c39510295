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
encoder attends over the whole window in both directions. Plain
`jax.numpy`; the softmax is float32.

The "absorbed" form, in which a decode step scores against a cache of
[latent, k_rope] without up-projecting it, has nothing to run on here: this
system has no cache and no decode step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


def halves_from_pairs(rotary_dim: int) -> np.ndarray:
  """The column order that turns a rotary part published for interleaved
  pairs (2i, 2i + 1) into the one `apply_rotary` rotates, halves
  (i, i + rotary_dim / 2): column j of the program's kernel is column
  `halves_from_pairs(d)[j]` of the published one. Applied to the rotary
  columns of the query kernel (every head) and of the shared rotary key,
  it is a relabelling: q_rope . k_rope is a sum over the same pairs."""
  return np.concatenate([np.arange(0, rotary_dim, 2),
                         np.arange(1, rotary_dim, 2)])
