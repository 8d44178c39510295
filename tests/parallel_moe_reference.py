"""Test-local plain reference of the fifth encoder block kind
(`config.BLOCK_PARALLEL_WINDOW_MOE`): float32 jax.numpy, the block, the norm
and the attention written as published (`transformers` modeling_cohere2.py:
one LayerNorm without a bias, attention and feed-forward both on its output,
one addition; grouped heads by repeating k and v; rotary over interleaved
pairs in the window layers alone; the window mask built always), the router
as a sigmoid over all experts and a plain top-k, the experts as a plain
loop, the shared experts one by one and averaged. It imports nothing from
deepconsensus_tpu/models or deepconsensus_tpu/ops; the benchmark keeps a
copy of its own (benchmark/families/parallel_moe_encoder.py).

LN(x, w) = (x - mean(x)) * rsqrt(var(x) + eps) * w, no bias. A layer is
out = x + attn_n(u) + ffn(u), u = LN(x); a final LN.

Attention (u [L, H], N query heads over K key-value heads of D): q = u W_q,
k = u W_k, v = u W_v; in a window layer ('W') q and k rotated by position
over pairs (2i, 2i + 1) of the whole head, and position i attends to j only
where |i - j| < window (two-sided: an encoder); in a full layer ('F')
neither rotation nor mask; query head h reads key-value head h // (N / K);
softmax(q_h k^T * D^-1/2) v; concat_h W_o.

Feed-forward: s = sigmoid(u W_r) over all E, top = the k largest of s,
p_e = s_e / sum_top s, ffn(u) = sum over the top-k experts that lie in
[first, first + held) of p_e expert_e(u), plus the MEAN of the m shared
experts, each a SwiGLU of the expert width. The program holds the m shared
experts as one SwiGLU of m x the width (columns [s F, (s + 1) F) of gate
and up and the same rows of down are shared expert s).

The program's leaves hold the columns of every head of W_q and W_k of a
window layer in the order its rotation pairs them, halves (i, i + D / 2);
`published_order` puts them back in the published order before anything is
computed.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

WINDOW, FULL = 'W', 'F'


def layer_norm(x, w, eps):
  centred = x - jnp.mean(x, axis=-1, keepdims=True)
  return centred * jax.lax.rsqrt(
      jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) * w


def rotary_pairs(x, theta):
  """x [B, L, N, D], positions 0..L-1: pair (2i, 2i + 1) turned by
  position * theta**(-2i / D)."""
  length, d = x.shape[1], x.shape[3]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
  angles = (np.arange(length, dtype=np.float64)[:, None] * inv[None, :])
  cos = np.cos(angles).astype(np.float32)[None, :, None, :]
  sin = np.sin(angles).astype(np.float32)[None, :, None, :]
  even, odd = x[..., 0::2], x[..., 1::2]
  return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                   axis=-1).reshape(x.shape)


def published_order(columns):
  """[..., D] columns in the program's order (halves: i, i + D/2) -> in
  the published one (pairs: 2i, 2i + 1)."""
  half = columns.shape[-1] // 2
  return jnp.stack([columns[..., :half], columns[..., half:]],
                   axis=-1).reshape(columns.shape)


def attention(w, u, *, rotated, window, theta):
  """The attention on the normed stream u [B, L, H]. `rotated`: a window
  layer's rotation of q and k over the whole head; `window`: positions
  |i - j| < window alone are attended (None: all)."""
  w_q, w_k = w['query']['kernel'], w['key']['kernel']  # [H, heads, D]
  if rotated:
    w_q, w_k = published_order(w_q), published_order(w_k)
  q = jnp.einsum('blh,hnd->blnd', u, w_q)
  k = jnp.einsum('blh,hnd->blnd', u, w_k)
  v = jnp.einsum('blh,hnd->blnd', u, w['value']['kernel'])
  if rotated:
    q, k = rotary_pairs(q, theta), rotary_pairs(k, theta)
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', q, k) * q.shape[-1] ** -0.5
  if window is not None:
    i = np.arange(u.shape[1])
    near = np.abs(i[:, None] - i[None, :]) < window
    scores = jnp.where(near[None, None], scores, -jnp.inf)
  out = jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), v)
  return jnp.einsum('blnd,ndh->blh', out, w['output_transform']['kernel'])


def swiglu(x, gate, up, down):
  return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def shared_experts(w, n, count, averaged=True):
  """The `count` shared experts one by one, each on its own columns of the
  program's wide leaves; their mean (`averaged` False: their sum, a fault
  the tests turn)."""
  gate, up, down = (w[name]['kernel'] for name in (
      'gate_layer', 'up_layer', 'output_layer'))
  width = gate.shape[1] // count
  outs = [swiglu(n, gate[:, s * width:(s + 1) * width],
                 up[:, s * width:(s + 1) * width],
                 down[s * width:(s + 1) * width]) for s in range(count)]
  total = sum(outs[1:], outs[0])
  return total / count if averaged else total


def routed_experts(w, n, *, top_k, renormalise=True, first=0):
  """n [T, H] tokens -> (sum over the held top-k experts of p_e expert_e(n)
  [T, H], assignments per held expert). The experts as a plain loop: rows
  routed to e, its three products, indexed addition. w's expert leaves hold
  experts first ... first + held - 1 of the router's width."""
  scores = jax.nn.sigmoid(n @ w['router']['kernel'])
  top_p, top_e = jax.lax.top_k(scores, top_k)
  if renormalise:
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
  top_p, top_e = np.asarray(top_p), np.asarray(top_e)
  held = w['experts_gate'].shape[0]
  out = np.zeros(n.shape, np.float32)
  counts = np.zeros(held, np.int64)
  for e in range(held):
    token, slot = np.nonzero(top_e == first + e)
    counts[e] = len(token)
    if len(token):
      y = swiglu(n[token], w['experts_gate'][e], w['experts_up'][e],
                 w['experts_down'][e])
      # A token names an expert at most once: plain indexed addition.
      out[token] += top_p[token, slot][:, None] * np.asarray(y)
  return jnp.asarray(out), counts


def _embed(table, ids):
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(table.shape[1]))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  emb = _embed(table, rows[:, lo:hi, :].astype(jnp.int32))
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits(params, rows, *, max_passes, layer_pattern, theta, window, eps,
           top_k, n_shared, renormalise=True, first=0, sequential=False,
           rotate_full=False, shared_summed=False):
  """rows [B, 4*max_passes+5, L] float32 -> (logits [B, L, 5], assignments
  [layers, held]). `layer_pattern`: one letter a layer, 'W' a window layer,
  'F' a full one. Not jitted: the experts' loop reads the routing on the
  host. Faults the tests turn: `sequential` (h = x + attn(LN(x)), then
  h + ffn(LN(h)), the one norm's weights twice), `rotate_full` (the full
  layers rotated as the window layers are), `shared_summed` (the shared
  experts added up, not averaged)."""
  p = max_passes
  table = lambda name: params[name + '_embedding']['embedding']
  x = jnp.concatenate([
      _family(table('bases'), rows, 0, p),
      _family(table('pw'), rows, p, 2 * p),
      _family(table('ip'), rows, 2 * p, 3 * p),
      _family(table('strand'), rows, 3 * p, 4 * p),
      _family(table('bases'), rows, 4 * p, 4 * p + 1),
      _family(table('sn'), rows, 4 * p + 1, 4 * p + 5),
  ], axis=-1) @ params['condenser']['kernel']
  enc = params['encoder']
  counts = []
  for n, letter in enumerate(layer_pattern):
    scale = enc[f'block_norm_{n}']['scale']
    u = layer_norm(x, scale, eps)
    attended = attention(
        enc[f'self_attention_{n}'], u, theta=theta,
        rotated=letter == WINDOW or rotate_full,
        window=window if letter == WINDOW else None)
    moe = enc[f'moe_{n}']
    fed = layer_norm(x + attended, scale, eps) if sequential else u
    tokens = fed.reshape(-1, fed.shape[-1])
    routed, took = routed_experts(moe, tokens, top_k=top_k,
                                  renormalise=renormalise, first=first)
    shared = shared_experts(moe['shared_expert'], tokens, n_shared,
                            averaged=not shared_summed)
    x = x + attended + (routed + shared).reshape(x.shape)
    counts.append(took)
  x = layer_norm(x, enc['output_normalization']['scale'], eps)
  return (x @ params['logits']['kernel'] + params['logits']['bias'],
          np.stack(counts))
