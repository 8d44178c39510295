"""Test-local plain reference of the fourth encoder block kind
(`config.BLOCK_LATENT_MOE`): float32 jax.numpy, the attention written as
published (`transformers` modeling_deepseek_v3.py: the one rotary key
expanded to every head and concatenated to keys of nope + rope, one
softmax of q k^T * (nope + rope)^-1/2, rotary over interleaved pairs), the
router as the published `get_topk_indices`, the experts as a plain loop.
It imports nothing from deepconsensus_tpu/models or deepconsensus_tpu/ops;
the benchmark keeps a copy of its own
(benchmark/families/mla_moe_encoder.py).

norm(x, w) = x * rsqrt(mean(x^2) + eps) * w. A layer is
h = x + attn(norm_1(x)); out = h + ffn_n(norm_2(h)); a final norm.

Attention (u [L, H], N heads): q = u W_q, a head [q_nope | q_rope];
[c | k_rope] = u W_kva, c <- norm(c); [k_nope | v] = c W_kvb a head;
q_rope and the one k_rope rotated by position over pairs (2i, 2i + 1);
k_h = [k_nope_h | k_rope], softmax(q_h k_h^T * (nope + rope)^-1/2) v_h
over the whole window; concat_h W_o.

Feed-forward: SwiGLU in the leading dense layers; behind them
s = sigmoid(n W_r), top = the k largest of s + b (the group step at
n_group groups kept, which at one group masks nothing), p_e = s_e /
(sum_top s + 1e-20) * factor, moe(n) = sum over the top-k experts that lie
in [first, first + held) of p_e expert_e(n), plus shared(n), ungated;
every expert a SwiGLU.

The program's leaves hold the rotary columns in the order its rotation
pairs them, halves (i, i + rope / 2); `published_order` puts them back in
the published order before anything is computed.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def norm(x, w, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


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


def latent_attention(w, u, *, nope, rope, rank, theta, eps, rotary=True):
  """The attention on the normed stream u [B, L, H]; `rotary` False leaves
  the rotary part out of the score (a fault the tests turn)."""
  w_q = w['query']['kernel']  # [H, N, nope + rope]
  w_q = jnp.concatenate([w_q[..., :nope], published_order(w_q[..., nope:])],
                        axis=-1)
  w_kva = w['kv_a']['kernel']  # [H, rank + rope]
  w_kva = jnp.concatenate(
      [w_kva[:, :rank], published_order(w_kva[:, rank:])], axis=-1)
  heads = w_q.shape[1]
  q = jnp.einsum('blh,hnd->blnd', u, w_q)
  q_nope, q_rope = q[..., :nope], q[..., nope:]
  kv_a = u @ w_kva
  latent = norm(kv_a[..., :rank], w['kv_a_norm']['scale'], eps)
  k_rope = kv_a[..., None, rank:]  # [B, L, 1, rope]: one head
  kv = jnp.einsum('blr,rnd->blnd', latent, w['kv_b']['kernel'])
  k_nope, v = kv[..., :nope], kv[..., nope:]
  q_rope, k_rope = rotary_pairs(q_rope, theta), rotary_pairs(k_rope, theta)
  if not rotary:
    q_rope, k_rope = jnp.zeros_like(q_rope), jnp.zeros_like(k_rope)
  query = jnp.concatenate([q_nope, q_rope], axis=-1)
  key = jnp.concatenate(
      [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (rope,))], axis=-1)
  assert key.shape[2:] == (heads, nope + rope)
  scores = jnp.einsum('bihd,bjhd->bhij', query, key) * (nope + rope) ** -0.5
  out = jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), v)
  return jnp.einsum('blnd,ndh->blh', out, w['output_transform']['kernel'])


def swiglu(x, gate, up, down):
  return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def topk_indices(scores, bias, *, top_k, n_group=1, topk_group=1):
  """The published `get_topk_indices`: scores [T, E] -> experts [T, k]."""
  tokens, n_experts = scores.shape
  choice = scores + bias[None, :]
  grouped = choice.reshape(tokens, n_group, n_experts // n_group)
  group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
  kept = jax.lax.top_k(group_scores, topk_group)[1]
  group_mask = jnp.zeros_like(group_scores).at[
      jnp.arange(tokens)[:, None], kept].set(1.0)
  mask = jnp.broadcast_to(group_mask[..., None], grouped.shape).reshape(
      tokens, n_experts)
  return jax.lax.top_k(jnp.where(mask > 0, choice, 0.0), top_k)[1]


def routed_experts(w, n, *, top_k, factor, renormalise=True, first=0,
                   shared=True, bias_in_weights=False):
  """n [T, H] tokens -> (moe(n) [T, H], assignments per held expert). The
  experts as a plain loop: rows routed to e, its three products,
  scatter-add. w's expert leaves hold experts first ... first + held - 1
  of the router's width. `bias_in_weights` takes the weights from s + b (a
  fault the tests turn)."""
  scores = jax.nn.sigmoid(n @ w['router']['kernel'])
  bias = w['router_selection_bias']
  top_e = topk_indices(scores, bias, top_k=top_k)
  top_p = jnp.take_along_axis(
      scores + bias[None, :] if bias_in_weights else scores, top_e, axis=-1)
  if renormalise:
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
  top_p, top_e = np.asarray(top_p * factor), np.asarray(top_e)
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
  out = jnp.asarray(out)
  if shared:
    s = w['shared_expert']
    out = out + swiglu(n, s['gate_layer']['kernel'], s['up_layer']['kernel'],
                       s['output_layer']['kernel'])
  return out, counts


def _embed(table, ids):
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(table.shape[1]))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  emb = _embed(table, rows[:, lo:hi, :].astype(jnp.int32))
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits(params, rows, *, max_passes, ffn_pattern, nope, rope, rank, theta,
           eps, top_k, factor, renormalise=True, first=0, **faults):
  """rows [B, 4*max_passes+5, L] float32 -> (logits [B, L, 5], assignments
  [expert layers, held]). `ffn_pattern`: one letter a layer, 'E' sparse
  experts, anything else the dense SwiGLU. Not jitted: the experts' loop
  reads the routing on the host. `faults`: rotary=False,
  bias_in_weights=True."""
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
  attention_faults = {k: faults[k] for k in ('rotary',) if k in faults}
  router_faults = {k: faults[k] for k in ('bias_in_weights',) if k in faults}
  counts = []
  for n, letter in enumerate(ffn_pattern):
    u = norm(x, enc[f'attention_wrapper_{n}']['rms_norm']['scale'], eps)
    x = x + latent_attention(enc[f'latent_attention_{n}'], u, nope=nope,
                             rope=rope, rank=rank, theta=theta, eps=eps,
                             **attention_faults)
    h = norm(x, enc[f'ffn_wrapper_{n}']['rms_norm']['scale'], eps)
    if letter == 'E':
      routed, took = routed_experts(
          enc[f'moe_{n}'], h.reshape(-1, h.shape[-1]), top_k=top_k,
          factor=factor, renormalise=renormalise, first=first,
          **router_faults)
      x = x + routed.reshape(x.shape)
      counts.append(took)
    else:
      w = enc[f'ffn_{n}']
      x = x + swiglu(h, w['gate_layer']['kernel'], w['up_layer']['kernel'],
                     w['output_layer']['kernel'])
  x = norm(x, enc['output_normalization']['scale'], eps)
  return (x @ params['logits']['kernel'] + params['logits']['bias'],
          np.stack(counts))
