"""Test-local plain reference of the sixth encoder block kind
(`config.BLOCK_WINDOW_MOE`): float32 jax.numpy, the block, the norm, the
rotation and the attention written as published (`transformers` Llama-style
modelling with per-layer-type rope parameters: a sequential pre-RMSNorm
block; grouped heads by repeating k and v; rotate-half over the whole head
in every layer, the default law in sliding_attention layers and YaRN in
full_attention layers; the window mask built always), the router as a
softmax over all experts and a plain top-k renormalised, the routed experts
as a plain loop over the held share, no shared expert. It imports nothing
from deepconsensus_tpu/models or deepconsensus_tpu/ops; the benchmark keeps
a copy of its own (benchmark/families/window_moe_encoder.py).

RMS(x, w) = x * rsqrt(mean(x^2) + eps) * w (plain weights). A layer is
h = x + attn_n(RMS(x)), out = h + moe(RMS(h)); a final RMS.

Attention (u [L, H], N query heads over K key-value heads of D): q = u W_q,
k = u W_k, v = u W_v, no biases, no q/k norm; q and k rotated by position
over halves (i, i + D/2) of the whole head, cos and sin from the layer
type's rope parameters (`rope_tables`); in a window layer ('W') position i
attends to j only where |i - j| < window (two-sided: an encoder has no
causal mask); query head h reads key-value head h // (N / K);
softmax(q_h k^T * D^-1/2) v; concat_h W_o.

YaRN, from the published formula (`transformers`
`_compute_yarn_parameters`), computed here on its own: c(r) = D ln(P / (2 pi
r)) / (2 ln theta) for the original P positions; low = max(floor(c(beta_fast)),
0), high = min(ceil(c(beta_slow)), D - 1); ramp_i = clamp((i - low) / (high -
low), 0, 1); inv_i = theta^(-2i/D) ((1 - ramp_i) + ramp_i / factor); cos and
sin multiplied by the attention factor.

Feed-forward: p = softmax(u W_r) over all E, top = the k largest of p,
renormalised over their sum; moe(u) = sum over the top-k experts that lie in
[first, first + held) of p_e SwiGLU_e(u).

Departures (the program's and this reference's alike): the window is
two-sided; no vocabulary, no MTP head: the pile-up embedding and the 5-way
head of this system in front and behind.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

WINDOW, FULL = 'W', 'F'
LAYER_TYPES = {'sliding_attention': WINDOW, 'full_attention': FULL}


def rms_norm(x, w, eps):
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                           + eps) * w


def yarn_range(d, theta, original, beta_fast, beta_slow):
  """(low, high) of the published YaRN correction range."""
  c = lambda r: d * math.log(original / (2 * math.pi * r)) / (
      2 * math.log(theta))
  return max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)), d - 1)


def rope_tables(rope, length, d, *, interpolate=True):
  """(cos, sin) [L, D] of one layer type's `rope_parameters` entry, for
  rotate-half (the half-head frequencies repeated). `interpolate` False, a
  fault: YaRN's magnitude without its interpolation."""
  theta = float(rope['rope_theta'])
  inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
  magnitude = 1.0
  if rope['rope_type'] == 'yarn':
    low, high = yarn_range(d, theta, rope['original_max_position_embeddings'],
                           rope['beta_fast'], rope['beta_slow'])
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    if interpolate:
      inv = inv * (1.0 - ramp) + inv / rope['factor'] * ramp
    magnitude = rope['attention_factor']
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)
  return (np.cos(angles) * magnitude).astype(np.float32), (
      np.sin(angles) * magnitude).astype(np.float32)


def rotate_half(x, cos, sin):
  """x [B, L, N, D] -> x cos + rotate_half(x) sin."""
  half = x.shape[-1] // 2
  turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
  return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def attention(w, u, *, rope, window, interpolate=True):
  """The attention on the normed stream u [B, L, H]: `rope` the layer
  type's rope parameters; `window`: positions |i - j| < window alone are
  attended (None: all)."""
  q = jnp.einsum('blh,hnd->blnd', u, w['query']['kernel'])
  k = jnp.einsum('blh,hnd->blnd', u, w['key']['kernel'])
  v = jnp.einsum('blh,hnd->blnd', u, w['value']['kernel'])
  cos, sin = rope_tables(rope, u.shape[1], q.shape[-1],
                         interpolate=interpolate)
  q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
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


def routed_experts(w, n, *, top_k, renormalise=True, first=0):
  """n [T, H] tokens -> (sum over the held top-k experts of p_e expert_e(n)
  [T, H], assignments per held expert). The router a softmax over all E in
  float32, the experts a plain loop: rows routed to e, its three products,
  indexed addition."""
  probs = jax.nn.softmax(n @ w['router']['kernel'], axis=-1)
  top_p, top_e = jax.lax.top_k(probs, top_k)
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


def logits(params, rows, *, max_passes, layer_types, rope_parameters,
           window, eps, top_k, renormalise=True, first=0, parallel=False,
           no_attention_factor=False, full_default_rope=False,
           no_interpolation=False):
  """rows [B, 4*max_passes+5, L] float32 -> (logits [B, L, 5], assignments
  [layers, held]). `layer_types`: the published name of each layer's type;
  `rope_parameters`: the published entry of each type. Not jitted: the
  experts' loop reads the routing on the host. Faults the tests turn:
  `parallel` (x + attn(RMS_1(x)) + moe(RMS_2(x)): both sublayers read
  the layer's input), `no_attention_factor` (YaRN's magnitude 1),
  `full_default_rope` (the full layers rotated with the window layers'
  rope), `no_interpolation` (YaRN's magnitude without its
  interpolation)."""
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
  for n, name in enumerate(layer_types):
    letter = LAYER_TYPES[name]
    rope = dict(rope_parameters[
        'sliding_attention' if full_default_rope else name])
    if no_attention_factor and rope['rope_type'] == 'yarn':
      rope['attention_factor'] = 1.0
    wrapper = enc[f'attention_wrapper_{n}']['rms_norm']['scale']
    attended = attention(
        enc[f'self_attention_{n}'], rms_norm(x, wrapper, eps), rope=rope,
        window=window if letter == WINDOW else None,
        interpolate=not no_interpolation)
    h = x + attended
    fed = rms_norm(x if parallel else h,
                   enc[f'ffn_wrapper_{n}']['rms_norm']['scale'], eps)
    routed, took = routed_experts(
        enc[f'moe_{n}'], fed.reshape(-1, fed.shape[-1]), top_k=top_k,
        renormalise=renormalise, first=first)
    x = h + routed.reshape(x.shape)
    counts.append(took)
  x = rms_norm(x, enc['output_normalization']['scale'], eps)
  return (x @ params['logits']['kernel'] + params['logits']['bias'],
          np.stack(counts))
