"""Family `power_retention_encoder`: the block of a public 14B
linear-attention language model (every attention layer a gated power
retention layer over grouped heads, SwiGLU feed-forward, RMSNorm, rotary
positions) behind this system's pile-up embedding and 5-way head, as the
program's preset `transformer_learn_values_retention` serves it.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test.

The layer, per window (x [L, H] from the condenser, positions 0..L-1):

  u = RMSNorm(x); q = u W_q [L, Hq, D]; k = u W_k, v = u W_v [L, Hkv, D]
  q, k: RMSNorm over D (one weight [D] each), then rotate-half rotary
  log g = logsigmoid(u W_g + b_g) [L, Hkv], G_t = sum_{m<=t} log g_m
  a_ij = (q_i . k_j / sqrt(D))^2 * exp(-|G_i - G_j|), all i, j (an encoder
         has no causal mask: the published causal recurrence run left to
         right plus the same run right to left, the diagonal once)
  y_i = sum_j a_ij v_j / (sum_j a_ij + eps); query head h reads key-value
        head h // (Hq // Hkv)
  h = x + y W_o; out = h + (silu(n W_gate) * (n W_up)) W_down, n = RMSNorm(h)

and after the last layer a final RMSNorm and the float32 5-way head.

Weights from the seed (`make_params`), so that every part counts in the
logits: matmul kernels uniform with variance 1/fan_in (each residual
branch then has an RMS of the order of the stream's, so a fault in one
block moves the logits), RMSNorm weights uniform [0.5, 1.5), gate bias
uniform [1.5, 2.5) (g then spreads over about (0.5, 1)), embeddings normal
with std E**-0.5 as published for the pile-up model, the head Glorot
uniform with a bias of std 0.02. All leaves bfloat16, which is what the
preset's `inference_dtype` leaves resident; the reference upcasts them, so
the rounding of the weights is not part of what is compared.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = 5
SN_ROWS = 4
RETENTION_EPS = 1e-6
WEIGHT_BYTES = 2  # bfloat16 leaves

SIZE_KEYS = ('num_hidden_layers', 'hidden_size', 'filter_size', 'num_heads',
             'num_kv_heads', 'head_dim', 'rope_theta', 'rms_norm_eps',
             'retention_degree', 'max_passes', 'max_length', 'total_rows',
             'condense_input_size', 'embedding', 'PW_MAX', 'IP_MAX',
             'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def stated(params) -> dict:
  """The program's sizes under the file's keys: the program's own names,
  and the published config.json's names for the sizes it publishes."""
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'num_hidden_layers': params.num_hidden_layers,
      'hidden_size': params.hidden_size,
      'filter_size': params.filter_size,
      'intermediate_size': params.filter_size,
      'num_heads': params.num_heads,
      'num_attention_heads': params.num_heads,
      'num_kv_heads': params.num_kv_heads,
      'num_key_value_heads': params.num_kv_heads,
      'head_dim': params.head_dim,
      'rope_theta': params.rope_theta,
      'rms_norm_eps': params.rms_norm_eps,
      'retention_degree': params.retention_degree,
      'add_pos_encoding': params.add_pos_encoding,
      'max_passes': params.max_passes,
      'max_length': params.max_length,
      'total_rows': params.total_rows,
      'use_ccs_bq': params.use_ccs_bq,
      'PW_MAX': params.PW_MAX, 'IP_MAX': params.IP_MAX,
      'STRAND_MAX': params.STRAND_MAX, 'SN_MAX': params.SN_MAX,
      'dtype': params.dtype,
      'inference_dtype': params.inference_dtype,
      'rezero': params.rezero,
      'use_fused_hotpath': params.use_fused_hotpath,
      'embedding': {
          'bases': params.per_base_hidden_size, 'pw': params.pw_hidden_size,
          'ip': params.ip_hidden_size, 'strand': params.strand_hidden_size,
          'sn': params.sn_hidden_size},
  }


# ------------------------------------------------------------------ the tree

def leaf_specs(shape: dict):
  """(path, shape, kind, fan_in) for every leaf, in a fixed order."""
  h, f = shape['hidden_size'], shape['filter_size']
  n_q, n_kv, d = shape['num_heads'], shape['num_kv_heads'], shape['head_dim']
  emb = shape['embedding']
  condense_in = shape['condense_input_size']
  specs = [
      (('bases_embedding', 'embedding'), (VOCAB, emb['bases']), 'embed', 0),
      (('pw_embedding', 'embedding'), (shape['PW_MAX'] + 1, emb['pw']),
       'embed', 0),
      (('ip_embedding', 'embedding'), (shape['IP_MAX'] + 1, emb['ip']),
       'embed', 0),
      (('strand_embedding', 'embedding'),
       (shape['STRAND_MAX'] + 1, emb['strand']), 'embed', 0),
      (('sn_embedding', 'embedding'), (shape['SN_MAX'] + 1, emb['sn']),
       'embed', 0),
      (('condenser', 'kernel'), (condense_in, h), 'fan_in', condense_in),
      (('logits', 'kernel'), (h, VOCAB), 'glorot', h + VOCAB),
      (('logits', 'bias'), (VOCAB,), 'bias', 0),
      (('encoder', 'output_normalization', 'scale'), (h,), 'norm', 0),
  ]
  for n in range(shape['num_hidden_layers']):
    att = ('encoder', f'self_attention_{n}')
    ffn = ('encoder', f'ffn_{n}')
    specs += [
        (('encoder', f'attention_wrapper_{n}', 'rms_norm', 'scale'), (h,),
         'norm', 0),
        (att + ('query', 'kernel'), (h, n_q, d), 'fan_in', h),
        (att + ('key', 'kernel'), (h, n_kv, d), 'fan_in', h),
        (att + ('value', 'kernel'), (h, n_kv, d), 'fan_in', h),
        (att + ('query_norm', 'scale'), (d,), 'norm', 0),
        (att + ('key_norm', 'scale'), (d,), 'norm', 0),
        (att + ('gate', 'kernel'), (h, n_kv), 'fan_in', h),
        (att + ('gate', 'bias'), (n_kv,), 'gate_bias', 0),
        (att + ('output_transform', 'kernel'), (n_q, d, h), 'fan_in',
         n_q * d),
        (('encoder', f'ffn_wrapper_{n}', 'rms_norm', 'scale'), (h,),
         'norm', 0),
        (ffn + ('gate_layer', 'kernel'), (h, f), 'fan_in', h),
        (ffn + ('up_layer', 'kernel'), (h, f), 'fan_in', h),
        (ffn + ('output_layer', 'kernel'), (f, h), 'fan_in', f),
    ]
  return specs


def _draw(key, shp, kind, fan):
  uniform = lambda lo, hi: jax.random.uniform(key, shp, jnp.float32, lo, hi)
  if kind == 'embed':
    return jax.random.normal(key, shp, jnp.float32) * shp[1] ** -0.5
  if kind == 'fan_in':
    lim = math.sqrt(3.0 / fan)
    return uniform(-lim, lim)
  if kind == 'glorot':
    lim = math.sqrt(6.0 / fan)
    return uniform(-lim, lim)
  if kind == 'bias':
    return jax.random.normal(key, shp, jnp.float32) * 0.02
  if kind == 'norm':
    return uniform(0.5, 1.5)
  if kind == 'gate_bias':
    return uniform(1.5, 2.5)
  raise ValueError(kind)


def make_params(shape: dict, seed: int):
  """The parameter tree on the device, every leaf bfloat16: one jitted
  call, one key per leaf, each leaf drawn in float32 and rounded once."""
  specs = leaf_specs(shape)

  def build(key):
    tree: dict = {}
    for i, (path, shp, kind, fan) in enumerate(specs):
      node = tree
      for part in path[:-1]:
        node = node.setdefault(part, {})
      node[path[-1]] = _draw(jax.random.fold_in(key, i), shp, kind,
                             fan).astype(jnp.bfloat16)
    return tree

  return jax.jit(build)(key_from_seed(seed))


# ------------------------------------------------------------------ the work

def param_count(shape: dict) -> int:
  return sum(math.prod(shp) for _p, shp, _k, _f in leaf_specs(shape))


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, rotary, gate sums, the decay and the division count as nothing;
  the retention parts are the quadratic form's L x L pairs per head."""
  length, h, f = shape['max_length'], shape['hidden_size'], shape['filter_size']
  n_q, n_kv, d = shape['num_heads'], shape['num_kv_heads'], shape['head_dim']
  layers = shape['num_hidden_layers']
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'qkvgo': layers * 2 * length * h * (2 * n_q * d + 2 * n_kv * d + n_kv),
      'retention_scores': layers * 2 * length * length * n_q * d,
      'retention_values': layers * 2 * length * length * n_q * d,
      'ffn': layers * 2 * length * 3 * h * f,
      'head': 2 * length * h * VOCAB,
  }
  parts['total'] = sum(parts.values())
  return parts


def bytes_per_pack(shape: dict, batch: int) -> dict:
  """Bytes the algorithm has to move for one pack: the uint8 rows and
  float32 SN scalars in, two uint8 planes out, the bfloat16 weights once."""
  length = shape['max_length']
  parts = {
      'rows_in': batch * (shape['total_rows'] - SN_ROWS) * length,
      'sn_in': batch * SN_ROWS * 4,
      'planes_out': batch * length * 2,
      'weights': param_count(shape) * WEIGHT_BYTES,
  }
  parts['total'] = sum(parts.values())
  return parts


def least_seconds_per_pack(shape: dict, batch: int, peaks: dict) -> dict:
  return least_seconds(flops_per_window(shape)['total'] * batch,
                       bytes_per_pack(shape, batch)['total'], peaks)


def part_work(shape: dict, batch: int, part: str) -> dict:
  """{'flops', 'bytes'} one pack needs of one part of the block, all
  layers together, as the device scope of that name covers it:

  'retention'  the operator alone: q, k, v (bfloat16) and log g (float32)
               in, y (bfloat16) out; scores, decay and weights are the
               algorithm's temporaries and count no bytes.
  'ffn'        the feed-forward residual branch: the three bfloat16
               matrices once a pack, the stream in and out."""
  length, h, f = shape['max_length'], shape['hidden_size'], shape['filter_size']
  n_q, n_kv, d = shape['num_heads'], shape['num_kv_heads'], shape['head_dim']
  layers, positions = shape['num_hidden_layers'], batch * length
  flops = flops_per_window(shape)
  if part == 'retention':
    per_position = (2 * n_q * d + 2 * n_kv * d) * WEIGHT_BYTES + n_kv * 4
    return {'flops': batch * (flops['retention_scores']
                              + flops['retention_values']),
            'bytes': layers * positions * per_position}
  if part == 'ffn':
    return {'flops': batch * flops['ffn'],
            'bytes': layers * WEIGHT_BYTES * (3 * h * f + 2 * positions * h)}
  raise KeyError(part)


retention_work = functools.partial(part_work, part='retention')
ffn_work = functools.partial(part_work, part='ffn')


# ------------------------------------------------------------- the reference

def row_ranges(max_passes: int):
  """(start, end) rows of bases, pw, ip, strand, ccs, sn in a window."""
  p = max_passes
  return ((0, p), (p, 2 * p), (2 * p, 3 * p), (3 * p, 4 * p),
          (4 * p, 4 * p + 1), (4 * p + 1, 4 * p + 1 + SN_ROWS))


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


def _feature_family(table, rows, lo, hi):
  ids = rows[:, lo:hi, :].astype(jnp.int32)
  emb = _embed(table.astype(jnp.float32), ids)  # [B, r, L, E]
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def rms_norm(x, scale, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta: float):
  """x [B, L, N, D], positions 0..L-1: rotate-half rotary embedding."""
  length, d = x.shape[1], x.shape[3]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)[None, :, None, :]
  cos, sin = np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)
  rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
  return x * cos + rotated * sin


def retention_quadratic(q, k, v, log_g, *, degree: int = 2,
                        causal: bool = False, eps: float = RETENTION_EPS,
                        rd=lambda a: a):
  """The quadratic form at width. q [B, L, Hq, D]; k, v [B, L, Hkv, D];
  log_g [B, L, Hkv] -> y [B, L, Hq, D]. Key-value heads are repeated
  outright. `causal` keeps j <= i alone (the published operator);
  `degree` and `causal` are what the fault tests turn."""
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  cum = jnp.repeat(jnp.cumsum(log_g, axis=1), group, axis=2)  # [B, L, Hq]
  cum = jnp.transpose(cum, (0, 2, 1))
  scores = jnp.einsum('bihd,bjhd->bhij', rd(q), rd(k))
  scores = (scores * q.shape[3] ** -0.5) ** degree
  diff = cum[:, :, :, None] - cum[:, :, None, :]  # G_i - G_j
  if causal:
    i = np.arange(q.shape[1])
    decay = jnp.where(i[:, None] >= i[None, :], jnp.exp(diff), 0.0)
  else:
    decay = jnp.exp(-jnp.abs(diff))
  weights = scores * decay
  norm = jnp.sum(weights, axis=-1)  # [B, Hq, L]
  out = jnp.einsum('bhij,bjhd->bihd', rd(weights), rd(v))
  return out / (jnp.transpose(norm, (0, 2, 1))[..., None] + eps)


def power_features(a: np.ndarray) -> np.ndarray:
  """phi: the symmetric second power of a [..., D] -> [..., D(D+1)/2],
  off-diagonal products scaled by sqrt 2, so phi(a) . phi(b) = (a . b)^2."""
  d = a.shape[-1]
  i, j = np.triu_indices(d)
  return a[..., i] * a[..., j] * np.where(i == j, 1.0, math.sqrt(2.0))


def retention_recurrence(q, k, v, log_g, *, causal: bool = False,
                         eps: float = RETENTION_EPS) -> np.ndarray:
  """The same operator token by token, in float64 numpy, for the tests:
  S_t = g_t S_{t-1} + phi(k_t) v_t^T, z_t = g_t z_{t-1} + phi(k_t),
  y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps), with q and k each scaled
  by D^(-1/4) so that phi(q') . phi(k') = (q . k / sqrt(D))^2. Two
  directions: the run left to right plus the run right to left (a state
  there decays by the gate of the position it leaves), numerators and
  normalisers summed with the j = i term once, one division."""
  q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
  gate = np.exp(np.asarray(log_g, np.float64))
  b, length, n_q, d = q.shape
  group = n_q // k.shape[2]
  phi_q = power_features(q * d ** -0.25)
  phi_k = power_features(k * d ** -0.25)
  num = np.zeros((b, length, n_q, d))
  den = np.zeros((b, length, n_q))
  for h in range(n_q):
    kv = h // group
    for direction in ((1,) if causal else (1, -1)):
      order = range(length) if direction == 1 else range(length - 1, -1, -1)
      state = np.zeros((b, phi_k.shape[-1], d))
      z = np.zeros((b, phi_k.shape[-1]))
      for t in order:
        # Left to right a state enters t through g_t; right to left it
        # left t + 1 through g_{t+1}.
        g = gate[:, t, kv] if direction == 1 else (
            gate[:, t + 1, kv] if t + 1 < length else np.ones(b))
        mine = phi_k[:, t, kv]
        state = g[:, None, None] * state + mine[:, :, None] * v[:, t, kv, None, :]
        z = g[:, None] * z + mine
        num[:, t, h] += np.einsum('bf,bfd->bd', phi_q[:, t, h], state)
        den[:, t, h] += np.einsum('bf,bf->b', phi_q[:, t, h], z)
    if not causal:  # the j = i term came with both runs
      own = np.einsum('bf,bf->b', phi_q[:, :, h].reshape(-1, phi_q.shape[-1]),
                      phi_k[:, :, kv].reshape(-1, phi_k.shape[-1]))
      own = own.reshape(b, length)
      num[:, :, h] -= own[..., None] * v[:, :, kv]
      den[:, :, h] -= own
  return num / (den[..., None] + eps)


def embed_fn(params, rows, *, max_passes: int, precision: str):
  """rows [B, 4*max_passes+5, L] float32 -> the stream [B, L, H]."""
  rd = _rounder(precision)
  base_r, pw_r, ip_r, st_r, ccs_r, sn_r = row_ranges(max_passes)
  table = lambda name: params[name + '_embedding']['embedding']
  x = jnp.concatenate([
      _feature_family(table('bases'), rows, *base_r),
      _feature_family(table('pw'), rows, *pw_r),
      _feature_family(table('ip'), rows, *ip_r),
      _feature_family(table('strand'), rows, *st_r),
      _feature_family(table('bases'), rows, *ccs_r),
      _feature_family(table('sn'), rows, *sn_r),
  ], axis=-1)
  return jnp.matmul(rd(x), rd(params['condenser']['kernel'].astype(
      jnp.float32)))


def layer_fn(att, att_norm, ffn, ffn_norm, x, *, rope_theta: float,
             eps: float, precision: str, gate: bool = True, degree: int = 2,
             causal: bool = False):
  """One block on the stream x [B, L, H]; the layer's leaves are upcast
  here, one layer at a time."""
  rd = _rounder(precision)
  f32 = lambda tree: jax.tree_util.tree_map(
      lambda a: a.astype(jnp.float32), tree)
  att, ffn = f32(att), f32(ffn)
  mm = lambda a, w: jnp.einsum('blh,h...->bl...', rd(a), rd(w))
  u = rms_norm(x, f32(att_norm)['rms_norm']['scale'], eps)
  q = rms_norm(mm(u, att['query']['kernel']), att['query_norm']['scale'], eps)
  k = rms_norm(mm(u, att['key']['kernel']), att['key_norm']['scale'], eps)
  v = mm(u, att['value']['kernel'])
  q, k = rotary(q, rope_theta), rotary(k, rope_theta)
  log_g = jax.nn.log_sigmoid(
      mm(u, att['gate']['kernel']) + att['gate']['bias'])
  if not gate:
    log_g = jnp.zeros_like(log_g)
  y = retention_quadratic(q, k, v, log_g, degree=degree, causal=causal,
                          rd=rd)
  x = x + jnp.einsum('blnd,ndh->blh', rd(y),
                     rd(att['output_transform']['kernel']))
  n = rms_norm(x, f32(ffn_norm)['rms_norm']['scale'], eps)
  hidden = jax.nn.silu(mm(n, ffn['gate_layer']['kernel'])) * mm(
      n, ffn['up_layer']['kernel'])
  return x + mm(hidden, ffn['output_layer']['kernel'])


def head_fn(params, x, *, eps: float):
  """Final RMSNorm and the 5-way head: float32 whatever the compute type,
  as the program keeps it, so no rounding."""
  f32 = lambda a: a.astype(jnp.float32)
  x = rms_norm(x, f32(params['encoder']['output_normalization']['scale']),
               eps)
  return jnp.matmul(x, f32(params['logits']['kernel'])) + f32(
      params['logits']['bias'])


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 32, **faults):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]:
  plain float32 under `jax.default_matmul_precision('highest')`, in blocks
  of windows, input clipping included. `precision` 'bfloat16' or 'fp8'
  rounds every matmul operand (activations and weights) to that type
  before a float32-accumulated product. `faults` (gate=False, degree=1,
  causal=True) are for the tests that show the comparison sees them."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  eps = float(shape['rms_norm_eps'])
  embed = jax.jit(functools.partial(embed_fn, max_passes=p,
                                    precision=precision))
  layer = jax.jit(functools.partial(
      layer_fn, rope_theta=float(shape['rope_theta']), eps=eps,
      precision=precision, **faults))
  head = jax.jit(functools.partial(head_fn, eps=eps))
  enc = params['encoder']
  out = []
  with jax.default_matmul_precision('highest'):
    for lo in range(0, len(rows), block):
      chunk = rows[lo:lo + block]
      n = len(chunk)
      if n < block:  # keep one compiled shape
        chunk = np.concatenate(
            [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
      x = embed(params, jnp.asarray(chunk))
      for i in range(shape['num_hidden_layers']):
        x = layer(enc[f'self_attention_{i}'], enc[f'attention_wrapper_{i}'],
                  enc[f'ffn_{i}'], enc[f'ffn_wrapper_{i}'], x)
      out.append(np.asarray(head(params, x))[:n])
  return np.concatenate(out)
