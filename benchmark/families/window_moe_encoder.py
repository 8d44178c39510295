"""Family `window_moe_encoder`: the block of a public 12B sparse-expert model
with 2.5B active parameters (`mellum` architecture: a sequential pre-RMSNorm
block; 32 query heads over 4 key-value heads of 128 without q/k norm, gate
or bias; the layer types LISTED in `layer_types`, three sliding_attention
layers to one full_attention layer, each type with its own rotation in
`rope_parameters`, the default law on the window layers and YaRN with its
attention factor on the full ones; 64 routed experts of width 896, 8 a
token, scored by a softmax and renormalised; no shared expert; hidden 2304)
behind this system's pile-up embedding and 5-way head, as the program's
preset `transformer_learn_values_window_moe` serves it, every expert on this
chip.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test; it shares the helpers of parallel_moe_encoder.py where
the mathematics is the same (SwiGLU, the routed experts' loop, embedding,
rounding, the router's balancing rule).

RMS(x, w) = x * rsqrt(mean(x^2) + eps) * w, float32 inside, plain weights.
Per window (x [L, H] from the condenser, positions 0..L-1) a layer is
h = x + attn_n(RMS_1(x)), out = h + moe(RMS_2(h)); a final RMS.

Attention, as published (N query heads over K key-value heads of D):
q = u W_q, k = u W_k, v = u W_v; q and k rotated by position over halves
(i, i + D/2) of the whole head (Llama-style rotate-half, the program's
order too: no column is permuted), with cos and sin of the layer type's
rope (`rope_tables`); in a window layer (`W`) position i attends to j only
where |i - j| < sliding_window (both ways: an encoder has no causal mask);
k and v repeated to the query heads (head h reads key-value head
h // (N / K)); softmax(q_h k_h^T * D^-1/2) v_h; concat_h W_o. The window
mask is built always, also where it masks nothing.

YaRN (full_attention), from the published formula and on its own:
c(r) = D ln(P / (2 pi r)) / (2 ln theta) over the original P positions;
low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), D - 1);
ramp_i = clamp((i - low) / (high - low), 0, 1); inv_i = theta^(-2i/D) ((1 -
ramp_i) + ramp_i / factor); cos and sin multiplied by the attention factor.

Feed-forward: p = softmax(u W_r) over all E in float32, top = the k largest,
renormalised over their sum; moe(u) = sum over the top-k experts this chip
holds, [first, first + held), of p_e expert_e(u), each a SwiGLU of width
896; nothing else is added.

Weights from the seed (`make_params`), so that every part counts in the
logits: matmul kernels uniform with variance 1/fan_in; norm weights uniform
[0.5, 1.5); embeddings normal with std E**-0.5 as published for the pile-up
model, the head Glorot uniform with a bias of std 0.02. The router is drawn
at ROUTER_SCALE times every other kernel's spread and then balanced as
training balances one (`balance_routers`): each column made orthogonal to
the mean of the tokens its layer routes. All leaves bfloat16, which is what
the preset's `inference_dtype` leaves resident; the reference upcasts them,
one projection or one expert at a time, so the rounding of the weights is
not part of what is compared.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import parallel_moe_encoder as shared
from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = shared.VOCAB
SN_ROWS = shared.SN_ROWS
WEIGHT_BYTES = shared.WEIGHT_BYTES  # bfloat16 leaves
LAYER_WINDOW, LAYER_FULL = 'W', 'F'  # the letters of `layer_pattern`
FFN_EXPERTS = 'E'  # the letter of an expert layer in `ffn_pattern`
LAYER_TYPES = {'sliding_attention': LAYER_WINDOW, 'full_attention': LAYER_FULL}
MLP_LAYER_TYPES = {'sparse': FFN_EXPERTS}
# A router's logits have this standard deviation (other kernels' products
# have 1), as the softmax router of gdn_moe_encoder.py draws its own: the
# first of a token's eight experts then carries a good part of its weight
# and the eighth a few hundredths, as a trained router's do; at 1 all eight
# would weigh about an eighth and the comparison would count near-ties.
ROUTER_SCALE = 3.0
TURN_BYTES = shared.TURN_BYTES
# The published depth.
PUBLISHED_LAYERS = 28
CALIBRATION_WINDOWS = shared.CALIBRATION_WINDOWS
CALIBRATION_TRAFFIC = shared.CALIBRATION_TRAFFIC

SIZE_KEYS = ('num_hidden_layers', 'num_hidden_layers_published',
             'hidden_size', 'num_attention_heads', 'num_key_value_heads',
             'head_dim', 'rms_norm_eps', 'sliding_window', 'rope_parameters',
             'layer_types', 'layer_pattern', 'ffn_pattern', 'num_experts',
             'num_experts_published', 'experts_held', 'num_experts_per_tok',
             'moe_intermediate_size', 'norm_topk_prob', 'max_passes',
             'max_length', 'total_rows', 'condense_input_size', 'embedding',
             'PW_MAX', 'IP_MAX', 'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def pattern_of(names, letters=LAYER_TYPES) -> str:
  """A listed pattern (`layer_types`, `mlp_layer_types`) as letters."""
  return ''.join(letters[name] for name in names)


def stated(params) -> dict:
  """The program's sizes under the file's keys: the published
  config.json's names for what it publishes, the program's own for the
  rest. The program holds the lists of the layers it runs
  (`layer_types_as_run`, `mlp_layer_types_as_run`); the file's
  `layer_types` is the published list, whose first layers they are (the
  family's tests hold the two against each other)."""
  first = params.experts_held_first
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'num_hidden_layers': params.num_hidden_layers,
      'num_hidden_layers_published': PUBLISHED_LAYERS,
      'hidden_size': params.hidden_size,
      'num_attention_heads': params.num_heads,
      'num_key_value_heads': params.num_kv_heads,
      'head_dim': params.head_dim,
      'rms_norm_eps': params.rms_norm_eps,
      'sliding_window': params.sliding_window,
      'rope_parameters': params.rope_parameters.to_dict(),
      'layer_types_as_run': list(params.layer_types),
      'mlp_layer_types_as_run': list(params.mlp_layer_types),
      'layer_pattern': pattern_of(params.layer_types),
      'ffn_pattern': pattern_of(params.mlp_layer_types, MLP_LAYER_TYPES),
      'num_experts': params.experts_held_count,
      'num_experts_published': params.num_experts,
      'experts_held': [first, first + params.experts_held_count],
      'num_experts_per_tok': params.num_experts_per_tok,
      'moe_intermediate_size': params.moe_intermediate_size,
      'num_shared_experts': params.num_shared_experts,
      'shared_expert_intermediate_size':
          params.shared_expert_intermediate_size,
      'norm_topk_prob': params.norm_topk_prob,
      'router_scoring': params.router_scoring,
      'router_selection_bias': params.router_selection_bias,
      'routed_scaling_factor': params.routed_scaling_factor,
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


def _sizes(shape: dict):
  """(H, query heads, key-value heads, D, E, held, F)."""
  first, end = shape['experts_held']
  assert end - first == shape['num_experts']
  return (shape['hidden_size'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['num_experts_published'], shape['num_experts'],
          shape['moe_intermediate_size'])


def expert_layers(shape: dict) -> int:
  return shape['ffn_pattern'].count(FFN_EXPERTS)


# ------------------------------------------------------------------ the tree

def layer_specs(shape: dict, n: int):
  """The leaves of layer n: two norms, the attention, the router and the
  held experts (no shared expert)."""
  h, heads, kv_heads, d, n_experts, held, f = _sizes(shape)
  att, moe = ('encoder', f'self_attention_{n}'), ('encoder', f'moe_{n}')
  norm = lambda wrapper: (('encoder', f'{wrapper}_{n}', 'rms_norm', 'scale'),
                          (h,), 'norm', 0)
  return [
      norm('attention_wrapper'),
      (att + ('query', 'kernel'), (h, heads, d), 'fan_in', h),
      (att + ('key', 'kernel'), (h, kv_heads, d), 'fan_in', h),
      (att + ('value', 'kernel'), (h, kv_heads, d), 'fan_in', h),
      (att + ('output_transform', 'kernel'), (heads, d, h), 'fan_in',
       heads * d),
      norm('ffn_wrapper'),
      (moe + ('router', 'kernel'), (h, n_experts), 'router', h),
      (moe + ('experts_gate',), (held, h, f), 'fan_in', h),
      (moe + ('experts_up',), (held, h, f), 'fan_in', h),
      (moe + ('experts_down',), (held, f, h), 'fan_in', f),
  ]


def leaf_specs(shape: dict):
  """(path, shape, kind, fan_in) for every leaf, in a fixed order."""
  h = shape['hidden_size']
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
    specs += layer_specs(shape, n)
  return specs


def _draw(key, shp, kind, fan):
  if kind == 'router':
    lim = ROUTER_SCALE * math.sqrt(3.0 / fan)
    return jax.random.uniform(key, shp, jnp.float32, -lim, lim)
  return shared._draw(key, shp, kind, fan)


def make_params(shape: dict, seed: int):
  """The parameter tree on the device, every leaf bfloat16: drawn from the
  seed, then the routers balanced on calibration windows from the same
  seed."""
  from benchmark.generators import pileup_windows

  p = shape['max_passes']
  windows = pileup_windows.make_windows(
      CALIBRATION_WINDOWS, seed=seed, max_passes=p,
      length=shape['max_length'],
      **dict(CALIBRATION_TRAFFIC, passes_min=min(3, p), passes_max=min(20, p)))
  return balance_routers(draw_params(shape, seed), windows, shape)


def draw_params(shape: dict, seed: int):
  """The tree as drawn: one jitted call, one key per leaf, each leaf drawn
  in float32 and rounded once."""
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


def layer_counts(shape: dict) -> dict:
  """Parameters of the parts of a layer: the attention, the two norms, the
  router, one routed expert."""
  h, heads, kv_heads, d, n_experts, _held, f = _sizes(shape)
  return {'attention': 2 * h * d * (heads + kv_heads), 'norms': 2 * h,
          'router': h * n_experts, 'expert': 3 * h * f}


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, the rotation, the router's softmax, the top-k and the attention's
  softmax count as nothing; the scores are counted over the whole window
  (the window of 1,024 masks nothing at this length). The routed experts
  are counted at the mean share of a token's k assignments that falls on
  held experts, k x held / E; what a run really routed is `moe_work`'s."""
  length = shape['max_length']
  h, heads, kv_heads, d, n_experts, held, f = _sizes(shape)
  layers = shape['num_hidden_layers']
  held_a_token = shape['num_experts_per_tok'] * held / n_experts
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'attention_projections': layers * 2 * length * h * d * (
          2 * heads + 2 * kv_heads),
      'gqa_scores': layers * 2 * length * length * heads * d,
      'gqa_values': layers * 2 * length * length * heads * d,
      'router': layers * 2 * length * h * n_experts,
      'experts': int(layers * 2 * length * held_a_token * 3 * h * f),
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


def turns_a_pack(shape: dict, assignments: int) -> int:
  """In how many turns the program takes a pack's assignments: the fewest
  halvings that bring one [rows, hidden] bfloat16 buffer within
  TURN_BYTES."""
  row_bytes = shape['hidden_size'] * WEIGHT_BYTES
  turns = 1
  while assignments // turns * row_bytes > TURN_BYTES:
    turns *= 2
  return turns


def moe_work(shape: dict, positions: int, assignments_held: int,
             packs: int) -> dict:
  """{'flops', 'bytes'} of the routed experts as device scope `moe` covers
  them (router, dispatch, grouped products, combine), all layers together,
  for `positions` routed positions of which `assignments_held` (token,
  expert) pairs fell on held experts, over `packs` packs: the router's
  product and three products an assignment; the stream in and out, the
  router's bfloat16 weights once a pack and the held experts' once a turn,
  as the program takes a pack. The sorted copy of the tokens is the
  program's choice and counts no bytes."""
  h, _heads, _kv, _d, n_experts, held, f = _sizes(shape)
  layers = expert_layers(shape)
  turns = packs * turns_a_pack(
      shape, positions // packs * shape['num_experts_per_tok'])
  return {
      'flops': (layers * positions * 2 * h * n_experts
                + assignments_held * 3 * 2 * h * f),
      'bytes': WEIGHT_BYTES * layers * (
          turns * held * 3 * h * f + packs * h * n_experts
          + 2 * positions * h),
  }


def part_work(shape: dict, batch: int, part: str) -> dict:
  """{'flops', 'bytes'} one pack needs of one part of the block, all
  layers together, as the device scope of that name covers it:

  'gqa'     the attention operator alone (scope `softmax`): the score
            product and the values, 2 L^2 D multiply-adds a query head
            each; q, k, v in and o out, once, in bfloat16; the [L, L]
            scores are the algorithm's temporaries and count no bytes.
  'rotary'  the rotation of q and k (scope `rotary`): elementwise, so no
            matmul FLOPs by this file's count; q and k read and written
            once in bfloat16, the tables a constant.
  'moe'     the routed experts at the mean held share (`moe_work`)."""
  length = shape['max_length']
  h, heads, kv_heads, d, n_experts, held, f = _sizes(shape)
  layers = shape['num_hidden_layers']
  positions = batch * length
  flops = flops_per_window(shape)
  if part == 'gqa':
    per_position = WEIGHT_BYTES * d * (2 * heads + 2 * kv_heads)
    return {'flops': batch * (flops['gqa_scores'] + flops['gqa_values']),
            'bytes': layers * positions * per_position}
  if part == 'rotary':
    return {'flops': 0, 'bytes': layers * positions * 2 * WEIGHT_BYTES * d
            * (heads + kv_heads)}
  if part == 'moe':
    return moe_work(
        shape, positions,
        layers * positions * shape['num_experts_per_tok'] * held // n_experts,
        1)
  raise KeyError(part)


# ------------------------------------------------------------- the reference

def rms_norm(x, w, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def yarn_range(d: int, rope: dict):
  """(low, high) of the published YaRN correction range."""
  theta = float(rope['rope_theta'])
  c = lambda r: d * math.log(
      rope['original_max_position_embeddings'] / (2 * math.pi * r)) / (
          2 * math.log(theta))
  return (max(math.floor(c(rope['beta_fast'])), 0),
          min(math.ceil(c(rope['beta_slow'])), d - 1))


def rope_tables(rope: dict, length: int, d: int, *, interpolate=True,
                attention_factor=True):
  """(cos, sin) [L, D] float32 of one layer type's `rope_parameters`
  entry, for rotate-half. Faults: `interpolate` False (YaRN's magnitude
  without its interpolation), `attention_factor` False (its interpolation
  at magnitude 1)."""
  theta = float(rope['rope_theta'])
  inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
  magnitude = 1.0
  if rope['rope_type'] == 'yarn':
    low, high = yarn_range(d, rope)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    if interpolate:
      inv = inv * (1.0 - ramp) + inv / rope['factor'] * ramp
    if attention_factor:
      magnitude = rope['attention_factor']
  elif rope['rope_type'] != 'default':
    raise ValueError(rope['rope_type'])
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)
  return ((np.cos(angles) * magnitude).astype(np.float32),
          (np.sin(angles) * magnitude).astype(np.float32))


def rotate_half(x, cos, sin):
  """x [B, L, N, D] -> x cos + rotate_half(x) sin."""
  half = x.shape[-1] // 2
  turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
  return x * cos[None, :, None, :] + turned * sin[None, :, None, :]


def grouped_attention(w, u, *, tables, window, rd):
  """The attention on the normed stream u [B, L, H], as published: q and k
  rotated by `tables` (cos, sin), k and v repeated to the query heads, one
  softmax a head. `window`: positions |i - j| < window alone are attended
  (None: a full layer), the mask built whether or not it masks anything."""
  w = shared._f32(w)
  q = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['query']['kernel']))
  k = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['key']['kernel']))
  v = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['value']['kernel']))
  cos, sin = tables
  q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', rd(q), rd(k)) * q.shape[-1] ** -0.5
  if window is not None:
    i = np.arange(u.shape[1])
    near = np.abs(i[:, None] - i[None, :]) < window
    scores = jnp.where(near[None, None], scores, -jnp.inf)
  out = jnp.einsum('bhij,bjhd->bihd', rd(jax.nn.softmax(scores, axis=-1)),
                   rd(v))
  return jnp.einsum('blnd,ndh->blh', rd(out),
                    rd(w['output_transform']['kernel']))


def attention_fn(w, norm_w, x, *, eps: float, precision: str, **layer):
  """attn(RMS_1(x)) for one block of windows (the branch alone)."""
  u = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  return grouped_attention(w, u, rd=shared._rounder(precision), **layer)


def scores_fn(w, norm_w, x, *, eps: float, precision: str):
  """The normed tokens [T, H] and their softmax over all E, float32."""
  rd = shared._rounder(precision)
  n = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  return n, jax.nn.softmax(jnp.matmul(
      rd(n), rd(w['router']['kernel'].astype(jnp.float32))), axis=-1)


def balanced_router(w, norm_w, x, *, eps: float):
  """The layer's router kernel with every column made orthogonal to the
  mean of the normed tokens x [T, H] it is about to route (the rule of
  parallel_moe_encoder.py, under this block's RMSNorm)."""
  n = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  mean = jnp.mean(n, axis=0)
  kernel = w['router']['kernel'].astype(jnp.float32)
  kernel = kernel - jnp.outer(mean, mean @ kernel) / jnp.dot(mean, mean)
  return kernel.astype(w['router']['kernel'].dtype)


def head_fn(params, x, *, eps: float):
  """Final RMSNorm and the 5-way head: float32 whatever the compute type,
  as the program keeps it, so no rounding."""
  x = rms_norm(x, params['encoder']['output_normalization']['scale'].astype(
      jnp.float32), eps)
  return jnp.matmul(x, params['logits']['kernel'].astype(
      jnp.float32)) + params['logits']['bias'].astype(jnp.float32)


def balance_routers(params, windows: np.ndarray, shape: dict):
  """The tree with its routers balanced, layer after layer, on what the
  plain reference makes of `windows` up to each layer."""
  return reference_forward(params, windows, shape, balance=True)[2]


def reference_forward(params, windows: np.ndarray, shape: dict,
                      precision: str = 'float32', block: int = 32,
                      balance: bool = False, parallel: bool = False,
                      no_attention_factor: bool = False,
                      full_default_rope: bool = False,
                      not_renormalised: bool = False,
                      no_interpolation: bool = False):
  """(logits [S, L, 5], assignments [layers, held], the tree) of the plain
  reference; `reference_logits` says how. `balance` replaces each layer's
  router by `balanced_router` on its own tokens before it routes them, and
  the tree returned is the balanced one."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  eps = float(shape['rms_norm_eps'])
  length, d = shape['max_length'], shape['head_dim']
  ropes = shape['rope_parameters']
  tables = {
      LAYER_WINDOW: rope_tables(ropes['sliding_attention'], length, d),
      # The faults of the full layers' rotation: the window layers' rope,
      # YaRN without its factor, YaRN's factor without its interpolation.
      LAYER_FULL: rope_tables(
          ropes['sliding_attention' if full_default_rope else
                'full_attention'], length, d,
          interpolate=not no_interpolation,
          attention_factor=not no_attention_factor)}
  embed = jax.jit(functools.partial(shared.embed_fn, max_passes=p,
                                    precision=precision))
  attention = {
      letter: jax.jit(functools.partial(
          attention_fn, eps=eps, precision=precision, tables=tables[letter],
          window=int(shape['sliding_window']) if letter == LAYER_WINDOW
          else None))
      for letter in (LAYER_WINDOW, LAYER_FULL)}
  scores_of = jax.jit(functools.partial(scores_fn, eps=eps,
                                        precision=precision))
  weights_of = jax.jit(functools.partial(
      shared.weights_fn, top_k=shape['num_experts_per_tok'],
      renormalise=bool(shape['norm_topk_prob']) and not not_renormalised))
  expert = jax.jit(functools.partial(shared.expert_fn, precision=precision))
  head = jax.jit(functools.partial(head_fn, eps=eps))
  balanced = jax.jit(functools.partial(balanced_router, eps=eps))
  enc = dict(params['encoder'])
  n_windows, short = len(rows), -len(rows) % block
  if short:  # keep one compiled shape
    rows = np.concatenate(
        [rows, np.zeros((short,) + rows.shape[1:], np.float32)])
  blocks = range(0, len(rows), block)
  in_blocks = lambda fn, x, *w: np.concatenate(
      [np.asarray(fn(*w, jnp.asarray(x[lo:lo + block]))) for lo in blocks])
  counts = []
  with jax.default_matmul_precision('highest'):
    x = in_blocks(embed, rows, params)
    for i, letter in enumerate(shape['layer_pattern']):
      attended = in_blocks(attention[letter], x, enc[f'self_attention_{i}'],
                           enc[f'attention_wrapper_{i}'])
      h = x + attended
      # The experts see every token of the sample at once, the padding
      # windows left out: an expert then has rows enough to count. In the
      # sequential block they read what the attention left (`parallel`, a
      # fault: the layer's input, as a parallel block's would).
      moe, norm_w = enc[f'moe_{i}'], enc[f'ffn_wrapper_{i}']
      source = x if parallel else h
      tokens = jnp.asarray(source[:n_windows].reshape(-1, x.shape[-1]))
      if balance:
        moe = enc[f'moe_{i}'] = dict(moe, router={'kernel': balanced(
            moe, norm_w, tokens)})
      n, scores = scores_of(moe, norm_w, tokens)
      top_p, top_e = weights_of(scores)
      routed, took = shared.routed_experts(moe, n, top_p, top_e,
                                           shape['experts_held'][0], expert)
      if balance:
        load = np.bincount(np.asarray(top_e).ravel(),
                           minlength=scores.shape[1])
        print(f'family: layer {i} balanced on {len(tokens)} tokens: load '
              f'max/mean {load.max() / load.mean():.3f} over all, '
              f'{took.max() / max(took.mean(), 1):.3f} over the held',
              file=sys.stderr, flush=True)
      x = h
      x[:n_windows] += np.asarray(routed).reshape(
          (n_windows,) + x.shape[1:])
      counts.append(took)
    logits = in_blocks(head, x, params)
  return logits[:n_windows], np.stack(counts), dict(params, encoder=enc)


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 32, **faults):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]:
  plain float32 under `jax.default_matmul_precision('highest')`, input
  clipping included; embedding, attention and head in blocks of windows,
  the experts of a layer over all the sample's tokens, one expert at a
  time. `precision` 'bfloat16' or 'fp8' rounds every matmul operand
  (activations and weights; for the attention q and the repeated keys after
  their rotation, the softmax weights and v) to that type before a
  float32-accumulated product; the router's softmax and weights, the
  rotation, the softmax and every norm stay float32. `faults` (parallel:
  the experts read the layer's input and not what the attention left;
  no_attention_factor: YaRN at magnitude 1; full_default_rope: the full
  layers rotated with the window layers' rope; not_renormalised: the top-k
  weights as the softmax gave them; no_interpolation: YaRN's magnitude
  without its interpolation) are for the tests that show what the
  comparison sees."""
  return reference_forward(params, windows, shape, precision, block,
                           **faults)[0]
