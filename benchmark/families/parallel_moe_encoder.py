"""Family `parallel_moe_encoder`: the block of a public 218B sparse-expert
language model with 25B active parameters (`cohere2_moe` architecture: a
parallel block, attention and feed-forward both behind ONE LayerNorm without
a bias and added to the stream together; 128 query heads over 8 key-value
heads of 128 without q/k norm or gate; three window layers that rotate the
whole head to one full layer that has no positions at all; 128 routed
experts of width 4096, 8 a token, scored by a sigmoid without a bias; four
shared experts that are averaged; hidden 4096) behind this system's pile-up
embedding and 5-way head, as the program's preset
`transformer_learn_values_parallel_moe` serves it, an eighth of each layer's
experts on this chip.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test.

LN(x, w) = (x - mean(x)) * rsqrt(var(x) + eps) * w, float32 inside, no
bias. Per window (x [L, H] from the condenser, positions 0..L-1) a layer is
out = x + attn_n(u) + ffn(u), u = LN(x); a final LN.

Attention, as published (N query heads over K key-value heads of D):
q = u W_q, k = u W_k, v = u W_v; in a window layer (`W` of `layer_pattern`)
q and k rotated by position over interleaved pairs (2i, 2i + 1) of the whole
head, and position i attends to j only where |i - j| < sliding_window (both
ways: an encoder has no causal mask); in a full layer (`F`) neither
rotation nor mask; k and v repeated to the query heads (head h reads
key-value head h // (N / K)); softmax(q_h k_h^T * D^-1/2) v_h; concat_h W_o.
The window mask is built always, also where it masks nothing.

Feed-forward: s = sigmoid(u W_r) over all E, top = the k largest of s,
p_e = s_e / sum_top s, ffn(u) = sum over the top-k experts this chip holds,
[first, first + held), of p_e expert_e(u), plus the MEAN of the m shared
experts, each run on its own and each a SwiGLU of the expert width, as is
every expert. The program holds the m shared experts as one SwiGLU of m x
the width times 1 / m: columns [s F, (s + 1) F) of its gate and up leaves
and the same rows of its down leaf are shared expert s.

The program rotates halves (i, i + D / 2) and its leaves hold the columns
of every head of W_q and W_k of a window layer in that order;
`published_order` puts them back in the published order (pairs) before the
reference computes anything, so the reference is the published arithmetic
on the published layout of the same weights.

Weights from the seed (`make_params`), so that every part counts in the
logits: matmul kernels uniform with variance 1/fan_in (each residual
branch then has an RMS of the order of the stream's); norm weights uniform
[0.5, 1.5); embeddings normal with std E**-0.5 as published for the
pile-up model, the head Glorot uniform with a bias of std 0.02. The router
is drawn like every other kernel (ROUTER_SCALE) and then balanced as
training balances one (`balance_routers`): each column made orthogonal to
the mean of the tokens its layer routes; the model has no selection bias,
so none is set. All leaves bfloat16, which is what the preset's
`inference_dtype` leaves resident; the reference upcasts them, one
projection or one expert at a time, so the rounding of the weights is not
part of what is compared.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = 5
SN_ROWS = 4
WEIGHT_BYTES = 2  # bfloat16 leaves
LAYER_WINDOW, LAYER_FULL = 'W', 'F'  # the letters of `layer_pattern`
FFN_EXPERTS = 'E'  # the letter of an expert layer in `ffn_pattern`
LAYER_TYPES = {LAYER_WINDOW: 'sliding_attention', LAYER_FULL: 'full_attention'}
# A router's logits have the standard deviation of every other kernel's
# product, 1: the eight chosen of 128 then score about 0.8-0.95, a ninth a
# few hundredths under the eighth, and the eight weigh about an eighth
# each, as the sigmoid family of the benchmark's other cell draws its own.
ROUTER_SCALE = 1.0
# The program takes a pack's (token, expert) assignments a turn at a time,
# as many as keep one [rows, hidden] bfloat16 buffer within this, halving
# the pack until they do, and reads the held experts' weights once a turn.
TURN_BYTES = 1 << 30
# The published depth, at which `stated` derives `layer_types`.
PUBLISHED_LAYERS = 32

SIZE_KEYS = ('num_hidden_layers', 'num_hidden_layers_published',
             'hidden_size', 'num_attention_heads', 'num_key_value_heads',
             'head_dim', 'rope_theta', 'layer_norm_eps', 'sliding_window',
             'layer_switch', 'layer_pattern', 'ffn_pattern', 'layer_types',
             'num_experts', 'num_experts_published', 'experts_held',
             'num_experts_per_tok', 'intermediate_size',
             'num_shared_experts', 'shared_expert_combination_strategy',
             'norm_topk_prob', 'expert_selection_fn', 'first_k_dense_replace',
             'max_passes', 'max_length', 'total_rows', 'condense_input_size',
             'embedding', 'PW_MAX', 'IP_MAX', 'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def pattern_of(layers: int, switch: int) -> str:
  """The derived rule: layer n is a full layer where (n + 1) % layer_switch
  == 0 (`order_of_interleaved_layers` local_attn_first)."""
  return ''.join(LAYER_FULL if (n + 1) % switch == 0 else LAYER_WINDOW
                 for n in range(layers))


def stated(params) -> dict:
  """The program's sizes under the file's keys: the published
  config.json's names for what it publishes, the program's own for the
  rest. `num_experts` is what runs (the experts held), as `reduced` says;
  the router's width is `num_experts_published`. `layer_types` is the
  derived rule at the PUBLISHED depth, so that the file's copy of the
  published list is held against the rule the program derives its pattern
  by."""
  first = params.experts_held_first
  layers, switch = params.num_hidden_layers, params.layer_switch
  published = PUBLISHED_LAYERS
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'use_parallel_block': True,
      'num_hidden_layers': layers,
      'num_hidden_layers_published': published,
      'hidden_size': params.hidden_size,
      'num_attention_heads': params.num_heads,
      'num_key_value_heads': params.num_kv_heads,
      'head_dim': params.head_dim,
      'rope_theta': params.rope_theta,
      'layer_norm_eps': params.layer_norm_eps,
      'sliding_window': params.sliding_window,
      'layer_switch': switch,
      'layer_pattern': pattern_of(layers, switch),
      'ffn_pattern': FFN_EXPERTS * layers,
      'layer_types': [LAYER_TYPES[c] for c in pattern_of(published, switch)],
      'first_k_dense_replace': params.first_k_dense_replace,
      'num_experts': params.experts_held_count,
      'num_experts_published': params.num_experts,
      'experts_held': [first, first + params.experts_held_count],
      'num_experts_per_tok': params.num_experts_per_tok,
      'intermediate_size': params.moe_intermediate_size,
      'num_shared_experts': params.num_shared_experts,
      'shared_expert_combination_strategy': params.shared_expert_combination,
      'shared_expert_intermediate_size':
          params.shared_expert_intermediate_size,
      'shared_expert_gated': params.shared_expert_gated,
      'norm_topk_prob': params.norm_topk_prob,
      'expert_selection_fn': params.router_scoring,
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
  """(H, query heads, key-value heads, D, E, held, F, shared experts)."""
  first, end = shape['experts_held']
  assert end - first == shape['num_experts']
  return (shape['hidden_size'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['num_experts_published'], shape['num_experts'],
          shape['intermediate_size'], shape['num_shared_experts'])


def expert_layers(shape: dict) -> int:
  return shape['ffn_pattern'].count(FFN_EXPERTS)


# ------------------------------------------------------------------ the tree

def attention_specs(shape: dict, n: int):
  h, heads, kv_heads, d, *_ = _sizes(shape)
  att = ('encoder', f'self_attention_{n}')
  return [
      (att + ('query', 'kernel'), (h, heads, d), 'fan_in', h),
      (att + ('key', 'kernel'), (h, kv_heads, d), 'fan_in', h),
      (att + ('value', 'kernel'), (h, kv_heads, d), 'fan_in', h),
      (att + ('output_transform', 'kernel'), (heads, d, h), 'fan_in',
       heads * d),
  ]


def swiglu_specs(path: tuple, h: int, width: int):
  return [
      (path + ('gate_layer', 'kernel'), (h, width), 'fan_in', h),
      (path + ('up_layer', 'kernel'), (h, width), 'fan_in', h),
      # Of a shared expert's own width: the program's leaf stacks the m of
      # them, and each is drawn as it would be alone.
      (path + ('output_layer', 'kernel'), (width, h), 'fan_in', width),
  ]


def ffn_specs(shape: dict, n: int):
  h, _heads, _kv, _d, n_experts, held, f, n_shared = _sizes(shape)
  moe = ('encoder', f'moe_{n}')
  shared = swiglu_specs(moe + ('shared_expert',), h, n_shared * f)
  # The down leaf's fan-in is one shared expert's width, not the stack's.
  shared[-1] = shared[-1][:3] + (f,)
  return [
      (moe + ('router', 'kernel'), (h, n_experts), 'router', h),
      (moe + ('experts_gate',), (held, h, f), 'fan_in', h),
      (moe + ('experts_up',), (held, h, f), 'fan_in', h),
      (moe + ('experts_down',), (held, f, h), 'fan_in', f),
  ] + shared


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
    specs.append((('encoder', f'block_norm_{n}', 'scale'), (h,), 'norm', 0))
    specs += attention_specs(shape, n)
    specs += ffn_specs(shape, n)
  return specs


def _draw(key, shp, kind, fan):
  uniform = lambda lo, hi: jax.random.uniform(key, shp, jnp.float32, lo, hi)
  if kind == 'embed':
    return jax.random.normal(key, shp, jnp.float32) * shp[1] ** -0.5
  if kind == 'fan_in':
    lim = math.sqrt(3.0 / fan)
    return uniform(-lim, lim)
  if kind == 'router':
    lim = ROUTER_SCALE * math.sqrt(3.0 / fan)
    return uniform(-lim, lim)
  if kind == 'glorot':
    lim = math.sqrt(6.0 / fan)
    return uniform(-lim, lim)
  if kind == 'bias':
    return jax.random.normal(key, shp, jnp.float32) * 0.02
  if kind == 'norm':
    return uniform(0.5, 1.5)
  raise ValueError(kind)


# The windows the routers are balanced on: the generator of the cells'
# traffic at its parameters (benchmark/traffic/window_stream*.json), from
# the seed; pass counts no higher than the shape holds.
CALIBRATION_WINDOWS = 32
CALIBRATION_TRAFFIC = dict(
    passes_min=3, passes_max=20, error_rate=0.1, insert_col_rate=0.08,
    partial_pass_rate=0.15, kinetics_mean=30.0, sn_min=4.0, sn_max=20.0)


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
  """Parameters of the parts of a layer: the attention, the one norm, the
  router, the shared experts together, one routed expert."""
  h, *_, f, n_shared = _sizes(shape)
  count = lambda specs: sum(math.prod(shp) for _p, shp, _k, _f in specs)
  return {'attention': count(attention_specs(shape, 0)), 'norm': h,
          'router': h * shape['num_experts_published'],
          'shared_experts': n_shared * 3 * h * f, 'expert': 3 * h * f}


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, rotary, the sigmoid, the top-k and the softmax count as nothing;
  the scores are counted over the whole window (the window of 4,096 masks
  nothing at this length). The routed experts are counted at the mean
  share of a token's k assignments that falls on held experts, k x held /
  E; what a run really routed is `moe_work`'s."""
  length = shape['max_length']
  h, heads, kv_heads, d, n_experts, held, f, n_shared = _sizes(shape)
  layers = shape['num_hidden_layers']
  held_a_token = shape['num_experts_per_tok'] * held / n_experts
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'attention_projections': layers * 2 * length * h * d * (
          2 * heads + 2 * kv_heads),
      'gqa_scores': layers * 2 * length * length * heads * d,
      'gqa_values': layers * 2 * length * length * heads * d,
      'router': layers * 2 * length * h * n_experts,
      'shared_experts': layers * 2 * length * 3 * h * n_shared * f,
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
  them (router, dispatch, grouped products, combine; not the shared
  experts), all layers together, for `positions` routed positions of
  which `assignments_held` (token, expert) pairs fell on held experts,
  over `packs` packs: the router's product and three products an
  assignment; the stream in and out, the router's bfloat16 weights once a
  pack and the held experts' once a turn, as the program takes a pack. The
  sorted copy of the tokens is the program's choice and counts no bytes,
  nor do the rows of assignments held elsewhere."""
  h, _heads, _kv, _d, n_experts, held, f, _m = _sizes(shape)
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

  'gqa'            the attention operator alone (scope `softmax`): the
                   score product and the values, 2 L^2 D multiply-adds a
                   query head each; q, k, v in and o out, once, in
                   bfloat16; the [L, L] scores are the algorithm's
                   temporaries and count no bytes.
  'shared_expert'  the shared experts (scope `shared_expert`): three
                   products of the hidden size by m x the expert width;
                   their weights once a pack, the stream in and out.
  'moe'            the routed experts at the mean held share
                   (`moe_work`)."""
  length = shape['max_length']
  h, heads, kv_heads, d, n_experts, held, f, n_shared = _sizes(shape)
  layers = shape['num_hidden_layers']
  positions = batch * length
  flops = flops_per_window(shape)
  if part == 'gqa':
    per_position = WEIGHT_BYTES * d * (2 * heads + 2 * kv_heads)
    return {'flops': batch * (flops['gqa_scores'] + flops['gqa_values']),
            'bytes': layers * positions * per_position}
  if part == 'shared_expert':
    return {'flops': batch * flops['shared_experts'],
            'bytes': WEIGHT_BYTES * layers * (
                n_shared * 3 * h * f + 2 * positions * h)}
  if part == 'moe':
    return moe_work(
        shape, positions,
        layers * positions * shape['num_experts_per_tok'] * held // n_experts,
        1)
  raise KeyError(part)


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


def _f32(tree):
  return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


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


def layer_norm(x, w, eps):
  centred = x - jnp.mean(x, axis=-1, keepdims=True)
  return centred * jax.lax.rsqrt(
      jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) * w


def rotary_pairs(x, theta: float):
  """x [B, L, N, D], positions 0..L-1: the published rotation, pair
  (2i, 2i + 1) turned by position * theta**(-2i / D)."""
  length, d = x.shape[1], x.shape[3]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  cos = np.cos(angles).astype(np.float32)[None, :, None, :]
  sin = np.sin(angles).astype(np.float32)[None, :, None, :]
  even, odd = x[..., 0::2], x[..., 1::2]
  return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                   axis=-1).reshape(x.shape)


def published_order(columns):
  """[..., D] columns of a head in the program's order (halves: i, i + D/2)
  -> in the published one (pairs: 2i, 2i + 1)."""
  half = columns.shape[-1] // 2
  return jnp.stack([columns[..., :half], columns[..., half:]],
                   axis=-1).reshape(columns.shape)


def grouped_attention(w, u, *, rotated: bool, window, theta: float, rd):
  """The attention on the normed stream u [B, L, H], as published: k and v
  repeated to the query heads, one softmax a head. `rotated`: a window
  layer's rotation of q and k; `window`: positions |i - j| < window alone
  are attended (None: a full layer), the mask built whether or not it
  masks anything."""
  w = _f32(w)
  w_q, w_k = w['query']['kernel'], w['key']['kernel']
  if rotated:
    w_q, w_k = published_order(w_q), published_order(w_k)
  q = jnp.einsum('blh,hnd->blnd', rd(u), rd(w_q))
  k = jnp.einsum('blh,hnd->blnd', rd(u), rd(w_k))
  v = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['value']['kernel']))
  if rotated:
    q, k = rotary_pairs(q, theta), rotary_pairs(k, theta)
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
  """attn(LN(x)) for one block of windows (the branch alone: the parallel
  block adds it to the stream together with the feed-forward's)."""
  u = layer_norm(x, norm_w['scale'].astype(jnp.float32), eps)
  return grouped_attention(w, u, rd=_rounder(precision), **layer)


def swiglu(gate, up, down, n, rd):
  pick = lambda w: rd(w.astype(jnp.float32))
  return jnp.matmul(
      rd(jax.nn.silu(jnp.matmul(rd(n), pick(gate)))
         * jnp.matmul(rd(n), pick(up))), pick(down))


def shared_fn(w, n, s: int, *, count: int, precision: str):
  """Shared expert s of `count` on the tokens n [T, H]: its own columns of
  the program's wide gate and up leaves, its own rows of the down leaf."""
  width = w['gate_layer']['kernel'].shape[1] // count
  at = slice(s * width, (s + 1) * width)
  return swiglu(w['gate_layer']['kernel'][:, at],
                w['up_layer']['kernel'][:, at],
                w['output_layer']['kernel'][at], n, _rounder(precision))


def scores_fn(w, norm_w, x, *, eps: float, precision: str):
  """The normed tokens [T, H] and their sigmoid scores over all E."""
  rd = _rounder(precision)
  n = layer_norm(x, norm_w['scale'].astype(jnp.float32), eps)
  return n, jax.nn.sigmoid(
      jnp.matmul(rd(n), rd(w['router']['kernel'].astype(jnp.float32))))


def weights_fn(scores, *, top_k: int, renormalise: bool):
  """(weights, experts) [T, k]: the k largest scores, over their sum."""
  top_p, top_e = jax.lax.top_k(scores, top_k)
  if renormalise:
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
  return top_p, top_e


def expert_fn(gate, up, down, e, n, token, weight, out, *, precision: str):
  """out with weight x expert_e(n[token]) added at rows `token`: expert e
  of the stacked leaves on the rows routed to it; its three matrices are
  upcast here, one expert at a time."""
  y = swiglu(gate[e], up[e], down[e], n[token], _rounder(precision))
  return out.at[token].add(weight[:, None] * y)


def routed_experts(w, n, top_p, top_e, first: int, expert, row_step: int = 128):
  """sum over the held experts of p_e expert_e(n): a plain loop over the
  experts, each on the rows routed to it, gathered and scatter-added where
  the tokens lie (the rows padded to a multiple of `row_step` with row 0 at
  weight zero, so that few shapes compile). -> (float32 [T, H],
  assignments per held expert)."""
  top_p, top_e = np.asarray(top_p), np.asarray(top_e)
  held = w['experts_gate'].shape[0]
  out = jnp.zeros(n.shape, jnp.float32)
  counts = np.zeros(held, np.int64)
  for e in range(held):
    token, slot = np.nonzero(top_e == first + e)
    counts[e] = len(token)
    if not len(token):
      continue
    size = -(-len(token) // row_step) * row_step
    index, weight = np.zeros(size, np.int32), np.zeros(size, np.float32)
    index[:len(token)], weight[:len(token)] = token, top_p[token, slot]
    # A token names an expert at most once: plain indexed addition.
    out = expert(w['experts_gate'], w['experts_up'], w['experts_down'], e, n,
                 index, weight, out)
  return out, counts


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


def head_fn(params, x, *, eps: float):
  """Final norm and the 5-way head: float32 whatever the compute type, as
  the program keeps it, so no rounding."""
  x = layer_norm(x, params['encoder']['output_normalization']['scale'].astype(
      jnp.float32), eps)
  return jnp.matmul(x, params['logits']['kernel'].astype(
      jnp.float32)) + params['logits']['bias'].astype(jnp.float32)


def balanced_router(w, norm_w, x, *, eps: float):
  """The layer's router kernel with every column made orthogonal to the
  mean of the normed tokens x [T, H] it is about to route: the offset
  that the tokens' common direction gives each expert's logit is gone,
  and what ranks the experts is what tells tokens apart."""
  n = layer_norm(x, norm_w['scale'].astype(jnp.float32), eps)
  mean = jnp.mean(n, axis=0)
  kernel = w['router']['kernel'].astype(jnp.float32)
  kernel = kernel - jnp.outer(mean, mean @ kernel) / jnp.dot(mean, mean)
  return kernel.astype(w['router']['kernel'].dtype)


def balance_routers(params, windows: np.ndarray, shape: dict):
  """The tree with its routers balanced, layer after layer, on what the
  plain reference makes of `windows` up to each layer (a router moves
  every later layer's tokens, so each is balanced on the tokens the
  balanced ones before it leave)."""
  return reference_forward(params, windows, shape, balance=True)[2]


def reference_forward(params, windows: np.ndarray, shape: dict,
                      precision: str = 'float32', block: int = 32,
                      balance: bool = False, sequential: bool = False,
                      rotate_full: bool = False, shared_summed: bool = False):
  """(logits [S, L, 5], assignments [layers, held], the tree) of the plain
  reference; `reference_logits` says how. `balance` replaces each layer's
  router by `balanced_router` on its own tokens before it routes them, and
  the tree returned is the balanced one."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  eps = float(shape['layer_norm_eps'])
  theta = float(shape['rope_theta'])
  n_shared = shape['num_shared_experts']
  embed = jax.jit(functools.partial(embed_fn, max_passes=p,
                                    precision=precision))
  attention_of = lambda rotated, window: jax.jit(functools.partial(
      attention_fn, eps=eps, precision=precision, theta=theta,
      rotated=rotated, window=window))
  attention = {
      LAYER_WINDOW: attention_of(True, int(shape['sliding_window'])),
      # `rotate_full`, a fault: rotated as a window layer is (no mask).
      LAYER_FULL: attention_of(rotate_full, None)}
  scores_of = jax.jit(functools.partial(scores_fn, eps=eps,
                                        precision=precision))
  weights_of = jax.jit(functools.partial(
      weights_fn, top_k=shape['num_experts_per_tok'],
      renormalise=bool(shape['norm_topk_prob'])))
  shared_of = jax.jit(functools.partial(shared_fn, count=n_shared,
                                        precision=precision),
                      static_argnums=2)
  expert = jax.jit(functools.partial(expert_fn, precision=precision))
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
      norm_w = enc[f'block_norm_{i}']
      attended = in_blocks(attention[letter], x, enc[f'self_attention_{i}'],
                           norm_w)
      # The experts see every token of the sample at once, the padding
      # windows left out: an expert then has rows enough to count. In the
      # parallel block they read the same normed tokens as the attention
      # (`sequential`, a fault: what the attention left).
      moe = enc[f'moe_{i}']
      source = x[:n_windows] + attended[:n_windows] if sequential else (
          x[:n_windows])
      tokens = jnp.asarray(source.reshape(-1, x.shape[-1]))
      if balance:
        moe = enc[f'moe_{i}'] = dict(moe, router={'kernel': balanced(
            moe, norm_w, tokens)})
      n, scores = scores_of(moe, norm_w, tokens)
      top_p, top_e = weights_of(scores)
      routed, took = routed_experts(moe, n, top_p, top_e,
                                    shape['experts_held'][0], expert)
      shared = shared_of(moe['shared_expert'], n, 0)
      for s in range(1, n_shared):
        shared = shared + shared_of(moe['shared_expert'], n, s)
      if not shared_summed:
        shared = shared / n_shared
      if balance:
        load = np.bincount(np.asarray(top_e).ravel(),
                           minlength=scores.shape[1])
        print(f'family: layer {i} balanced on {len(tokens)} tokens: load '
              f'max/mean {load.max() / load.mean():.3f} over all, '
              f'{took.max() / max(took.mean(), 1):.3f} over the held',
              file=sys.stderr, flush=True)
      x = x + attended
      x[:n_windows] += np.asarray(routed + shared).reshape(
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
  time, the shared experts one by one and averaged. `precision` 'bfloat16'
  or 'fp8' rounds every matmul operand (activations and weights; for the
  attention q, the repeated keys, the softmax weights and v) to that type
  before a float32-accumulated product; the router's sigmoid and weights,
  the rotation, the softmax and every norm stay float32. `faults`
  (sequential=True: the feed-forward reads LN(x + attn) and not the one
  norm's output; rotate_full=True: the full layers rotated;
  shared_summed=True: the shared experts added up, not averaged) are for
  the tests that show the comparison sees them."""
  return reference_forward(params, windows, shape, precision, block,
                           **faults)[0]
