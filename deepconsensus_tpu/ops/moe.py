"""Sparse experts: top-k routing and the products of the experts held here.

A token's feed-forward is the weighted sum of k of E small gated
feed-forwards, chosen by a router:

  p = softmax(n W_r) over all E;  top = the k largest, renormalised
  moe(n) = sum_{e in top} p_e (silu(n W_gate_e) * (n W_up_e)) W_down_e

or, with a sigmoid router and a balancing bias b [E] (`route_top_k`):

  s = sigmoid(n W_r);  top = the k largest of s + b
  p_e = s_e / (sum_{e' in top} s_e' + 1e-20) * scale

where b chooses and weighs nothing.

A process holds a contiguous share [first, first + held) of the experts.
The router keeps its E outputs and its top-k; of a token's k assignments
those that fall on held experts are computed here and the others are
left to the process that holds them (shares add up: tests/
test_moe_share.py). There is no capacity and no dropped token: the (token,
expert) assignments are sorted by expert, the held ones first, and the
three products run as grouped products over `held` ragged groups of rows.
Rows behind the last held group are never computed and never read.

Which grouped product, `grouped_product_path` says from the shapes. On one
TPU, in a trace declared inference for one device, bfloat16 rows whose
count a row tile divides take the repository's Pallas kernel
(ops/grouped_product.py): its grid follows the groups, a group's matrices
stay in VMEM while its row tiles pass, and gate and up are one call that
reads a row tile once and writes silu(gate) * up * weight. Everywhere else
(the CPU, a mesh, export, float32) `jax.lax.ragged_dot` runs, which the TPU
compiler lowers to a grouped matrix-multiply kernel of its own that visits
only the rows its groups cover; on a v5e that lowering ran the published
shapes at 37-38% of the chip's peak (74.6 TFLOP/s where the same program's
plain products reach 159-187; PERF.md, PR 34), which is why it was
replaced there.

The combine adds, for every token, the rows of the down product's output
that its held assignments point at. `combine_path` says how, by the same
conditions: on one TPU one Pallas call a turn (ops/moe_combine.py) reads,
for a tile of 128 tokens, each held expert's run of rows by its own copies
of whole 8-row blocks into VMEM and brings them to their tokens by a 0/1
product with a float32 accumulator; no [k, tokens, hidden] copy is
written and an assignment held elsewhere is not read. Everywhere else
XLA's gather of one row an assignment and a float32 sum over k, which on a
v5e ran at 34-46 ns a row of 4 kB out of HBM (PERF.md, PR 35 and PR 37).

Tokens are taken a turn at a time (jax.lax.map; `turns_of`): the sorted
copy of the tokens and the experts' output are [tokens x k, hidden] each,
and a turn is as many assignments as keep one such buffer within
MAX_TURN_BYTES: 2^18 rows of 4 kB at hidden 2048 in bfloat16, 2^17 of 8 kB
at hidden 4096. A turn's tokens, the [tokens, hidden] block the dispatch's
gather reads, must also fit MAX_TURN_TOKEN_BYTES, so that XLA keeps them in
VMEM: 25,600 tokens of 4 kB or 12,800 of 8 kB, and 12,800 of 4.5 kB at
hidden 2304, where the first bound alone would leave 25,600 in HBM.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepconsensus_tpu.ops import grouped_product
from deepconsensus_tpu.ops import moe_combine
from deepconsensus_tpu.ops import pallas_util

MAX_TURN_BYTES = 1 << 30
# The largest turn of tokens that compiles for a v5e with the tokens in VMEM
# (`S(1)`) for the dispatch's gather to read: 100 MiB in three cells'
# forwards; 112.5 MiB (25,600 tokens at hidden 2304) stayed in HBM, where the
# gather of a row costs about 4x what it does out of VMEM (PERF.md, section
# 5).
MAX_TURN_TOKEN_BYTES = 100 << 20

# Which form of the grouped products a turn runs (`forward_launch`'s
# `grouped_product_path`, docs/observability.md).
GROUPED_GROUP_KERNEL = 'group_kernel'
GROUPED_RAGGED_DOT = 'ragged_dot'

# Which form of the combine a turn runs (`forward_launch`'s `combine_path`,
# docs/observability.md).
COMBINE_TOKEN_TILE_KERNEL = 'token_tile_kernel'
COMBINE_GATHER = 'gather'

SCORING_SOFTMAX = 'softmax'
SCORING_SIGMOID = 'sigmoid'


def route_top_k(logits: jnp.ndarray, k: int, renormalise: bool,
                scoring: str = SCORING_SOFTMAX,
                bias: Optional[jnp.ndarray] = None,
                scale: float = 1.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """Router logits [N, E] -> (weights [N, k] float32, experts [N, k]
  int32). Scores over all E in float32, a softmax or a sigmoid each; the k
  largest, of `scores + bias` where a selection bias [E] is given (the
  weights are the scores of the chosen, without it); made to sum to one
  where the model renormalises (the sigmoid's sum with the published
  1e-20 beside it); times `scale`."""
  logits = logits.astype(jnp.float32)
  if scoring == SCORING_SOFTMAX:
    scores = jax.nn.softmax(logits, axis=-1)
  elif scoring == SCORING_SIGMOID:
    scores = jax.nn.sigmoid(logits)
  else:
    raise ValueError(f'unknown router scoring {scoring!r}; have '
                     f'{(SCORING_SOFTMAX, SCORING_SIGMOID)}')
  if bias is None:
    weights, experts = jax.lax.top_k(scores, k)
  else:
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
  if renormalise:
    total = jnp.sum(weights, axis=-1, keepdims=True)
    if scoring == SCORING_SIGMOID:
      total = total + jnp.float32(1e-20)
    weights = weights / total
  if scale != 1.0:
    weights = weights * jnp.float32(scale)
  return weights, experts.astype(jnp.int32)


def grouped_product_path(rows: int, groups: int, k: int, n: int,
                         dtype) -> str:
  """The one rule by which a turn's grouped products, `rows` sorted rows
  over `groups` matrices [k, n] (and [n, k] back), take the Pallas kernel
  in place of `jax.lax.ragged_dot`; no option asks for it. bfloat16 rows,
  shapes both calls have tiles for within their VMEM (gate and up
  together, then down), and a TPU in a trace its caller declared inference
  for one device (pallas_util.may_choose_kernels: ModelRunner without a
  mesh)."""
  del groups  # A boundary costs one part of a tile, however many there are.
  kernel = (
      jnp.dtype(dtype) == jnp.bfloat16
      and grouped_product.tiles(rows, k, n, matrices=2) is not None
      and grouped_product.tiles(rows, n, k) is not None
      and pallas_util.may_choose_kernels())
  return GROUPED_GROUP_KERNEL if kernel else GROUPED_RAGGED_DOT


def combine_path(n: int, k: int, groups: int, hidden: int, dtype) -> str:
  """The one rule by which a turn's combine, n tokens of k assignments over
  `groups` held experts, takes the Pallas kernel a tile of tokens
  (ops/moe_combine.py) in place of XLA's gather and sum; no option asks for
  it. bfloat16 rows of whole lane tiles, n a multiple of the tile, buffers
  that fit the kernel's VMEM, and a TPU in a trace its caller declared
  inference for one device (pallas_util.may_choose_kernels)."""
  kernel = (
      jnp.dtype(dtype) == jnp.bfloat16
      and moe_combine.fits(n, k, groups, hidden)
      and pallas_util.may_choose_kernels())
  return COMBINE_TOKEN_TILE_KERNEL if kernel else COMBINE_GATHER


def turns_of(n: int, k: int, hidden: int, dtype) -> int:
  """In how many turns `held_experts` takes n tokens of k assignments: the
  fewest halvings that bring one [rows, hidden] buffer of a turn within
  MAX_TURN_BYTES and the turn's [tokens, hidden], the dispatch's source,
  within MAX_TURN_TOKEN_BYTES."""
  row_bytes = hidden * jnp.dtype(dtype).itemsize
  over = lambda tokens: (tokens * k * row_bytes > MAX_TURN_BYTES
                         or tokens * row_bytes > MAX_TURN_TOKEN_BYTES)
  turns = 1
  while over(n // turns) and n % (turns * 2) == 0:
    turns *= 2
  return turns


def _held_experts(x, weights, experts, w_gate, w_up, w_down, first: int):
  """One turn of `held_experts`: every row of x at once."""
  n, k = experts.shape
  held = w_gate.shape[0]
  with jax.named_scope('dispatch'):
    local = experts - first
    mine = (local >= 0) & (local < held)
    # Assignments by held expert, the others behind them all.
    group = jnp.where(mine, local, held).reshape(n * k)
    # The routing weights ride along with the sort: no gather of scalars.
    group_sorted, order, row_weight = jax.lax.sort(
        (group, jnp.arange(n * k, dtype=jnp.int32), weights.reshape(n * k)),
        num_keys=1)
    bounds = jnp.searchsorted(
        group_sorted, jnp.arange(held + 1, dtype=jnp.int32), side='left')
    counts = jnp.diff(bounds).astype(jnp.int32)
    # Every index below is a position of a permutation: `clip` spares the
    # bounds check and the pass that fills what it would reject.
    rows = jnp.take(x, order // k, axis=0, mode='clip')  # [n * k, hidden]
  with jax.named_scope('experts'):
    # Each product leaves its kernel in x's type, from a float32
    # accumulator: a float32 copy of [n * k, hidden] is never written. The
    # routing weight multiplies the row before the last product, which is
    # linear, so the combine only adds.
    if grouped_product_path(n * k, held, x.shape[1], w_gate.shape[2],
                            x.dtype) == GROUPED_GROUP_KERNEL:
      hidden = grouped_product.gated_up(rows, w_gate, w_up, row_weight, bounds)
      out = grouped_product.grouped_product(hidden, w_down, bounds)
    else:
      grouped = lambda a, w: jax.lax.ragged_dot(
          a, w.astype(a.dtype), counts, preferred_element_type=a.dtype)
      hidden = jax.nn.silu(grouped(rows, w_gate).astype(jnp.float32))
      hidden = hidden * grouped(rows, w_up).astype(jnp.float32)
      out = grouped((hidden * row_weight[:, None]).astype(x.dtype), w_down)
  with jax.named_scope('combine'):
    # Where each assignment's row went: the inverse of the sort.
    _, place = jax.lax.sort((order, jnp.arange(n * k, dtype=jnp.int32)),
                            num_keys=1)
    if combine_path(n, k, held, x.shape[1], x.dtype) == (
        COMBINE_TOKEN_TILE_KERNEL):
      # A tile of tokens a grid step: the held rows come by the kernel's
      # own copies and are added in VMEM; held elsewhere is place -1.
      y = moe_combine.combine(
          out, group.reshape(n, k), jnp.where(mine, place.reshape(n, k), -1),
          bounds)
      return y, counts
    # The rows come back one assignment of every token after another
    # ([k, n, H]: a token's k rows are then k planes to add, and no
    # [n, k, H] array is laid out anew).
    mine_t = mine.T  # [k, n]
    # An assignment held elsewhere reads row 0 (any row: it is masked
    # below), so that half the reads do not wander over rows nobody wrote.
    back = jnp.take(out, jnp.where(mine_t, place.reshape(n, k).T, 0).reshape(
        n * k), axis=0, mode='clip').reshape(k, n, -1)
    # A `where`, not a product by zero: rows past the held groups hold
    # whatever the buffer held. Summed in float32 as the rows are read.
    y = jnp.sum(jnp.where(mine_t[..., None], back, jnp.zeros((), back.dtype)),
                axis=0, dtype=jnp.float32)
  return y.astype(x.dtype), counts


def held_experts(x: jnp.ndarray, weights: jnp.ndarray, experts: jnp.ndarray,
                 w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
                 first: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """What the held experts add to every token.

  x [N, H] tokens; weights, experts [N, k] from `route_top_k`; w_gate,
  w_up [held, H, F] and w_down [held, F, H] the experts first ...
  first + held - 1. -> (y [N, H] in x's type, counts [held] int32: the
  assignments each held expert took). Products take x's type with a
  float32 accumulator and leave it in x's type; the gate, the routing
  weight and the combine's sum are float32."""
  n, k = experts.shape
  turns = turns_of(n, k, x.shape[1], x.dtype)
  if turns == 1:
    return _held_experts(x, weights, experts, w_gate, w_up, w_down, first)
  split = lambda a: a.reshape((turns, n // turns) + a.shape[1:])
  y, counts = jax.lax.map(
      lambda xs: _held_experts(*xs, w_gate, w_up, w_down, first),
      (split(x), split(weights), split(experts)))
  return y.reshape(n, -1), jnp.sum(counts, axis=0)
