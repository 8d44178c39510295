"""Pallas TPU kernel: ragged mixed-width windows in fixed page-style slots.

The bucketed engine (PR 12) ended pad-to-max waste but left N buckets
= N packers and N compiled forwards, plus a starvation flush that
re-introduces padding whenever one bucket starves. This kernel removes
the bucket dimension entirely, borrowing the page layout from Ragged
Paged Attention (arxiv 2604.15464): windows of any bucket width are
packed back-to-back into fixed-length SLOTS (slot length = the largest
bucket), and a per-slot ``lengths`` int32 vector — not the compile-time
L — drives everything that used to depend on the window width:

  * the banded attention mask becomes band AND same-window AND valid,
    where the window ownership of each position is derived from
    ``lengths`` with static iota/compare ops (`slot_geometry`);
  * the sinusoidal position add becomes a per-position gather of
    ``pos[p - window_start(p)]``, done in-kernel as a one-hot matmul
    (exact: each one-hot row has a single 1, so the MXU sum has one
    non-zero term);
  * the condenser contraction needs no change at all — embed+condense
    are position-wise, and pad positions carry id 0, which the masked
    one-hot embeds to the zero vector.

One pack stream, one compiled forward: every pack has the same
[n_slots, R, S] shape regardless of the width mix, so
``n_forward_shapes`` collapses to 1 and the per-bucket packer fleet
(and its starvation flush) disappears.

Semantics are defined by `reference_ragged_forward` (pure jnp, shares
the helpers below and fused_window_attention's embed/condense); the
kernel is validated against it in interpret mode on CPU at every
configured bucket width and at an overflow width
(tests/test_ragged_kernel.py). The byte-identity contract with the
bucketed engine is carried by the XLA model path (models/model.py
reshape-select routing), which this kernel mirrors numerically —
identical-shape reshaped compute is bitwise, masked-wide compute is
1-ulp-close (XLA reassociates reductions over different contraction
lengths), so kernel parity is asserted with tight allclose rather
than bitwise.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import fused_window_attention as fwa

Array = jnp.ndarray

_NEG = -1e9

# Slot-length ceiling for the whole-S score block ([tile, S, S] f32 in
# VMEM). Deliberately above FUSED_MAX_WINDOW_LEN: slots span the
# LARGEST bucket, and the score block at 256 is ~2 MB per tile — still
# comfortable next to the weights. Above this, callers stay bucketed.
RAGGED_MAX_SLOT_LEN = 256


def validate_ragged_buckets(buckets: Sequence[int]) -> Tuple[int, ...]:
  """Ragged packing needs a divisibility chain: each bucket must divide
  every larger bucket.

  Largest-first packing into a slot then guarantees every window
  starts at an offset that is a multiple of its own width, which is
  what lets the XLA byte-identity path recover each window as one
  contiguous reshape chunk (models/model.py) and keeps mixed
  compositions aligned for the kernel mask. The default (100, 200)
  chain satisfies this; an operator bucket spec that does not fails
  loudly here instead of silently corrupting window boundaries.
  """
  out = tuple(int(b) for b in buckets)
  if not out or any(b <= 0 for b in out):
    raise ValueError(f'ragged buckets must be positive ints, got {out}')
  if list(out) != sorted(set(out)):
    raise ValueError(f'ragged buckets must be strictly ascending, got {out}')
  for small, big in zip(out, out[1:]):
    if big % small:
      raise ValueError(
          f'ragged buckets must form a divisibility chain (each bucket '
          f'divides every larger one); {small} does not divide {big} '
          f'in {out}')
  return out


def windows_per_slot(buckets: Sequence[int]) -> int:
  """Max windows one slot can hold: slot_len // smallest bucket."""
  b = validate_ragged_buckets(buckets)
  return b[-1] // b[0]


def _geometry(lengths: Array, p: Array
              ) -> Tuple[Array, Array, Array, Array]:
  """slot_geometry in the orientation of p: an int32 position iota of
  shape [B, S, 1] (positions on sublanes) or [B, 1, S] (on lanes).
  Deriving each orientation from lengths directly keeps the kernel
  free of [B, S] -> [B, S, 1] relayouts, a shape cast Mosaic does not
  implement."""
  lengths = lengths.astype(jnp.int32)
  b, wps = lengths.shape
  lengths3 = lengths.reshape(b, 1, wps)
  seg = jnp.zeros(p.shape, jnp.int32)
  width = jnp.zeros(p.shape, jnp.int32)
  start = jnp.zeros(p.shape, jnp.int32)
  cur = jnp.zeros((b, 1, 1), jnp.int32)
  for j in range(wps):
    w_j = lengths3[:, :, j:j + 1]
    nxt = cur + w_j
    sel = (p >= cur) & (p < nxt)
    seg = jnp.where(sel, j, seg)
    width = jnp.where(sel, w_j, width)
    start = jnp.where(sel, cur, start)
    cur = nxt
  valid = p < cur
  return seg, start, width, valid


def _positions(b: int, slot_len: int, axis: int) -> Array:
  shape = (b, slot_len, 1) if axis == 1 else (b, 1, slot_len)
  return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def slot_geometry(lengths: Array, slot_len: int
                  ) -> Tuple[Array, Array, Array, Array]:
  """Per-position window geometry derived from per-slot window lengths.

  lengths: [B, wps] int32 — widths of the windows packed back-to-back
  into each slot in placement order (0 = unused capacity; zeros are
  trailing). Returns (seg, start, width, valid), each [B, slot_len]:
  the window ordinal owning each position, that window's start offset
  and width, and whether the position holds real window data.

  Built from static-shape iota/compare/where only (no gather, no
  cumsum primitive), so the same helper runs inside the Pallas kernel,
  the jnp reference, and the XLA model path.
  """
  return tuple(
      a[:, 0, :]
      for a in _geometry(lengths, _positions(lengths.shape[0], slot_len, 2)))


def ragged_attention_mask(lengths: Array, slot_len: int,
                          attn_win_size: Optional[int]) -> Array:
  """[B, S, S] bool attention mask for ragged slots: the static band
  AND same-window AND both-positions-valid. Within one window the
  absolute-position band equals the window-relative band (|i - j| is
  offset-invariant), so this is exactly the per-width band the
  bucketed path applies."""
  b = lengths.shape[0]
  seg_q, _, _, valid_q = _geometry(lengths, _positions(b, slot_len, 1))
  seg_k, _, _, valid_k = _geometry(lengths, _positions(b, slot_len, 2))
  mask = (seg_q == seg_k) & valid_q & valid_k
  if attn_win_size is not None:
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, slot_len, slot_len), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, slot_len, slot_len), 2)
    mask = mask & (jnp.abs(rows - cols) <= attn_win_size)
  return mask


def _pos_contribution(lengths: Array, slot_len: int, pos: Array) -> Array:
  """Per-position sinusoidal encoding pos[p - start(p)] as a one-hot
  matmul (MXU-friendly and exact: one 1 per row, so the accumulation
  has a single non-zero term). Invalid positions contribute zero."""
  b = lengths.shape[0]
  pos_len = pos.shape[0]
  p = _positions(b, slot_len, 1)
  _seg, start, _width, valid = _geometry(lengths, p)
  off = jnp.clip(p - start, 0, pos_len - 1)
  k = jax.lax.broadcasted_iota(jnp.int32, (b, slot_len, pos_len), 2)
  onehot = ((off == k) & valid).astype(jnp.float32)
  return jax.lax.dot_general(
      onehot.reshape(b * slot_len, pos_len), pos.astype(jnp.float32),
      (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32,
  ).reshape(b, slot_len, pos.shape[1])


def _ragged_attention(x, mask, wq, wk, wv, wo, *, num_heads, qscale,
                      slot_len, softmax_dtype):
  """Banded MHA on a [tile, S, H] f32 block with a precomputed ragged
  mask; mirrors fused_window_attention._attention's op order (batch-
  major projections, per-head softmax in softmax_dtype, output
  projection) with the band test swapped for the lengths-derived
  mask. Shared between the kernel and the jnp reference."""
  tile, _, hidden = x.shape
  head_dim = hidden // num_heads
  x2 = x.reshape(tile * slot_len, hidden)

  def proj(w):
    return jax.lax.dot_general(
        x2, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(tile, slot_len, hidden)

  q = proj(wq) * qscale
  k = proj(wk)
  v = proj(wv)
  # Heads are lane slices of the [tile, L, H] projections: Mosaic has
  # no shape cast that splits the lane dimension into (heads, depth).
  head = lambda t, h: t[:, :, h * head_dim:(h + 1) * head_dim]
  outs = []
  for h in range(num_heads):
    s = jax.lax.dot_general(
        head(q, h), head(k, h), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [tile, S, S]
    s = jnp.where(mask, s, _NEG)
    sd = s.astype(softmax_dtype)
    m = jnp.max(sd, axis=2, keepdims=True)
    p = jnp.exp(sd - m)
    w = (p / jnp.sum(p, axis=2, keepdims=True)).astype(jnp.float32)
    outs.append(jax.lax.dot_general(
        w, head(v, h), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ))
  o = jnp.concatenate(outs, axis=-1).reshape(tile * slot_len, hidden)
  out = jax.lax.dot_general(
      o, wo.astype(jnp.float32), (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32,
  )
  return out.reshape(tile, slot_len, hidden)


def _kernel(*refs, specs, n_tables, num_heads, qscale, attn_win_size,
            slot_len, hidden, softmax_dtype):
  ids_ref = refs[0]
  lengths_ref = refs[1]
  table_refs = refs[2:2 + n_tables]
  w_cond_ref, wq_ref, wk_ref, wv_ref, wo_ref, pos_ref = refs[
      2 + n_tables:8 + n_tables]
  xbase_ref, attn_ref, x_ref = refs[8 + n_tables:11 + n_tables]

  lengths = lengths_ref[:]
  table_vals = [t[:] for t in table_refs]
  w_cond = w_cond_ref[:].astype(jnp.float32)
  mask = ragged_attention_mask(lengths, slot_len, attn_win_size)
  fwa.embed_condense_tile(
      ids_ref, table_vals, w_cond, specs, x_ref, slot_len, hidden)
  x = x_ref[:] + _pos_contribution(lengths, slot_len, pos_ref[:])
  xbase_ref[:] = x.astype(xbase_ref.dtype)
  out = _ragged_attention(
      x, mask, wq_ref[:], wk_ref[:], wv_ref[:], wo_ref[:],
      num_heads=num_heads, qscale=qscale, slot_len=slot_len,
      softmax_dtype=softmax_dtype,
  )
  attn_ref[:] = out.astype(attn_ref.dtype)


def ragged_embed_condense_attention(
    rows: Array,
    lengths: Array,
    tables: Dict[str, Array],
    w_cond: Array,
    wq: Array,
    wk: Array,
    wv: Array,
    wo: Array,
    pos: Optional[Array],
    *,
    specs: Tuple[fwa.FamilySpec, ...],
    table_keys: Tuple[str, ...],
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    compute_dtype: Any = jnp.float32,
    tile_windows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
  """Fused embed->condense->pos->layer-0 attention over ragged slots.

  rows: [B, R, S] raw pileup rows with mixed-width windows packed
  back-to-back per slot (pad positions zero). lengths: [B, wps] int32
  per-slot window widths. Weight arguments match
  fused_window_attention.fused_embed_condense_attention; pos is the
  [S, H] sinusoidal table indexed per position by window offset.

  Returns (x_base, attn_out), both [B, S, H] in compute_dtype; the
  caller applies the ReZero residual outside, same split as the
  bucketed kernel.
  """
  from deepconsensus_tpu.ops import pallas_util

  b, r, slot_len = rows.shape
  if slot_len > RAGGED_MAX_SLOT_LEN:
    raise ValueError(
        f'ragged slot length {slot_len} exceeds RAGGED_MAX_SLOT_LEN '
        f'{RAGGED_MAX_SLOT_LEN}')
  hidden = w_cond.shape[1]
  head_dim = hidden // num_heads
  cond_in = sum(s.n_rows * s.width for s in specs)
  if cond_in != w_cond.shape[0]:
    raise ValueError(
        f'condenser expects {w_cond.shape[0]} input features, family '
        f'specs cover {cond_in}; config and weights disagree')
  if hidden % num_heads:
    raise ValueError('hidden size must divide num_heads')

  tile = tile_windows or fwa.DEFAULT_TILE_WINDOWS
  tile = max(1, min(tile, b))
  ids = fwa.prepare_ids(rows, specs)
  lengths = jnp.asarray(lengths, jnp.int32)
  pad = (-b) % tile
  if pad:
    # Zero lengths mark every position of a padded slot invalid; zero
    # ids embed to zero vectors. Padded slots are sliced away.
    ids = jnp.pad(ids, ((0, pad), (0, 0), (0, 0)))
    lengths = jnp.pad(lengths, ((0, pad), (0, 0)))
  n_tiles = (b + pad) // tile
  wps = lengths.shape[1]

  # dclint: allow=dtype-downcast (kernel inputs follow the configured
  # compute dtype; bf16 here is the inference_dtype lever, not a leak)
  cast = lambda a: jnp.asarray(a, compute_dtype)
  table_in = [
      # dclint: allow=dtype-downcast (sqrt(width) embed scale folded at
      # compute dtype, same fold as the bucketed kernel)
      cast(tables[key]) * jnp.asarray(
          next(s.width for s in specs if s.table_idx == i) ** 0.5,
          compute_dtype)
      for i, key in enumerate(table_keys)
  ]
  if pos is None:
    pos = jnp.zeros((slot_len, hidden), compute_dtype)

  full = lambda a: pl.BlockSpec(
      a.shape, lambda i: (0,) * a.ndim, memory_space=pltpu.VMEM)
  ids_spec = pl.BlockSpec((tile, r, slot_len), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
  lengths_spec = pl.BlockSpec((tile, wps), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
  out_spec = pl.BlockSpec((tile, slot_len, hidden), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
  inputs = [ids, lengths, *table_in, cast(w_cond), cast(wq), cast(wk),
            cast(wv), cast(wo), cast(pos)]
  x_base, attn_out = pl.pallas_call(
      functools.partial(
          _kernel, specs=specs, n_tables=len(table_keys),
          num_heads=num_heads, qscale=head_dim ** -0.5,
          attn_win_size=attn_win_size, slot_len=slot_len, hidden=hidden,
          softmax_dtype=jnp.dtype(softmax_dtype),
      ),
      grid=(n_tiles,),
      in_specs=[ids_spec, lengths_spec] + [full(a) for a in inputs[2:]],
      out_specs=[out_spec, out_spec],
      out_shape=[
          jax.ShapeDtypeStruct((b + pad, slot_len, hidden), compute_dtype),
          jax.ShapeDtypeStruct((b + pad, slot_len, hidden), compute_dtype),
      ],
      scratch_shapes=[pltpu.VMEM((tile, slot_len, hidden), jnp.float32)],
      compiler_params=pallas_util.batch_tile_compiler_params(),
      interpret=pallas_util.resolve_interpret(interpret),
  )(*inputs)
  return x_base[:b], attn_out[:b]


def reference_ragged_forward(
    rows: Array,
    lengths: Array,
    tables: Dict[str, Array],
    w_cond: Array,
    wq: Array,
    wk: Array,
    wv: Array,
    wo: Array,
    pos: Optional[Array],
    *,
    specs: Tuple[fwa.FamilySpec, ...],
    table_keys: Tuple[str, ...],
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
) -> Tuple[Array, Array]:
  """Pure-jnp semantics of the ragged kernel (same helpers, no
  Pallas): the interpret-mode parity oracle for unit tests."""
  b, _, slot_len = rows.shape
  hidden = w_cond.shape[1]
  head_dim = hidden // num_heads
  ids = fwa.prepare_ids(rows, specs)
  lengths = jnp.asarray(lengths, jnp.int32)
  table_vals = [
      tables[key].astype(jnp.float32) * (
          next(s.width for s in specs if s.table_idx == i) ** 0.5)
      for i, key in enumerate(table_keys)
  ]
  mask = ragged_attention_mask(lengths, slot_len, attn_win_size)
  x = fwa._embed_condense(ids, table_vals, w_cond.astype(jnp.float32),
                          specs, b, slot_len, hidden)
  if pos is not None:
    x = x + _pos_contribution(lengths, slot_len, pos)
  out = _ragged_attention(
      x, mask, wq, wk, wv, wo, num_heads=num_heads,
      qscale=head_dim ** -0.5, slot_len=slot_len,
      softmax_dtype=jnp.dtype(softmax_dtype),
  )
  return x, out
