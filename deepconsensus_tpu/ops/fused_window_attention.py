"""Pallas TPU kernel: batch-major fused embed->condense->attention.

The L=100 production hot path. The per-(batch, head) kernels in
ops/banded_attention.py hand each grid program one window: with L=100
every per-window matmul is smaller than one 128x128 MXU tile, so a
grid that hands each program one window (or one batch*head pair)
starves the systolic array no matter how well it tiles. The short-
sequence lesson from the TPU serving literature (Ragged Paged
Attention, arxiv 2604.15464) is to make the *batch* dimension the
unit of work: each grid program here processes a TILE OF WINDOWS and
runs every projection as one [tile*L, K] x [K, N] matmul, so the MXU
sees token-major operands hundreds of rows tall instead of window-
sized crumbs.

Per grid program, for a tile of windows, one VMEM-resident pass:

  1. one-hot feature embedding (the one-hot is built in VMEM with an
     iota compare and immediately matmul'd against the family table:
     no gather and no [B, R, L, E] HBM intermediate, as on the XLA
     path's MaskedEmbed);
  2. the condenser projection (`condense_transformer_input`), fused
     per row-chunk as a two-axis contraction so the 560-wide concat
     never materializes anywhere;
  3. sinusoidal position add;
  4. layer-0 banded multi-head attention: q/k/v projections
     (batch-major), per-head banded softmax with configurable
     accumulation dtype (the `attn_softmax_dtype` lever), and the
     output projection.

The kernel returns (x_base, attn_out) — the embedded/condensed/
position-encoded activations and the attention block output — and the
caller applies the ReZero residual, so checkpointed alpha scalars and
any residual-wrapper variant stay outside the kernel.

Semantics are defined by `reference_fused_forward` (pure jnp, mirrors
models/model.py exactly); the kernel is validated against it and
against the full XLA model in interpret mode on CPU
(tests/test_fused_hotpath.py), so correctness is provable without a
chip. models/model.py routes through this kernel when
params.use_fused_hotpath is set and the config is eligible
(inference, condensed learn-values input, ReZero, L <= MAX_WINDOW_LEN).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu import constants
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.preprocess.pileup import row_indices

Array = jnp.ndarray

_NEG = -1e9

# Above this window length the [tile, L, L] score block stops paying
# for itself against the flash kernel's structural band; callers fall
# back to the XLA path / flash kernel (same boundary as
# flash_band_attention.WHOLE_L_LIMIT). With window buckets, eligibility
# is per bucket: traces at L <= this run fused, longer buckets XLA.
MAX_WINDOW_LEN = config_lib.FUSED_MAX_WINDOW_LEN

# Windows per grid program. 8 keeps the peak VMEM footprint (one-hot
# chunk + live q/k/v/x values + weights) near 11 MB at the production
# shape.
DEFAULT_TILE_WINDOWS = 8

# VMEM budget for one transient one-hot block [tile, chunk, L, V] f32;
# bounds how many rows of a family are one-hot-encoded at once.
_ONEHOT_BUDGET_BYTES = 4 << 20


class FamilySpec(NamedTuple):
  """Static description of one feature family's slice of the pileup.

  cond_offset is the family's first row in the condenser weight (the
  concat order of DeepConsensusModel._embed_rows); shift is added to
  raw ids before clipping/embedding (ccs_bq stores gap as -1).
  """

  name: str
  row_start: int
  n_rows: int
  vocab: int
  width: int
  table_idx: int
  cond_offset: int
  shift: int


def build_family_specs(params) -> Tuple[Tuple[FamilySpec, ...],
                                        Tuple[str, ...], int]:
  """Family specs + table keys + condenser input width for a config.

  Mirrors DeepConsensusModel._embed_rows: same row ranges, same concat
  order, same table sharing (ccs rows embed through the bases table).
  Table keys name the embedding param that backs each table input.
  """
  (base_r, pw_r, ip_r, strand_r, ccs_r, ccs_bq_r, sn_r) = row_indices(
      params.max_passes, params.use_ccs_bq
  )
  specs = []
  table_keys: list = []
  offset = 0

  def add(name, rng, vocab, width, table_key, shift=0):
    nonlocal offset
    if table_key not in table_keys:
      table_keys.append(table_key)
    specs.append(FamilySpec(
        name=name, row_start=rng[0], n_rows=rng[1] - rng[0], vocab=vocab,
        width=width, table_idx=table_keys.index(table_key),
        cond_offset=offset, shift=shift,
    ))
    offset += (rng[1] - rng[0]) * width

  if params.use_bases:
    add('bases', base_r, constants.SEQ_VOCAB_SIZE,
        params.per_base_hidden_size, 'bases')
  if params.use_pw:
    add('pw', pw_r, params.PW_MAX + 1, params.pw_hidden_size, 'pw')
  if params.use_ip:
    add('ip', ip_r, params.IP_MAX + 1, params.ip_hidden_size, 'ip')
  if params.use_strand:
    add('strand', strand_r, params.STRAND_MAX + 1,
        params.strand_hidden_size, 'strand')
  if params.use_ccs:
    add('ccs', ccs_r, constants.SEQ_VOCAB_SIZE,
        params.per_base_hidden_size, 'bases')
  if params.use_ccs_bq:
    add('ccs_bq', ccs_bq_r, params.CCS_BQ_MAX,
        params.ccs_bq_hidden_size, 'ccs_bq', shift=1)
  if params.use_sn:
    add('sn', sn_r, params.SN_MAX + 1, params.sn_hidden_size, 'sn')
  return tuple(specs), tuple(table_keys), offset


def prepare_ids(rows: Array, specs: Sequence[FamilySpec]) -> Array:
  """[B, R, L] raw float/int rows -> int32 ids, shifted and clipped
  per family exactly like MaskedEmbed's gather (mode='clip') and
  one-hot (jnp.clip) paths — both clamp to [0, vocab-1]."""
  ids = rows.astype(jnp.int32)
  for spec in specs:
    seg = ids[:, spec.row_start:spec.row_start + spec.n_rows, :]
    seg = jnp.clip(seg + spec.shift, 0, spec.vocab - 1)
    ids = ids.at[:, spec.row_start:spec.row_start + spec.n_rows, :].set(seg)
  return ids


def _row_chunk(tile: int, length: int, spec: FamilySpec) -> int:
  per_row = tile * length * spec.vocab * 4
  return max(1, min(spec.n_rows, _ONEHOT_BUDGET_BYTES // max(per_row, 1)))


def _embed_condense(ids, table_vals, w_cond, specs, tile, length, hidden):
  """One-hot embed + condense for a tile, accumulated per row-chunk so
  neither the one-hot nor the 560-wide pre-condense concat ever leaves
  VMEM. Every matmul contracts exactly one dimension of 2-D operands
  (Mosaic implements nothing else): the chunk's rows embed in one
  [tile*c*L, vocab] x [vocab, width] product, then each row condenses
  through its own [width, hidden] slice of the condenser. Shared
  between the kernel and the jnp reference (plain jnp ops only)."""
  x = jnp.zeros((tile * length, hidden), jnp.float32)
  for spec in specs:
    table = table_vals[spec.table_idx].astype(jnp.float32)
    chunk = _row_chunk(tile, length, spec)
    for c0 in range(0, spec.n_rows, chunk):
      c = min(chunk, spec.n_rows - c0)
      r0 = spec.row_start + c0
      seg = ids[:, r0:r0 + c, :]  # [tile, c, L] int32
      iota = jax.lax.broadcasted_iota(
          jnp.int32, (tile, c, length, spec.vocab), 3)
      # Masked one-hot: id 0 embeds to the zero vector (MaskedEmbed's
      # (ids != 0) mask); matching col 0 and masking it are the same.
      onehot = ((seg[..., None] == iota) & (seg[..., None] > 0)).astype(
          jnp.float32)
      emb = jax.lax.dot_general(
          onehot.reshape(tile * c * length, spec.vocab), table,
          (((1,), (0,)), ((), ())),
          preferred_element_type=jnp.float32,
      ).reshape(tile, c, length, spec.width)
      w0 = spec.cond_offset + c0 * spec.width
      for j in range(c):
        # Row j's width-wide embedding against the condenser rows it
        # owns: the concat never materializes.
        x = x + jax.lax.dot_general(
            emb[:, j].reshape(tile * length, spec.width),
            w_cond[w0 + j * spec.width:w0 + (j + 1) * spec.width, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
  return x.reshape(tile, length, hidden)


def embed_condense_tile(ids_ref, table_vals, w_cond, specs, x_ref, length,
                        hidden):
  """Kernel side of _embed_condense: fills the [tile, L, H] f32 scratch
  x_ref one window per loop trip. Embedding is per position, so nothing
  is lost by not batching it across the tile, while unrolling it over
  the whole tile made Mosaic's compile time grow ~7x per doubling of
  the tile (23 minutes at the default 8)."""

  def one_window(t, carry):
    x_ref[pl.ds(t, 1)] = _embed_condense(
        ids_ref[pl.ds(t, 1)], table_vals, w_cond, specs, 1, length, hidden)
    return carry

  jax.lax.fori_loop(0, ids_ref.shape[0], one_window, 0)


def _attention(x, wq, wk, wv, wo, *, num_heads, qscale, attn_win_size,
               length, softmax_dtype):
  """Layer-0 banded MHA on a [tile, L, H] f32 block: batch-major
  projections, per-head banded softmax in softmax_dtype (the
  attn_softmax_dtype lever), output projection. Shared between the
  kernel and the jnp reference."""
  tile, _, hidden = x.shape
  head_dim = hidden // num_heads
  x2 = x.reshape(tile * length, hidden)

  def proj(w):
    return jax.lax.dot_general(
        x2, w.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(tile, length, hidden)

  q = proj(wq) * qscale
  k = proj(wk)
  v = proj(wv)
  # Heads are lane slices of the [tile, L, H] projections: Mosaic has
  # no shape cast that splits the lane dimension into (heads, depth).
  head = lambda t, h: t[:, :, h * head_dim:(h + 1) * head_dim]
  if attn_win_size is not None:
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, length, length), 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, length, length), 2)
    band = jnp.abs(rows - cols) <= attn_win_size
  outs = []
  for h in range(num_heads):
    s = jax.lax.dot_general(
        head(q, h), head(k, h), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [tile, L, L]
    if attn_win_size is not None:
      s = jnp.where(band, s, _NEG)
    sd = s.astype(softmax_dtype)
    m = jnp.max(sd, axis=2, keepdims=True)
    p = jnp.exp(sd - m)
    w = (p / jnp.sum(p, axis=2, keepdims=True)).astype(jnp.float32)
    outs.append(jax.lax.dot_general(
        w, head(v, h), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ))
  o = jnp.concatenate(outs, axis=-1).reshape(tile * length, hidden)
  out = jax.lax.dot_general(
      o, wo.astype(jnp.float32), (((1,), (0,)), ((), ())),
      preferred_element_type=jnp.float32,
  )
  return out.reshape(tile, length, hidden)


def _kernel(*refs, specs, n_tables, num_heads, qscale, attn_win_size,
            length, hidden, softmax_dtype):
  ids_ref = refs[0]
  table_refs = refs[1:1 + n_tables]
  w_cond_ref, wq_ref, wk_ref, wv_ref, wo_ref, pos_ref = refs[
      1 + n_tables:7 + n_tables]
  xbase_ref, attn_ref, x_ref = refs[7 + n_tables:10 + n_tables]

  table_vals = [t[:] for t in table_refs]
  w_cond = w_cond_ref[:].astype(jnp.float32)
  embed_condense_tile(ids_ref, table_vals, w_cond, specs, x_ref, length,
                      hidden)
  x = x_ref[:] + pos_ref[:].astype(jnp.float32)[None]
  xbase_ref[:] = x.astype(xbase_ref.dtype)
  out = _attention(
      x, wq_ref[:], wk_ref[:], wv_ref[:], wo_ref[:],
      num_heads=num_heads, qscale=qscale, attn_win_size=attn_win_size,
      length=length, softmax_dtype=softmax_dtype,
  )
  attn_ref[:] = out.astype(attn_ref.dtype)


def fused_embed_condense_attention(
    rows: Array,
    tables: Dict[str, Array],
    w_cond: Array,
    wq: Array,
    wk: Array,
    wv: Array,
    wo: Array,
    pos: Optional[Array],
    *,
    specs: Tuple[FamilySpec, ...],
    table_keys: Tuple[str, ...],
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
    compute_dtype: Any = jnp.float32,
    tile_windows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
  """Fused embed->condense->pos->layer-0-attention over a window batch.

  rows: [B, R, L] raw pileup rows (float or int). tables: unscaled
  embedding params keyed per build_family_specs. w_cond: [cond_in, H]
  condenser kernel. wq/wk/wv: [H, H] (DenseGeneral kernels flattened;
  the 1/sqrt(head_dim) query scale is applied in-kernel after the
  projection, matching the model's op order). wo: [H, H] output
  projection. pos: [L, H] positional encoding or None.

  Returns (x_base, attn_out), both [B, L, H] in compute_dtype: the
  pre-attention activations and the attention block output. The caller
  applies the residual (ReZero alpha lives with its checkpointed
  parameter, not in the kernel).
  """
  from deepconsensus_tpu.ops import pallas_util

  b, r, length = rows.shape
  hidden = w_cond.shape[1]
  head_dim = hidden // num_heads
  cond_in = sum(s.n_rows * s.width for s in specs)
  if cond_in != w_cond.shape[0]:
    raise ValueError(
        f'condenser expects {w_cond.shape[0]} input features, family '
        f'specs cover {cond_in}; config and weights disagree')
  if hidden % num_heads:
    raise ValueError('hidden size must divide num_heads')

  tile = tile_windows or DEFAULT_TILE_WINDOWS
  tile = max(1, min(tile, b))
  ids = prepare_ids(rows, specs)
  pad = (-b) % tile
  if pad:
    # Zero ids embed to zero vectors; padded windows compute garbage-
    # free attention over pure position encodings and are sliced away.
    ids = jnp.pad(ids, ((0, pad), (0, 0), (0, 0)))
  n_tiles = (b + pad) // tile

  # dclint: allow=dtype-downcast (kernel inputs follow the configured
  # compute dtype; bf16 here is the inference_dtype lever, not a leak)
  cast = lambda a: jnp.asarray(a, compute_dtype)
  # Fold the sqrt(width) embedding output scale into the tables
  # (MaskedEmbed multiplies after the lookup; the lookup is linear so
  # the fold is exact up to one f32 rounding).
  table_in = [
      # dclint: allow=dtype-downcast (scale folded at compute dtype)
      cast(tables[key]) * jnp.asarray(
          next(s.width for s in specs if s.table_idx == i) ** 0.5,
          compute_dtype)
      for i, key in enumerate(table_keys)
  ]
  if pos is None:
    pos = jnp.zeros((length, hidden), compute_dtype)

  full = lambda a: pl.BlockSpec(
      a.shape, lambda i: (0,) * a.ndim, memory_space=pltpu.VMEM)
  ids_spec = pl.BlockSpec((tile, r, length), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
  out_spec = pl.BlockSpec((tile, length, hidden), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
  inputs = [ids, *table_in, cast(w_cond), cast(wq), cast(wk), cast(wv),
            cast(wo), cast(pos)]
  x_base, attn_out = pl.pallas_call(
      functools.partial(
          _kernel, specs=specs, n_tables=len(table_keys),
          num_heads=num_heads, qscale=head_dim ** -0.5,
          attn_win_size=attn_win_size, length=length, hidden=hidden,
          softmax_dtype=jnp.dtype(softmax_dtype),
      ),
      grid=(n_tiles,),
      in_specs=[ids_spec] + [full(a) for a in inputs[1:]],
      out_specs=[out_spec, out_spec],
      out_shape=[
          jax.ShapeDtypeStruct((b + pad, length, hidden), compute_dtype),
          jax.ShapeDtypeStruct((b + pad, length, hidden), compute_dtype),
      ],
      scratch_shapes=[pltpu.VMEM((tile, length, hidden), jnp.float32)],
      compiler_params=pallas_util.batch_tile_compiler_params(),
      interpret=pallas_util.resolve_interpret(interpret),
  )(*inputs)
  return x_base[:b], attn_out[:b]


def reference_fused_forward(
    rows: Array,
    tables: Dict[str, Array],
    w_cond: Array,
    wq: Array,
    wk: Array,
    wv: Array,
    wo: Array,
    pos: Optional[Array],
    *,
    specs: Tuple[FamilySpec, ...],
    table_keys: Tuple[str, ...],
    num_heads: int,
    attn_win_size: Optional[int],
    softmax_dtype: Any = jnp.float32,
) -> Tuple[Array, Array]:
  """Pure-jnp semantics of the fused kernel (same helpers, no Pallas):
  the parity oracle for unit tests and a CPU-debuggable mirror."""
  b, _, length = rows.shape
  hidden = w_cond.shape[1]
  head_dim = hidden // num_heads
  ids = prepare_ids(rows, specs)
  table_vals = [
      tables[key].astype(jnp.float32) * (
          next(s.width for s in specs if s.table_idx == i) ** 0.5)
      for i, key in enumerate(table_keys)
  ]
  x = _embed_condense(ids, table_vals, w_cond.astype(jnp.float32), specs,
                      b, length, hidden)
  if pos is not None:
    x = x + pos.astype(jnp.float32)[None]
  out = _attention(
      x, wq, wk, wv, wo, num_heads=num_heads, qscale=head_dim ** -0.5,
      attn_win_size=attn_win_size, length=length,
      softmax_dtype=jnp.dtype(softmax_dtype),
  )
  return x, out
