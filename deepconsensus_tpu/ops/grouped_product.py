"""Grouped products over ragged groups of rows as one Pallas call.

  out[r] = rows[r] @ w[g]   for the rows r of group g, bounds[g] <= r <
                            bounds[g + 1]

the operands of `jax.lax.ragged_dot` (rows sorted by group, one [k, n]
matrix a group), as a kernel whose grid follows the groups: one step a
(group, row tile) pair that share a row, the pairs of a group one after
another, so that a group's weights are one block that is fetched once and
stays in VMEM while the group's row tiles pass. A tile that straddles a
boundary is visited once a group it touches; a visit computes only the
SUB_ROWS-row parts of the tile that hold a row of its group and stores
only that group's rows, so a boundary costs one part computed twice, not a
tile. Rows behind the last group are not visited, not read and not
written.

`gated_up` is the same call with two matrices a group: a row tile is read
once, multiplied by the group's gate and up matrices, and the epilogue
writes silu(gate) * up * row_weight in the rows' type; gate and up are
rounded to the rows' type before the float32 silu, as two separate
products that leave their kernels in that type would be. bfloat16 (or
float32) operands, float32 accumulators.

A group's matrix that does not fit the call's VMEM whole (`tiles`: [4096,
4096] is 32 MiB, gate and up two of them, each buffered twice) passes as
blocks of `tn` columns, for the gated call the same columns of gate and up
together. The column blocks are the grid's OUTER axis and the visits run
under each: a block [k, tn] of a group is fetched when the visits reach
that group and stays while the group's row tiles pass, so every byte of
every held matrix is fetched once a call, as in the whole-matrix form; what
is read once a column block instead is the rows. A tile that two groups
share is still visited by both one after the other, so its output block
stays in VMEM between them. Where the matrix fits whole the grid is the
visits alone, as it was.

Measured on a v5e (PERF.md, PR 35) against the TPU compiler's lowering of
`ragged_dot`, which runs these shapes at 37-38% of the chip's peak.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util

LANES = 128
# The part of a row tile that a visit computes or skips: one pass of an
# MXU's 128 rows (and one row of the routing weights laid 128 to a row).
SUB_ROWS = LANES


def vmem_bytes(rows: int, tm: int, k: int, tn: int, matrices: int,
               itemsize: int = 2) -> int:
  """What a call keeps in VMEM: a group's `matrices` blocks [k, tn], a row
  tile [tm, k] and an output tile [tm, tn], each buffered twice; for the
  gated call every row's float32 weight, twice; and a part's float32
  products and epilogue, [SUB_ROWS, tn] each."""
  return (2 * matrices * k * tn * itemsize + 2 * tm * k * itemsize
          + 2 * tm * tn * itemsize + (matrices > 1) * 2 * rows * 4
          + (matrices + 1) * SUB_ROWS * tn * 4)


def tiles(rows: int, k: int, n: int, matrices: int = 1,
          itemsize: int = 2) -> Optional[Tuple[int, int]]:
  """(rows, columns) of a tile for `rows` sorted rows times `matrices`
  matrices [k, n] a group, or None where the kernel does not take the
  shapes: rows that no tile divides, widths that are not whole lane tiles,
  or blocks that do not fit the call's scoped VMEM with an eighth to spare.

  Rows: the largest of 512, 256 and 128 that divides `rows`; parts are
  skipped inside a tile, so a larger tile costs no more at a boundary and
  pays the grid's step less often (tiles of 1,024 measured no faster).
  Columns: all n where a group's matrices fit whole, else the widest block
  of whole lane tiles that divides n and fits: every block more is one
  more read of the rows."""
  if k % LANES or n % LANES:
    return None
  tm = next((tm for tm in (512, 256, 128) if rows % tm == 0), None)
  if tm is None:
    return None
  budget = pallas_util.GROUPED_PRODUCT_VMEM_LIMIT_BYTES * 7 // 8
  for blocks in range(1, n // LANES + 1):
    tn = n // blocks
    if n % blocks == 0 and tn % LANES == 0 and vmem_bytes(
        rows, tm, k, tn, matrices, itemsize) <= budget:
      return tm, tn
  return None


def visits(bounds: jnp.ndarray, rows: int,
           tm: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
  """The grid of a call: bounds [groups + 1] (ascending row offsets, the
  first 0) -> (group [V], tile [V], count) with V = rows / tm + groups - 1
  the most visits there can be: visit v < count multiplies the rows of
  `group[v]` that lie in row tile `tile[v]`; a group's visits are
  consecutive, tiles ascend, an empty group has none."""
  groups = bounds.shape[0] - 1
  start, end = bounds[:-1], bounds[1:]
  first = start // tm
  held = jnp.where(end > start, (end - 1) // tm - first + 1, 0)
  upto = jnp.cumsum(held)
  visit = jnp.arange(rows // tm + groups - 1, dtype=jnp.int32)
  group = jnp.minimum(
      jnp.searchsorted(upto, visit, side='right'), groups - 1).astype(
          jnp.int32)
  tile = first[group] + visit - (upto[group] - held[group])
  return group, jnp.clip(tile, 0, rows // tm - 1).astype(jnp.int32), upto[-1]


def _kernel(bounds_ref, group_ref, tile_ref, *refs, tm: int, gated: bool,
            visit_axis: int):
  if gated:
    rows_ref, w_gate_ref, w_up_ref, weight_ref, out_ref = refs
  else:
    rows_ref, w_ref, out_ref = refs
  v = pl.program_id(visit_axis)
  g = group_ref[v]
  start, end = bounds_ref[g], bounds_ref[g + 1]
  row0 = tile_ref[v] * tm
  sub = SUB_ROWS  # Divides every tile `tile_rows` gives.
  product = lambda a, w_ref: jnp.dot(
      a, w_ref[...], preferred_element_type=jnp.float32)
  for s in range(tm // sub):
    lo = row0 + s * sub

    @pl.when((lo < end) & (lo + sub > start))
    def _part(s=s, lo=lo):
      at = pl.ds(s * sub, sub)
      a = rows_ref[at, :]
      if gated:
        rounded = lambda t: t.astype(out_ref.dtype).astype(jnp.float32)
        y = jax.nn.silu(rounded(product(a, w_gate_ref)))
        y = y * rounded(product(a, w_up_ref))
        # The part's weights lie along the lanes of one row of
        # `weight_ref`; as a column they are the diagonal's row sums. (A
        # [m, 1] operand instead is a padded copy of 512 bytes a row.)
        along = weight_ref[pl.ds(lo // SUB_ROWS, 1), :]
        square = (SUB_ROWS, SUB_ROWS)
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, square, 0)
                    == jax.lax.broadcasted_iota(jnp.int32, square, 1))
        y = y * jnp.sum(jnp.where(diagonal, along, 0.0), axis=1,
                        keepdims=True)
      else:
        y = product(a, w_ref)
      # The rows of other groups in this part keep what their own visits
      # wrote or will write (storing every part of a group unmasked where
      # no boundary crosses it measured no faster).
      row = lo + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
      out_ref[at, :] = jnp.where((row >= start) & (row < end),
                                 y.astype(out_ref.dtype), out_ref[at, :])


# Traced once a shape and inlined where it is called: a stack's expert
# layers are alike, and tracing the kernel anew for each cost a forward of
# seven of them 4.5 s of set-up on the chip's host (PERF.md, PR 35).
@functools.partial(jax.jit, static_argnames=('interpret',), inline=True)
def _call(rows, weights, bounds, row_weight, interpret: bool):
  m, k = rows.shape
  groups, _, n = weights[0].shape
  gated = row_weight is not None
  tile_of = tiles(m, k, n, len(weights), rows.dtype.itemsize)
  if tile_of is None:
    raise ValueError(
        f'no tile for rows {rows.shape} over {groups} groups of {(k, n)}')
  tm, tn = tile_of
  bounds = bounds.astype(jnp.int32)
  group, tile, count = visits(bounds, m, tm)
  # A block's place from (column block c, visit v) and the visits' lists.
  if tn == n:
    # A group's matrices whole: the grid is the visits.
    grid = (count,)
    at = lambda place: lambda v, bounds, group, tile: place(0, v, group, tile)
  else:
    # Column blocks outside, the visits under each.
    grid = (n // tn, count)
    at = lambda place: lambda c, v, bounds, group, tile: place(
        c, v, group, tile)
  by_tile = lambda width: pl.BlockSpec(
      (tm, width), at(lambda c, v, group, tile: (tile[v], 0)))
  by_group = pl.BlockSpec(
      (None, k, tn), at(lambda c, v, group, tile: (group[v], 0, c)))
  out_spec = pl.BlockSpec(
      (tm, tn), at(lambda c, v, group, tile: (tile[v], c)))
  operands = [rows] + [w.astype(rows.dtype) for w in weights]
  in_specs = [by_tile(k)] + [by_group] * len(weights)
  if gated:
    # Every row's weight, 128 to a row, whole in VMEM (half a megabyte);
    # what lies behind the last group weighs nothing. That `where` is also
    # what stands between the sort that yields the weights and this call:
    # handed the sort's output itself, XLA's memory assignment no longer
    # kept the dispatch gather's source in VMEM, and the gather of
    # kanana_polish ran 10.2 ms a layer for 1.5 (PERF.md, PR 35;
    # tests/test_tpu_compile.py holds the placement).
    held = jax.lax.broadcasted_iota(jnp.int32, (m,), 0) < bounds[-1]
    row_weight = jnp.where(held, row_weight.astype(jnp.float32), 0.0)
    operands.append(row_weight.reshape(m // SUB_ROWS, SUB_ROWS))
    in_specs.append(pl.BlockSpec(
        (m // SUB_ROWS, SUB_ROWS), at(lambda c, v, group, tile: (0, 0))))
  return pl.pallas_call(
      functools.partial(_kernel, tm=tm, gated=gated,
                        visit_axis=len(grid) - 1),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
          out_specs=out_spec),
      out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('arbitrary',) * len(grid),
          vmem_limit_bytes=pallas_util.GROUPED_PRODUCT_VMEM_LIMIT_BYTES),
      interpret=interpret,
      name='grouped_gated_up' if gated else 'grouped_product',
  )(bounds, group, tile, *operands)


def grouped_product(rows: jnp.ndarray, w: jnp.ndarray, bounds: jnp.ndarray,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
  """rows [m, k] sorted by group, w [groups, k, n], bounds [groups + 1]
  -> [m, n] in rows' type from a float32 accumulator; rows at or behind
  bounds[-1] hold whatever the buffer held."""
  return _call(rows, (w,), bounds, None,
               interpret=pallas_util.resolve_interpret(interpret))


def gated_up(rows: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
             row_weight: jnp.ndarray, bounds: jnp.ndarray,
             interpret: Optional[bool] = None) -> jnp.ndarray:
  """silu(rows @ w_gate[g]) * (rows @ w_up[g]) * row_weight [m] float32,
  each product rounded to rows' type first -> [m, n] in rows' type."""
  return _call(rows, (w_gate, w_up), bounds, row_weight,
               interpret=pallas_util.resolve_interpret(interpret))
