"""The experts' combine as one Pallas call over tiles of tokens.

  y[t] = sum over the held assignments (t, j) of out[place[t, j]]

`out` [n * k, H] is the down product's output, its rows sorted by (held
expert, token); `place` says where each assignment's row went. A tile of
TILE consecutive tokens needs, of every held expert, ONE run of
consecutive rows (the sort is stable, so inside a group the rows ascend by
token). The kernel copies those runs itself, `out` staying in HBM, into a
VMEM buffer in (expert, token) order, the next tile's copies started
before this tile is added, and brings the rows to their tokens by a
product on the MXU with a 0/1 matrix, a float32 accumulator. Nothing of
[k, n, H] is written; an assignment held elsewhere starts no copy.

A copy moves whole 8-row tiles of `out`. Mosaic refuses a slice of a
[rows, H] array in HBM that is not of whole tiles ("Slice shape along
dimension 0 must be aligned to tiling (8)"): a bfloat16 row lies
interleaved with its neighbour across 16 tiles and is no contiguous piece
of memory. So a run goes as the aligned 8-row blocks that cover it, one
copy a block, and the buffer holds up to 7 rows of other tokens before and
behind a run. XLA lists a tile's blocks and says, for every buffer row,
which row of `out` it will hold if that row is one of the tile's own
(`_runs`); the 0/1 matrix is then `that row == place[t, j]` for any j: a
row outside its run (NO_ROW), or an assignment held elsewhere (place -1),
is selected by nobody. What the matrix does not select is multiplied by
zero, so it has to be finite: the kernel zeroes the buffer behind a tile's
last copy, and `combine` zeroes the (at most 7) rows of `out` that lie
behind the last held group inside a block that holds a held row. A held
row of `out` that is not finite therefore reaches, beside its own token,
the tokens whose tile copies its block (the gather form keeps it to its
token).

The float32 sum is the MXU's, not j = 0 .. k-1 in order, so the result is
promised within one bfloat16 unit in the last place of the gather form's,
not to the bit (on a v5e it came out equal to the bit: PERF.md, PR 37).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util

LANES = 128
# Tokens a grid step: the rows of the 0/1 matrix, one pass of an MXU.
TILE = LANES
# Rows a copy: one tile of a bfloat16 array in HBM.
BLOCK = 8
# Buffer rows a product: what is skipped when a tile's runs end before it.
SEGMENT = 512
# What a buffer row holds that is none of its tile's own rows.
NO_ROW = -2


def capacity(k: int, groups: int) -> int:
  """Buffer rows that hold any tile's runs: TILE * k rows in at most
  min(groups, TILE * k) runs, each with up to 14 rows of other tokens in
  its first and last block; in whole segments."""
  rows = TILE * k + 14 * min(groups, TILE * k)
  return -(-rows // SEGMENT) * SEGMENT


def vmem_bytes(k: int, groups: int, hidden: int) -> int:
  """What the call keeps in VMEM: two buffers of `capacity` rows in
  bfloat16, a token's k places along the lanes, the float32 accumulator
  and a product beside it, and the blocks of y, the places and the buffer
  rows' sources twice each."""
  rows = capacity(k, groups)
  return (2 * rows * hidden * 2 + k * TILE * LANES * 4
          + 2 * TILE * hidden * 4 + 2 * TILE * hidden * 2
          + 2 * TILE * LANES * 4 + 2 * rows * 4)


def fits(n: int, k: int, groups: int, hidden: int) -> bool:
  """Whether the kernel takes these shapes: whole tiles of tokens, rows of
  whole lane tiles, and buffers within the call's scoped VMEM with an
  eighth to spare."""
  if n % TILE or hidden % LANES:
    return False
  return vmem_bytes(k, groups, hidden) <= (
      pallas_util.COMBINE_VMEM_LIMIT_BYTES * 7 // 8)


def _kernel(count_ref, block_ref, source_ref, place_ref, out_hbm, y_ref, buf,
            wanted, acc, sem, *, k: int, rows: int):
  i = pl.program_id(0)
  tiles = pl.num_programs(0) - 1

  @pl.when(i < tiles)
  def _start():
    slot = i % 2

    def copy(b, _):
      row = pl.multiple_of(block_ref[0, b] * BLOCK, BLOCK)
      at = pl.multiple_of(b * BLOCK, BLOCK)
      pltpu.make_async_copy(
          out_hbm.at[pl.ds(row, BLOCK), :],
          buf.at[slot, pl.ds(at, BLOCK), :], sem.at[slot]).start()
      return 0

    jax.lax.fori_loop(0, count_ref[i], copy, 0)

  @pl.when(i > 0)
  def _finish():
    slot = (i - 1) % 2
    blocks = count_ref[i - 1]
    # One wait for the bytes of all the tile's copies, as powers of two.
    bit = 1
    while bit * BLOCK <= rows:
      @pl.when((blocks & bit) != 0)
      def _(bit=bit):
        pltpu.make_async_copy(
            out_hbm.at[pl.ds(0, bit * BLOCK), :],
            buf.at[slot, pl.ds(0, bit * BLOCK), :], sem.at[slot]).wait()
      bit *= 2
    # Whatever the matrix does not select is multiplied by zero: behind the
    # tile's last copy, up to the end of the last segment that is
    # multiplied, the buffer holds what an earlier tile or nobody left.
    per_segment = SEGMENT // BLOCK
    multiplied = jnp.maximum(-(-blocks // per_segment), 1) * per_segment

    def clear(b, _):
      at = pl.multiple_of(b * BLOCK, BLOCK)
      buf[slot, pl.ds(at, BLOCK), :] = jnp.zeros((BLOCK, buf.shape[2]),
                                                  buf.dtype)
      return 0

    jax.lax.fori_loop(blocks, multiplied, clear, 0)
    # A token's k rows of `out`, each along the lanes.
    for j in range(k):
      wanted[j] = jnp.broadcast_to(place_ref[:, j:j + 1], (TILE, LANES))

    def product(segment):
      at = segment * SEGMENT
      parts = []
      for c in range(SEGMENT // LANES):
        held = source_ref[pl.ds(at // LANES + c, 1), :]  # [1, buffer rows]
        hit = wanted[0] == held
        for j in range(1, k):
          hit = hit | (wanted[j] == held)
        parts.append(jnp.where(hit, 1.0, 0.0).astype(buf.dtype))
      chosen = jnp.concatenate(parts, axis=1)  # [TILE, SEGMENT]
      return jnp.dot(chosen, buf[slot, pl.ds(at, SEGMENT), :],
                     preferred_element_type=jnp.float32)

    acc[...] = product(0)
    for segment in range(1, rows // SEGMENT):
      @pl.when(segment * (SEGMENT // BLOCK) < blocks)
      def _(segment=segment):
        acc[...] += product(segment)
    y_ref[...] = acc[...].astype(y_ref.dtype)


def _runs(group, bounds, rows: int):
  """The copies of every tile and what each buffer row then holds.

  group [tiles, TILE * k] the held group of each assignment (`groups` where
  it is held elsewhere), bounds [groups + 1] -> (count [tiles] the 8-row
  blocks a tile copies; block [tiles, rows / 8] their places in `out`, in
  8-row blocks, run after run in group order; source [tiles, rows] the row
  of `out` a buffer row holds where that row is one of the tile's own,
  NO_ROW elsewhere)."""
  groups = bounds.shape[0] - 1
  # How many rows of each held group a tile's tokens have, and where the
  # first of them lies: the group's start and the tiles' rows before it.
  held = jnp.sum(group[:, :, None] == jnp.arange(groups, dtype=jnp.int32),
                 axis=1, dtype=jnp.int32)  # [tiles, groups]
  start = bounds[:-1].astype(jnp.int32) + jnp.cumsum(held, axis=0) - held
  end = start + held
  first = start // BLOCK
  blocks = jnp.where(held > 0, (end - 1) // BLOCK - first + 1, 0)
  before = jnp.cumsum(blocks, axis=1) - blocks
  count = jnp.sum(blocks, axis=1)
  # Block b of a tile's buffer belongs to the one run with before <= b <
  # before + blocks: a masked sum over the groups picks the run's numbers.
  b = jnp.arange(rows // BLOCK, dtype=jnp.int32)[None, :, None]
  inside = (before[:, None, :] <= b) & (b < (before + blocks)[:, None, :])
  pick = lambda a: jnp.sum(jnp.where(inside, a[:, None, :], 0), axis=2)
  block = pick(first - before) + b[:, :, 0]
  row = block[:, :, None] * BLOCK + jnp.arange(BLOCK, dtype=jnp.int32)
  own = (row >= pick(start)[:, :, None]) & (row < pick(end)[:, :, None])
  source = jnp.where(own, row, NO_ROW).reshape(-1, rows)
  return count, jnp.where(b[:, :, 0] < count[:, None], block, 0), source


# Traced once a shape and inlined where it is called, as
# grouped_product._call is: a stack's expert layers are alike.
@functools.partial(jax.jit, static_argnames=('interpret',), inline=True)
def _call(out, group, place, bounds, interpret: bool):
  n, k = place.shape
  hidden = out.shape[1]
  tiles = n // TILE
  rows = capacity(k, bounds.shape[0] - 1)
  count, block, source = _runs(group.reshape(tiles, TILE * k), bounds, rows)
  last = lambda i, count: (jnp.maximum(i - 1, 0), 0)
  return pl.pallas_call(
      functools.partial(_kernel, k=k, rows=rows),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=1, grid=(tiles + 1,),
          in_specs=[
              # Step i starts tile i's copies and adds tile i - 1.
              pl.BlockSpec((None, 1, rows // BLOCK),
                           lambda i, count: (jnp.minimum(i, tiles - 1), 0, 0),
                           memory_space=pltpu.SMEM),
              pl.BlockSpec((None, rows // LANES, LANES),
                           lambda i, count: (jnp.maximum(i - 1, 0), 0, 0)),
              pl.BlockSpec((TILE, k), last),
              pl.BlockSpec(memory_space=pl.ANY),
          ],
          out_specs=pl.BlockSpec((TILE, hidden), last),
          scratch_shapes=[
              pltpu.VMEM((2, rows, hidden), out.dtype),
              pltpu.VMEM((k, TILE, LANES), jnp.int32),
              pltpu.VMEM((TILE, hidden), jnp.float32),
              pltpu.SemaphoreType.DMA((2,))]),
      out_shape=jax.ShapeDtypeStruct((n, hidden), out.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('arbitrary',),
          vmem_limit_bytes=pallas_util.COMBINE_VMEM_LIMIT_BYTES),
      interpret=interpret, name='moe_combine',
  )(count, block.reshape(tiles, 1, rows // BLOCK),
    source.reshape(tiles, rows // LANES, LANES), place, out)


def combine(out: jnp.ndarray, group: jnp.ndarray, place: jnp.ndarray,
            bounds: jnp.ndarray,
            interpret: Optional[bool] = None) -> jnp.ndarray:
  """out [n * k, H] sorted by (group, token); group [n, k] the held group
  of each assignment, `bounds.shape[0] - 1` where it is held elsewhere;
  place [n, k] the assignment's row of `out`, -1 where it is held
  elsewhere; bounds [groups + 1] -> y [n, H] in out's type."""
  m = out.shape[0]
  # The block that holds the last held row may hold rows behind it, which
  # nobody wrote: they are copied with it, so they are made zeros.
  end = bounds[-1].astype(jnp.int32)
  at = jnp.minimum(end, m - 1) // BLOCK * BLOCK
  last = jax.lax.dynamic_slice_in_dim(out, at, BLOCK, axis=0)
  row = at + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
  out = jax.lax.dynamic_update_slice_in_dim(
      out, jnp.where(row < end, last, jnp.zeros((), out.dtype)), at, axis=0)
  y = _call(out, group.astype(jnp.int32), place.astype(jnp.int32), bounds,
            interpret=pallas_util.resolve_interpret(interpret))
  return jax.lax.optimization_barrier(y)
