"""ops/moe_combine.py: the experts' combine as a Pallas call a tile of
tokens, in interpret mode on the CPU.

Against the gather form of `ops/moe.py::_held_experts` (XLA's gather of one
row an assignment, a `where` and a float32 sum over k): k = 6 with every
expert held and k = 10 with half of them, an expert nobody chose, a token
with no held assignment (its row exactly zero), the rows of `out` behind
the last held group filled with NaN (nothing of them reaches y); the
result within one bfloat16 unit in the last place, since the sum is the
0/1 product's; the bookkeeping XLA does for the kernel (`_runs`: a tile's
8-row blocks and what each buffer row then holds) against a plain loop,
with the buffer's `capacity` at runs that all straddle a block;
`ops/moe.py::combine_path`'s answers by shape, dtype and
`may_choose_kernels()`; `held_experts` through the kernel, and one trace
for two layers alike.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.ops import moe
from deepconsensus_tpu.ops import moe_combine
from deepconsensus_tpu.ops import pallas_util
from tests.test_grouped_product import _routed as routed_experts
from tests.test_grouped_product import kernel_taken as as_on_one_tpu

HIDDEN = 256
CASES = {
    # tokens, k, experts published, first held, held
    'k6_all_held': (256, 6, 16, 0, 16),
    'k10_half_held': (256, 10, 32, 0, 16),
    'k10_the_upper_half_held': (256, 10, 32, 16, 16),
    'k3_of_8_four_held_three_tiles': (384, 3, 8, 2, 4),
    'k2_one_held_expert': (128, 2, 8, 5, 1),
}


def routed(n, k, experts, first, held, seed=0, nobody_chose=None,
           nothing_held_by=()):
  """A turn as `_held_experts` sorts it -> (out [n * k, H] bfloat16 with
  NaN behind the last held group, group [n, k], place [n, k] with -1 held
  elsewhere, bounds [held + 1], mine [n, k])."""
  rng = np.random.default_rng(seed)
  scores = rng.random((n, experts))
  if nobody_chose is not None:
    scores[:, nobody_chose] = -1.0
  for token in nothing_held_by:  # every choice falls outside the share
    scores[token, first:first + held] = -1.0
  chosen = np.argsort(-scores, axis=1)[:, :k].astype(np.int32)
  local = chosen - first
  mine = (local >= 0) & (local < held)
  group = np.where(mine, local, held).astype(np.int32)
  order = np.argsort(group.reshape(-1), kind='stable')
  bounds = np.searchsorted(group.reshape(-1)[order],
                           np.arange(held + 1)).astype(np.int32)
  place = np.empty(n * k, np.int32)
  place[order] = np.arange(n * k, dtype=np.int32)
  out = rng.normal(size=(n * k, HIDDEN)).astype(np.float32)
  out[bounds[-1]:] = np.nan
  return (jnp.asarray(out, jnp.bfloat16), jnp.asarray(group),
          jnp.asarray(np.where(mine, place.reshape(n, k), -1)),
          jnp.asarray(bounds), mine)


def gathered(out, place):
  """The gather form's arithmetic: `_held_experts`'s combine block."""
  n, k = place.shape
  mine_t = (place >= 0).T
  back = jnp.take(out, jnp.where(mine_t, place.T, 0).reshape(n * k), axis=0,
                  mode='clip').reshape(k, n, -1)
  return jnp.sum(jnp.where(mine_t[..., None], back, jnp.zeros((), back.dtype)),
                 axis=0, dtype=jnp.float32).astype(out.dtype)


def assert_within_one_bfloat16_ulp(got, want):
  got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
  assert np.isfinite(got).all()
  magnitude = np.maximum(np.abs(want), np.float32(2.0 ** -126))
  ulp = 2.0 ** (np.floor(np.log2(magnitude)) - 7)
  assert (np.abs(got - want) <= ulp).all()
  assert (got[want == 0] == 0).all()


@pytest.mark.parametrize('case', sorted(CASES))
def test_the_kernel_adds_what_the_gather_adds(case):
  n, k, experts, first, held = CASES[case]
  out, group, place, bounds, mine = routed(n, k, experts, first, held)
  assert (held == experts) == bool(mine.all())
  got = moe_combine.combine(out, group, place, bounds, interpret=True)
  assert got.shape == (n, HIDDEN) and got.dtype == jnp.bfloat16
  assert_within_one_bfloat16_ulp(got, gathered(out, place))


@pytest.mark.parametrize('case', ['k6_all_held', 'k10_half_held'])
def test_an_expert_nobody_chose_has_no_run_and_no_copy(case):
  n, k, experts, first, held = CASES[case]
  out, group, place, bounds, _ = routed(n, k, experts, first, held, seed=1,
                                        nobody_chose=first + 3)
  assert int(bounds[4] - bounds[3]) == 0
  got = moe_combine.combine(out, group, place, bounds, interpret=True)
  assert_within_one_bfloat16_ulp(got, gathered(out, place))


def test_a_token_with_no_held_assignment_gets_exactly_zero():
  n, k, experts, first, held = CASES['k10_half_held']
  lonely = (0, 77, 128, 255)
  out, group, place, bounds, mine = routed(n, k, experts, first, held, seed=2,
                                           nothing_held_by=lonely)
  assert not mine[list(lonely)].any()
  got = np.asarray(
      moe_combine.combine(out, group, place, bounds, interpret=True),
      np.float32)
  assert (got[list(lonely)] == 0).all()
  assert_within_one_bfloat16_ulp(got, gathered(out, place))


def test_nothing_held_at_all_is_all_zeros():
  n, k = 128, 4
  group = jnp.full((n, k), 4, jnp.int32)
  out = jnp.full((n * k, HIDDEN), jnp.nan, jnp.bfloat16)
  got = moe_combine.combine(out, group, jnp.full((n, k), -1, jnp.int32),
                            jnp.zeros(5, jnp.int32), interpret=True)
  assert (np.asarray(got, np.float32) == 0).all()


def test_what_lies_behind_the_last_held_group_reaches_nothing():
  """The last held row's block is copied whole: the rows behind it, which
  nobody wrote, are made zeros before the kernel multiplies them by zero."""
  n, k, experts, first, held = CASES['k10_half_held']
  out, group, place, bounds, _ = routed(n, k, experts, first, held, seed=3)
  end = int(bounds[-1])
  assert end % moe_combine.BLOCK  # the block of the last held row straddles
  assert np.isnan(np.asarray(out[end:], np.float32)).all()
  clean = jnp.where(jnp.arange(out.shape[0])[:, None] < end, out, 0)
  got = moe_combine.combine(out, group, place, bounds, interpret=True)
  want = moe_combine.combine(clean, group, place, bounds, interpret=True)
  assert np.array_equal(np.asarray(got, np.float32),
                        np.asarray(want, np.float32))


def runs_by_loop(group, bounds, rows):
  """`_runs` as a plain loop over tiles and groups."""
  block = moe_combine.BLOCK
  tiles, groups = group.shape[0], len(bounds) - 1
  count = np.zeros(tiles, np.int32)
  blocks = np.zeros((tiles, rows // block), np.int32)
  source = np.full((tiles, rows), moe_combine.NO_ROW, np.int32)
  taken = bounds[:-1].copy()
  for t in range(tiles):
    at = 0
    for g in range(groups):
      held = int((group[t] == g).sum())
      if not held:
        continue
      start, end = taken[g], taken[g] + held
      taken[g] = end
      for b in range(start // block, (end - 1) // block + 1):
        blocks[t, at] = b
        for r in range(block):
          if start <= b * block + r < end:
            source[t, at * block + r] = b * block + r
        at += 1
    count[t] = at
  return count, blocks, source


@pytest.mark.parametrize('case', sorted(CASES))
def test_the_blocks_and_sources_xla_lists_are_the_runs_of_each_tile(case):
  n, k, experts, first, held = CASES[case]
  _, group, place, bounds, mine = routed(n, k, experts, first, held, seed=4)
  rows = moe_combine.capacity(k, held)
  by_tile = np.asarray(group).reshape(n // moe_combine.TILE, -1)
  count, block, source = jax.jit(moe_combine._runs, static_argnums=2)(
      jnp.asarray(by_tile), bounds, rows)
  want_count, want_block, want_source = runs_by_loop(
      by_tile, np.asarray(bounds), rows)
  assert np.array_equal(np.asarray(count), want_count)
  assert np.array_equal(np.asarray(block), want_block)
  assert np.array_equal(np.asarray(source), want_source)
  # Every held assignment of a tile finds its row once in the tile's buffer.
  place = np.asarray(place).reshape(by_tile.shape[0], -1)
  for t in range(by_tile.shape[0]):
    own = np.sort(place[t][place[t] >= 0])
    listed = np.sort(want_source[t][want_source[t] >= 0])
    assert np.array_equal(own, listed)
  assert mine.sum() == (want_source >= 0).sum()


def test_capacity_holds_runs_that_all_straddle_a_block():
  """The most a tile can ask of its buffer: as many runs as it has
  assignments. One row a run is one block a run; two rows a run, the first
  the last row of its block, are two blocks for two rows."""
  k, groups = 3, 1000
  tile, block = moe_combine.TILE, moe_combine.BLOCK
  rows = moe_combine.capacity(k, groups)
  assert rows % moe_combine.SEGMENT == 0
  assert rows >= tile * k + 14 * tile * k
  # Every group's rows start at the last row of a block: 7, 15, 23, ...
  bounds = 7 + block * jnp.arange(groups + 1, dtype=jnp.int32)
  singles = jnp.arange(tile * k, dtype=jnp.int32)[None, :]
  count, listed, _ = moe_combine._runs(singles, bounds, rows)
  assert int(count[0]) == tile * k <= rows // block
  assert np.array_equal(np.asarray(listed[0, :tile * k]), np.arange(tile * k))
  pairs = jnp.repeat(jnp.arange(tile * k // 2, dtype=jnp.int32), 2)[None, :]
  count, _, source = moe_combine._runs(pairs, bounds, rows)
  assert int(count[0]) == tile * k <= rows // block
  assert int((np.asarray(source) >= 0).sum()) == tile * k


@pytest.mark.parametrize('where,want', [
    ('tpu', 'token_tile_kernel'), ('cpu', 'gather'), ('tpu_mesh', 'gather'),
    ('tpu_float32', 'gather'), ('tpu_tokens_no_tile_divides', 'gather'),
    ('tpu_hidden_of_64', 'gather'), ('tpu_undeclared', 'gather'),
    ('tpu_buffers_beyond_vmem', 'gather')])
def test_the_rule_takes_the_kernel_on_one_tpu_in_bfloat16(where, want,
                                                          monkeypatch):
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: where != 'cpu')
  n = 25_000 if where == 'tpu_tokens_no_tile_divides' else 25_600
  hidden = 64 if where == 'tpu_hidden_of_64' else 2048
  k = 32 if where == 'tpu_buffers_beyond_vmem' else 6
  dtype = jnp.float32 if where == 'tpu_float32' else jnp.bfloat16
  ask = lambda groups: moe.combine_path(n, k, groups, hidden, dtype)
  if where == 'tpu_undeclared':
    assert ask(128) == want
    return
  with pallas_util.single_device_inference(where != 'tpu_mesh'):
    assert ask(128) == want
    # qwen3next_polish's turn: ten assignments over 256 held experts.
    if where in ('tpu', 'cpu'):
      assert moe.combine_path(25_600, 10, 256, 2048, dtype) == want


def test_both_cells_buffers_fit_the_calls_vmem():
  for k, groups, rows in ((6, 128, 2560), (10, 256, 5120)):
    assert moe_combine.capacity(k, groups) == rows
    assert moe_combine.vmem_bytes(k, groups, 2048) <= (
        pallas_util.COMBINE_VMEM_LIMIT_BYTES * 7 // 8)


def test_held_experts_through_the_kernel_are_held_experts_through_the_gather(
    monkeypatch):
  args = routed_experts(jnp.bfloat16)
  taken = []
  real = moe_combine.combine
  monkeypatch.setattr(moe_combine, 'combine',
                      lambda *a, **k: taken.append(1) or real(*a, **k))
  with as_on_one_tpu(monkeypatch):
    got, counts = moe.held_experts(*args)
    assert taken == [1]
    # The same grouped products, the combine by the gather: only it differs.
    monkeypatch.setattr(moe, 'combine_path', lambda *_: moe.COMBINE_GATHER)
    want, want_counts = moe.held_experts(*args)
    assert taken == [1]
  assert got.dtype == jnp.bfloat16
  assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
  assert 0 < int(want_counts.sum()) < 256 * 4
  assert_within_one_bfloat16_ulp(got, want)


def test_float32_and_the_cpu_keep_the_gather(monkeypatch):
  taken = []
  monkeypatch.setattr(moe_combine, 'combine',
                      lambda *a, **k: taken.append(1))
  moe.held_experts(*routed_experts(jnp.bfloat16))  # the CPU
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    moe.held_experts(*routed_experts(jnp.float32))
  assert not taken


def test_two_layers_alike_trace_the_kernel_once(monkeypatch):
  traced = []
  real = moe_combine._kernel
  monkeypatch.setattr(
      moe_combine, '_kernel',
      lambda *a, **k: traced.append(1) or real(*a, **k))
  moe_combine._call.clear_cache()
  args = routed_experts(jnp.bfloat16, seed=3)

  @jax.jit
  def two_layers(*args):
    with as_on_one_tpu(monkeypatch):
      first, _ = moe.held_experts(*args)
      second, _ = moe.held_experts(first, *args[1:])
    return second

  two_layers(*args)
  assert len(traced) == 1
