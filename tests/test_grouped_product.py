"""ops/grouped_product.py: the grouped products as a Pallas call whose grid
follows the groups, in interpret mode on the CPU.

Against `jax.lax.ragged_dot` (the form it replaces on one TPU) and against
a plain float32 loop over the groups: empty groups, one group holding every
row, boundaries that fall inside a row tile and inside a part of one, rows
behind the last group (never read: poisoned with NaN; never written), the
call that multiplies a row tile by gate and up at once against the
three-call form with the routing weight, both published width pairs scaled
down; the grid's bookkeeping (`visits`); and `ops/moe.py::
grouped_product_path`'s answers by shape, dtype and
`may_choose_kernels()`, with `held_experts` taking the kernel where the
rule says so.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.ops import grouped_product
from deepconsensus_tpu.ops import moe
from deepconsensus_tpu.ops import moe_combine
from deepconsensus_tpu.ops import pallas_util

# (hidden, expert width): kanana_polish's 2048 x 768 and qwen3next_polish's
# 2048 x 512, a quarter of each.
WIDTHS = {'w768': (512, 192 + 64), 'w512': (512, 128)}
# Rows a group; every case over 1,024 rows = two tiles of 512 = eight parts.
COUNTS = {
    'uneven': [100, 37, 400, 203, 1, 283],
    'empty_groups': [0, 300, 0, 0, 724, 0],
    'one_group_holds_every_row': [0, 0, 1024, 0, 0, 0],
    'boundaries_on_tiles_and_parts': [128, 384, 512, 0, 0, 0],
    'rows_behind_the_last_group': [90, 0, 310, 45, 0, 7],
    'nothing_held': [0, 0, 0, 0, 0, 0],
}
ROWS = 1024


def bounds_of(counts):
  return jnp.concatenate(
      [jnp.zeros(1, jnp.int32), jnp.cumsum(jnp.asarray(counts, jnp.int32))])


def draw(seed, *shape, dtype=jnp.float32):
  scale = shape[-2] ** -0.5 if len(shape) == 3 else 1.0
  return jnp.asarray(
      np.random.default_rng(seed).normal(0, scale, shape), dtype)


def loop_product(rows, w, counts):
  """The plain form: one float32 product a group."""
  out = np.zeros((rows.shape[0], w.shape[2]), np.float32)
  start = 0
  for g, count in enumerate(counts):
    out[start:start + count] = (
        np.asarray(rows[start:start + count], np.float32)
        @ np.asarray(w[g], np.float32))
    start += count
  return out


@pytest.mark.parametrize('widths', sorted(WIDTHS))
@pytest.mark.parametrize('case', sorted(COUNTS))
def test_product_is_the_plain_loops_and_ragged_dots(case, widths):
  counts = COUNTS[case]
  k, n = WIDTHS[widths]
  held = sum(counts)
  for a, b in ((k, n), (n, k)):  # up and gate; down
    rows, w = draw(1, ROWS, a), draw(2, len(counts), a, b)
    # What lies behind the last group is never read.
    rows = rows.at[held:].set(jnp.nan)
    got = np.asarray(jax.jit(lambda r, w, c: grouped_product.grouped_product(
        r, w, bounds_of(c), interpret=True))(rows, w, jnp.asarray(counts)))
    np.testing.assert_allclose(got[:held], loop_product(rows, w, counts)[:held],
                               atol=2e-5)
    ragged = jax.lax.ragged_dot(
        rows.at[held:].set(0.0), w, jnp.asarray(counts, jnp.int32),
        preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got[:held], np.asarray(ragged)[:held],
                               atol=2e-5)
    assert np.isfinite(got[:held]).all()


@pytest.mark.parametrize('case', ['uneven', 'rows_behind_the_last_group'])
def test_bfloat16_rows_leave_in_bfloat16_from_a_float32_accumulator(case):
  counts = COUNTS[case]
  k, n = WIDTHS['w768']
  held = sum(counts)
  rows = draw(3, ROWS, k, dtype=jnp.bfloat16)
  w = draw(4, len(counts), k, n, dtype=jnp.bfloat16)
  got = grouped_product.grouped_product(rows, w, bounds_of(counts),
                                        interpret=True)
  assert got.dtype == jnp.bfloat16 and got.shape == (ROWS, n)
  # The float32 sum of the bfloat16 operands' products, rounded once.
  want = jnp.asarray(loop_product(rows, w, counts), jnp.bfloat16)
  np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                             np.asarray(want[:held], np.float32),
                             rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('widths', sorted(WIDTHS))
@pytest.mark.parametrize('case', ['uneven', 'empty_groups',
                                  'rows_behind_the_last_group'])
def test_gate_and_up_in_one_call_are_the_three_call_form(case, widths, dtype):
  """silu(gate) * up * weight from one read of the rows, each product
  rounded to the rows' type before the float32 silu, as `held_experts`
  writes it with three `ragged_dot` calls; then the down product."""
  counts = COUNTS[case]
  k, n = WIDTHS[widths]
  held, groups = sum(counts), len(counts)
  rows = draw(5, ROWS, k, dtype=dtype)
  w_gate, w_up = (draw(s, groups, k, n, dtype=dtype) for s in (6, 7))
  w_down = draw(8, groups, n, k, dtype=dtype)
  weight = jnp.asarray(np.random.default_rng(9).uniform(0.1, 1.0, ROWS),
                       jnp.float32)
  group_sizes = jnp.asarray(counts, jnp.int32)
  ragged = lambda a, w: jax.lax.ragged_dot(
      a, w, group_sizes, preferred_element_type=a.dtype)
  hidden = jax.nn.silu(ragged(rows, w_gate).astype(jnp.float32))
  hidden = hidden * ragged(rows, w_up).astype(jnp.float32)
  hidden = (hidden * weight[:, None]).astype(dtype)
  want = ragged(hidden, w_down)

  bounds = bounds_of(counts)
  got_hidden = grouped_product.gated_up(rows, w_gate, w_up, weight, bounds,
                                        interpret=True)
  assert got_hidden.dtype == dtype and got_hidden.shape == (ROWS, n)
  got = grouped_product.grouped_product(got_hidden, w_down, bounds,
                                        interpret=True)
  # float32: the same sums in another order. bfloat16: a product that
  # lands on the other side of a rounding moves its output one step.
  tol = dict(atol=3e-5) if dtype == jnp.float32 else dict(
      rtol=2 ** -6, atol=2 ** -7)
  for g, w in ((got_hidden, hidden), (got, want)):
    np.testing.assert_allclose(np.asarray(g[:held], np.float32),
                               np.asarray(w[:held], np.float32), **tol)


@pytest.mark.parametrize('tm', [128, 512])
@pytest.mark.parametrize('case', sorted(COUNTS))
def test_visits_walk_each_groups_tiles_once_and_in_order(case, tm):
  counts = COUNTS[case]
  bounds = np.asarray(bounds_of(counts))
  group, tile, count = (np.asarray(a) for a in grouped_product.visits(
      jnp.asarray(bounds), ROWS, tm))
  assert len(group) == len(tile) == ROWS // tm + len(counts) - 1
  want = [(g, t) for g in range(len(counts)) if counts[g]
          for t in range(bounds[g] // tm, (bounds[g + 1] - 1) // tm + 1)]
  assert count == len(want)
  assert list(zip(group[:count], tile[:count])) == want
  # A tile is revisited only by neighbours: its output block stays put.
  assert np.all(np.diff(tile[:count]) >= 0)
  # What lies behind the count indexes a block that exists.
  assert group.max(initial=0) < len(counts) and tile.max() < ROWS // tm


def test_layers_alike_trace_the_kernel_once(monkeypatch):
  """A stack's expert layers call the kernel at one shape: the second
  call finds the first one's trace (each costs the chip's host a third of
  a second of set-up), under whatever scope it is made."""
  traced = []
  kernel = grouped_product._kernel
  monkeypatch.setattr(
      grouped_product, '_kernel',
      lambda *refs, **sizes: traced.append(1) or kernel(*refs, **sizes))
  rows, w = draw(10, 384, 128), draw(11, 3, 128, 256)  # no other test's shape
  bounds = bounds_of([100, 200, 84])

  @jax.jit
  def two_layers(rows, w, bounds):
    with jax.named_scope('moe_1'):
      a = grouped_product.grouped_product(rows, w, bounds, interpret=True)
    with jax.named_scope('moe_2'):
      b = grouped_product.grouped_product(rows + 1.0, w, bounds,
                                          interpret=True)
    return a, b

  a, b = two_layers(rows, w, bounds)
  assert len(traced) == 1
  np.testing.assert_allclose(np.asarray(a), loop_product(rows, w, [100, 200, 84]),
                             atol=2e-5)
  np.testing.assert_allclose(np.asarray(b),
                             loop_product(rows + 1.0, w, [100, 200, 84]),
                             atol=2e-5)


def test_a_tile_that_divides_the_rows_or_none():
  # Widths of 512 and 768 at hidden 2048 keep today's block: a group's
  # matrices whole, gate and up together and the down product alike.
  assert grouped_product.tiles(153_600, 2048, 768, matrices=2) == (512, 768)
  assert grouped_product.tiles(153_600, 768, 2048) == (512, 2048)
  assert grouped_product.tiles(256_000, 2048, 512, matrices=2) == (512, 512)
  assert grouped_product.tiles(256_000, 512, 2048) == (512, 2048)
  assert grouped_product.tiles(768, 128, 256) == (256, 256)
  assert grouped_product.tiles(640, 128, 256) == (128, 256)
  assert grouped_product.tiles(1000, 128, 256) is None
  assert grouped_product.tiles(1024, 64, 256) is None
  assert grouped_product.tiles(1024, 128, 24) is None
  with pytest.raises(ValueError, match='no tile'):
    grouped_product.grouped_product(
        jnp.zeros((1000, 128)), jnp.zeros((2, 128, 128)), bounds_of([5, 5]),
        interpret=True)


@pytest.mark.parametrize('where,want', [
    ('tpu', 'group_kernel'), ('cpu', 'ragged_dot'), ('tpu_mesh', 'ragged_dot'),
    ('tpu_float32', 'ragged_dot'), ('tpu_rows_no_tile_divides', 'ragged_dot'),
    ('tpu_width_of_24', 'ragged_dot'), ('tpu_undeclared', 'ragged_dot')])
def test_the_rule_takes_the_kernel_on_one_tpu_in_bfloat16(where, want,
                                                          monkeypatch):
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: where != 'cpu')
  rows = 1000 if where == 'tpu_rows_no_tile_divides' else 153_600
  width = 24 if where == 'tpu_width_of_24' else 768
  dtype = jnp.float32 if where == 'tpu_float32' else jnp.bfloat16
  ask = lambda: moe.grouped_product_path(rows, 128, 2048, width, dtype)
  if where == 'tpu_undeclared':
    assert ask() == want
    return
  with pallas_util.single_device_inference(where != 'tpu_mesh'):
    assert ask() == want
    # The down product is the same question with the widths exchanged.
    assert moe.grouped_product_path(rows, 128, width, 2048, dtype) == want


def _routed(dtype, seed=0, n=256, hidden=128, width=128, experts=8, top_k=4,
            first=2, held=4):
  rng = np.random.default_rng(seed)
  x = jnp.asarray(rng.normal(size=(n, hidden)), dtype)
  weights, chosen = moe.route_top_k(
      jnp.asarray(rng.normal(size=(n, experts)) * 2, jnp.float32), top_k, True)
  w = lambda a, b: jnp.asarray(rng.normal(0, a ** -0.5, (held, a, b)), dtype)
  return (x, weights, chosen, w(hidden, width), w(hidden, width),
          w(width, hidden), first)


@contextlib.contextmanager
def kernel_taken(monkeypatch):
  """As on one TPU: the rule takes the kernel, which runs interpreted."""
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  monkeypatch.setattr(pallas_util, 'resolve_interpret', lambda _: True)
  with pallas_util.single_device_inference():
    yield


def test_held_experts_through_the_kernel_are_held_experts_through_ragged_dot(
    monkeypatch):
  args = _routed(jnp.bfloat16)
  want, want_counts = moe.held_experts(*args)
  traced = []
  real = jax.lax.ragged_dot
  monkeypatch.setattr(jax.lax, 'ragged_dot',
                      lambda *a, **k: traced.append(1) or real(*a, **k))
  with kernel_taken(monkeypatch):
    got, counts = moe.held_experts(*args)
  # One path a regime: where the rule takes the kernel no ragged_dot runs.
  assert not traced
  assert got.dtype == jnp.bfloat16
  assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
  # Half the assignments fall on experts held elsewhere: rows behind the
  # last held group.
  assert 0 < int(want_counts.sum()) < 256 * 4
  np.testing.assert_allclose(np.asarray(got, np.float32),
                             np.asarray(want, np.float32),
                             rtol=2 ** -6, atol=2 ** -6)


def test_what_the_kernel_leaves_behind_the_last_group_is_never_summed(
    monkeypatch):
  """The kernel neither visits nor zeroes the rows behind the last held
  group: whatever they hold (here NaN) the combine masks."""
  args = _routed(jnp.bfloat16, seed=1)
  with kernel_taken(monkeypatch):
    want, _ = moe.held_experts(*args)

  def poisoned(call):
    def wrapped(*operands):
      out, bounds = call(*operands), operands[-1]
      behind = jnp.arange(out.shape[0])[:, None] >= bounds[-1]
      return jnp.where(behind, jnp.nan, out)
    return wrapped

  monkeypatch.setattr(grouped_product, 'gated_up',
                      poisoned(grouped_product.gated_up))
  monkeypatch.setattr(grouped_product, 'grouped_product',
                      poisoned(grouped_product.grouped_product))
  with kernel_taken(monkeypatch):
    got, _ = moe.held_experts(*args)
  assert np.isfinite(np.asarray(got, np.float32)).all()
  assert np.array_equal(np.asarray(got, np.float32),
                        np.asarray(want, np.float32))


def test_turns_take_the_kernel_too(monkeypatch):
  args = _routed(jnp.bfloat16, seed=2)
  with kernel_taken(monkeypatch):
    whole, counts = moe.held_experts(*args)
    # 128 tokens a turn: 512 rows of 128 in bfloat16.
    monkeypatch.setattr(moe, 'MAX_TURN_BYTES', 128 * 4 * 128 * 2)
    assert moe.turns_of(256, 4, 128, jnp.bfloat16) == 2
    in_turn, counts_in_turn = moe.held_experts(*args)
  np.testing.assert_allclose(np.asarray(in_turn, np.float32),
                             np.asarray(whole, np.float32),
                             rtol=2 ** -7, atol=2 ** -7)
  assert np.array_equal(np.asarray(counts), np.asarray(counts_in_turn))


def test_turns_the_tokens_bound_halves_are_the_turns_it_halves(monkeypatch):
  """Two turns by the rows' bound against four by the tokens' bound, both
  on the kernels (grouped products and combine): a token's assignments,
  rows, products and sum are the same whichever turn holds it, so the
  output and the counts are the same to the bit."""
  args = _routed(jnp.bfloat16, seed=3, n=512)
  taken = []
  combine = moe_combine.combine
  monkeypatch.setattr(moe_combine, 'combine',
                      lambda *a, **k: taken.append(1) or combine(*a, **k))
  with kernel_taken(monkeypatch):
    # 256 tokens a turn: 1,024 rows of 128 in bfloat16.
    monkeypatch.setattr(moe, 'MAX_TURN_BYTES', 256 * 4 * 128 * 2)
    assert moe.turns_of(512, 4, 128, jnp.bfloat16) == 2
    two, two_counts = moe.held_experts(*args)
    # 128 tokens a turn by the tokens' bound alone.
    monkeypatch.setattr(moe, 'MAX_TURN_TOKEN_BYTES', 128 * 128 * 2)
    assert moe.turns_of(512, 4, 128, jnp.bfloat16) == 4
    assert moe.grouped_product_path(128 * 4, 4, 128, 128, jnp.bfloat16) == (
        moe.GROUPED_GROUP_KERNEL)
    assert moe.combine_path(128, 4, 4, 128, jnp.bfloat16) == (
        moe.COMBINE_TOKEN_TILE_KERNEL)
    four, four_counts = moe.held_experts(*args)
  # Each form traced the combine's kernel once, in its loop's body.
  assert taken == [1, 1]
  assert np.array_equal(np.asarray(two_counts), np.asarray(four_counts))
  assert np.array_equal(np.asarray(two, np.float32),
                        np.asarray(four, np.float32))


def test_the_rule_declines_toy_widths_and_float32_on_the_cpu_and_a_tpu(
    monkeypatch):
  """What tier-1's other files rely on: at their widths (hidden 32-64,
  float32) the program is the parent's wherever it runs."""
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    assert moe.grouped_product_path(240, 8, 32, 24, jnp.float32) == (
        moe.GROUPED_RAGGED_DOT)
    assert moe.grouped_product_path(240, 8, 64, 24, jnp.bfloat16) == (
        moe.GROUPED_RAGGED_DOT)


# ------------------------------------------- matrices wider than the VMEM

def test_a_matrix_of_4096_by_4096_passes_in_column_blocks_by_the_vmem_rule():
  """One [4096, 4096] matrix is 32 MiB in bfloat16, the call's whole scoped
  VMEM, and gate and up are two, each buffered twice: asked by widths
  alone the rule would take the kernel and Mosaic refuse it."""
  limit = pallas_util.GROUPED_PRODUCT_VMEM_LIMIT_BYTES
  rows = 102_400  # one turn of commanda_polish: 12,800 tokens x 8
  assert grouped_product.vmem_bytes(rows, 512, 4096, 4096, 2) > 4 * limit
  assert grouped_product.tiles(rows, 4096, 4096, matrices=2) == (512, 512)
  assert grouped_product.tiles(rows, 4096, 4096) == (512, 1024)
  for matrices, tn in ((2, 512), (1, 1024)):
    assert grouped_product.vmem_bytes(rows, 512, 4096, tn, matrices) <= (
        limit * 7 // 8)
    # The next wider block does not fit.
    assert grouped_product.vmem_bytes(rows, 512, 4096, 2 * tn, matrices) > (
        limit * 7 // 8)


def test_no_block_that_fits_declines_the_kernel(monkeypatch):
  monkeypatch.setattr(pallas_util, 'on_tpu', lambda: True)
  with pallas_util.single_device_inference():
    assert moe.grouped_product_path(102_400, 16, 4096, 4096, jnp.bfloat16) == (
        moe.GROUPED_GROUP_KERNEL)
    # A row tile of [512, 65536] alone is 64 MiB: no column block helps.
    assert grouped_product.tiles(102_400, 65_536, 4096, matrices=2) is None
    assert moe.grouped_product_path(
        102_400, 16, 65_536, 4096, jnp.bfloat16) == moe.GROUPED_RAGGED_DOT


@pytest.fixture
def narrow_vmem(monkeypatch):
  """A scoped VMEM so small that float32 [256, 384] matrices pass as three
  column blocks of 128 (and gate and up of 256 columns as two), the kernel
  traced anew under it."""
  monkeypatch.setattr(pallas_util, 'GROUPED_PRODUCT_VMEM_LIMIT_BYTES',
                      2_700_000)
  grouped_product._call.clear_cache()
  yield
  grouped_product._call.clear_cache()


@pytest.mark.parametrize('case', sorted(COUNTS))
def test_column_blocks_are_the_ragged_dot(case, narrow_vmem):
  counts = COUNTS[case]
  k, n = 256, 384
  assert grouped_product.tiles(ROWS, k, n, itemsize=4) == (512, 128)
  rows, w = draw(1, ROWS, k), draw(2, len(counts), k, n)
  got = np.asarray(grouped_product.grouped_product(
      rows, w, bounds_of(counts), interpret=True))
  held = sum(counts)
  want = np.asarray(jax.lax.ragged_dot(
      rows, w, jnp.asarray(counts, jnp.int32)))
  np.testing.assert_allclose(got[:held], want[:held], atol=2e-5)
  np.testing.assert_allclose(got[:held], loop_product(rows, w, counts)[:held],
                             atol=2e-5)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('case', ['uneven', 'rows_behind_the_last_group',
                                  'boundaries_on_tiles_and_parts'])
def test_gate_and_up_in_column_blocks_are_the_whole_matrix_call(
    case, dtype, narrow_vmem, monkeypatch):
  """The same columns of gate and up pass together: the epilogue of a
  block is the epilogue of those columns of the whole-matrix call."""
  counts = COUNTS[case]
  # The narrower type takes the deeper matrices to need the blocks.
  k, n = (256 if dtype == jnp.float32 else 512), 256
  size = jnp.dtype(dtype).itemsize
  assert grouped_product.tiles(ROWS, k, n, matrices=2, itemsize=size)[1] < n
  rows = draw(3, ROWS, k, dtype=dtype)
  w_gate = draw(4, len(counts), k, n, dtype=dtype)
  w_up = draw(5, len(counts), k, n, dtype=dtype)
  weight = jnp.asarray(np.random.default_rng(6).uniform(0.1, 1, ROWS),
                       jnp.float32)
  bounds = bounds_of(counts)
  blocked = np.asarray(grouped_product.gated_up(
      rows, w_gate, w_up, weight, bounds, interpret=True), np.float32)
  monkeypatch.undo()  # the shipped VMEM: the matrices whole
  grouped_product._call.clear_cache()
  assert grouped_product.tiles(ROWS, k, n, matrices=2, itemsize=size)[1] == n
  whole = np.asarray(grouped_product.gated_up(
      rows, w_gate, w_up, weight, bounds, interpret=True), np.float32)
  held = sum(counts)
  # A column of the output is one column of gate and of up: the same
  # products, whatever block they came in.
  assert np.array_equal(blocked[:held], whole[:held])


def _within(tokens, k, hidden):
  """(rows bound, tokens bound): does a turn of `tokens` satisfy each."""
  return (tokens * k * hidden * 2 <= moe.MAX_TURN_BYTES,
          tokens * hidden * 2 <= moe.MAX_TURN_TOKEN_BYTES)


@pytest.mark.parametrize('cell,tokens,k,hidden,turns,binds', [
    ('kanana_polish', 51_200, 6, 2048, 2, 'rows'),
    ('qwen3next_polish', 51_200, 10, 2048, 2, 'rows'),
    ('commanda_polish', 25_600, 8, 4096, 2, 'rows'),
    ('mellum_polish', 51_200, 8, 2304, 4, 'tokens'),
    ('two_assignments_a_token', 51_200, 2, 2048, 2, 'tokens_alone')])
def test_a_turn_is_reckoned_in_bytes_of_one_buffer_of_rows(cell, tokens, k,
                                                           hidden, turns,
                                                           binds):
  """The fewest halvings that satisfy both bounds: one [rows, hidden]
  buffer within MAX_TURN_BYTES, the turn's [tokens, hidden] within
  MAX_TURN_TOKEN_BYTES. 2^18 rows of 4 kB at hidden 2048 (the two cells
  keep their turns of 153,600 and 256,000 assignments), 2^17 rows of 8 kB
  at hidden 4096, a turn's tokens at exactly 100 MiB in all three;
  mellum_polish's 25,600 tokens of 4.5 kB would be 112.5 MiB, so its turn
  is 12,800 (56.25 MiB, 102,400 rows)."""
  del cell
  assert moe.turns_of(tokens, k, hidden, jnp.bfloat16) == turns
  per_turn = tokens // turns
  assert _within(per_turn, k, hidden) == (True, True)
  # One halving fewer breaks the bound that decided.
  rows_fit, tokens_fit = _within(2 * per_turn, k, hidden)
  assert (rows_fit, tokens_fit) == {
      'rows': (False, False), 'tokens': (True, False),
      'tokens_alone': (True, False)}[binds]
  if binds == 'rows':
    assert per_turn * hidden * 2 == moe.MAX_TURN_TOKEN_BYTES
  if binds == 'tokens':
    assert (per_turn, per_turn * k) == (12_800, 102_400)
    assert per_turn * hidden * 2 == 56.25 * 2 ** 20
  if binds == 'tokens_alone':
    # The rows alone would take the pack in one turn.
    assert _within(tokens, k, hidden)[0]
