"""Single-pack-stream ragged engine: packing, byte identity, residency.

The use_ragged_kernel path replaces the per-bucket _WindowPacker fleet
with ONE _RaggedPacker feeding ONE compiled forward
(ModelRunner.dispatch_ragged). Three contracts under test:

  * packing mechanics — exact-fill cuts, largest-first placement over
    the bucket divisibility chain, end-of-input-only partial packs, no
    starvation flush, dp round-up of the slot batch;
  * byte identity — mixed-width streams produce (ids, quals) identical
    to the bucketed multi-packer path, at dp 1 and dp 8, with
    n_forward_shapes collapsed to 1;
  * residency — the traced pack loop's device_compute gaps are
    attributable to transfers, asserted through `dctpu trace --json`.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_fused_hotpath import make_params, nonzero_alphas
from test_ragged_kernel import fake_rows_at

from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.obs import trace as trace_lib

BUCKETS = (100, 200)
STUB_QUAL = 40


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


def _win(params, length, rng):
  return rng.integers(
      0, 5, size=(params.total_rows, length, 1)).astype(np.float32)


def _ragged_stub_engine(params, batch_size=4, fail_packs=(),
                        buckets=BUCKETS):
  """Engine on the ragged path over a weightless runner whose
  dispatch_ragged/finalize are host stubs echoing each window's
  draft-CCS row (per-slot, per-offset — so placement correctness is
  observable in the delivered bytes)."""
  options = runner_lib.InferenceOptions(batch_size=batch_size)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  options.window_buckets = buckets
  options.use_ragged_kernel = True
  runner = runner_lib.ModelRunner(params, {}, options)
  mp = params.max_passes
  seq = [0]

  def dispatch_ragged(pack, lengths):
    s = seq[0]
    seq[0] += 1
    if s in fail_packs:
      raise RuntimeError(f'stub failure in ragged pack {s}')
    return pack, lengths

  def finalize(handle):
    pack, _lengths = handle
    ids = pack[:, 4 * mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)

  runner.dispatch_ragged = dispatch_ragged
  runner.finalize = finalize
  delivered = {}
  failures = []
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(t, (ids, quals)),
      on_pack_failure=lambda ts, s, e: failures.append((list(ts), s, e)))
  return engine, delivered, failures


# ----------------------------------------------------------------------
# Packing mechanics (stub runner)


def test_exact_fill_cuts_immediately_no_padding(params):
  """batch_size=4 with buckets (100, 200) compiles 2 slots of 200; any
  400 positions of windows cut as a zero-padding pack mid-stream."""
  rng = np.random.default_rng(1)
  engine, delivered, failures = _ragged_stub_engine(params)
  engine.submit([_win(params, 100, rng) for _ in range(4)],
                list(range(4)))
  assert engine.n_packs == 1  # 4x100 fills 2x200 exactly
  engine.submit([_win(params, 200, rng), _win(params, 100, rng),
                 _win(params, 100, rng)], [4, 5, 6])
  assert engine.n_packs == 2  # 200 + 2x100 fills 2x200 exactly
  engine.flush()
  assert engine.n_packs == 2  # nothing buffered: flush cuts no pack
  assert engine.n_pack_rows == 7
  assert engine.n_pad_rows == 0
  assert engine.n_starvation_flushes == 0
  assert not failures
  assert set(delivered) == set(range(7))


def test_partial_packs_only_at_end_of_input(params):
  """An inexact fill defers: 3x100 waits (no starvation flush ever),
  a 200 completes the plan (largest-first: the 200 takes its own slot),
  and only flush() cuts the leftover as a zero-length-padded pack."""
  rng = np.random.default_rng(2)
  engine, delivered, _ = _ragged_stub_engine(params)
  engine.submit([_win(params, 100, rng) for _ in range(3)], [0, 1, 2])
  assert engine.n_packs == 0  # 300 of 400 positions: cannot fill exactly
  engine.submit([_win(params, 200, rng)], [3])
  assert engine.n_packs == 1  # slot0=[200], slot1=[100,100]; one 100 waits
  assert engine.has_work
  engine.flush()
  assert engine.n_packs == 2
  assert engine.n_pack_rows == 4
  # The final partial pack wasted 300 positions = 3 min-width windows.
  assert engine.n_pad_rows == 3
  assert set(delivered) == {0, 1, 2, 3}


def test_delivery_is_placement_exact_across_widths(params):
  """The stub echoes the CCS row through the slot layout, so each
  delivered window must byte-match its own submission — proving the
  (slot, offset, width) scatter/gather round-trips exactly."""
  rng = np.random.default_rng(3)
  engine, delivered, failures = _ragged_stub_engine(params)
  widths = (100, 200, 100, 100, 200, 100, 100, 100)
  wins = [_win(params, w, rng) for w in widths]
  engine.submit(wins, list(range(len(wins))))
  engine.flush()
  assert not failures
  mp = params.max_passes
  for i, w in enumerate(wins):
    np.testing.assert_array_equal(
        delivered[i][0], w[4 * mp, :, 0].astype(np.uint8))
    assert delivered[i][1].shape == (w.shape[1],)
    assert (delivered[i][1] == STUB_QUAL).all()


def test_no_starvation_flush_on_single_stream(params):
  """The bucketed path's pathological stream — one wide tail behind
  full narrow packs — needs no starvation flush here: the tail rides
  the next exact-fill pack with the narrow traffic."""
  rng = np.random.default_rng(4)
  engine, delivered, _ = _ragged_stub_engine(params)
  engine.submit([_win(params, 200, rng)], ['tail'])
  engine.submit([_win(params, 100, rng) for _ in range(8)],
                [('a', i) for i in range(8)])
  # 200 + 8x100 = 1000 positions -> two exact packs (800), 2x100 wait.
  # The wide tail rode pack 0 (largest-first), not a padded flush.
  assert engine.n_packs == 2
  assert engine.n_pad_rows == 0
  assert engine.n_starvation_flushes == 0
  engine.flush()
  assert delivered['tail'][0].shape == (200,)
  stats = engine.stats()
  assert stats['n_starvation_flushes'] == 0
  assert stats['flush_padding_fraction'] == 0.0
  assert stats['use_ragged_kernel'] == 1


def test_slot_batch_rounds_up_to_dp(params):
  import types

  options = runner_lib.InferenceOptions(batch_size=4)
  fake = types.SimpleNamespace(mesh_dp=8, obs=None)
  packer = engine_lib._RaggedPacker(
      fake, options, BUCKETS, timing_rows=[],
      on_pack_failure=lambda *a: None, deliver=lambda *a: None)
  assert packer.slot_len == 200
  assert packer.windows_per_slot == 2
  assert packer.n_slots == 8  # max(1, 4 // 2) = 2, rounded up to dp


def test_rejects_width_outside_buckets(params):
  engine, _, _ = _ragged_stub_engine(params)
  rng = np.random.default_rng(5)
  with pytest.raises(ValueError, match='not in window buckets'):
    engine.submit([_win(params, 150, rng)], [0])


def test_rejects_buckets_without_divisibility_chain(params):
  engine, _, _ = _ragged_stub_engine(params, buckets=(100, 250))
  rng = np.random.default_rng(6)
  with pytest.raises(ValueError, match='divisibility chain'):
    engine.submit([_win(params, 100, rng)], [0])


def test_poison_fails_whole_ragged_pack_once(params):
  rng = np.random.default_rng(7)
  engine, delivered, failures = _ragged_stub_engine(params)
  tickets = [object() for _ in range(8)]
  engine.poison_ticket(tickets[5])  # second pack (windows 4..7)
  engine.submit([_win(params, 100, rng) for _ in range(8)], tickets)
  engine.flush()
  assert len(failures) == 1
  failed_tickets, seq, err = failures[0]
  assert seq == 1
  assert failed_tickets == tickets[4:8]
  assert 'poison' in str(err)
  assert set(map(id, delivered)) == set(map(id, tickets[:4]))
  # Consume-once: the same ticket goes through on resubmission.
  engine.submit([_win(params, 100, rng)], [tickets[5]])
  engine.flush()
  assert len(failures) == 1
  assert tickets[5] in delivered


def test_dispatch_failure_routes_tickets_not_deliver(params):
  rng = np.random.default_rng(8)
  engine, delivered, failures = _ragged_stub_engine(params,
                                                    fail_packs=(0,))
  engine.submit([_win(params, 100, rng) for _ in range(6)],
                list(range(6)))
  engine.flush()
  assert len(failures) == 1
  failed_tickets, seq, err = failures[0]
  assert seq == 0
  assert failed_tickets == [0, 1, 2, 3]
  assert 'stub failure' in str(err)
  assert set(delivered) == {4, 5}


# ----------------------------------------------------------------------
# Byte identity vs the multi-packer path (real weights)


@pytest.fixture(scope='module')
def real_setup():
  p = make_params(pre=dict(window_buckets=BUCKETS))
  model = model_lib.get_model(p)
  init_rows = jnp.asarray(fake_rows_at(p, BUCKETS[0], 2, 0))
  variables = nonzero_alphas(model.init(jax.random.PRNGKey(0), init_rows))
  return p, jax.tree.map(np.asarray, variables)


def _run_stream(real_setup, stream, use_ragged, mesh=None, batch=4,
                depth=2):
  p, variables = real_setup
  opts = runner_lib.InferenceOptions(
      max_length=p.max_length, max_passes=p.max_passes,
      use_ccs_bq=p.use_ccs_bq, batch_size=batch, dispatch_depth=depth,
      window_buckets=BUCKETS, use_ragged_kernel=use_ragged)
  runner = runner_lib.ModelRunner(
      p, jax.tree.map(np.array, variables), opts, mesh=mesh)
  out = {}
  eng = engine_lib.ConsensusEngine(
      runner, opts,
      deliver=lambda t, ids, quals: out.__setitem__(
          t, (ids.copy(), quals.copy())))
  eng.submit_formatted(list(stream), list(range(len(stream))))
  eng.flush()
  return out, eng


def _mixed_stream(p, seed=5):
  """20 windows, ~70/30 narrow/wide, interleaved pseudo-randomly."""
  rng = np.random.default_rng(seed)
  narrow = fake_rows_at(p, BUCKETS[0], 14, 21)
  wide = fake_rows_at(p, BUCKETS[-1], 6, 22)
  stream, i1, i2 = [], 0, 0
  for flip in rng.random(20):
    if (flip < 0.7 and i1 < 14) or i2 >= 6:
      stream.append(narrow[i1])
      i1 += 1
    else:
      stream.append(wide[i2])
      i2 += 1
  return stream


def _adversarial_stream(p):
  """One window per bucket, strictly interleaved — the stream that
  maximizes multi-packer fragmentation (every bucket always holds a
  sub-batch tail) and exercises every mixed slot composition."""
  narrow = fake_rows_at(p, BUCKETS[0], 8, 31)
  wide = fake_rows_at(p, BUCKETS[-1], 8, 32)
  stream = []
  for i in range(8):
    stream.append(narrow[i])
    stream.append(wide[i])
  return stream


def _assert_identical(base, ragged, n):
  assert set(base) == set(ragged) == set(range(n))
  for t in range(n):
    np.testing.assert_array_equal(base[t][0], ragged[t][0])
    np.testing.assert_array_equal(base[t][1], ragged[t][1])


def test_mixed_stream_byte_identity(real_setup):
  stream = _mixed_stream(real_setup[0])
  base, be = _run_stream(real_setup, stream, use_ragged=False)
  ragged, re_ = _run_stream(real_setup, stream, use_ragged=True)
  _assert_identical(base, ragged, len(stream))
  # The whole point: one compiled forward where the bucketed path
  # needed one per bucket.
  assert be.stats()['n_forward_shapes'] == len(BUCKETS)
  assert re_.stats()['n_forward_shapes'] == 1
  assert re_.stats()['use_ragged_kernel'] == 1
  assert re_.stats()['n_packs_by_bucket'] == {BUCKETS[-1]: re_.n_packs}
  assert re_.stats()['n_starvation_flushes'] == 0


def test_adversarial_interleave_byte_identity(real_setup):
  stream = _adversarial_stream(real_setup[0])
  base, _ = _run_stream(real_setup, stream, use_ragged=False)
  ragged, re_ = _run_stream(real_setup, stream, use_ragged=True)
  _assert_identical(base, ragged, len(stream))
  assert re_.stats()['n_forward_shapes'] == 1


@pytest.mark.multichip
def test_mixed_stream_byte_identity_dp8(real_setup):
  """dp=8 over the forced host devices: the ragged slot batch rounds
  up to the data axis and each pack shards; bytes must not move."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  stream = _mixed_stream(real_setup[0], seed=6)
  base, _ = _run_stream(real_setup, stream, use_ragged=False,
                        mesh=mesh, batch=8)
  ragged, re_ = _run_stream(real_setup, stream, use_ragged=True,
                            mesh=mesh, batch=8)
  _assert_identical(base, ragged, len(stream))
  assert re_.stats()['n_forward_shapes'] == 1
  assert re_.stats()['n_packs_dispatched_sharded'] == re_.n_packs > 0


@pytest.mark.multichip
def test_adversarial_interleave_byte_identity_dp8(real_setup):
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  stream = _adversarial_stream(real_setup[0])
  base, _ = _run_stream(real_setup, stream, use_ragged=False,
                        mesh=mesh, batch=8)
  ragged, re_ = _run_stream(real_setup, stream, use_ragged=True,
                            mesh=mesh, batch=8)
  _assert_identical(base, ragged, len(stream))
  assert re_.stats()['n_forward_shapes'] == 1


# ----------------------------------------------------------------------
# Residency: trace spans through `dctpu trace --json`


def test_traced_ragged_run_reports_self_time(real_setup, tmp_path,
                                             capsys):
  """A live traced ragged run drives the full span pipeline: every
  pack gets a dispatch with its h2d_transfer, and a device_compute wait
  at ONE bucket (the slot length); the summary gives self time per
  stage and lists the waits apart."""
  from deepconsensus_tpu import cli

  path = str(tmp_path / 'ragged_trace.jsonl')
  trace_lib.configure(path, tier='run')
  try:
    _out, eng = _run_stream(real_setup, _mixed_stream(real_setup[0]),
                            use_ragged=True)
  finally:
    trace_lib.configure(None)
  assert cli.main(['trace', path, '--json']) == 0
  payload = json.loads(capsys.readouterr().out)
  assert payload['stage_counts']['device_compute'] == eng.n_packs
  assert payload['stage_counts']['h2d_transfer'] == eng.n_packs
  assert payload['overlap']['n_packs'] == eng.n_packs
  assert not [key for key in payload if 'gaps' in key]
  self_time = payload['self_time']
  assert self_time['dispatch']['count'] == eng.n_packs
  assert set(self_time['dispatch']['under']) <= {'flush', 'submit'}
  assert self_time['pack_cast']['under'] == ['dispatch']
  assert self_time['deliver']['count'] == eng.n_packs
  assert set(payload['waits']) == {'device_compute', 'pack_wait'}
  assert not set(payload['waits']) & set(self_time)
  # submit_formatted of a list: stacked, never formatted.
  assert 'format_rows' not in self_time
  for row in self_time.values():
    assert 0.0 <= row['self_s'] <= row['total_s'] + 1e-9


def test_resident_pack_loop_trace_counts_overlap_and_waits(tmp_path, capsys):
  """The residency acceptance fixture: a device-resident pack loop's
  trace — back-to-back device_compute spans whose gaps hold only the
  next pack's h2d_transfer, drains batched at end-of-input (so no
  finalize_drain span per pack). `dctpu trace --json` must count every
  drain-free pack's launch as overlapped, and read a trace written
  before the `wait` category existed: device_compute is a wait by its
  name, so the transfers are the only work on the critical path."""
  from deepconsensus_tpu import cli

  def span(name, ts_s, dur_s, **args):
    return {'name': name, 'cat': 'stage', 'ph': 'X', 'ts': ts_s * 1e6,
            'dur': dur_s * 1e6, 'pid': 1, 'tid': 1, 'args': args}

  events = [{'name': 'process_name', 'ph': 'M', 'pid': 1, 'tid': 0,
             'args': {'name': 'dctpu-run'}}]
  # Pack k computes on [k, k+0.9]; the 0.1s gap to pack k+1 is exactly
  # the h2d of pack k+2's uint8 planes. No finalize_drain spans at all.
  for k in range(4):
    events.append(span('h2d_transfer', max(0.0, k - 0.1), 0.1,
                       pack=k, bucket=200))
    events.append(span('device_compute', float(k), 0.9, pack=k,
                       bucket=200, dp=1, n_rows=8))
  path = tmp_path / 'resident.jsonl'
  path.write_text('\n'.join(json.dumps(e) for e in events) + '\n')

  assert cli.main(['trace', str(path), '--json']) == 0
  payload = json.loads(capsys.readouterr().out)
  # Drain-free packs: launches can only have been overlapped (a direct
  # launch happens inside finalize, which would have emitted a span).
  assert payload['overlap']['n_packs'] == 4
  assert payload['overlap']['n_overlapped'] == 4
  assert payload['overlap']['span_overlap_fraction'] == 1.0
  assert not [key for key in payload if 'gaps' in key]
  assert payload['waits'] == {
      'device_compute': {'total_s': pytest.approx(3.6), 'count': 4}}
  assert [r['stage'] for r in payload['critical_path']] == ['h2d_transfer']
  assert payload['self_time']['h2d_transfer']['self_s'] == pytest.approx(0.4)
