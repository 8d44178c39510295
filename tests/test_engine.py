"""ConsensusEngine boundary tests.

Ports the compile-once smoke (test_perf_smoke.py) and the packer edge
cases (test_window_packer.py) to the engine's submit/deliver interface,
and proves the runner refactor behavior-preserving: driving the engine
directly over a featurized synthetic input reproduces the batch CLI's
FASTQ byte-for-byte.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import test_util as jtu

from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.postprocess import stitch

pytestmark = pytest.mark.resilience

BATCH = 8
STUB_QUAL = 40


@pytest.fixture(scope='module')
def params():
  p = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(p, is_training=False)
  return p


def _stub_runner(params, batch_size=BATCH, fail_packs=()):
  """Weightless ModelRunner whose finalize echoes each window's
  draft-CCS row; packs listed in fail_packs raise at dispatch."""
  options = runner_lib.InferenceOptions(batch_size=batch_size)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  runner = runner_lib.ModelRunner(params, {}, options)
  mp = params.max_passes
  seq = [0]

  def dispatch_pack(main_u8, sn, n_rows=None, batch_size=None):
    # A view into the engine's pack buffer: finalize reads it only when
    # the pack drains, so a buffer handed out again too early shows.
    pack = seq[0]
    seq[0] += 1
    if pack in fail_packs:
      raise RuntimeError(f'stub failure in pack {pack}')
    return main_u8[:n_rows]

  def finalize(rows):
    ids = rows[:, 4 * mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)

  runner.dispatch_pack = dispatch_pack
  runner.finalize = finalize
  return runner, options


def _raw_windows(params, n, seed=0):
  rng = np.random.default_rng(seed)
  shape = (n, params.total_rows, params.max_length, 1)
  return rng.integers(0, 5, size=shape).astype(np.float32)


def _collecting_engine(params, batch_size=BATCH, fail_packs=()):
  runner, options = _stub_runner(params, batch_size, fail_packs)
  delivered = {}
  failures = []
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(t, (ids, quals)),
      on_pack_failure=lambda ts, seq, e: failures.append((list(ts), seq, e)))
  return engine, delivered, failures


# ----------------------------------------------------------------------
# Compile-once smoke at the engine boundary (port of test_perf_smoke)


@pytest.fixture(scope='module')
def real_engine(params):
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  options = runner_lib.InferenceOptions(batch_size=BATCH)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  runner = runner_lib.ModelRunner(params, variables, options)
  return engine_lib.ConsensusEngine(
      runner, options, deliver=lambda t, ids, quals: None)


def test_engine_compiles_once_per_shape(real_engine, params):
  ids, quals = real_engine.predict_windows(_raw_windows(params, BATCH))
  assert ids.shape == (BATCH, params.max_length)
  with jtu.count_jit_and_pmap_lowerings() as count:
    # Full packs AND ragged tails (flush pads them) must all reuse the
    # executable paid for above.
    for i, n in enumerate((BATCH, BATCH, BATCH // 2, 3, 1)):
      ids, quals = real_engine.predict_windows(
          _raw_windows(params, n, seed=i + 1))
      assert ids.shape == (n, params.max_length)
      assert quals.dtype == np.uint8
  assert count() == 0, (
      f'{count()} re-lowerings behind the engine boundary: the '
      'forward is recompiled per submission instead of per shape')


def test_engine_uint8_contract(real_engine, params):
  ids, quals = real_engine.predict_windows(_raw_windows(params, 3, 7))
  assert ids.dtype == np.uint8 and quals.dtype == np.uint8
  assert quals.max() <= real_engine.options.max_base_quality


# ----------------------------------------------------------------------
# Packer edge cases at the engine boundary (port of test_window_packer)


def test_full_packs_cut_across_submissions(params):
  """3 submissions of 5 windows at batch_size=8: packs cut at 8-window
  boundaries regardless of submission seams; tail pads on flush."""
  engine, delivered, failures = _collecting_engine(params)
  for s in range(3):
    engine.submit(_raw_windows(params, 5, seed=s),
                  [(s, i) for i in range(5)])
  assert engine.n_packs == 1  # 15 buffered -> one full pack cut
  engine.flush()
  assert engine.n_packs == 2
  assert engine.n_pack_rows == 15
  assert engine.n_pad_rows == 2 * BATCH - 15
  assert not failures
  assert set(delivered) == {(s, i) for s in range(3) for i in range(5)}


def test_delivery_matches_submission(params):
  """Each ticket gets exactly its own window's result (stub echoes the
  CCS row, so scatter correctness is observable)."""
  engine, delivered, _ = _collecting_engine(params)
  raw = _raw_windows(params, 11, seed=3)
  engine.submit(raw, list(range(11)))
  engine.flush()
  mp = params.max_passes
  for t in range(11):
    np.testing.assert_array_equal(
        delivered[t][0], raw[t, 4 * mp, :, 0].astype(np.uint8))
    assert (delivered[t][1] == STUB_QUAL).all()


def test_pack_failure_routes_tickets_not_deliver(params):
  """A failed pack surfaces ALL of its tickets through on_pack_failure
  and none through deliver; sibling packs are untouched."""
  engine, delivered, failures = _collecting_engine(
      params, fail_packs=(1,))
  engine.submit(_raw_windows(params, 20, seed=4), list(range(20)))
  engine.flush()
  assert len(failures) == 1
  failed_tickets, seq, err = failures[0]
  assert seq == 1
  assert failed_tickets == list(range(8, 16))
  assert 'stub failure' in str(err)
  assert set(delivered) == set(range(8)) | set(range(16, 20))


def test_poison_ticket_fails_only_its_pack(params):
  """poison_ticket makes exactly the pack carrying that ticket fail at
  dispatch (the DCTPU_FAULT_POISON_WINDOW mechanism) and is
  consume-once."""
  engine, delivered, failures = _collecting_engine(params)
  tickets = [object() for _ in range(20)]
  engine.poison_ticket(tickets[10])  # lands in pack 1 (windows 8..15)
  engine.submit(_raw_windows(params, 20, seed=5), tickets)
  engine.flush()
  assert len(failures) == 1
  failed_tickets, seq, err = failures[0]
  assert seq == 1
  assert failed_tickets == tickets[8:16]
  assert 'poison' in str(err)
  assert set(map(id, delivered)) == set(
      map(id, tickets[:8] + tickets[16:]))
  # Consume-once: resubmitting the same ticket succeeds.
  engine.submit(_raw_windows(params, 1, seed=6), [tickets[10]])
  engine.flush()
  assert len(failures) == 1
  assert tickets[10] in delivered


def test_submit_validates_ticket_alignment(params):
  engine, _, _ = _collecting_engine(params)
  with pytest.raises(ValueError, match='tickets'):
    engine.submit(_raw_windows(params, 3), [1, 2])
  with pytest.raises(ValueError, match='tickets'):
    engine.submit_formatted(np.zeros((2, 4, 4, 1), np.float32), [1])


def test_flush_without_drain_leaves_packs_in_flight(params):
  engine, delivered, _ = _collecting_engine(params)
  engine.submit(_raw_windows(params, 3, seed=8), [0, 1, 2])
  engine.flush(drain=False)
  assert engine.n_packs == 1
  assert engine.has_work  # dispatched but not finalized
  engine.flush(drain=True)
  assert not engine.has_work
  assert set(delivered) == {0, 1, 2}


# ----------------------------------------------------------------------
# Bucketed variable-length windows: per-bucket packing + ragged dispatch


def _win(params, length, rng):
  return rng.integers(
      0, 5, size=(params.total_rows, length, 1)).astype(np.float32)


def _bucketed_engine(params, batch_size=BATCH, fail_packs=(),
                     buckets=(100, 200), flush_packs=8):
  runner, options = _stub_runner(params, batch_size, fail_packs)
  options.window_buckets = buckets
  options.bucket_flush_packs = flush_packs
  delivered = {}
  failures = []
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(t, (ids, quals)),
      on_pack_failure=lambda ts, seq, e: failures.append((list(ts), seq, e)))
  return engine, delivered, failures


def test_mixed_length_submission_routes_per_bucket(params):
  """One submit carrying L=100 and L=200 windows routes each to its
  bucket's packer; every ticket delivers at its window's natural width
  (no pad-to-max) and the per-bucket counters account for all of it."""
  rng = np.random.default_rng(21)
  engine, delivered, failures = _bucketed_engine(params, batch_size=4)
  widths = (100, 200, 100, 200, 100, 100)
  wins = [_win(params, w, rng) for w in widths]
  engine.submit(wins, list(range(len(wins))))
  engine.flush()
  assert not failures
  mp = params.max_passes
  for i, w in enumerate(wins):
    np.testing.assert_array_equal(
        delivered[i][0], w[4 * mp, :, 0].astype(np.uint8))
    assert delivered[i][1].shape == (w.shape[1],)
  stats = engine.stats()
  assert stats['window_buckets'] == [100, 200]
  assert stats['n_windows_by_bucket'] == {100: 4, 200: 2}
  assert stats['n_packs_by_bucket'] == {100: 1, 200: 1}
  # Bucketed dispatch moved 4*100 + 2*200 = 800 positions where
  # pad-to-max would have moved 6*200 = 1200.
  assert stats['padding_fraction'] == pytest.approx(1 - 800 / 1200, abs=1e-4)


def test_single_bucket_reports_zero_padding_fraction(params):
  engine, _, _ = _bucketed_engine(params, buckets=(100,))
  engine.submit(_raw_windows(params, 3, seed=2), [0, 1, 2])
  engine.flush()
  assert engine.stats()['padding_fraction'] == 0.0


def test_ragged_tails_flush_in_both_buckets(params):
  """Both buckets hold sub-batch tails at end of input: flush() cuts
  each as its own padded pack and no window crosses buckets."""
  rng = np.random.default_rng(22)
  engine, delivered, _ = _bucketed_engine(params)
  wins = ([_win(params, 100, rng) for _ in range(3)]
          + [_win(params, 200, rng) for _ in range(5)])
  engine.submit(wins, list(range(len(wins))))
  assert engine.n_packs == 0  # neither bucket reached batch_size
  engine.flush()
  assert engine.n_packs_by_bucket == {100: 1, 200: 1}
  assert engine.n_pack_rows == 8
  assert engine.n_pad_rows == 2 * BATCH - 8
  assert set(delivered) == set(range(len(wins)))
  for i, w in enumerate(wins):
    assert delivered[i][0].shape == (w.shape[1],)


def test_bucket_starvation_flush(params):
  """A tail stranded in a rarely-fed bucket is force-cut (padded) once
  the engine as a whole has dispatched bucket_flush_packs packs since
  the tail started waiting — it can't sit buffered until end of input
  behind a stream of full packs in the other bucket."""
  rng = np.random.default_rng(23)
  engine, delivered, _ = _bucketed_engine(params, flush_packs=2)
  engine.submit([_win(params, 200, rng)], ['tail'])
  engine.submit([_win(params, 100, rng) for _ in range(BATCH)],
                [('a', i) for i in range(BATCH)])
  # One pack cut since the tail buffered: below the limit, still held.
  assert engine.n_packs_by_bucket.get(200, 0) == 0
  engine.submit([_win(params, 100, rng) for _ in range(BATCH)],
                [('b', i) for i in range(BATCH)])
  # Second pack hit the limit: the tail was cut as a padded pack.
  assert engine.n_packs_by_bucket[200] == 1
  assert engine.n_pad_rows == BATCH - 1
  engine.flush()
  assert delivered['tail'][0].shape == (200,)
  # The cut reset the mark: nothing further to flush, no empty packs.
  assert engine.n_packs == 3


def test_starvation_flush_counters_and_fraction(params):
  """Satellite of the ragged-kernel PR: starvation flushes get their
  own counters — how often a stranded tail was force-cut and what
  position fraction of all dispatched capacity those flushes padded —
  so operators can see the cost the single-pack-stream path removes."""
  rng = np.random.default_rng(27)
  engine, delivered, _ = _bucketed_engine(params, flush_packs=2)
  engine.submit([_win(params, 200, rng)], ['tail'])
  for group in ('a', 'b'):
    engine.submit([_win(params, 100, rng) for _ in range(BATCH)],
                  [(group, i) for i in range(BATCH)])
  # The 200-tail was starvation-flushed after the second 100-pack.
  assert engine.n_starvation_flushes == 1
  stats = engine.stats()
  assert stats['n_starvation_flushes'] == 1
  # Flush-padded positions / dispatched position capacity:
  # (BATCH-1)*200 over (2 packs * BATCH * 100 + 1 pack * BATCH * 200).
  expect = ((BATCH - 1) * 200) / (2 * BATCH * 100 + BATCH * 200)
  assert stats['flush_padding_fraction'] == pytest.approx(expect,
                                                          abs=1e-4)
  engine.flush()
  assert delivered['tail'][0].shape == (200,)


def test_starvation_flush_pads_counted_once(params):
  """Regression: a bucket whose FINAL pack was a starvation flush must
  not double-count its pad rows — the flush attributes them once, and
  the end-of-input flush() (buffered == 0 after the cut) cannot re-pad
  the same tail. n_pad_rows stays exactly batch - k."""
  rng = np.random.default_rng(28)
  engine, delivered, _ = _bucketed_engine(params, flush_packs=2)
  engine.submit([_win(params, 200, rng)], ['tail'])
  for group in ('a', 'b'):
    engine.submit([_win(params, 100, rng) for _ in range(BATCH)],
                  [(group, i) for i in range(BATCH)])
  assert engine.n_pad_rows == BATCH - 1
  before = engine.stats()['flush_padding_fraction']
  engine.flush()
  # No window entered the 200 bucket after its starvation flush: the
  # end-of-input flush adds no pack, no pad rows, no fraction drift —
  # the flush-cut tail (buffered == 0 after the cut) is not re-padded.
  assert engine.n_packs_by_bucket[200] == 1
  assert engine.n_pad_rows == BATCH - 1
  assert engine.n_starvation_flushes == 1
  assert engine.stats()['flush_padding_fraction'] == before
  assert set(delivered) > {'tail'}


def test_end_of_input_flush_is_not_starvation(params):
  """Ordinary end-of-input tails (both buckets sub-batch at flush())
  pad the general pool but never the starvation counters."""
  rng = np.random.default_rng(29)
  engine, _, _ = _bucketed_engine(params)
  engine.submit([_win(params, 100, rng) for _ in range(3)]
                + [_win(params, 200, rng) for _ in range(2)],
                list(range(5)))
  engine.flush()
  assert engine.n_pad_rows == 2 * BATCH - 5
  assert engine.n_starvation_flushes == 0
  stats = engine.stats()
  assert stats['n_starvation_flushes'] == 0
  assert stats['flush_padding_fraction'] == 0.0
  assert stats['padding_fraction'] > 0


def test_poison_in_one_bucket_leaves_other_bucket_identical(params):
  """Poisoning a ticket whose window lands in the 200-bucket fails only
  that bucket's pack; the 100-bucket's deliveries are byte-identical to
  the same run without the poison."""
  rng = np.random.default_rng(24)
  widths = (100, 200, 100, 200, 100, 100, 200, 100)
  wins = [_win(params, w, rng) for w in widths]

  def run(poison_idx=None):
    engine, delivered, failures = _bucketed_engine(params, batch_size=4)
    tickets = list(range(len(wins)))
    if poison_idx is not None:
      engine.poison_ticket(tickets[poison_idx])
    engine.submit(wins, tickets)
    engine.flush()
    return delivered, failures

  clean, clean_failures = run()
  poisoned, failures = run(poison_idx=3)  # a 200-bucket window
  assert not clean_failures
  assert len(failures) == 1
  failed_tickets, _seq, err = failures[0]
  assert 'poison' in str(err)
  # Exactly the 200-bucket tickets failed; every 100-bucket ticket
  # delivered bytes identical to the clean run.
  assert failed_tickets == [i for i, w in enumerate(widths) if w == 200]
  for i, w in enumerate(widths):
    if w == 100:
      np.testing.assert_array_equal(poisoned[i][0], clean[i][0])
      np.testing.assert_array_equal(poisoned[i][1], clean[i][1])
    else:
      assert i not in poisoned


def test_submit_rejects_width_outside_buckets(params):
  engine, _, _ = _bucketed_engine(params)
  rng = np.random.default_rng(25)
  with pytest.raises(ValueError, match='not in window buckets'):
    engine.submit([_win(params, 150, rng)], [0])


def test_engine_compiles_once_per_bucket(params):
  """Two buckets cost exactly two forward traces; every later pack —
  full or padded, either width — reuses its bucket's executable. The
  runner's n_forward_shapes counter exposes the same fact."""
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  options = runner_lib.InferenceOptions(batch_size=4)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  options.window_buckets = (100, 200)
  runner = runner_lib.ModelRunner(params, variables, options)
  engine = engine_lib.ConsensusEngine(
      runner, options, deliver=lambda t, ids, quals: None)
  rng = np.random.default_rng(26)
  # Warm both buckets (one trace each).
  engine.predict_windows([_win(params, 100, rng), _win(params, 200, rng)])
  with jtu.count_jit_and_pmap_lowerings() as count:
    out_ids, _ = engine.predict_windows(
        [_win(params, w, rng) for w in (100, 200, 200, 100, 100, 200)])
    assert [i.shape[0] for i in out_ids] == [100, 200, 200, 100, 100, 200]
  assert count() == 0, (
      f'{count()} re-lowerings across bucketed packs: each bucket '
      'must compile once and reuse its executable')
  assert runner.dispatch_stats()['n_forward_shapes'] == 2


# ----------------------------------------------------------------------
# Behavior preservation: engine-direct output == batch pipeline output


def test_engine_reproduces_batch_pipeline_bytes(tmp_path, synthetic_bams,
                                                params):
  """Featurize a synthetic input once; polish it (a) through the full
  run_inference pipeline and (b) by driving ConsensusEngine + stitch
  directly. The FASTQ bytes must match exactly — the refactored
  pipeline is a thin client of the same engine."""
  subreads, ccs = synthetic_bams(subdir='bams_engine', n_zmws=6,
                                 seq_len=600)

  def make_options():
    opts = runner_lib.InferenceOptions(
        batch_size=BATCH, batch_zmws=100, skip_windows_above=0,
        min_quality=0)
    opts.max_passes = params.max_passes
    opts.max_length = params.max_length
    opts.use_ccs_bq = params.use_ccs_bq
    return opts

  # (a) the batch pipeline
  options = make_options()
  runner, _ = _stub_runner(params, BATCH)
  out = str(tmp_path / 'pipeline.fastq')
  runner_lib.run_inference(
      subreads_to_ccs=subreads, ccs_bam=ccs, checkpoint=None,
      output=out, options=options, runner=runner)
  with open(out, 'rb') as f:
    pipeline_bytes = f.read()

  # (b) engine-direct: featurize, triage, submit, stitch, format
  from deepconsensus_tpu.preprocess import (FeatureLayout,
                                            create_proc_feeder)

  options = make_options()
  runner, _ = _stub_runner(params, BATCH)
  layout = FeatureLayout(
      max_passes=options.max_passes, max_length=options.max_length,
      use_ccs_bq=options.use_ccs_bq)
  feeder, _ = create_proc_feeder(
      subreads_to_ccs=subreads, ccs_bam=ccs, layout=layout,
      ins_trim=options.ins_trim)
  mols = {}  # name -> [(pos, ids, quals)]

  def deliver(ticket, ids, quals):
    name, pos = ticket
    mols[name].append((pos, ids, quals))

  engine = engine_lib.ConsensusEngine(runner, options, deliver=deliver)
  counter = collections.Counter()
  for zmw_input in feeder():
    features, _ = runner_lib.preprocess_zmw(zmw_input, options)
    to_model, to_skip = engine_lib.triage_windows(
        features, options, counter)
    for fd in to_skip:
      name = fd['name'] if isinstance(fd['name'], str) else fd['name'].decode()
      mols.setdefault(name, []).append(
          (fd['window_pos'],
           *engine_lib.skipped_window_arrays(fd, options)))
    tickets = []
    for fd in to_model:
      name = fd['name'] if isinstance(fd['name'], str) else fd['name'].decode()
      mols.setdefault(name, [])
      tickets.append((name, fd['window_pos']))
    if to_model:
      engine.submit(
          np.stack([fd['subreads'] for fd in to_model]), tickets)
  engine.flush()

  outcome = stitch.OutcomeCounter()
  direct = b''
  for name in sorted(mols):
    windows = mols[name]
    result = stitch.stitch_arrays(
        name,
        np.asarray([w[0] for w in windows], dtype=np.int64),
        np.stack([w[1] for w in windows]),
        np.stack([w[2] for w in windows]),
        max_length=options.max_length,
        min_quality=options.min_quality,
        min_length=options.min_length,
        outcome_counter=outcome)
    if result is not None:
      direct += stitch.format_fastq_bytes(name, *result)
  assert direct == pipeline_bytes


# ----------------------------------------------------------------------
# Data-parallel sharded dispatch (8 forced host-platform devices)


def _real_runner(params, mesh=None, batch=BATCH):
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  options = runner_lib.InferenceOptions(batch_size=batch)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  return runner_lib.ModelRunner(params, variables, options,
                                mesh=mesh), options


@pytest.mark.multichip
def test_engine_byte_identity_single_vs_dp8(params):
  """The engine boundary must produce identical uint8 (ids, quals)
  whether the runner dispatches to one device or dp-shards each pack
  over all 8 — full packs and the padded flush tail alike."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  raw = _raw_windows(params, 21, seed=11)  # 2 full packs + ragged tail
  runner_s, options_s = _real_runner(params)
  runner_m, options_m = _real_runner(params, mesh=mesh)
  engine_s = engine_lib.ConsensusEngine(
      runner_s, options_s, deliver=lambda t, ids, quals: None)
  engine_m = engine_lib.ConsensusEngine(
      runner_m, options_m, deliver=lambda t, ids, quals: None)
  ids_s, quals_s = engine_s.predict_windows(raw)
  ids_m, quals_m = engine_m.predict_windows(raw)
  np.testing.assert_array_equal(ids_s, ids_m)
  np.testing.assert_array_equal(quals_s, quals_m)
  stats = engine_m.stats()
  assert stats['n_packs_dispatched_sharded'] == 3
  assert engine_s.stats()['n_packs_dispatched_sharded'] == 0


@pytest.mark.multichip
def test_dispatch_handles_are_dp_sharded(params):
  """The dispatch contract: the transfer slot holds dp-sharded input
  buffers, the forward launches when the next pack dispatches
  (overlapped) or at finalize (direct), and the logits come back
  sharded on the data axis."""
  from deepconsensus_tpu.models import data as data_lib
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  batch_sh = mesh_lib.batch_sharding(mesh)
  runner, _ = _real_runner(params, mesh=mesh)
  rows1 = data_lib.format_rows_batch(_raw_windows(params, BATCH, 1), params)
  rows2 = data_lib.format_rows_batch(_raw_windows(params, BATCH, 2), params)
  h1 = runner.dispatch(rows1)
  # Pack 1 sits in the transfer slot: inputs placed, forward not run.
  assert not h1.launched
  assert h1.inputs[0].sharding == batch_sh
  assert h1.inputs[1].sharding == batch_sh
  h2 = runner.dispatch(rows2)
  # Pack 2's dispatch launched pack 1's forward (overlapped); its own
  # transfer slot is sharded and still pending.
  assert h1.launched and h1.outputs is not None
  assert h1.outputs[0].sharding.is_equivalent_to(
      batch_sh, h1.outputs[0].ndim)
  assert not h2.launched
  assert h2.inputs[0].sharding == batch_sh
  ids1, quals1 = runner.finalize(h1)
  ids2, quals2 = runner.finalize(h2)  # direct launch: nothing followed
  assert ids1.shape == ids2.shape == (BATCH, params.max_length)
  stats = runner.dispatch_stats()
  assert stats['n_packs_dispatched_sharded'] == 2
  assert stats['n_transfer_overlapped'] == 1
  assert stats['n_transfer_direct'] == 1
  assert stats['transfer_overlap_fraction'] == 0.5


@pytest.mark.multichip
def test_deferred_launch_failure_attributed_to_failing_pack(params):
  """Double-buffering defers pack N's forward launch into pack N+1's
  dispatch; a launch error must still surface at pack N's finalize so
  the engine quarantines pack N's tickets — and the packs around it
  deliver, in featurize order."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  mesh = mesh_lib.make_mesh(dp=8, tp=1, devices=jax.devices()[:8])
  runner, options = _real_runner(params, mesh=mesh)
  real_forward = runner._forward
  calls = [0]

  def flaky_forward(variables, main_u8, sn):
    calls[0] += 1
    if calls[0] == 2:
      raise RuntimeError('injected mid-stream forward failure')
    return real_forward(variables, main_u8, sn)

  runner._forward = flaky_forward
  delivered = {}
  failures = []
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(t, ids),
      on_pack_failure=lambda ts, seq, e: failures.append(
          (list(ts), seq, str(e))))
  engine.submit(_raw_windows(params, 3 * BATCH, seed=13),
                list(range(3 * BATCH)))
  engine.flush()
  # The error was raised while pack 2 dispatched, but it belongs to
  # pack 1: exactly pack 1's tickets fail, with its pack seq.
  assert len(failures) == 1
  failed_tickets, seq, err = failures[0]
  assert seq == 1
  assert failed_tickets == list(range(BATCH, 2 * BATCH))
  assert 'injected mid-stream forward failure' in err
  # Packs 0 and 2 delivered, in featurize order.
  assert list(delivered) == (
      list(range(BATCH)) + list(range(2 * BATCH, 3 * BATCH)))
