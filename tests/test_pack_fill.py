"""The in-place pack fill and the life of a pack buffer.

`data.fill_pack` writes each window once, at its final row of a compact
pack (`main_u8` uint8 + `sn` float32), clipped and cast on the way. The
oracle throughout is the path it replaced, kept here as the reference:
`np.stack` -> `data.format_rows_batch` -> `ModelRunner._cast_main_u8`
plus the SN gather, compared byte for byte. The second half holds the
packer to the buffer contract: a buffer is not handed out again before
its pack has been drained or routed, degrade-mode retries read intact
rows, the pool stays within dispatch_depth + 2, and the caller's
windows are free the moment `submit` returns.
"""
import numpy as np
import pytest

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import runner as runner_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib

pytestmark = pytest.mark.resilience

BATCH = 8
STUB_QUAL = 40


def _params(use_ccs_bq=False):
  p = config_lib.get_config(
      'transformer_learn_values+' + ('test_bq' if use_ccs_bq else 'test'))
  config_lib.finalize_params(p, is_training=False)
  return p


@pytest.fixture(scope='module')
def params():
  return _params()


def _runner(params, **kw):
  kw.setdefault('batch_size', BATCH)
  options = runner_lib.InferenceOptions(**kw)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  return runner_lib.ModelRunner(params, {}, options), options


def _zmw_matrices(params, n, seed, passes=None, per_zmw=5, width=None):
  """Pile-up matrices [H, per_zmw, L] as the featurizer leaves them, with
  values that the format must clip: PW/IP below 0 and above 255, SN above
  SN_MAX, and (use_ccs_bq) the -1 sentinels of spaced ccs_bq."""
  from deepconsensus_tpu.preprocess import pileup

  rng = np.random.default_rng(seed)
  passes = passes or params.max_passes
  width = width or params.max_length
  height = pileup.total_rows(passes, params.use_ccs_bq)
  base, pw, ip, strand, ccs, bq, sn = pileup.row_indices(
      passes, params.use_ccs_bq)
  out = []
  for _ in range(-(-n // per_zmw)):
    m = np.zeros((height, per_zmw, width), np.float32)
    m[base[0]:base[1]] = rng.integers(0, 5, m[base[0]:base[1]].shape)
    m[pw[0]:ip[1]] = rng.integers(-40, 700, m[pw[0]:ip[1]].shape)
    m[strand[0]:strand[1]] = rng.integers(0, 3, m[strand[0]:strand[1]].shape)
    m[ccs[0]] = rng.integers(0, 5, m[ccs[0]].shape)
    if params.use_ccs_bq:
      m[bq[0]] = rng.integers(-1, 94, m[bq[0]].shape)
    m[sn[0]:sn[1]] = rng.uniform(
        -3, 2 * params.SN_MAX, (4, per_zmw, 1)).astype(np.float32)
    out.append(m)
  return out


def _views(matrices, n):
  """Per-window strided views, as `run_inference` hands them over."""
  views = [m[:, i, :, None] for m in matrices for i in range(m.shape[1])]
  assert not views[0].flags['C_CONTIGUOUS']
  return views[:n]


def _reference_pack(runner, windows, params, buckets=()):
  """The path the fill replaced: (main_u8, sn) of all windows."""
  rows = data_lib.format_rows_batch(
      np.stack(windows), params, window_buckets=buckets)
  return runner._cast_main_u8(rows), np.ascontiguousarray(
      rows[:, -4:, 0, 0].astype(np.float32))


# ----------------------------------------------------------------------
# The fill against the path it replaced, byte for byte


@pytest.mark.parametrize('use_ccs_bq', [False, True], ids=['no_bq', 'bq'])
@pytest.mark.parametrize('form,extra_passes', [
    ('views', 0), ('views', 3), ('array', 0), ('array', 3),
    ('formatted', 0), ('formatted_array', 0)])
def test_fill_equals_stack_format_cast(form, extra_passes, use_ccs_bq):
  params = _params(use_ccs_bq)
  runner, _ = _runner(params)
  n = 37  # several scratch chunks would need more; see the chunk test
  matrices = _zmw_matrices(
      params, n, seed=11, passes=params.max_passes + extra_passes)
  views = _views(matrices, n)
  want_main, want_sn = _reference_pack(runner, views, params)
  # The values under test are really there.
  raw = np.stack(views)
  assert raw.max() > 255 and raw.min() < 0
  if use_ccs_bq:
    assert (raw == -1).any()

  formatted = form.startswith('formatted')
  if formatted:
    windows = data_lib.format_rows_batch(raw, params)
    if form == 'formatted':
      windows = list(windows)
  else:
    windows = views if form == 'views' else raw
  layout = data_lib.pack_layout(
      np.shape(windows[0])[0], params, formatted=formatted)
  at = 3
  main = np.full((n + 5,) + want_main.shape[1:], 77, np.uint8)
  sn = np.full((n + 5, 4), 7.0, np.float32)
  data_lib.fill_pack(windows, layout, main, sn, at=at)
  assert main[at:at + n].tobytes() == want_main.tobytes()
  assert sn[at:at + n].tobytes() == want_sn.tobytes()
  # Rows outside at..at+n are not touched.
  assert (main[:at] == 77).all() and (main[at + n:] == 77).all()
  assert (sn[:at] == 7.0).all() and (sn[at + n:] == 7.0).all()


@pytest.mark.parametrize('scratch_bytes', [1, 40_000, 1 << 20])
def test_fill_is_the_same_at_any_scratch_size(params, monkeypatch,
                                              scratch_bytes):
  """One window a chunk, a few, or all in one: the chunking is not part
  of the result."""
  runner, _ = _runner(params)
  views = _views(_zmw_matrices(params, 23, seed=5), 23)
  want_main, want_sn = _reference_pack(runner, views, params)
  monkeypatch.setattr(data_lib, '_FILL_SCRATCH_BYTES', scratch_bytes)
  main = np.zeros_like(want_main)
  sn = np.zeros_like(want_sn)
  data_lib.fill_pack(views, data_lib.pack_layout(
      views[0].shape[0], params), main, sn)
  assert main.tobytes() == want_main.tobytes()
  assert sn.tobytes() == want_sn.tobytes()


def test_layout_is_one_run_when_the_example_has_the_models_passes(params):
  layout = data_lib.pack_layout(params.total_rows, params)
  assert layout.runs == ((0, params.total_rows - 4, 0),)
  assert layout == data_lib.pack_layout(
      params.total_rows, params, formatted=True)
  cropped = data_lib.pack_layout(params.total_rows + 4 * 2, params)
  assert len(cropped.runs) == 5 and cropped.n_main == layout.n_main


@pytest.mark.parametrize('what', ['fewer_passes', 'bad_height',
                                  'formatted_height', 'odd_window',
                                  'wrong_pack'])
def test_fill_refuses_what_does_not_fit(params, what):
  views = _views(_zmw_matrices(params, 4, seed=1), 4)
  layout = data_lib.pack_layout(params.total_rows, params)
  main = np.zeros((4, layout.n_main, params.max_length, 1), np.uint8)
  sn = np.zeros((4, 4), np.float32)
  with pytest.raises(ValueError):
    if what == 'fewer_passes':
      data_lib.pack_layout(params.total_rows - 4, params)
    elif what == 'bad_height':
      data_lib.pack_layout(params.total_rows - 1, params)
    elif what == 'formatted_height':
      data_lib.pack_layout(params.total_rows + 4, params, formatted=True)
    elif what == 'odd_window':
      # A [1, L, 1] window would broadcast over all rows without a check.
      data_lib.fill_pack(views[:2] + [views[2][:1]], layout, main, sn)
    else:
      data_lib.fill_pack(views, layout, main[:, :-1], sn)
  assert not main.any() or what == 'odd_window'


# ----------------------------------------------------------------------
# The engine over the fill: packs, order, counters


class _Recorder:
  """Stub forward behind the real engine: keeps a copy of every pack as
  dispatched, and echoes each window's draft-CCS row at finalize from the
  engine's own buffer (a view), so a buffer reused or rewritten before
  its pack drained shows in what is delivered."""

  def __init__(self, params, fail=(), faults=None):
    self.mp = params.max_passes
    self.packs = []
    self.in_flight = []
    self.fail = set(fail)
    self.faults = dict(faults or {})  # dispatch ordinal -> exception
    self.n_dispatched = 0
    self.reused_while_in_flight = 0

  def attach(self, runner):
    runner.dispatch_pack = self.dispatch_pack
    runner.finalize = self.finalize
    return runner

  def dispatch_pack(self, main_u8, sn, n_rows=None, batch_size=None):
    ordinal = self.n_dispatched
    self.n_dispatched += 1
    base = main_u8 if main_u8.base is None else main_u8.base
    if any(base is b for b in self.in_flight):
      self.reused_while_in_flight += 1
    if ordinal in self.fail:
      raise RuntimeError(f'stub failure at dispatch {ordinal}')
    if ordinal in self.faults:
      raise self.faults[ordinal]
    n = len(main_u8) if n_rows is None else n_rows
    self.packs.append((main_u8.copy(), sn.copy(), n, batch_size))
    self.in_flight.append(base)
    return (main_u8, n, base)

  def finalize(self, handle):
    main_u8, n, base = handle
    self.in_flight = [b for b in self.in_flight if b is not base]
    ids = main_u8[:n, 4 * self.mp, :, 0].astype(np.int32)
    return ids, np.full(ids.shape, STUB_QUAL, np.int32)


def _recorded_engine(params, recorder=None, **kw):
  runner, options = _runner(params, **kw)
  recorder = recorder or _Recorder(params)
  recorder.attach(runner)
  delivered = []
  failures = []
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.append((t, ids.copy())),
      on_pack_failure=lambda ts, seq, e: failures.append((list(ts), seq, e)))
  return engine, recorder, delivered, failures


def _ccs_rows(windows, params):
  return [np.asarray(w)[4 * params.max_passes, :, 0].astype(np.uint8)
          for w in windows]


@pytest.mark.parametrize('depth', [1, 2, 8])
def test_uneven_submits_cut_the_old_packers_packs(params, depth):
  """A stream of uneven submits over several pack seams: every pack
  equals rows [i*B, (i+1)*B) of the stream through the old path, tickets
  come back in submission order, the flushed pack's stale rows are
  zero in a reused buffer, and the counters are the old ones."""
  engine, rec, delivered, failures = _recorded_engine(
      params, dispatch_depth=depth)
  runner = engine.runner
  sizes = [3, BATCH, 1, 2 * BATCH + 5, 7, BATCH - 1, 4 * BATCH, 2]
  total = sum(sizes)
  views = _views(_zmw_matrices(params, total, seed=3), total)
  lo = 0
  for size in sizes:
    engine.submit(views[lo:lo + size], list(range(lo, lo + size)))
    lo += size
  assert engine.has_work
  engine.flush()
  assert not engine.has_work and not failures

  want_main, want_sn = _reference_pack(runner, views, params)
  n_full, tail = divmod(total, BATCH)
  assert tail and len(rec.packs) == n_full + 1
  for i, (main, sn, n, batch_size) in enumerate(rec.packs):
    rows = slice(i * BATCH, i * BATCH + n)
    assert n == (BATCH if i < n_full else tail) and batch_size is None
    assert main.shape[0] == sn.shape[0] == BATCH
    assert main[:n].tobytes() == want_main[rows].tobytes(), i
    assert sn[:n].tobytes() == want_sn[rows].tobytes(), i
    # The short pack went out in a buffer an earlier pack had filled.
    assert not main[n:].any() and not sn[n:].any()
  assert [t for t, _ in delivered] == list(range(total))
  for (_, ids), want in zip(delivered, _ccs_rows(views, params)):
    assert ids.tobytes() == want.tobytes()
  assert (engine.n_packs, engine.n_pack_rows, engine.n_pad_rows) == (
      n_full + 1, total, BATCH - tail)
  stats = engine.stats()
  # depth in flight and the one being filled; never depth + 2.
  assert stats['n_pack_buffers_allocated'] == min(depth, n_full + 1) + 1
  assert rec.reused_while_in_flight == 0


def test_formatted_submit_dispatches_the_same_packs(params):
  """`dctpu serve` hands over formatted float32 rows: the same fill with
  the identity row map gives the packs a raw submit gives."""
  n = 2 * BATCH + 3
  views = _views(_zmw_matrices(params, n, seed=9), n)
  raw_engine, raw_rec, _, _ = _recorded_engine(params)
  raw_engine.submit(views, list(range(n)))
  raw_engine.flush()
  fmt_engine, fmt_rec, delivered, _ = _recorded_engine(params)
  rows = data_lib.format_rows_batch(np.stack(views), params)
  fmt_engine.submit_formatted(list(rows), list(range(n)))
  fmt_engine.flush()
  assert len(raw_rec.packs) == len(fmt_rec.packs) == 3
  for a, b in zip(raw_rec.packs, fmt_rec.packs):
    assert a[0].tobytes() == b[0].tobytes() and a[1].tobytes() == b[1].tobytes()
    assert a[2] == b[2]
  assert [t for t, _ in delivered] == list(range(n))


@pytest.mark.parametrize('formatted', [False, True], ids=['raw', 'formatted'])
def test_off_bucket_width_raises_before_anything_is_written(params, formatted):
  buckets = (params.max_length, 2 * params.max_length)
  engine, rec, delivered, _ = _recorded_engine(
      params, window_buckets=buckets)
  good = _views(_zmw_matrices(params, 3, seed=2), 3)
  odd = _views(_zmw_matrices(params, 2, seed=2, width=150), 2)
  submit = engine.submit_formatted if formatted else engine.submit
  with pytest.raises(faults_lib.WindowBucketError,
                     match='150 not in window buckets'):
    # The good width sorts first: it must not be buffered either.
    submit(good + odd, list(range(5)))
  assert not engine.has_work and not engine.n_packs
  assert engine.stats()['n_pack_buffers_allocated'] == 0
  assert engine.stats()['n_windows_by_bucket'] == {}
  engine.flush()
  assert not rec.packs and not delivered


def test_mixed_widths_fill_a_pool_per_bucket(params):
  buckets = (params.max_length, 2 * params.max_length)
  engine, rec, delivered, _ = _recorded_engine(
      params, window_buckets=buckets, dispatch_depth=1)
  narrow = _views(_zmw_matrices(params, 3 * BATCH, seed=4), 3 * BATCH)
  wide = _views(_zmw_matrices(
      params, BATCH + 2, seed=5, width=buckets[1]), BATCH + 2)
  windows = [w for pair in zip(narrow, wide) for w in pair]
  windows += narrow[len(wide):]
  engine.submit(windows, list(range(len(windows))))
  engine.flush()
  assert engine.n_packs_by_bucket == {buckets[0]: 3, buckets[1]: 2}
  assert sorted(t for t, _ in delivered) == list(range(len(windows)))
  by_ticket = dict(delivered)
  for t, want in enumerate(_ccs_rows(windows, params)):
    assert by_ticket[t].tobytes() == want.tobytes()
  # Two buffers a bucket at depth 1.
  assert engine.stats()['n_pack_buffers_allocated'] == 4


# ----------------------------------------------------------------------
# The life of a buffer


def test_callers_windows_are_free_when_submit_returns(params):
  """`run_inference` releases the shm segments behind its windows as soon
  as `submit` returns (inference/runner.py): nothing may read them later,
  not for the buffered tail either."""
  n = 2 * BATCH + 3
  matrices = _zmw_matrices(params, n, seed=6)
  views = _views(matrices, n)
  want = _ccs_rows([v.copy() for v in views], params)
  engine, rec, delivered, _ = _recorded_engine(params)
  engine.submit(views, list(range(n)))
  for m in matrices:
    m[...] = 3.0
  engine.flush()
  assert [t for t, _ in delivered] == list(range(n))
  for (_, ids), row in zip(delivered, want):
    assert ids.tobytes() == row.tobytes()


@pytest.mark.parametrize('depth', [1, 3])
def test_failed_packs_give_their_buffer_back_and_no_sooner(params, depth):
  """Packs that fail at dispatch are routed to on_pack_failure and their
  buffer goes back to the pool; no buffer is filled again while its
  pack is in flight, and the pool stays within depth + 2."""
  n_packs = 12
  rec = _Recorder(params, fail=(1, 4, 5))
  engine, rec, delivered, failures = _recorded_engine(
      params, rec, dispatch_depth=depth)
  n = n_packs * BATCH
  views = _views(_zmw_matrices(params, n, seed=7), n)
  for lo in range(0, n, 5):
    engine.submit(views[lo:lo + 5], list(range(lo, min(n, lo + 5))))
  engine.flush()
  assert [seq for _, seq, _ in failures] == [1, 4, 5]
  failed = {t for ts, _, _ in failures for t in ts}
  assert failed == {t for s in (1, 4, 5)
                    for t in range(s * BATCH, (s + 1) * BATCH)}
  assert [t for t, _ in delivered] == [
      t for t in range(n) if t not in failed]
  want = _ccs_rows(views, params)
  for t, ids in delivered:
    assert ids.tobytes() == want[t].tobytes(), t
  assert rec.reused_while_in_flight == 0
  assert engine.stats()['n_pack_buffers_allocated'] <= depth + 2


def test_oom_bisection_reads_intact_rows(params):
  """Degrade mode: a pack that meets RESOURCE_EXHAUSTED is retried as
  halves cut from its retained buffer (a quarter where a half fails
  again); every window is delivered once, in order, with its own rows,
  the short last pack's halves among them."""
  oom = faults_lib.DeviceOomError('stub')
  # Dispatch ordinals: 0 ok; 1 OOM -> halves 2 (OOM -> quarters 3, 4), 5;
  # 6 the flushed short pack, OOM -> halves 7, 8.
  rec = _Recorder(params, faults={1: oom, 2: oom, 6: oom})
  engine, rec, delivered, failures = _recorded_engine(
      params, rec, on_device_error='degrade', dispatch_depth=1)
  n = 2 * BATCH + 5
  views = _views(_zmw_matrices(params, n, seed=8), n)
  engine.submit(views, list(range(n)))
  engine.flush()
  assert not failures and engine.n_oom_bisections == 3
  assert sorted(t for t, _ in delivered) == list(range(n))
  want = _ccs_rows(views, params)
  for t, ids in delivered:
    assert ids.tobytes() == want[t].tobytes(), t
  shapes = [(len(main), n_rows, batch) for main, _, n_rows, batch in rec.packs]
  half, quarter = BATCH // 2, BATCH // 4
  assert shapes == [
      (BATCH, BATCH, None),
      (quarter, quarter, quarter), (quarter, quarter, quarter),
      (half, half, half),
      # 5 windows: a full half and one window in a half the flush zeroed.
      (half, half, half), (half, 1, half)]
  assert not rec.packs[-1][0][1:].any()
  assert engine.stats()['n_pack_buffers_allocated'] <= 3


def test_mesh_degrade_resubmits_every_pack_in_flight_from_its_buffer(params):
  """Degrade mode: a lost device at finalize resubmits the failed pack
  and everything launched after it, each from its own retained buffer,
  in featurize order; the pack being filled is not disturbed."""
  rec = _Recorder(params)
  engine, rec, delivered, failures = _recorded_engine(
      params, rec, on_device_error='degrade', dispatch_depth=3)
  degraded = []
  engine.runner.degrade_mesh = lambda: degraded.append(1) or 4
  finalize = rec.finalize
  lost = [True]

  def finalize_losing_the_first(handle):
    if lost.pop() if lost else False:
      raise faults_lib.DeviceLostError('DATA_LOSS: stub')
    return finalize(handle)

  engine.runner.finalize = finalize_losing_the_first
  n = 5 * BATCH + 3
  views = _views(_zmw_matrices(params, n, seed=10), n)
  for lo in range(0, n, 7):
    engine.submit(views[lo:lo + 7], list(range(lo, min(n, lo + 7))))
  engine.flush()
  assert degraded == [1] and not failures and engine.n_device_faults == 1
  assert [t for t, _ in delivered] == list(range(n))
  want = _ccs_rows(views, params)
  for t, ids in delivered:
    assert ids.tobytes() == want[t].tobytes(), t
  # Packs 0-3 were in flight when pack 0's drain failed: all four went out
  # again, then 4 and the short 5.
  assert [p[2] for p in rec.packs] == [BATCH] * 4 + [BATCH] * 4 + [BATCH, 3]
  assert engine.stats()['n_pack_buffers_allocated'] <= 5
