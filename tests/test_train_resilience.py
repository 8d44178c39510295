"""Fault-injection tests for the self-healing training layer.

Everything runs against synthetic TFRecord shards written by
scripts/inject_faults.write_synthetic_tfrecords (no reference testdata):
checkpoint integrity manifests + quarantine, preemption-safe saves, the
NaN sentinel's rollback, corrupt-shard tolerance, and the crash-loop
breaker in run_training_with_retry.
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.models import checkpoints as checkpoints_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import train as train_lib

pytestmark = pytest.mark.resilience

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
  sys.path.insert(0, _REPO_ROOT)

MAX_PASSES = 5
MAX_LENGTH = 20


@pytest.fixture
def fresh_faults(monkeypatch):
  """Fault hooks are consume-once per process; isolate each test."""
  monkeypatch.setattr(faults_lib, '_fired', set())


@pytest.fixture(scope='module')
def shards(tmp_path_factory):
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('synth_shards')
  return inject_faults.write_synthetic_tfrecords(
      str(d), n_shards=4, n_examples=64,
      max_passes=MAX_PASSES, max_length=MAX_LENGTH,
  )


def tiny_params(**overrides):
  params = config_lib.get_config('fc+test')
  with params.unlocked():
    params.max_passes = MAX_PASSES
    params.max_length = MAX_LENGTH
  config_lib.finalize_params(params)
  with params.unlocked():
    params.dtype = 'float32'
    params.batch_size = 8
    params.warmup_steps = 2
    params.buffer_size = 16
    params.log_every_n_steps = 4
    params.streaming = True
    params.n_examples_train = 64  # 8 steps per "epoch"
    for k, v in overrides.items():
      setattr(params, k, v)
  return params


def ckpt_dir_of(out_dir):
  return os.path.join(out_dir, 'checkpoints')


def list_ckpts(out_dir):
  d = ckpt_dir_of(out_dir)
  return sorted(
      n for n in os.listdir(d)
      if checkpoints_lib.checkpoint_step(n) is not None
  )


def metrics_entries(out_dir, split=None):
  entries = []
  with open(os.path.join(out_dir, 'metrics.jsonl')) as f:
    for line in f:
      e = json.loads(line)
      if split is None or e.get('split') == split:
        entries.append(e)
  return entries


# ----------------------------------------------------------------------
# Checkpoint integrity: manifests, validation, quarantine (unit level)


def _fake_checkpoint(ckpt_root, step, payload=b'x' * 64):
  path = os.path.join(ckpt_root, f'checkpoint-{step}')
  os.makedirs(os.path.join(path, 'sub'))
  with open(os.path.join(path, 'arrays.bin'), 'wb') as f:
    f.write(payload)
  with open(os.path.join(path, 'sub', 'meta.json'), 'w') as f:
    f.write('{}')
  return path


def test_manifest_roundtrip_and_truncation_detected(tmp_path):
  root = str(tmp_path)
  path = _fake_checkpoint(root, 5)
  checkpoints_lib.write_manifest(path, 5, digest='d' * 8)
  ok, reason = checkpoints_lib.validate_checkpoint(path)
  assert ok, reason
  manifest = checkpoints_lib.read_manifest(path)
  assert manifest['step'] == 5
  assert manifest['files']['arrays.bin'] == 64

  with open(os.path.join(path, 'arrays.bin'), 'r+b') as f:
    f.truncate(10)
  ok, reason = checkpoints_lib.validate_checkpoint(path)
  assert not ok and 'size mismatch' in reason

  os.unlink(checkpoints_lib.manifest_path(path))
  ok, reason = checkpoints_lib.validate_checkpoint(path)
  assert not ok and 'manifest' in reason


def test_latest_valid_quarantines_corrupt_newest(tmp_path):
  root = str(tmp_path)
  good = _fake_checkpoint(root, 2)
  checkpoints_lib.write_manifest(good, 2)
  bad = _fake_checkpoint(root, 4)
  checkpoints_lib.write_manifest(bad, 4)
  with open(os.path.join(bad, 'arrays.bin'), 'r+b') as f:
    f.truncate(3)

  assert checkpoints_lib.latest_valid_checkpoint(root) == good
  qdir = os.path.join(root, checkpoints_lib.QUARANTINE_DIRNAME)
  assert os.path.isdir(os.path.join(qdir, 'checkpoint-4'))
  assert os.path.exists(os.path.join(qdir, 'checkpoint-4.reason.txt'))
  assert not os.path.exists(bad)
  # Second scan is stable: the quarantined dir never reappears.
  assert checkpoints_lib.latest_valid_checkpoint(root) == good


def test_uncommitted_newest_is_quarantined(tmp_path):
  """A directory without a committed manifest (crash between orbax
  finishing and the manifest write, or mid-save) must not be resumed
  when a committed sibling exists."""
  root = str(tmp_path)
  good = _fake_checkpoint(root, 8)
  checkpoints_lib.write_manifest(good, 8)
  _fake_checkpoint(root, 12)  # no manifest: save never committed

  assert checkpoints_lib.latest_valid_checkpoint(root) == good
  qdir = os.path.join(root, checkpoints_lib.QUARANTINE_DIRNAME)
  assert os.path.isdir(os.path.join(qdir, 'checkpoint-12'))


def test_legacy_dir_without_manifests_uses_newest(tmp_path):
  """Pre-manifest checkpoint dirs resume with the old newest-step rule
  instead of quarantining a whole run's history."""
  root = str(tmp_path)
  _fake_checkpoint(root, 2)
  newest = _fake_checkpoint(root, 4)
  assert checkpoints_lib.latest_valid_checkpoint(root) == newest
  assert not os.path.exists(
      os.path.join(root, checkpoints_lib.QUARANTINE_DIRNAME))
  assert checkpoints_lib.latest_valid_step(root) == 4


def test_latest_valid_step_is_read_only(tmp_path):
  root = str(tmp_path)
  good = _fake_checkpoint(root, 2)
  checkpoints_lib.write_manifest(good, 2)
  bad = _fake_checkpoint(root, 4)
  checkpoints_lib.write_manifest(bad, 4)
  with open(os.path.join(bad, 'arrays.bin'), 'r+b') as f:
    f.truncate(1)
  assert checkpoints_lib.latest_valid_step(root) == 2
  assert os.path.exists(bad)  # not quarantined by the read-only probe


def test_load_missing_checkpoint_names_path(tmp_path):
  missing = str(tmp_path / 'no' / 'such' / 'checkpoint-3')
  with pytest.raises(FileNotFoundError, match='checkpoint-3'):
    checkpoints_lib.load_params(missing)
  with pytest.raises(FileNotFoundError, match='checkpoint-3'):
    checkpoints_lib.load_full_state(missing)


def test_tree_digest_sensitive_to_values():
  tree = {'a': np.arange(8, dtype=np.float32), 'b': np.zeros(3)}
  d1 = checkpoints_lib.tree_digest(tree)
  tree['a'] = tree['a'] + 1
  assert checkpoints_lib.tree_digest(tree) != d1


def test_save_checkpoint_commits_manifest_and_digest(tmp_path):
  params = tiny_params()
  out_dir = str(tmp_path / 'save')
  trainer = train_lib.Trainer(params=params, out_dir=out_dir)
  state = trainer.init_state(steps_total=8)
  path = trainer.save_checkpoint(state, 0, {})
  ok, reason = checkpoints_lib.validate_checkpoint(path)
  assert ok, reason
  assert checkpoints_lib.verify_digest(path)
  assert trainer.latest_valid_checkpoint() == path


# ----------------------------------------------------------------------
# End-to-end recovery paths (in-process training on synthetic shards)


@pytest.mark.slow


def test_resume_skips_truncated_checkpoint(tmp_path, shards):
  from scripts import inject_faults

  params = tiny_params()
  out_dir = str(tmp_path / 'resume')
  train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=2, eval_every=4,
  )
  assert list_ckpts(out_dir) == [
      'checkpoint-12', 'checkpoint-16', 'checkpoint-4', 'checkpoint-8'
  ]
  newest = os.path.join(ckpt_dir_of(out_dir), 'checkpoint-16')
  inject_faults.corrupt_checkpoint(newest, mode='truncate')

  m = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=3, eval_every=4,
  )
  assert np.isfinite(m['eval/loss'])
  qdir = os.path.join(ckpt_dir_of(out_dir),
                      checkpoints_lib.QUARANTINE_DIRNAME)
  assert os.path.isdir(os.path.join(qdir, 'checkpoint-16'))
  # Resumed from checkpoint-12 and trained through the 3-epoch budget.
  assert 'checkpoint-24' in list_ckpts(out_dir)
  steps = [e['step'] for e in metrics_entries(out_dir, 'train')]
  # A restart from step 0 would log step 4 a second time.
  assert steps.count(4) == 1
  assert 24 in steps


@pytest.mark.slow


def test_nan_sentinel_rolls_back_and_dead_letters(
    tmp_path, shards, monkeypatch, fresh_faults):
  params = tiny_params(nan_sentinel_steps=1, track_window_ids=True)
  out_dir = str(tmp_path / 'nan')
  monkeypatch.setenv(faults_lib.ENV_NAN_AT_STEP, '6')
  m = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=2, eval_every=4,
  )
  assert np.isfinite(m['eval/loss'])
  # 16 batches; steps 1..6 (6 poisoned), detected during iteration 7,
  # rolled back to checkpoint-4, remaining 9 batches run steps 5..13.
  assert 'checkpoint-13' in list_ckpts(out_dir)
  letters = faults_lib.read_dead_letters(
      os.path.join(out_dir, 'training.failed.jsonl'))
  assert letters and letters[0]['action'] == 'rollback'
  assert letters[0]['step'] == 6
  ids = letters[0]['window_ids']
  assert len(ids) == params.batch_size
  assert all(i.startswith('syn/') for i in ids)
  faults = metrics_entries(out_dir, 'faults')[-1]
  assert faults['n_nonfinite_steps'] >= 1
  assert faults['n_nan_rollbacks'] == 1


@pytest.mark.slow


def test_nan_sentinel_never_checkpoints_contaminated_state(
    tmp_path, shards, monkeypatch, fresh_faults):
  # NaN at step 6 with the default 3-step sentinel: the step-8 eval
  # boundary arrives while the state is contaminated but the verdict
  # is still pending (verdicts read one step late). The boundary must
  # force-resolve the verdict and skip the save — a poisoned
  # checkpoint-8 would otherwise become the "last valid checkpoint"
  # the rollback restores, and the run would exhaust its rollback
  # budget ping-ponging on NaN weights (caught by the CLI drive).
  params = tiny_params(nan_sentinel_steps=3, nan_max_rollbacks=2)
  out_dir = str(tmp_path / 'nan_boundary')
  monkeypatch.setenv(faults_lib.ENV_NAN_AT_STEP, '6')
  m = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=2, eval_every=4,
  )
  assert np.isfinite(m['eval/loss'])
  faults = metrics_entries(out_dir, 'faults')[-1]
  assert faults['n_nan_rollbacks'] == 1
  assert faults['n_nonfinite_steps'] == 3
  # Rolled back from step 8 to checkpoint-4 (16-batch budget, 8 spent,
  # remaining 8 land on steps 5..12); the surviving checkpoints all
  # hold finite weights.
  assert 'checkpoint-12' in list_ckpts(out_dir)
  letters = faults_lib.read_dead_letters(
      os.path.join(out_dir, 'training.failed.jsonl'))
  assert [l['action'] for l in letters] == [
      'recorded', 'recorded', 'rollback']


def test_nan_sentinel_without_checkpoint_raises_permanent(
    tmp_path, shards, monkeypatch, fresh_faults):
  """Divergence before the first checkpoint has nothing to roll back
  to: the error must be permanent (no retry loop on a diverged run)."""
  params = tiny_params(nan_sentinel_steps=1)
  monkeypatch.setenv(faults_lib.ENV_NAN_AT_STEP, '2')
  with pytest.raises(faults_lib.NonFiniteTrainingError):
    train_lib.run_training(
        params=params, out_dir=str(tmp_path / 'nan2'),
        train_patterns=shards, eval_patterns=shards,
        num_epochs=1, eval_every=10**9,
    )
  err = 'NonFiniteTrainingError: training diverged'
  assert faults_lib.classify_error(err) == faults_lib.FaultKind.PERMANENT


@pytest.mark.slow


def test_sigterm_checkpoints_and_exits_cleanly(
    tmp_path, shards, monkeypatch, fresh_faults):
  params = tiny_params()
  out_dir = str(tmp_path / 'preempt')
  monkeypatch.setenv(faults_lib.ENV_SIGTERM_AT_STEP, '5')
  before = signal.getsignal(signal.SIGTERM)
  m = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=2, eval_every=10**9,
  )
  assert m == {'preempted': 1.0, 'stop_step': 5.0}
  # The emergency save is a committed, resumable checkpoint.
  path = os.path.join(ckpt_dir_of(out_dir), 'checkpoint-5')
  ok, reason = checkpoints_lib.validate_checkpoint(path)
  assert ok, reason
  # Handlers restored after the run.
  assert signal.getsignal(signal.SIGTERM) == before
  # A restart resumes from the emergency checkpoint and completes.
  m2 = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards,
      eval_patterns=shards, num_epochs=2, eval_every=10**9,
  )
  assert np.isfinite(m2['eval/loss'])
  assert 'checkpoint-16' in list_ckpts(out_dir)


# ----------------------------------------------------------------------
# Corrupt-shard tolerance (StreamingDataset --on_shard_error)


def _truncate(path, keep=40):
  with open(path, 'r+b') as f:
    f.truncate(keep)


@pytest.fixture
def shards_one_corrupt(tmp_path):
  # 4 shards so the workers=2 round-robin assignment gives the corrupt
  # shard's owner a good shard too (a worker whose ENTIRE subset is
  # undecodable exits by design, even under skip).
  from scripts import inject_faults

  paths = inject_faults.write_synthetic_tfrecords(
      str(tmp_path / 'mixed'), n_shards=4, n_examples=64,
      max_passes=MAX_PASSES, max_length=MAX_LENGTH,
  )
  _truncate(paths[1])
  return paths


def _drain(ds, n):
  it = iter(ds)
  try:
    return [next(it) for _ in range(n)]
  finally:
    it.close()


def _drain_until(ds, done):
  """Draws full batches until `done(ds.counters)` holds. Two spawned
  reader processes come up in whichever order the machine schedules
  them, and the first one up can feed any fixed number of batches by
  itself: what a worker-side counter reads after N batches is the
  clock's to decide, so wait on the counter, bounded by a batch count
  that a late interpreter start-up cannot outlast."""
  max_batches = 50_000  # about 20 s of one worker's output
  it = iter(ds)
  try:
    for n in range(1, max_batches + 1):
      assert next(it)['rows'].shape[0] == 8
      # 12 batches: more than one epoch of the three good shards.
      if n >= 12 and done(ds.counters):
        return
  finally:
    it.close()
  pytest.fail(f'condition not met after {max_batches} batches; '
              f'counters: {dict(ds.counters)}')


def test_corrupt_shard_fails_by_default(shards_one_corrupt):
  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=shards_one_corrupt, params=params, batch_size=8,
      buffer_size=16, seed=0,
  )
  with pytest.raises(Exception, match='end-of-stream|truncated'):
    _drain(ds, 20)


def test_corrupt_shard_skipped_serial(shards_one_corrupt):
  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=shards_one_corrupt, params=params, batch_size=8,
      buffer_size=16, seed=0, on_shard_error='skip',
  )
  batches = _drain(ds, 12)  # > one epoch of the three good shards
  assert all(b['rows'].shape[0] == 8 for b in batches)
  assert ds.counters['n_shard_errors'] >= 1


def test_corrupt_shard_skipped_with_workers(shards_one_corrupt):
  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=shards_one_corrupt, params=params, batch_size=8,
      buffer_size=16, seed=0, workers=2, on_shard_error='skip',
  )
  _drain_until(ds, lambda c: c['n_shard_errors'] >= 1)


def test_per_worker_decode_counters_cover_all_workers(shards):
  """Every worker's parses land in its own n_parsed_worker_N counter:
  the evidence that the decode load splits across the workers."""
  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=shards, params=params, batch_size=8,
      buffer_size=16, seed=0, workers=2,
  )
  _drain_until(ds, lambda c: c['n_parsed_worker_0'] > 0
               and c['n_parsed_worker_1'] > 0)
  per_worker = {k for k in ds.counters if k.startswith('n_parsed_worker_')}
  assert per_worker == {'n_parsed_worker_0', 'n_parsed_worker_1'}


def test_all_shards_corrupt_raises_even_under_skip(tmp_path):
  from scripts import inject_faults

  paths = inject_faults.write_synthetic_tfrecords(
      str(tmp_path / 'allbad'), n_shards=2, n_examples=16,
      max_passes=MAX_PASSES, max_length=MAX_LENGTH,
  )
  for p in paths:
    _truncate(p)
  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=paths, params=params, batch_size=8, buffer_size=16,
      seed=0, on_shard_error='skip',
  )
  with pytest.raises(RuntimeError, match='every shard failed'):
    _drain(ds, 1)


def test_worker_crash_names_owned_shards(shards, monkeypatch, tmp_path):
  """A SIGKILLed shard reader must be reported with the exact shard
  paths it owned, so the operator can bisect to the corrupt file."""
  params = tiny_params()
  monkeypatch.setenv(faults_lib.ENV_KILL_SHARD_READER, 'shard-00001')
  monkeypatch.setenv(faults_lib.ENV_KILL_TOKEN,
                     str(tmp_path / 'kill.token'))
  ds = data_lib.StreamingDataset(
      patterns=shards, params=params, batch_size=8, buffer_size=16,
      seed=0, workers=2,
  )
  with pytest.raises(RuntimeError) as err:
    # Until the liveness check meets the killed reader: the other
    # worker can feed any fixed number of batches before that.
    _drain_until(ds, lambda c: False)
  msg = str(err.value)
  assert 'owned shards' in msg
  assert 'shard-00001' in msg


def test_abandoned_iterator_stops_workers(shards):
  """Regression: closing/abandoning the iterator must stop the reader
  machinery (workers + producer thread), not leak it into the next
  retry attempt."""
  import multiprocessing

  params = tiny_params()
  ds = data_lib.StreamingDataset(
      patterns=shards, params=params, batch_size=8, buffer_size=16,
      seed=0, workers=2,
  )
  it = iter(ds)
  assert next(it)['rows'].shape[0] == 8
  it.close()
  leftover = [p for p in multiprocessing.active_children()
              if p.is_alive()]
  assert not leftover


def test_training_survives_corrupt_shard_with_skip(
    tmp_path, shards_one_corrupt):
  """Acceptance demo (c): a corrupt shard under --on_shard_error=skip
  ends at the expected step with the skip counted in the summary."""
  params = tiny_params(on_shard_error='skip', n_examples_train=32)
  out_dir = str(tmp_path / 'skiprun')
  m = train_lib.run_training(
      params=params, out_dir=out_dir, train_patterns=shards_one_corrupt,
      eval_patterns=[shards_one_corrupt[0], shards_one_corrupt[2]],
      num_epochs=2, eval_every=10**9,
  )
  assert np.isfinite(m['eval/loss'])
  assert 'checkpoint-8' in list_ckpts(out_dir)  # 2 * 32/8 steps
  faults = metrics_entries(out_dir, 'faults')[-1]
  assert faults['n_shard_errors'] >= 1


# ----------------------------------------------------------------------
# Crash-loop breaker + retry taxonomy


def test_crash_loop_breaker_aborts_stalled_restarts(monkeypatch, tmp_path):
  calls = []

  def fake_run_training(*args, **kwargs):
    calls.append(1)
    raise RuntimeError('UNAVAILABLE: TPU worker restarted')

  monkeypatch.setattr(train_lib, 'run_training', fake_run_training)
  monkeypatch.setattr(train_lib.time, 'sleep', lambda s: None)
  with pytest.raises(faults_lib.CrashLoopError, match='resume step'):
    train_lib.run_training_with_retry(out_dir=str(tmp_path / 'loop'))
  # 1 initial + max_stalled_restarts retries without progress.
  assert len(calls) == 4


def test_retry_continues_while_resume_step_advances(monkeypatch, tmp_path):
  calls = []
  steps = iter([4, 8, 12, 16, 20, 24])

  def fake_run_training(*args, **kwargs):
    calls.append(1)
    if len(calls) <= 6:
      raise RuntimeError('UNAVAILABLE: preempted')
    return {'eval/loss': 0.1}

  monkeypatch.setattr(train_lib, 'run_training', fake_run_training)
  monkeypatch.setattr(train_lib.time, 'sleep', lambda s: None)
  monkeypatch.setattr(
      train_lib.checkpoints_lib, 'latest_valid_step',
      lambda d: next(steps, 24),
  )
  out = train_lib.run_training_with_retry(out_dir=str(tmp_path / 'adv'))
  assert out == {'eval/loss': 0.1}
  assert len(calls) == 7  # breaker never tripped


def test_retry_backoff_is_exponential(monkeypatch, tmp_path):
  delays = []

  def fake_run_training(*args, **kwargs):
    if len(delays) < 3:
      raise RuntimeError('UNAVAILABLE: flapping')
    return {}

  monkeypatch.setattr(train_lib, 'run_training', fake_run_training)
  monkeypatch.setattr(train_lib.time, 'sleep', delays.append)
  train_lib.run_training_with_retry(backoff_base=0.5, backoff_max=64.0)
  assert delays == [0.5, 1.0, 2.0]


def test_nonfinite_error_not_retried(monkeypatch):
  calls = []

  def fake_run_training(*args, **kwargs):
    calls.append(1)
    raise faults_lib.NonFiniteTrainingError('training diverged')

  monkeypatch.setattr(train_lib, 'run_training', fake_run_training)
  with pytest.raises(faults_lib.NonFiniteTrainingError):
    train_lib.run_training_with_retry()
  assert len(calls) == 1


# ----------------------------------------------------------------------
# Acceptance demo (a): SIGKILL mid-run, truncate the newest checkpoint,
# restart resumes from the previous valid one and finishes.


@pytest.mark.slow
def test_subprocess_kill_truncate_resume(tmp_path):
  from scripts import inject_faults

  repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  shard_dir = str(tmp_path / 'shards')
  inject_faults.write_synthetic_tfrecords(
      shard_dir, n_shards=2, n_examples=64,
      max_passes=MAX_PASSES, max_length=MAX_LENGTH,
  )
  out_dir = str(tmp_path / 'run')
  cmd = [
      sys.executable, '-m', 'deepconsensus_tpu.cli', 'train',
      '--config', 'fc+test', '--out_dir', out_dir,
      '--train_path', os.path.join(shard_dir, 'shard-*.tfrecord.gz'),
      '--eval_path', os.path.join(shard_dir, 'shard-*.tfrecord.gz'),
      '--num_epochs', '4', '--batch_size', '8',
      '--set', 'max_passes=5', '--set', 'max_length=20',
      '--set', 'dtype=float32', '--set', 'warmup_steps=2',
      '--set', 'eval_every_n_steps=4', '--set', 'log_every_n_steps=4',
  ]
  env = dict(
      os.environ,
      JAX_PLATFORMS='cpu',
      PYTHONPATH=repo_root,
      **{
          faults_lib.ENV_KILL_TRAIN_AT_STEP: '10',
          faults_lib.ENV_KILL_TOKEN: str(tmp_path / 'kill.token'),
      },
  )
  first = subprocess.run(cmd, env=env, cwd=repo_root,
                         capture_output=True, text=True, timeout=300)
  assert first.returncode == -signal.SIGKILL, first.stderr[-2000:]
  # 64 examples / batch 8 = 8 steps/epoch; killed at step 10 after the
  # saves at 4 and 8.
  assert {'checkpoint-4', 'checkpoint-8'} <= set(list_ckpts(out_dir))

  inject_faults.corrupt_checkpoint(
      os.path.join(ckpt_dir_of(out_dir), 'checkpoint-8'),
      mode='truncate',
  )
  second = subprocess.run(cmd, env=env, cwd=repo_root,
                          capture_output=True, text=True, timeout=300)
  assert second.returncode == 0, second.stderr[-2000:]
  qdir = os.path.join(ckpt_dir_of(out_dir),
                      checkpoints_lib.QUARANTINE_DIRNAME)
  assert os.path.isdir(os.path.join(qdir, 'checkpoint-8'))
  # Resumed from checkpoint-4 and ran out the 4-epoch (32-step) budget.
  ckpts = list_ckpts(out_dir)
  assert 'checkpoint-32' in ckpts
  # The restart re-saves a FRESH checkpoint-8 (resuming from 4 passes
  # the step-8 eval boundary again); it must validate, unlike the
  # truncated original now in quarantine.
  ok, reason = checkpoints_lib.validate_checkpoint(
      os.path.join(ckpt_dir_of(out_dir), 'checkpoint-8'))
  assert ok, reason
  train_steps = [e['step'] for e in metrics_entries(out_dir, 'train')]
  # A restart from step 0 would log step 4 a second time.
  assert train_steps.count(4) == 1
  assert 32 in train_steps
