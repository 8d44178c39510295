"""Elastic multi-host training: bounded barriers, coordinated pod
rebuild, and host re-admission (PR 18).

Each in-process "host" is a thread running the real `run_training`
loop over its own forced CPU device with an `elastic_config`; the
shared-filesystem pod under <out_dir>/.pod is the only channel
between them, exactly as on a real fleet with a shared out_dir.

The identity contract mirrors test_train_parallel's cross-dp one:
every member consumes the SAME global batch (same seed) and slices it
by member rank, and step_sync's weighted mean (weights = local slice
rows) reconstructs the exact global-batch-mean gradient — so a run
disturbed by a host death (pod shrinks to the survivors) or a
re-admission (pod grows back) must trace the SAME loss curve as an
undisturbed run, to all-reduce reduction order (~1e-6 relative on
CPU; pinned at rtol=1e-4 plus the 1e-4-quantized digest).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu import obs as obs_lib
from deepconsensus_tpu.models import checkpoints as checkpoints_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import train as train_lib
from deepconsensus_tpu.parallel import distributed
from deepconsensus_tpu.parallel import elastic as elastic_lib
from deepconsensus_tpu.parallel import mesh as mesh_lib

pytestmark = [pytest.mark.multichip, pytest.mark.resilience]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
  sys.path.insert(0, _REPO_ROOT)

MAX_PASSES = 5
MAX_LENGTH = 20
GLOBAL_BATCH = 16
N_EXAMPLES = 96  # 6 steps per epoch at the fixed global batch
STEPS_PER_EPOCH = 6


@pytest.fixture(scope='module')
def shards(tmp_path_factory):
  from scripts import inject_faults

  d = tmp_path_factory.mktemp('elastic_shards')
  return inject_faults.write_synthetic_tfrecords(
      str(d), n_shards=4, n_examples=N_EXAMPLES,
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
    params.batch_size = GLOBAL_BATCH
    params.warmup_steps = 2
    params.log_every_n_steps = 1
    params.seed = 7
    for k, v in overrides.items():
      setattr(params, k, v)
  return params


def elastic_host(shards, out_dir, host_id, n_hosts, num_epochs,
                 results, key=None, **ecfg):
  """One pod member: the full training loop on its own device, talking
  to peers only through <out_dir>/.pod."""
  key = host_id if key is None else key
  try:
    params = tiny_params()
    mesh = mesh_lib.make_mesh(dp=1, tp=1,
                              devices=[jax.devices()[host_id]])
    m = train_lib.run_training(
        params=params, out_dir=out_dir,
        train_patterns=list(shards), eval_patterns=list(shards),
        num_epochs=num_epochs, mesh=mesh, eval_every=1_000_000,
        elastic_config={'host_id': host_id, 'n_hosts': n_hosts,
                        'barrier_timeout': 5.0,
                        'heartbeat_interval': 0.1, **ecfg},
    )
    results[key] = ('ok', m)
  except BaseException as e:  # noqa: B036 - drills inject BaseException
    results[key] = ('err', e)


def metrics_entries(out_dir, split=None):
  entries = []
  with open(os.path.join(out_dir, 'metrics.jsonl')) as f:
    for line in f:
      e = json.loads(line)
      if split is None or e.get('split') == split:
        entries.append(e)
  return entries


def train_losses(out_dir):
  return [e['loss'] for e in metrics_entries(out_dir, 'train')]


def curve_digest_1e4(losses):
  import hashlib

  return hashlib.sha256(
      json.dumps([round(l, 4) for l in losses]).encode()
  ).hexdigest()[:16]


def final_checkpoint_params(out_dir):
  latest = checkpoints_lib.latest_valid_checkpoint(
      os.path.join(out_dir, 'checkpoints'))
  assert latest is not None
  return checkpoints_lib.load_params(latest)


def trace_event_names(trace_path):
  names = []
  with open(trace_path) as f:
    for line in f:
      line = line.strip().rstrip(',')
      if not line or line == '[':
        continue
      names.append(json.loads(line).get('name'))
  return names


class _shared_trace:
  """Context manager: one stable trace writer for all drill threads.

  run_training calls trace.configure_from_env per invocation; with two
  in-process hosts that would close the sibling's writer mid-run (real
  fleets are separate processes, where per-process configure is
  correct). Configure once here and no-op the per-run reconfigure."""

  def __init__(self, path):
    self.path = path

  def __enter__(self):
    self._orig = obs_lib.trace.configure_from_env
    obs_lib.trace.configure(self.path, tier='train')
    obs_lib.trace.configure_from_env = lambda tier='': None
    return self

  def __exit__(self, *exc):
    obs_lib.trace.configure_from_env = self._orig
    obs_lib.trace.configure(None)
    return False


class _deadline_only_at_step:
  """Context manager: of all the drill's collectives only the one the
  victim never answers, the step barrier it dies in front of, keeps
  elastic_host's 5 s deadline; every other wait gets LOADED_DEADLINE_S.

  Which barrier expires must follow from the step count, not from the
  clock: the first steps of a process compile and open the summary
  writer, and on a loaded machine (six test workers) either outlasts
  5 s, so a live peer was declared lost at step 1 or 2, the pod split
  before the injected death, a step was logged twice and the epoch
  bumped once more than the drill expects."""

  LOADED_DEADLINE_S = 120.0

  def __init__(self, step):
    self.name = f'r0-step-{step}'

  def __enter__(self):
    self._orig = orig = elastic_lib.ElasticPod._collect
    kill_barrier, floor = self.name, self.LOADED_DEADLINE_S

    def collect(pod, epoch, name, expected, timeout_s):
      if name != kill_barrier:
        timeout_s = max(timeout_s, floor)
      return orig(pod, epoch, name, expected, timeout_s)

    elastic_lib.ElasticPod._collect = collect
    return self

  def __exit__(self, *exc):
    elastic_lib.ElasticPod._collect = self._orig
    return False


def assert_params_close(out_a, out_b):
  la = jax.tree_util.tree_leaves(final_checkpoint_params(out_a))
  lb = jax.tree_util.tree_leaves(final_checkpoint_params(out_b))
  assert len(la) == len(lb)
  for va, vb in zip(la, lb):
    np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                               rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------------------
# bounded_call: the watchdog for uncancellable legacy collectives


def test_bounded_call_passes_value_and_error_through():
  assert elastic_lib.bounded_call(lambda: 42, 5.0, 'ok') == 42
  with pytest.raises(ZeroDivisionError):
    elastic_lib.bounded_call(lambda: 1 / 0, 5.0, 'boom')


def test_bounded_call_deadline_is_bounded_and_typed():
  t0 = time.monotonic()
  with pytest.raises(faults_lib.HostLostError) as ei:
    elastic_lib.bounded_call(lambda: time.sleep(60), 0.3, 'stuck-vote')
  elapsed = time.monotonic() - t0
  assert elapsed < 5.0, f'watchdog waited {elapsed:.1f}s for a 0.3s deadline'
  assert 'stuck-vote' in str(ei.value)
  assert faults_lib.classify_error(
      f'{type(ei.value).__name__}: {ei.value}'
  ) == faults_lib.FaultKind.TRANSIENT


# ----------------------------------------------------------------------
# Pod protocol units (no training loop)


def test_atomic_write_survives_two_writers_of_one_path(tmp_path):
  """The heartbeat thread and the training thread of one host both
  publish hb/<host>.json (rebuild and boot write a beat themselves):
  neither may trip over the other's temp file."""
  path = str(tmp_path / 'beat.json')
  errors = []

  def writer(tag):
    try:
      for i in range(300):
        elastic_lib._atomic_write_bytes(path, b'%s %d' % (tag, i))
    except OSError as e:
      errors.append(e)

  threads = [threading.Thread(target=writer, args=(tag,))
             for tag in (b'beat', b'main')]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=120)
  assert not errors, errors
  with open(path, 'rb') as f:
    assert f.read().split()[0] in (b'beat', b'main')


def test_pod_geometry_and_timeout_validation(tmp_path):
  with pytest.raises(ValueError):
    elastic_lib.ElasticPod(str(tmp_path / 'p'), host_id=0, n_hosts=0)
  with pytest.raises(ValueError):
    elastic_lib.ElasticPod(str(tmp_path / 'p'), host_id=-1, n_hosts=2)
  with pytest.raises(ValueError):
    elastic_lib.ElasticPod(str(tmp_path / 'p'), host_id=0, n_hosts=1,
                           barrier_timeout=0.0)


def test_member_batch_slice_partitions_exactly():
  for n, k in [(16, 2), (16, 3), (7, 3), (5, 8)]:
    slices = [distributed.member_batch_slice(n, k, r) for r in range(k)]
    rows = np.concatenate([np.arange(n)[s] for s in slices])
    np.testing.assert_array_equal(rows, np.arange(n))
    sizes = [len(np.arange(n)[s]) for s in slices]
    assert sizes == [len(part) for part in np.array_split(np.arange(n), k)]


@pytest.fixture
def booted_pair(tmp_path):
  """Two started pod endpoints that rendezvoused as founding members."""
  pods = [
      elastic_lib.ElasticPod(str(tmp_path / 'pod'), host_id=i, n_hosts=2,
                             barrier_timeout=5.0, heartbeat_interval=0.1,
                             boot_timeout=30.0)
      for i in range(2)
  ]
  starts = [None, None]

  def boot(i):
    starts[i] = pods[i].start()

  threads = [threading.Thread(target=boot, args=(i,)) for i in range(2)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=60)
  assert all(s is not None and not s.joined for s in starts)
  assert all(p.members == (0, 1) and p.epoch == 1 for p in pods)
  yield pods
  for p in pods:
    p.close()


def test_barrier_timeout_sweep_no_unbounded_wait(booted_pair):
  """A silent peer surfaces as a typed error naming the missing host
  after ~the configured deadline — for every deadline, never an
  unbounded wait."""
  pod0, _ = booted_pair
  for timeout_s in (0.4, 0.8, 1.6):
    t0 = time.monotonic()
    with pytest.raises(faults_lib.HostLostError) as ei:
      pod0.barrier(f'sweep-{timeout_s}', timeout_s=timeout_s)
    elapsed = time.monotonic() - t0
    # Generous slack for fs polling; the point is elapsed tracks the
    # configured deadline instead of growing without bound.
    assert elapsed < timeout_s + 3.0, (
        f'{timeout_s}s barrier took {elapsed:.1f}s')
    assert ei.value.missing == (1,)
    assert ei.value.epoch == 1
  assert pod0.counters()['n_barrier_timeouts'] == 3.0


def test_step_sync_weighted_mean_and_control_plane(booted_pair):
  pods = booted_pair
  grads = {0: np.full(4, 1.0, np.float32), 1: np.full(4, 4.0, np.float32)}
  weights = {0: 6.0, 1: 2.0}
  out = [None, None]

  def sync(i):
    out[i] = pods[i].step_sync(
        1, [grads[i]], weight=weights[i],
        meta={'loss': float(i)}, stop_vote=(i == 1))

  threads = [threading.Thread(target=sync, args=(i,)) for i in range(2)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=30)
  for i in range(2):
    assert out[i] is not None
    # Exact global mean: (6*1 + 2*4) / 8 = 1.75.
    np.testing.assert_allclose(out[i].arrays[0],
                               np.full(4, 1.75, np.float32), rtol=1e-6)
    assert out[i].stop  # one vote is enough: stop is ORed
    assert out[i].weight_total == 8.0
    assert out[i].metas[0]['loss'] == 0.0
    assert out[i].metas[1]['loss'] == 1.0


def test_advance_round_isolates_replayed_steps(booted_pair):
  """After a rollback (advance_round) a replayed step number must NOT
  collect the stale payloads of its first pass."""
  pods = booted_pair
  out = [None, None]

  def sync(i, value):
    out[i] = pods[i].step_sync(1, [np.full(2, value, np.float32)],
                               weight=1.0)

  for value in (1.0, 9.0):
    threads = [threading.Thread(target=sync, args=(i, value))
               for i in range(2)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=30)
    np.testing.assert_allclose(out[0].arrays[0],
                               np.full(2, value, np.float32))
    for p in pods:
      p.advance_round()


# ----------------------------------------------------------------------
# Bounded legacy collectives: stop vote + orbax save


def test_preemption_guard_stop_vote_bounded(monkeypatch):
  from jax.experimental import multihost_utils

  monkeypatch.setattr(jax, 'process_count', lambda: 2)
  monkeypatch.setattr(multihost_utils, 'process_allgather',
                      lambda *a, **k: time.sleep(60))
  guard = train_lib.PreemptionGuard(barrier_timeout=0.3)
  t0 = time.monotonic()
  with pytest.raises(faults_lib.HostLostError) as ei:
    guard.requested()
  assert time.monotonic() - t0 < 5.0
  assert 'preemption-stop-vote' in str(ei.value)


def test_orbax_save_bounded_names_missing_peer(tmp_path, monkeypatch):
  params = tiny_params()
  trainer = train_lib.Trainer(params=params, out_dir=str(tmp_path / 's'))
  state = trainer.init_state(steps_total=8)
  monkeypatch.setattr(jax, 'process_count', lambda: 2)
  monkeypatch.setattr(trainer, '_save_timeout', lambda: 0.3)
  monkeypatch.setattr(trainer._checkpointer, 'save',
                      lambda *a, **k: time.sleep(60))
  t0 = time.monotonic()
  with pytest.raises(faults_lib.HostLostError) as ei:
    trainer.save_checkpoint(state, 0, {})
  assert time.monotonic() - t0 < 5.0
  assert 'orbax-save-0' in str(ei.value)


# ----------------------------------------------------------------------
# Drill 1: kill one host mid-run -> coordinated rebuild, survivors
# finish, and the result is indistinguishable from an undisturbed run.


@pytest.fixture(scope='module')
def solo6_run(shards, tmp_path_factory):
  """Undisturbed pod-of-1 elastic baseline, 1 epoch (6 steps)."""
  out = str(tmp_path_factory.mktemp('elastic_solo6'))
  results = {}
  elastic_host(shards, out, 0, 1, 1, results)
  assert results[0][0] == 'ok', results[0]
  return out


@pytest.fixture(scope='module')
def kill_drill(shards, tmp_path_factory):
  """2-host pod; host 1 dies (drop mode: barriers abandoned, thread
  keeps heartbeating until the exception unwinds) at step 3."""
  out = str(tmp_path_factory.mktemp('elastic_kill'))
  fired_before = faults_lib._fired
  faults_lib._fired = set()
  os.environ['DCTPU_FAULT_HOST_LOST_AT_STEP'] = '3'
  os.environ['DCTPU_FAULT_HOST_LOST_HOST'] = '1'
  os.environ['DCTPU_FAULT_HOST_LOST_MODE'] = 'drop'
  results = {}
  try:
    with _shared_trace(os.path.join(out, 'trace.jsonl')), \
        _deadline_only_at_step(3):
      threads = [
          threading.Thread(target=elastic_host,
                           args=(shards, out, i, 2, 1, results))
          for i in range(2)
      ]
      for t in threads:
        t.start()
      for t in threads:
        t.join(timeout=420)
  finally:
    for key in list(os.environ):
      if key.startswith('DCTPU_FAULT_HOST_LOST'):
        del os.environ[key]
    faults_lib._fired = fired_before
  return out, results


def test_kill_drill_survivor_finishes_and_victim_died(kill_drill):
  _, results = kill_drill
  assert results[0][0] == 'ok', results[0]
  assert results[1][0] == 'err'
  assert isinstance(results[1][1], faults_lib.InjectedHostDeath)


def test_kill_drill_counts_one_rebuild_and_bumps_epoch(kill_drill):
  out, _ = kill_drill
  row = metrics_entries(out, 'faults')[-1]
  assert row['n_host_rebuilds'] == 1.0
  assert row['n_barrier_timeouts'] >= 1.0
  assert row['pod_epoch'] == 2.0  # boot(1) -> rebuild(2)
  assert row['n_host_readmissions'] == 0.0


def test_kill_drill_curve_matches_undisturbed_run(kill_drill, solo6_run):
  out, _ = kill_drill
  disturbed, solo = train_losses(out), train_losses(solo6_run)
  assert len(disturbed) == len(solo) == STEPS_PER_EPOCH
  np.testing.assert_allclose(solo, disturbed, rtol=1e-4, atol=1e-6)
  assert curve_digest_1e4(disturbed) == curve_digest_1e4(solo)


def test_kill_drill_final_weights_match_undisturbed_run(
    kill_drill, solo6_run):
  out, _ = kill_drill
  assert_params_close(out, solo6_run)


def test_kill_drill_manifest_records_shrunken_pod(kill_drill):
  out, _ = kill_drill
  latest = checkpoints_lib.latest_valid_checkpoint(
      os.path.join(out, 'checkpoints'))
  manifest = checkpoints_lib.read_manifest(latest)
  assert manifest['pod_epoch'] == 2
  assert manifest['pod_members'] == [0]


def test_kill_drill_emits_rebuild_trace_span(kill_drill):
  out, _ = kill_drill
  names = trace_event_names(os.path.join(out, 'trace.jsonl'))
  assert 'host_rebuild' in names
  assert 'host_readmit' not in names


# ----------------------------------------------------------------------
# Drill 2: the dead host comes back -> admitted at a step boundary,
# epoch bumped twice (rebuild + readmit), identity preserved.


@pytest.fixture(scope='module')
def solo12_run(shards, tmp_path_factory):
  """Undisturbed pod-of-1 elastic baseline, 2 epochs (12 steps)."""
  out = str(tmp_path_factory.mktemp('elastic_solo12'))
  results = {}
  elastic_host(shards, out, 0, 1, 2, results)
  assert results[0][0] == 'ok', results[0]
  return out


@pytest.fixture(scope='module')
def rejoin_drill(shards, tmp_path_factory):
  """2-host pod over 2 epochs: host 1 dies at step 2, restarts, and
  defers its join announcement to step 6 — survivors admit it at the
  next boundary. Steps are paced (~0.2s) so the announcement lands
  while the run is still going; on a real fleet the step time itself
  provides the window."""
  out = str(tmp_path_factory.mktemp('elastic_rejoin'))
  fired_before = faults_lib._fired
  faults_lib._fired = set()
  orig_sync = elastic_lib.ElasticPod.step_sync

  def paced_sync(self, *args, **kwargs):
    time.sleep(0.2)
    return orig_sync(self, *args, **kwargs)

  elastic_lib.ElasticPod.step_sync = paced_sync
  os.environ['DCTPU_FAULT_HOST_LOST_AT_STEP'] = '2'
  os.environ['DCTPU_FAULT_HOST_LOST_HOST'] = '1'
  os.environ['DCTPU_FAULT_HOST_LOST_MODE'] = 'drop'
  results = {}
  try:
    with _shared_trace(os.path.join(out, 'trace.jsonl')), \
        _deadline_only_at_step(2):
      threads = [
          threading.Thread(target=elastic_host,
                           args=(shards, out, i, 2, 2, results))
          for i in range(2)
      ]
      for t in threads:
        t.start()
      deadline = time.monotonic() + 300
      while 1 not in results and time.monotonic() < deadline:
        time.sleep(0.05)
      assert results.get(1, ('missing',))[0] == 'err', (
          'injected death never fired')
      assert isinstance(results[1][1], faults_lib.InjectedHostDeath)
      for key in list(os.environ):
        if key.startswith('DCTPU_FAULT_HOST_LOST'):
          del os.environ[key]
      faults_lib._fired = set()
      os.environ['DCTPU_FAULT_HOST_REJOIN_AT_STEP'] = '6'
      rejoin = threading.Thread(
          target=elastic_host,
          args=(shards, out, 1, 2, 2, results), kwargs={'key': 'rejoin'})
      rejoin.start()
      threads[0].join(timeout=420)
      rejoin.join(timeout=420)
  finally:
    elastic_lib.ElasticPod.step_sync = orig_sync
    for key in list(os.environ):
      if key.startswith('DCTPU_FAULT_HOST'):
        del os.environ[key]
    faults_lib._fired = fired_before
  return out, results


def test_rejoin_drill_both_sides_finish(rejoin_drill):
  _, results = rejoin_drill
  assert results[0][0] == 'ok', results[0]
  assert results['rejoin'][0] == 'ok', results['rejoin']


def test_rejoin_drill_bumps_epoch_twice_and_counts_readmission(
    rejoin_drill):
  out, _ = rejoin_drill
  row = metrics_entries(out, 'faults')[-1]
  assert row['pod_epoch'] == 3.0  # boot(1) -> rebuild(2) -> readmit(3)
  assert row['n_host_rebuilds'] == 1.0
  assert row['n_host_readmissions'] == 1.0


def test_rejoin_drill_curve_matches_undisturbed_run(
    rejoin_drill, solo12_run):
  out, _ = rejoin_drill
  disturbed, solo = train_losses(out), train_losses(solo12_run)
  assert len(disturbed) == len(solo) == 2 * STEPS_PER_EPOCH
  np.testing.assert_allclose(solo, disturbed, rtol=1e-4, atol=1e-6)
  assert curve_digest_1e4(disturbed) == curve_digest_1e4(solo)


def test_rejoin_drill_final_weights_match_undisturbed_run(
    rejoin_drill, solo12_run):
  out, _ = rejoin_drill
  assert_params_close(out, solo12_run)


def test_rejoin_drill_manifest_records_full_strength_pod(rejoin_drill):
  out, _ = rejoin_drill
  latest = checkpoints_lib.latest_valid_checkpoint(
      os.path.join(out, 'checkpoints'))
  manifest = checkpoints_lib.read_manifest(latest)
  assert manifest['pod_epoch'] == 3
  assert manifest['pod_members'] == [0, 1]


def test_rejoin_drill_emits_rebuild_and_readmit_spans(rejoin_drill):
  out, _ = rejoin_drill
  names = trace_event_names(os.path.join(out, 'trace.jsonl'))
  assert 'host_rebuild' in names
  assert 'host_readmit' in names


def test_solo_baselines_share_their_prefix(solo6_run, solo12_run):
  """The data stream is deterministic in (seed, epoch): the 2-epoch
  baseline's first epoch IS the 1-epoch baseline."""
  np.testing.assert_allclose(
      train_losses(solo12_run)[:STEPS_PER_EPOCH],
      train_losses(solo6_run), rtol=1e-6)


# ----------------------------------------------------------------------
# The hard drill: a REAL process SIGKILLed mid-step, driven through the
# CLI exactly as an operator would run it.


@pytest.mark.slow
def test_subprocess_sigkill_drill_survivor_finishes(shards, tmp_path):
  out = str(tmp_path / 'pod_run')
  base = [
      sys.executable, '-m', 'deepconsensus_tpu.cli', 'train',
      '--config', 'fc+test', '--out_dir', out,
      '--train_path', *shards, '--eval_path', *shards,
      '--num_epochs', '1', '--batch_size', str(GLOBAL_BATCH),
      '--set', f'max_passes={MAX_PASSES}',
      '--set', f'max_length={MAX_LENGTH}',
      '--set', 'log_every_n_steps=1',
      '--elastic', '--num_processes', '2',
      '--elastic_barrier_timeout', '10',
  ]
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  env.pop('DCTPU_FAULT_KILL_TOKEN', None)
  env_victim = dict(env)
  env_victim[faults_lib.ENV_HOST_LOST_AT_STEP] = '3'
  env_victim[faults_lib.ENV_HOST_LOST_HOST] = '1'
  env_victim[faults_lib.ENV_KILL_TOKEN] = str(tmp_path / 'kill.token')
  survivor = subprocess.Popen(base + ['--process_id', '0'], env=env)
  victim = subprocess.Popen(base + ['--process_id', '1'], env=env_victim)
  try:
    assert victim.wait(timeout=600) == -9  # SIGKILL, not a clean exit
    assert survivor.wait(timeout=600) == 0
  finally:
    for proc in (survivor, victim):
      if proc.poll() is None:
        proc.kill()
  row = metrics_entries(out, 'faults')[-1]
  assert row['n_host_rebuilds'] == 1.0
  assert row['pod_epoch'] == 2.0
  assert len(train_losses(out)) == STEPS_PER_EPOCH
