"""Fault-tolerance layer for the inference pipeline.

The serving path polishes millions of ZMWs per run; fail-fast semantics
(one malformed ZMW aborting the whole run, a crash at ZMW 9M losing all
output) don't survive production traffic. This module provides:

* a structured error taxonomy (stage x kind) and per-ZMW quarantine
  governed by --on-zmw-error={fail,skip,ccs-fallback},
* a dead-letter sidecar (<output>.failed.jsonl) recording every
  quarantined ZMW for replay,
* a watchdog for the featurization worker pool (per-batch timeout,
  bounded retry/backoff, pool re-spawn, shm reclamation),
* a resumable progress manifest for atomic <output>.tmp writes,
* env-var fault-injection hooks driven by scripts/inject_faults.py.

Counterpart of the training-side retry/resume stack
(models/train.py run_training_with_retry); inference needs per-item
granularity rather than restart-the-world.

The error taxonomy, dead-letter sidecar, and kill-style injection
hooks now live in the shared deepconsensus_tpu/faults.py (the training
loop uses the same primitives); they are re-exported here so existing
imports keep working.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

# Full public surface of the shared module, so callers never have to
# know which side of the split a name lives on. tests/test_dclint.py
# asserts this block stays in sync (no drift: every shared public name
# resolves here to the identical object).
from deepconsensus_tpu.faults import (  # noqa: F401 - re-exports
    ENV_CRASH_AFTER_BATCHES,
    ENV_DEVICE_HANG_AT_PACK,
    ENV_DEVICE_HANG_S,
    ENV_DEVICE_LOST_AT_PACK,
    ENV_DEVICE_LOST_AT_STEP,
    ENV_DEVICE_OOM_AT_PACK,
    ENV_FLYWHEEL_KILL_AT_STAGE,
    ENV_HOST_LOST_AT_STEP,
    ENV_HOST_LOST_HOST,
    ENV_HOST_LOST_MODE,
    ENV_HOST_REJOIN_AT_STEP,
    ENV_KILL_SHARD_READER,
    ENV_KILL_TOKEN,
    ENV_KILL_TRAIN_AT_STEP,
    ENV_KILL_ZMW,
    ENV_NAN_AT_STEP,
    ENV_POISON_WINDOW,
    ENV_PREEMPT_AT_S,
    ENV_SERVE_CLIENT_FAULT,
    ENV_SERVE_CLIENT_FAULT_ZMW,
    ENV_SIGTERM_AT_STEP,
    _TRANSIENT_MARKERS,
    BackpressureError,
    BadRequestError,
    CorruptInputError,
    CrashLoopError,
    DeadLetterWriter,
    DeadlineExceededError,
    DeviceFault,
    DeviceLostError,
    DeviceOomError,
    DispatchTimeoutError,
    DrainingError,
    ElasticRebuildError,
    ExportedArtifactMismatchError,
    FaultKind,
    FleetRejection,
    FlywheelGateError,
    FlywheelResumeError,
    FlywheelStageError,
    HostLostError,
    InjectedHostDeath,
    NonFiniteTrainingError,
    QuotaExceededError,
    ReplicaLostError,
    RequestTooLargeError,
    ServeRejection,
    WindowBucketError,
    classify_device_error,
    classify_error,
    host_rejoin_step,
    injected_crash_after_batches,
    injected_device_fault,
    injected_device_hang,
    injected_train_device_fault,
    maybe_host_lost,
    maybe_kill_flywheel_at_stage,
    maybe_kill_shard_reader,
    maybe_kill_train_at_step,
    maybe_kill_worker,
    maybe_poison_batch,
    maybe_sigterm_at_step,
    preempt_notice_after_s,
    read_dead_letters,
)

log = logging.getLogger(__name__)


# ----------------------------------------------------------------------
# Error taxonomy (inference-side stages; kinds live in the shared
# deepconsensus_tpu/faults.py)


class OnZmwError:
  """--on-zmw-error policy values."""

  FAIL = 'fail'
  SKIP = 'skip'
  CCS_FALLBACK = 'ccs-fallback'

  CHOICES = (FAIL, SKIP, CCS_FALLBACK)


@dataclasses.dataclass
class ZmwFault(Exception):
  """A classified per-ZMW failure."""

  zmw: Optional[str]
  stage: str
  kind: str
  message: str

  def __str__(self) -> str:
    return (
        f'[{self.stage}/{self.kind}] zmw={self.zmw or "<stream>"}: '
        f'{self.message}'
    )


class WatchdogTimeout(RuntimeError):
  """A featurization batch exhausted its watchdog retries."""


# ----------------------------------------------------------------------
# CCS fallback payloads


@dataclasses.dataclass
class CcsFallback:
  """The draft CCS read emitted in place of a quarantined ZMW so yield
  degrades gracefully instead of the read (or run) disappearing."""

  molecule_name: str
  sequence: str
  quality_scores: np.ndarray  # int array, one per base
  ec: Optional[float] = None
  np_num_passes: Optional[int] = None
  rq: Optional[float] = None
  rg: Optional[str] = None


def fallback_from_record(record) -> CcsFallback:
  """Builds a fallback from a raw ccs BamRecord (feeder stage)."""
  n = len(record.seq)
  quals = (
      np.asarray(record.quals, dtype=np.int64)
      if record.quals is not None else np.zeros(n, dtype=np.int64)
  )
  tags = record.tags
  return CcsFallback(
      molecule_name=record.qname,
      sequence=record.seq,
      quality_scores=quals,
      ec=tags.get('ec'),
      np_num_passes=tags.get('np'),
      rq=tags.get('rq'),
      rg=tags.get('RG'),
  )


def fallback_from_ccs_read(ccs_read) -> CcsFallback:
  """Builds a fallback from an expanded AlignedRead draft CCS
  (featurize stage: zmw_input's subreads[-1])."""
  from deepconsensus_tpu.utils import phred

  return CcsFallback(
      molecule_name=ccs_read.name,
      sequence=phred.encoded_sequence_to_string(ccs_read.bases),
      quality_scores=np.asarray(ccs_read.base_quality_scores,
                                dtype=np.int64),
      ec=ccs_read.ec,
      np_num_passes=ccs_read.np_num_passes,
      rq=ccs_read.rq,
      rg=ccs_read.rg,
  )


# ----------------------------------------------------------------------
# Quarantine


class Quarantine:
  """Applies the --on-zmw-error policy to per-ZMW faults.

  handle() re-raises under the 'fail' policy; otherwise it records a
  dead letter, bumps counters, and returns the CcsFallback to emit (or
  None). Thread-safe: the producer thread (feeder/featurize) and the
  consumer thread (model/stitch) both report faults.
  """

  def __init__(self, policy: str, dead_letter: Optional[DeadLetterWriter]):
    if policy not in OnZmwError.CHOICES:
      # dclint: allow=typed-faults (flag validation at startup: the
      # CLI maps ValueError to operator-error exit code 2)
      raise ValueError(
          f'on_zmw_error must be one of {OnZmwError.CHOICES}, '
          f'got {policy!r}'
      )
    self.policy = policy
    self.dead_letter = dead_letter
    self.counters: collections.Counter = collections.Counter()
    self._lock = threading.Lock()

  def handle(
      self,
      zmw: Optional[str],
      stage: str,
      error: BaseException | str,
      fallback: Optional[Callable[[], Optional[CcsFallback]]] = None,
      extra: Optional[Dict[str, Any]] = None,
  ) -> Optional[CcsFallback]:
    """Quarantines one ZMW. fallback is a thunk (evaluated only under
    the ccs-fallback policy) producing the draft-CCS payload, or None
    when no draft is recoverable (the quarantine downgrades to skip).
    extra rides into the dead-letter line — model-pack failures use it
    to attribute one shared device fault to every member molecule."""
    if self.policy == OnZmwError.FAIL:
      if isinstance(error, BaseException):
        raise error
      raise ZmwFault(zmw, stage, classify_error(error), error)
    text = (
        error if isinstance(error, str)
        else f'{type(error).__name__}: {error}'
    )
    kind = classify_error(text)
    payload = None
    action = OnZmwError.SKIP
    if self.policy == OnZmwError.CCS_FALLBACK and fallback is not None:
      try:
        payload = fallback()
      # dclint: allow=typed-faults (the fallback failing degrades the
      # action to skip; the quarantine record below still routes it)
      except Exception as fb_err:  # fallback itself unrecoverable
        log.warning('ccs-fallback for %s failed (%s); skipping', zmw, fb_err)
      if payload is not None:
        action = OnZmwError.CCS_FALLBACK
    with self._lock:
      self.counters['n_zmw_quarantined'] += 1
      self.counters[f'n_fault_{stage}'] += 1
      if action == OnZmwError.CCS_FALLBACK:
        self.counters['n_zmw_ccs_fallback'] += 1
      else:
        self.counters['n_zmw_skipped_on_error'] += 1
      if self.dead_letter is not None:
        self.dead_letter.record(zmw, stage, kind, text, action, extra=extra)
    log.warning('quarantined zmw=%s stage=%s kind=%s action=%s: %s',
                zmw, stage, kind, action, text.splitlines()[-1] if text
                else text)
    return payload

  def bump(self, key: str, n: int = 1) -> None:
    with self._lock:
      self.counters[key] += n


# ----------------------------------------------------------------------
# Worker-pool watchdog


def reclaim_shm_segments(prefix: str) -> int:
  """Unlinks every /dev/shm segment carrying this run/batch prefix —
  the only owner record left after a worker was SIGKILLed (the worker
  unregisters its segments from its resource tracker before handing
  ownership to the parent)."""
  if not prefix:
    return 0
  n = 0
  for path in glob.glob(f'/dev/shm/{glob.escape(prefix)}*'):
    try:
      os.unlink(path)
      n += 1
    except OSError:
      pass
  if n:
    log.warning('reclaimed %d leaked shm segment(s) with prefix %s',
                n, prefix)
  return n


class PoolWatchdog:
  """Supervises the featurization multiprocessing.Pool.

  run_batch() bounds each starmap with a timeout; a hung or SIGKILLed
  worker (multiprocessing.Pool silently loses the in-flight task when a
  worker dies, so its result never arrives) surfaces as a timeout. The
  watchdog then reclaims the batch's shm segments, terminates and
  re-spawns the pool, backs off, and retries the whole batch; after
  `retries` failed retries it raises WatchdogTimeout for the quarantine
  layer to apply the --on-zmw-error policy.
  """

  # Pool-machinery failures that merit a respawn/retry like a timeout.
  _POOL_ERRORS = (BrokenPipeError, EOFError, ConnectionError)

  def __init__(
      self,
      make_pool: Callable[[], Any],
      timeout: float = 0.0,
      retries: int = 2,
      backoff: float = 0.5,
      quarantine: Optional[Quarantine] = None,
  ):
    self._make_pool = make_pool
    self.timeout = timeout
    self.retries = max(0, retries)
    self.backoff = backoff
    self.quarantine = quarantine
    self.pool = make_pool()

  def _bump(self, key: str) -> None:
    if self.quarantine is not None:
      self.quarantine.bump(key)

  def run_batch(self, func, tasks, chunksize: int, shm_prefix: str = ''):
    """starmap with watchdog semantics; returns the results list."""
    import multiprocessing

    if not self.timeout:
      return self.pool.starmap(func, tasks, chunksize=chunksize)
    last_error = 'timeout'
    for attempt in range(self.retries + 1):
      if attempt:
        self._bump('n_watchdog_retries')
        time.sleep(self.backoff * (2 ** (attempt - 1)))
      async_result = self.pool.starmap_async(
          func, tasks, chunksize=chunksize
      )
      try:
        return async_result.get(self.timeout)
      except multiprocessing.TimeoutError:
        last_error = f'no result within {self.timeout}s'
      except self._POOL_ERRORS as e:
        last_error = f'pool failure: {type(e).__name__}: {e}'
      self._bump('n_watchdog_timeouts')
      log.warning(
          'featurization batch watchdog fired (attempt %d/%d): %s; '
          're-spawning the worker pool',
          attempt + 1, self.retries + 1, last_error,
      )
      self.respawn(shm_prefix)
    raise WatchdogTimeout(
        f'featurization batch failed the watchdog {self.retries + 1} '
        f'time(s): {last_error}'
    )

  def respawn(self, shm_prefix: str = '') -> None:
    """Terminates the (possibly hung) pool, reclaims this batch's shm
    segments, and brings up a fresh pool."""
    try:
      self.pool.terminate()
      self.pool.join()
    # dclint: allow=typed-faults (teardown is best-effort: the pool is
    # being replaced; shm reclamation below still runs)
    except Exception as e:  # pragma: no cover - teardown best-effort
      log.warning('pool terminate failed: %s', e)
    reclaim_shm_segments(shm_prefix)
    self._bump('n_pool_respawns')
    self.pool = self._make_pool()

  def close(self) -> None:
    try:
      self.pool.close()
      self.pool.join()
    # dclint: allow=typed-faults (teardown is best-effort: escalate a
    # failed close to terminate, nothing to route)
    except Exception:  # pragma: no cover - teardown best-effort
      self.pool.terminate()
      self.pool.join()


# ----------------------------------------------------------------------
# Resumable, atomic output


class ProgressManifest:
  """Crash-consistent progress record for <output>.tmp.

  Commits are atomic (write + rename) and record the number of feeder
  groups fully written plus the flushed tmp-file size, so --resume can
  truncate the tmp output to the last committed byte and skip exactly
  the committed ZMW groups. `source` pins the input identity; resuming
  against a different input fails loudly.
  """

  VERSION = 1

  def __init__(self, path: str):
    self.path = path

  def commit(self, groups_done: int, tmp_size: int,
             source: Dict[str, Any], last_zmw: Optional[str] = None,
             extra: Optional[Dict[str, Any]] = None) -> None:
    state = {
        'version': self.VERSION,
        'groups_done': groups_done,
        'tmp_size': tmp_size,
        'last_zmw': last_zmw,
        'source': source,
        'time': time.time(),
    }
    if extra:
      state.update(extra)
    tmp = self.path + '.tmp'
    with open(tmp, 'w') as f:
      json.dump(state, f)
      f.flush()
      os.fsync(f.fileno())
    os.replace(tmp, self.path)

  def load(self) -> Optional[Dict[str, Any]]:
    try:
      with open(self.path) as f:
        state = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
      return None
    if state.get('version') != self.VERSION:
      log.warning('ignoring %s with unknown version %s', self.path,
                  state.get('version'))
      return None
    return state

  def delete(self) -> None:
    for path in (self.path, self.path + '.tmp'):
      try:
        os.unlink(path)
      except FileNotFoundError:
        pass


def validate_resume_source(state: Dict[str, Any],
                           source: Dict[str, Any]) -> None:
  """A manifest written for different inputs/options must not silently
  graft a resumed run onto them."""
  recorded = state.get('source') or {}
  for key, value in source.items():
    if recorded.get(key) != value:
      # dclint: allow=typed-faults (operator error at startup; tests
      # and the CLI rely on ValueError('manifest mismatch ...'))
      raise ValueError(
          f'--resume manifest mismatch for {key!r}: run was started '
          f'with {recorded.get(key)!r}, resume requested {value!r} '
          f'(delete the .progress.json to restart from scratch)'
      )


# Fault-injection hooks (ENV_KILL_ZMW / ENV_KILL_TOKEN /
# ENV_CRASH_AFTER_BATCHES, maybe_kill_worker,
# injected_crash_after_batches) are re-exported from the shared
# deepconsensus_tpu/faults.py above.
