"""Elastic pod membership: epoch-numbered member sets over bounded
collectives.

The PR-9/14 degradation ladder made single-host training survivable
(device loss -> mesh rebuild at lower dp) but deliberately refused on
multi-host meshes, and every cross-host barrier in the stock stack —
`jax.distributed`'s collectives, the PreemptionGuard stop vote, orbax's
multihost save protocol — waits FOREVER on a peer that will never
answer. This module is the membership layer that makes pod-scale
training elastic both ways:

* **Epoch-numbered member set.** The pod's authoritative state is
  (epoch, members, step), bumped by every membership change and
  committed by the leader (the lowest live host id) to `epoch.json`.
  Barrier namespaces embed the epoch, so a rebuilt pod can re-run the
  failed step without colliding with payloads the old membership left
  behind.

* **Bounded barriers.** Every collective is a deadline-bounded
  file-transport allgather: each member atomically publishes its
  payload under `barrier/<epoch>/<name>/` and polls for the others
  until `barrier_timeout`. A missed deadline raises a typed
  `HostLostError` NAMING the missing process indices — never a hang.
  `bounded_call` extends the same guarantee to collectives we don't
  own (the legacy `process_allgather` stop vote, orbax's save barrier)
  by running them under a watchdog deadline.

* **Agreement round.** On `HostLostError` every survivor proposes its
  candidate member set (hosts with fresh heartbeats), the proposals are
  allgathered and intersected, a confirm round checks all survivors
  computed the same set, and the epoch bumps. Bounded retries shrink
  the candidate set until it converges; exhaustion raises the permanent
  `ElasticRebuildError` instead of looping.

* **Re-admission.** A recovered host writes a join request and waits;
  live members observe it piggybacked on the per-step sync, admit it at
  the next step boundary (epoch bump, leader-written state snapshot),
  and the joiner resumes from the exact step the pod is on.

Transport is a shared directory (`<out_dir>/.pod/`) rather than a
socket mesh: TPU pods already share the checkpoint filesystem, atomic
rename gives publish-or-nothing semantics, and — critically for the
fault model — a payload a host wrote before dying REMAINS readable, so
a step where every survivor collected the full set completes
consistently even if the writer is already gone. The jit-visible mesh
of an elastic member never spans processes (`mesh.local_mesh`);
cross-host gradient reduction happens at host level through
`step_sync`'s weighted mean, which reproduces the global-batch-mean
gradient exactly (up to summation order) because per-host losses are
batch means weighted by their slice sizes. On a real multi-controller
pod the same membership protocol drives `distributed.reinitialize`
to re-enter jax.distributed at the agreed process count
(docs/training.md "Elastic multi-host training").
"""
from __future__ import annotations

import collections
import io
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepconsensus_tpu.faults import ElasticRebuildError, HostLostError

log = logging.getLogger(__name__)

# Pod-dir layout (all paths relative to pod_dir):
#   hb/<host>.json               heartbeat, touched every interval
#   epoch.json                   authoritative (epoch, members, step)
#   join/<host>.json             re-admission requests
#   barrier/<epoch>/<name>/<h>.npz   one bounded-collective payload
#   state/epoch-<E>.npz          leader-written snapshot for joiners
_HB_DIR = 'hb'
_JOIN_DIR = 'join'
_BARRIER_DIR = 'barrier'
_STATE_DIR = 'state'
_EPOCH_FILE = 'epoch.json'

# Collect-side poll interval. Publishing is one atomic rename; waiting
# is a listdir poll, so the floor on barrier latency is this interval.
_POLL_S = 0.01


def _atomic_write_bytes(path: str, payload: bytes) -> None:
  # The thread id keeps the heartbeat thread and the training thread of
  # one host (both write hb/<host>.json) off each other's temp file:
  # sharing it, the slower rename found the file already moved.
  tmp = f'{path}.tmp.{os.getpid()}.{threading.get_ident()}'
  with open(tmp, 'wb') as f:
    f.write(payload)
    f.flush()
    os.fsync(f.fileno())
  os.replace(tmp, path)


def _write_payload(path: str, meta: Dict[str, Any],
                   arrays: Optional[Sequence[np.ndarray]] = None) -> None:
  """Publishes one barrier payload atomically: meta (JSON) + arrays in
  a single .npz, written to a temp name and renamed into place so a
  reader never observes a torn file."""
  buf = io.BytesIO()
  named = {
      f'arr_{i}': np.asarray(a) for i, a in enumerate(arrays or ())
  }
  named['__meta__'] = np.frombuffer(
      json.dumps(meta).encode('utf-8'), dtype=np.uint8
  )
  np.savez(buf, **named)
  _atomic_write_bytes(path, buf.getvalue())


def _read_payload(path: str) -> Tuple[Dict[str, Any], List[np.ndarray]]:
  with np.load(path) as z:
    meta = json.loads(bytes(z['__meta__'].tobytes()).decode('utf-8'))
    n = sum(1 for k in z.files if k.startswith('arr_'))
    arrays = [np.asarray(z[f'arr_{i}']) for i in range(n)]
  return meta, arrays


def bounded_call(fn: Callable[[], Any], timeout_s: float, name: str):
  """Runs a blocking collective under a deadline: the typed-HostLostError
  counterpart of the PR-9 dispatch watchdog, for barriers whose C++
  implementations cannot be cancelled (the legacy multihost
  `process_allgather` stop vote, orbax's multihost save protocol).

  The call runs on a daemon worker thread; if it misses the deadline
  the caller gets `HostLostError` immediately and the stuck thread is
  abandoned (it holds no locks the training loop needs — exactly the
  trade the dispatch watchdog already makes for hung device packs).
  Values and exceptions from a call that DOES finish pass through
  unchanged.
  """
  # dclint: lock-free (single hand-off dict: the worker writes, the
  # caller reads only after join() establishes the ordering)
  box: Dict[str, Any] = {}

  def run():
    try:
      box['value'] = fn()
    # dclint: allow=typed-faults (cross-thread hand-off: the exception
    # is re-raised verbatim on the caller's thread below)
    except BaseException as e:
      box['error'] = e

  worker = threading.Thread(target=run, daemon=True,
                            name=f'bounded-{name}')
  worker.start()
  worker.join(timeout=max(timeout_s, 0.0))
  if worker.is_alive():
    raise HostLostError(
        f'collective {name!r} exceeded its {timeout_s:.1f}s deadline '
        '(bounded-barrier watchdog); a peer died inside the barrier',
        barrier=name,
    )
  if 'error' in box:
    raise box['error']
  return box.get('value')


class StepSync:
  """Result of one `ElasticPod.step_sync`: the weighted-mean arrays,
  the per-host metas, and the merged control plane (stop votes ORed,
  join requests unioned) every member computed identically from the
  same payload files."""

  __slots__ = ('arrays', 'metas', 'stop', 'join_requests', 'weight_total')

  def __init__(self, arrays, metas, stop, join_requests, weight_total):
    self.arrays = arrays
    self.metas = metas
    self.stop = stop
    self.join_requests = join_requests
    self.weight_total = weight_total


class PodStart:
  """Outcome of `ElasticPod.start`: whether this host booted with the
  founding member set or joined a live pod (in which case `state`
  carries the leader's snapshot leaves and `step` the resume step)."""

  __slots__ = ('joined', 'epoch', 'members', 'step', 'state')

  def __init__(self, joined, epoch, members, step, state=None):
    self.joined = joined
    self.epoch = epoch
    self.members = members
    self.step = step
    self.state = state


class ElasticPod:
  """One host's membership endpoint: heartbeats, bounded collectives,
  the agreement round, and join/admit. See the module docstring for
  the protocol; `models/train.py run_training` is the driver."""

  def __init__(self, pod_dir: str, host_id: int, n_hosts: int, *,
               barrier_timeout: float = 30.0,
               heartbeat_interval: float = 0.25,
               boot_timeout: Optional[float] = None,
               join_timeout: Optional[float] = None,
               rebuild_attempts: int = 4,
               readmit: bool = True,
               defer_join_until_step: int = 0):
    if n_hosts < 1 or not 0 <= host_id < max(n_hosts, host_id + 1):
      # dclint: allow=typed-faults (startup flag validation)
      raise ValueError(
          f'invalid pod geometry: host_id={host_id} n_hosts={n_hosts}')
    if barrier_timeout <= 0:
      # dclint: allow=typed-faults (startup flag validation)
      raise ValueError('barrier_timeout must be > 0 (the bounded-'
                       'barrier rule: no collective may wait unbounded)')
    self.pod_dir = os.path.abspath(pod_dir)
    self.host_id = int(host_id)
    self.n_hosts = int(n_hosts)
    self.barrier_timeout = float(barrier_timeout)
    self.heartbeat_interval = float(heartbeat_interval)
    # A host counts as a live candidate while its heartbeat file is
    # fresher than this; comfortably above the touch interval so one
    # slow fsync doesn't evict a healthy member.
    self.heartbeat_timeout = max(2.0, 8.0 * self.heartbeat_interval)
    self.boot_timeout = float(
        boot_timeout if boot_timeout is not None else barrier_timeout)
    self.join_timeout = float(
        join_timeout if join_timeout is not None
        else max(120.0, 4.0 * barrier_timeout))
    self.rebuild_attempts = int(rebuild_attempts)
    self.readmit = bool(readmit)
    self.defer_join_until_step = int(defer_join_until_step)
    # Incarnation distinguishes a restarted host from its dead previous
    # self (same id) in epoch.json / join records.
    self.incarnation = int(time.time() * 1e6) ^ os.getpid()
    self._lock = threading.Lock()
    self._epoch = 0  # guarded by: self._lock
    self._members: Tuple[int, ...] = ()  # guarded by: self._lock
    self._step = 0  # guarded by: self._lock
    self._round = 0  # guarded by: self._lock
    # First step barrier after a re-admission runs under join_timeout
    # instead of barrier_timeout: the joiner still has to adopt the
    # snapshot and compile its step before it can post, and evicting it
    # for warming up would turn every admission into a rebuild.
    self._grace_until_step = 0  # guarded by: self._lock
    self._counters: collections.Counter = (
        collections.Counter())  # guarded by: self._lock
    self._abandoned = False  # guarded by: self._lock
    self._stop = threading.Event()
    # dclint: lock-free (written once in start() before any concurrent
    # access; abandon/close only join() it, which is thread-safe)
    self._hb_thread: Optional[threading.Thread] = None
    for sub in (_HB_DIR, _JOIN_DIR, _BARRIER_DIR, _STATE_DIR):
      os.makedirs(os.path.join(self.pod_dir, sub), exist_ok=True)

  # ---- views ---------------------------------------------------------
  @property
  def epoch(self) -> int:
    with self._lock:
      return self._epoch

  @property
  def members(self) -> Tuple[int, ...]:
    with self._lock:
      return self._members

  @property
  def is_leader(self) -> bool:
    with self._lock:
      return bool(self._members) and self.host_id == min(self._members)

  def advance_round(self) -> None:
    """Call when the training loop rewinds its step counter (NaN
    rollback): named barriers are namespaced by (epoch, round, step),
    so replayed step numbers get fresh barriers instead of matching the
    stale payload files their first pass left behind. The rollback
    decision is deterministic pod-wide (every member judges the same
    merged metrics), so rounds advance in lockstep."""
    with self._lock:
      self._round += 1

  def counters(self) -> Dict[str, float]:
    """Snapshot for the train metrics sidecar's `faults` split."""
    with self._lock:
      out = {k: float(v) for k, v in self._counters.items()}
      out['pod_epoch'] = float(self._epoch)
      out.setdefault('n_host_rebuilds', 0.0)
      out.setdefault('n_host_readmissions', 0.0)
      out.setdefault('n_barrier_timeouts', 0.0)
    return out

  # ---- heartbeats ----------------------------------------------------
  def _hb_path(self, host: int) -> str:
    return os.path.join(self.pod_dir, _HB_DIR, f'{host}.json')

  def _write_heartbeat(self, left: bool = False) -> None:
    with self._lock:
      beat = {
          'host': self.host_id,
          'incarnation': self.incarnation,
          'epoch': self._epoch,
          'step': self._step,
          'left': bool(left),
      }
    _atomic_write_bytes(self._hb_path(self.host_id),
                        json.dumps(beat).encode('utf-8'))

  def _heartbeat_main(self) -> None:
    while not self._stop.wait(self.heartbeat_interval):
      try:
        self._write_heartbeat()
      except OSError:  # pragma: no cover - transient fs hiccup
        continue

  def read_heartbeat(self, host: int) -> Optional[Dict[str, Any]]:
    """The peer's last beat plus its staleness, or None when the host
    never checked in. `fresh` is the liveness verdict the agreement
    round uses."""
    path = self._hb_path(host)
    try:
      age = time.time() - os.stat(path).st_mtime
      with open(path, 'rb') as f:
        beat = json.loads(f.read().decode('utf-8'))
    except (OSError, ValueError):
      return None
    beat['age_s'] = age
    beat['fresh'] = age < self.heartbeat_timeout and not beat.get('left')
    return beat

  def _live_candidates(self) -> List[int]:
    """Hosts (self always included) whose heartbeats are fresh — the
    candidate set each survivor proposes in the agreement round."""
    live = {self.host_id}
    hb_dir = os.path.join(self.pod_dir, _HB_DIR)
    for entry in sorted(os.listdir(hb_dir)):
      if not entry.endswith('.json'):
        continue
      host = int(entry[:-5])
      beat = self.read_heartbeat(host)
      if beat is not None and beat['fresh']:
        live.add(host)
    return sorted(live)

  def observed_step(self) -> int:
    """Highest step any live peer advertises — what a deferred joiner
    polls to time its announcement to a target step boundary."""
    best = 0
    for host in self._live_candidates():
      beat = self.read_heartbeat(host)
      if beat is not None:
        best = max(best, int(beat.get('step', 0)))
    return best

  # ---- bounded barrier primitives ------------------------------------
  def _barrier_dir(self, epoch: int, name: str) -> str:
    return os.path.join(self.pod_dir, _BARRIER_DIR, str(epoch), name)

  def _post(self, epoch: int, name: str, meta: Dict[str, Any],
            arrays: Optional[Sequence[np.ndarray]] = None) -> None:
    bdir = self._barrier_dir(epoch, name)
    os.makedirs(bdir, exist_ok=True)
    _write_payload(os.path.join(bdir, f'{self.host_id}.npz'),
                   meta, arrays)

  def _collect(self, epoch: int, name: str, expected: Sequence[int],
               timeout_s: float
               ) -> Dict[int, Tuple[Dict[str, Any], List[np.ndarray]]]:
    """Waits (bounded) for every expected host's payload. The deadline
    is absolute from entry: no code path through here can block longer
    than `timeout_s`, and a miss raises HostLostError naming exactly
    the hosts whose payloads never appeared."""
    bdir = self._barrier_dir(epoch, name)
    expected = sorted(set(int(h) for h in expected))
    deadline = time.monotonic() + timeout_s
    got: Dict[int, Tuple[Dict[str, Any], List[np.ndarray]]] = {}
    while True:
      for host in expected:
        if host in got:
          continue
        path = os.path.join(bdir, f'{host}.npz')
        if os.path.exists(path):
          try:
            got[host] = _read_payload(path)
          except (OSError, ValueError, KeyError):
            # Concurrent GC or a torn read under a dying writer: treat
            # as not-yet-posted; the deadline still bounds the wait.
            continue
      if len(got) == len(expected):
        return got
      if time.monotonic() >= deadline:
        missing = [h for h in expected if h not in got]
        with self._lock:
          self._counters['n_barrier_timeouts'] += 1
        raise HostLostError(
            f'bounded barrier expired after {timeout_s:.1f}s waiting '
            f'for {len(missing)} of {len(expected)} member(s)',
            missing=missing, barrier=name, epoch=epoch,
        )
      time.sleep(_POLL_S)

  def allgather(self, name: str, meta: Dict[str, Any],
                arrays: Optional[Sequence[np.ndarray]] = None,
                timeout_s: Optional[float] = None
                ) -> Dict[int, Tuple[Dict[str, Any], List[np.ndarray]]]:
    """Bounded allgather across the CURRENT member set. Names are
    additionally namespaced by the rollback round (advance_round), so a
    training loop that rewinds its step counter never collides with the
    stale payloads of the first pass."""
    with self._lock:
      epoch, members = self._epoch, self._members
      name = f'r{self._round}-{name}'
    self._post(epoch, name, meta, arrays)
    return self._collect(
        epoch, name, members,
        self.barrier_timeout if timeout_s is None else timeout_s)

  def barrier(self, name: str,
              timeout_s: Optional[float] = None) -> None:
    """Bounded rendezvous with no payload (e.g. checkpoint-commit
    alignment)."""
    self.allgather(name, {'host': self.host_id}, timeout_s=timeout_s)

  # ---- per-step sync --------------------------------------------------
  def step_sync(self, step: int, arrays: Sequence[np.ndarray],
                weight: float, meta: Optional[Dict[str, Any]] = None,
                stop_vote: bool = False) -> StepSync:
    """The elastic data-plane collective: weighted-mean allreduce of
    this step's host arrays (gradients + model-state deltas), with the
    control plane piggybacked — stop votes (the PreemptionGuard's
    unanimity requirement, now bounded for free) and join requests, so
    membership changes land exactly at step boundaries without extra
    barriers.

    Weights are local slice sizes: sum(w_k * mean_k) / sum(w_k) is the
    exact global-batch mean, so elastic training matches the fused
    single-mesh step to summation order.
    """
    payload_meta = {
        'host': self.host_id,
        'weight': float(weight),
        'stop': bool(stop_vote),
        'join_requests': self._scan_join_requests() if self.readmit
                         else [],
    }
    if meta:
      payload_meta.update(meta)
    with self._lock:
      epoch, members = self._epoch, self._members
      name = f'r{self._round}-step-{step}'
      timeout = (self.join_timeout if step <= self._grace_until_step
                 else self.barrier_timeout)
    self._post(epoch, name, payload_meta, arrays)
    got = self._collect(epoch, name, members, timeout)
    hosts = sorted(got)
    weights = np.asarray(
        [float(got[h][0]['weight']) for h in hosts], np.float32)
    total = float(weights.sum()) or 1.0
    merged: List[np.ndarray] = []
    for i in range(len(arrays)):
      acc = np.zeros_like(np.asarray(got[hosts[0]][1][i], np.float32))
      for h, w in zip(hosts, weights):
        acc += (w / total) * np.asarray(got[h][1][i], np.float32)
      merged.append(acc)
    join_requests = sorted({
        int(j) for h in hosts for j in got[h][0].get('join_requests', ())
    })
    with self._lock:
      self._step = max(self._step, int(step))
    self._gc_step_barriers(step)
    return StepSync(
        arrays=merged,
        metas={h: got[h][0] for h in hosts},
        stop=any(bool(got[h][0].get('stop')) for h in hosts),
        join_requests=join_requests,
        weight_total=total,
    )

  def _gc_step_barriers(self, step: int, keep: int = 4) -> None:
    """Removes this host's own payloads for long-completed steps.
    Members run in lockstep (a step completes only when everyone
    posted), so files `keep` steps back are unreachable; empty barrier
    dirs are reaped best-effort."""
    with self._lock:
      epoch = self._epoch
      rnd = self._round
    for old in (step - keep, step - keep - 1):
      if old < 0:
        continue
      bdir = self._barrier_dir(epoch, f'r{rnd}-step-{old}')
      try:
        os.unlink(os.path.join(bdir, f'{self.host_id}.npz'))
        os.rmdir(bdir)
      except OSError:
        pass

  # ---- formation ------------------------------------------------------
  def start(self, resume_step: int = 0) -> PodStart:
    """Boot or join. A live pod (fresh peer heartbeat + committed
    epoch.json) means this host is a RE-ADMISSION: it announces itself
    and waits to be admitted at a step boundary. Otherwise all
    founding hosts rendezvous (bounded by boot_timeout), agree on the
    founding member set, and epoch 1 (or stale-epoch + 1 on a
    whole-pod restart) commits."""
    self._write_heartbeat()
    self._hb_thread = threading.Thread(
        target=self._heartbeat_main, daemon=True,
        name=f'pod-heartbeat-{self.host_id}')
    self._hb_thread.start()
    committed = self._read_epoch_file()
    peers_alive = any(
        h != self.host_id for h in self._live_candidates())
    if committed is not None and peers_alive and self.readmit:
      return self._join(committed)
    return self._boot(committed, resume_step)

  def _read_epoch_file(self) -> Optional[Dict[str, Any]]:
    try:
      with open(os.path.join(self.pod_dir, _EPOCH_FILE), 'rb') as f:
        return json.loads(f.read().decode('utf-8'))
    except (OSError, ValueError):
      return None

  def _commit_epoch(self, epoch: int, members: Sequence[int],
                    step: int, incarnations: Dict[int, int]) -> None:
    with self._lock:
      rnd = self._round
    record = {
        'epoch': int(epoch),
        'members': sorted(int(m) for m in members),
        'step': int(step),
        'round': rnd,
        'incarnations': {str(k): int(v) for k, v in incarnations.items()},
    }
    _atomic_write_bytes(os.path.join(self.pod_dir, _EPOCH_FILE),
                        json.dumps(record).encode('utf-8'))

  def _boot(self, committed: Optional[Dict[str, Any]],
            resume_step: int) -> PodStart:
    base_epoch = int(committed['epoch']) if committed else 0
    target = base_epoch + 1
    self._post(0, f'boot-{target}',
               {'host': self.host_id, 'incarnation': self.incarnation})
    expected = sorted(set(range(self.n_hosts)) | {self.host_id})
    try:
      got = self._collect(0, f'boot-{target}', expected,
                          self.boot_timeout)
      candidates = sorted(got)
    except HostLostError as e:
      # Founding members that never arrived are left out; they come
      # back through the join path. A pod of one is still a pod.
      log.warning('pod boot proceeding without missing host(s): %s', e)
      candidates = sorted(
          set(self._barrier_posters(0, f'boot-{target}')) | {self.host_id})
    epoch, members, incarnations = self._agree(
        target, participants=candidates, proposal=candidates,
        round_name='boot')
    with self._lock:
      self._epoch, self._members = epoch, tuple(members)
      self._step = int(resume_step)
    if self.host_id == min(members):
      self._commit_epoch(epoch, members, resume_step, incarnations)
    self._write_heartbeat()
    log.info('pod booted: epoch=%d members=%s host=%d',
             epoch, members, self.host_id)
    return PodStart(joined=False, epoch=epoch, members=tuple(members),
                    step=int(resume_step))

  def _barrier_posters(self, epoch: int, name: str) -> List[int]:
    bdir = self._barrier_dir(epoch, name)
    try:
      return sorted(
          int(f[:-4]) for f in os.listdir(bdir) if f.endswith('.npz'))
    except OSError:
      return []

  # ---- agreement round ------------------------------------------------
  def _agree(self, target_epoch: int, participants: Sequence[int],
             proposal: Sequence[int], round_name: str
             ) -> Tuple[int, List[int], Dict[int, int]]:
    """Two-phase bounded agreement: allgather proposals, intersect,
    then confirm every participant computed the same set. A participant
    that dies mid-round is dropped and the round retries at the next
    epoch number; `rebuild_attempts` misses raise ElasticRebuildError
    (permanent — the pod cannot converge)."""
    participants = sorted(set(int(p) for p in participants))
    proposal = sorted(set(int(p) for p in proposal))
    epoch = int(target_epoch)
    for attempt in range(self.rebuild_attempts):
      name = f'{round_name}-{epoch}'
      try:
        got = self._collect_after_post(
            0, f'propose-{name}',
            {'host': self.host_id, 'incarnation': self.incarnation,
             'members': proposal},
            participants)
        agreed = set(proposal)
        incarnations = {self.host_id: self.incarnation}
        for h, (meta, _) in got.items():
          agreed &= set(int(m) for m in meta['members'])
          incarnations[int(h)] = int(meta.get('incarnation', 0))
        # Participants that posted survive; proposed non-participants
        # (joiners being admitted) stay without voting.
        agreed |= set(proposal) - set(participants)
        agreed &= set(proposal)
        agreed |= {int(h) for h in got}
        confirm = self._collect_after_post(
            0, f'confirm-{name}',
            {'host': self.host_id, 'members': sorted(agreed)},
            sorted(set(got) | {self.host_id}))
        views = {tuple(sorted(meta['members']))
                 for meta, _ in confirm.values()}
        if len(views) == 1:
          members = sorted(agreed)
          if self.host_id not in members:
            raise ElasticRebuildError(
                f'host {self.host_id} was voted out of the pod at '
                f'epoch {epoch} (agreed members: {members}); its '
                'heartbeats went stale during the agreement round')
          return epoch, members, incarnations
        # Divergent views (a candidate died between propose and
        # confirm): shrink to the still-live intersection and retry.
        proposal = sorted(set.intersection(*[set(v) for v in views]))
        participants = [p for p in proposal if p in participants]
      except HostLostError as e:
        with self._lock:
          self._counters['n_agreement_retries'] += 1
        participants = [p for p in participants if p not in e.missing]
        proposal = [p for p in proposal if p not in e.missing]
        log.warning('agreement round %s retrying without %s (%s)',
                    name, list(e.missing), e)
      epoch += 1
      if not participants or participants == [self.host_id] and (
          len(proposal) > 1):
        proposal = [self.host_id]
        participants = [self.host_id]
    raise ElasticRebuildError(
        f'pod agreement failed to converge after '
        f'{self.rebuild_attempts} round(s) (last proposal {proposal}, '
        f'participants {participants}); refusing to continue with an '
        'ambiguous member set')

  def _collect_after_post(self, epoch: int, name: str,
                          meta: Dict[str, Any],
                          expected: Sequence[int]
                          ) -> Dict[int, Tuple[Dict[str, Any],
                                               List[np.ndarray]]]:
    self._post(epoch, name, meta)
    return self._collect(epoch, name, expected, self.barrier_timeout)

  # ---- rebuild (host loss) -------------------------------------------
  def rebuild(self) -> Tuple[int, ...]:
    """The coordinated survivor-side rebuild: candidates are the hosts
    with fresh heartbeats, the agreement round converges the member
    set, the epoch bumps, and the leader commits. Returns the new
    member set; raises ElasticRebuildError when no consistent set can
    form (or this host was voted out)."""
    with self._lock:
      old_members = self._members
      old_epoch = self._epoch
      step = self._step
    candidates = []
    for h in self._live_candidates():
      if h == self.host_id:
        candidates.append(h)
        continue
      if h not in old_members:
        continue
      # A restarted instance of a lost member heartbeats at epoch 0
      # until it is re-admitted; it must come back through the join
      # path, not vote in a rebuild it has no membership state for.
      beat = self.read_heartbeat(h)
      if beat is not None and int(beat.get('epoch', 0)) >= old_epoch:
        candidates.append(h)
    epoch, members, incarnations = self._agree(
        old_epoch + 1, participants=candidates, proposal=candidates,
        round_name='rebuild')
    with self._lock:
      self._epoch, self._members = epoch, tuple(members)
      self._counters['n_host_rebuilds'] += 1
    if self.host_id == min(members):
      self._commit_epoch(epoch, members, step, incarnations)
    self._write_heartbeat()
    log.warning(
        'pod rebuilt: epoch %d -> %d, members %s -> %s',
        old_epoch, epoch, list(old_members), members)
    return tuple(members)

  # ---- re-admission ---------------------------------------------------
  def _join_path(self, host: int) -> str:
    return os.path.join(self.pod_dir, _JOIN_DIR, f'{host}.json')

  def _scan_join_requests(self) -> List[int]:
    """Join requests from hosts that are NOT current members and whose
    requester still heartbeats (a joiner that died while waiting is
    ignored rather than admitted into a timeout)."""
    with self._lock:
      members = set(self._members)
    out = []
    jdir = os.path.join(self.pod_dir, _JOIN_DIR)
    try:
      entries = sorted(os.listdir(jdir))
    except OSError:
      return out
    for entry in entries:
      if not entry.endswith('.json'):
        continue
      host = int(entry[:-5])
      if host in members:
        continue
      beat = self.read_heartbeat(host)
      if beat is not None and beat['fresh']:
        out.append(host)
    return sorted(out)

  def admit(self, joiners: Sequence[int], state_arrays: Sequence[np.ndarray],
            step: int) -> Tuple[int, ...]:
    """Survivor side of re-admission, run at a step boundary: the
    leader snapshots the live state for the incoming host(s), current
    members agree on the expanded set, the epoch bumps, and the commit
    record (which the joiner is polling) publishes the admission. The
    joiners do not vote — they are proposed members; a joiner that died
    while waiting simply goes missing at the next step's sync."""
    with self._lock:
      members = list(self._members)
      old_epoch = self._epoch
    joiners = sorted(set(int(j) for j in joiners) - set(members))
    if not joiners:
      return tuple(members)
    target = old_epoch + 1
    if self.host_id == min(members):
      self.write_state_snapshot(target, step, state_arrays)
    epoch, new_members, incarnations = self._agree(
        target, participants=members, proposal=members + joiners,
        round_name='admit')
    for j in joiners:
      beat = self.read_heartbeat(j)
      if beat is not None:
        incarnations[j] = int(beat.get('incarnation', 0))
    with self._lock:
      self._epoch, self._members = epoch, tuple(sorted(new_members))
      self._grace_until_step = int(step) + 1
      self._counters['n_host_readmissions'] += len(
          set(new_members) - set(members))
    if self.host_id == min(new_members + [self.host_id]):
      self._commit_epoch(epoch, new_members, step, incarnations)
    self._write_heartbeat()
    log.warning('pod re-admitted %s at step %d: epoch %d -> %d, '
                'members %s', joiners, step, old_epoch, epoch,
                sorted(new_members))
    return tuple(sorted(new_members))

  def _join(self, committed: Dict[str, Any]) -> PodStart:
    """Joiner side: announce, optionally defer to a target step
    boundary (the DCTPU_FAULT_HOST_REJOIN_AT_STEP hook), then poll the
    commit record until an epoch admits THIS incarnation. Bounded by
    join_timeout — an unresponsive pod raises HostLostError (transient:
    the retry wrapper restarts, and a truly dead pod boots fresh)."""
    deadline = time.monotonic() + self.join_timeout
    while (self.defer_join_until_step
           and self.observed_step() < self.defer_join_until_step):
      if time.monotonic() >= deadline:
        with self._lock:
          self._counters['n_barrier_timeouts'] += 1
        raise HostLostError(
            f'pod never reached step {self.defer_join_until_step} '
            f'within the {self.join_timeout:.0f}s join deadline',
            barrier='join-defer')
      time.sleep(_POLL_S)
    _atomic_write_bytes(
        self._join_path(self.host_id),
        json.dumps({'host': self.host_id,
                    'incarnation': self.incarnation}).encode('utf-8'))
    log.info('host %d requesting re-admission (incarnation %d)',
             self.host_id, self.incarnation)
    while True:
      record = self._read_epoch_file()
      if (record is not None
          and self.host_id in record.get('members', ())
          and int(record.get('incarnations', {}).get(
              str(self.host_id), -1)) == self.incarnation):
        break
      if time.monotonic() >= deadline:
        with self._lock:
          self._counters['n_barrier_timeouts'] += 1
        raise HostLostError(
            f'pod did not admit host {self.host_id} within the '
            f'{self.join_timeout:.0f}s join deadline',
            barrier='join-admit')
      time.sleep(_POLL_S)
    epoch = int(record['epoch'])
    members = tuple(sorted(int(m) for m in record['members']))
    step = int(record['step'])
    state = self.read_state_snapshot(epoch)
    with self._lock:
      self._epoch, self._members, self._step = epoch, members, step
      # Adopt the pod's rollback round or the joiner's barrier names
      # would never match the survivors' after a NaN rollback.
      self._round = int(record.get('round', 0))
      self._grace_until_step = step + 1
      self._counters['n_host_readmissions'] += 1
    try:
      os.unlink(self._join_path(self.host_id))
    except OSError:
      pass
    self._write_heartbeat()
    log.info('host %d re-admitted: epoch=%d members=%s step=%d',
             self.host_id, epoch, members, step)
    return PodStart(joined=True, epoch=epoch, members=members,
                    step=step, state=state)

  # ---- state snapshots ------------------------------------------------
  def _snapshot_path(self, epoch: int) -> str:
    return os.path.join(self.pod_dir, _STATE_DIR, f'epoch-{epoch}.npz')

  def write_state_snapshot(self, epoch: int, step: int,
                           arrays: Sequence[np.ndarray]) -> None:
    """Leader-written flattened TrainState leaves a joiner adopts, so
    re-admission re-places state OUTWARD (live memory -> new member)
    instead of rolling the pod back to a checkpoint."""
    _write_payload(self._snapshot_path(epoch),
                   {'epoch': int(epoch), 'step': int(step)}, arrays)

  def read_state_snapshot(self, epoch: int
                          ) -> Optional[List[np.ndarray]]:
    try:
      _, arrays = _read_payload(self._snapshot_path(epoch))
      return arrays
    except (OSError, ValueError, KeyError):
      return None

  # ---- lifecycle ------------------------------------------------------
  def abandon(self) -> None:
    """Abrupt detach for fault drills (ENV_HOST_LOST_MODE=drop): stop
    heartbeating WITHOUT a tombstone, so peers observe exactly what a
    SIGKILL leaves behind — a stale heartbeat and a missed barrier."""
    with self._lock:
      self._abandoned = True
    self._stop.set()
    if self._hb_thread is not None:
      self._hb_thread.join(timeout=2.0)

  def close(self) -> None:
    """Clean shutdown at end of training: the final heartbeat carries a
    `left` tombstone so late peers classify this host as departed, not
    lost."""
    self._stop.set()
    if self._hb_thread is not None:
      self._hb_thread.join(timeout=2.0)
    with self._lock:
      abandoned = self._abandoned
    if not abandoned:
      try:
        self._write_heartbeat(left=True)
      except OSError:  # pragma: no cover - best-effort tombstone
        pass
