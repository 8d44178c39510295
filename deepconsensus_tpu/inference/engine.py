"""ConsensusEngine: the shared window -> consensus model stage.

The model stage of inference (triage -> pack -> dispatch -> finalize)
used to live entangled with BAM-pipeline concerns inside
inference/runner.py, which made every new consumer — sharded inference,
`dctpu serve`, variable-length workloads — re-touch the same 600-line
file (ROADMAP item 5). This module extracts it behind a narrow
interface:

  engine = ConsensusEngine(runner, options, deliver=..., on_pack_failure=...)
  engine.submit(raw_windows, tickets)   # featurized windows in
  engine.flush()                        # end of input
  # finalized uint8 (ids, quals) rows come back through deliver()

* `tickets` are opaque, one per submitted window; the engine never
  inspects them. deliver(ticket, ids_u8, quals_u8) fires once per
  window as its pack finalizes (same thread as submit/flush).
* The engine owns the cross-batch `_WindowPacker` (full fixed-shape
  packs cut across submissions, pad only on flush), the dispatch depth
  (packs in flight on the device), and — through the ModelRunner and
  its params — the fused-Pallas vs XLA path choice
  (`use_fused_hotpath`, models/model.py `_fused_hotpath_eligible`).
* A pack that fails to dispatch or finalize routes its tickets to
  on_pack_failure(tickets, pack_seq, error); without the callback the
  error propagates (fail-fast).

Two thin clients consume it: the batch CLI pipeline
(inference/runner.py run_inference) and the resident service
(deepconsensus_tpu/serve/). The engine is deliberately NOT thread-safe:
each client drives it from a single model-loop thread.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu import obs as obs_lib
from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.preprocess.pileup import row_indices
from deepconsensus_tpu.utils import phred

Ticket = Any
# One compact pack on the host: (main_u8 [B, R - 4, L, 1], sn [B, 4]).
PackBuffer = Tuple[np.ndarray, np.ndarray]
DeliverFn = Callable[[Ticket, np.ndarray, np.ndarray], None]
PackFailureFn = Callable[[Sequence[Ticket], int, BaseException], None]


# ----------------------------------------------------------------------
# Window triage (shared by the batch pipeline and the serve path)


def triage_windows(
    feature_dicts: List[Dict[str, Any]],
    options,
    counter: collections.Counter,
) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
  """Splits windows into (model, skip) per overflow/quality rules
  (reference: quick_inference.py:653-678)."""
  to_model: List[Dict[str, Any]] = []
  to_skip: List[Dict[str, Any]] = []
  for fd in feature_dicts:
    if fd['overflow']:
      to_skip.append(fd)
      counter['n_windows_overflow_skipped'] += 1
      continue
    if options.skip_windows_above:
      avg_q = phred.avg_phred(fd['ccs_base_quality_scores'])
      # Strictly above, matching the reference (quick_inference.py:671).
      if avg_q > options.skip_windows_above:
        to_skip.append(fd)
        counter['n_windows_quality_skipped'] += 1
        continue
    to_model.append(fd)
    counter['n_windows_to_model'] += 1
  return to_model, to_skip


def ccs_quals_array(bq_scores, options) -> np.ndarray:
  """CCS base qualities -> emitted phred uint8 (calibration, cap at
  max_base_quality, floor at 0) — the quality half of a skipped-window
  CCS adoption without the string round-trip."""
  quals = np.asarray(bq_scores)
  if options.ccs_calibration_values.enabled:
    quals = calibration_lib.calibrate_quality_scores(
        quals, options.ccs_calibration_values
    )
  quals = np.minimum(quals, options.max_base_quality).astype(np.int32)
  return np.maximum(quals, 0).astype(np.uint8)


def skipped_window_arrays(
    feature_dict: Dict[str, Any], options
) -> Tuple[np.ndarray, np.ndarray]:
  """Array-native skipped-window CCS adoption: (vocab ids uint8 [L],
  phred uint8 [L]) adopted from the draft CCS. Copies out of the
  feature tensor, so any backing shm segment can be released."""
  rows = feature_dict['subreads']
  ccs_range = row_indices(options.max_passes, options.use_ccs_bq)[4]
  ids = rows[ccs_range[0], :, 0].astype(np.uint8)
  return ids, ccs_quals_array(
      feature_dict['ccs_base_quality_scores'], options)


# ----------------------------------------------------------------------
# What both packers share: the stage sites and the in-flight queue


class _PackerBase:
  """The pieces `_WindowPacker` and `_RaggedPacker` have in common: the
  poison set, the `pack_wait` stamp, drain -> deliver of the oldest
  in-flight pack, flush. Subclasses cut packs (`_cut_packs`), drain the
  oldest in-flight pack under their own fault policy (`_drain_one`) and
  say how its rows reach their tickets (`_deliver_rows`). One span
  vocabulary for both:
  `pack_cut` around a cut, `deliver` around a pack's delivery; the
  runner adds `dispatch` and `finalize_drain` under the same parent."""

  def __init__(self, runner, options, timing_rows: List[Dict[str, Any]],
               on_pack_failure: PackFailureFn, deliver: DeliverFn,
               poisoned: Optional[set] = None,
               pack_clock: Optional[List[int]] = None):
    self._runner = runner
    self._depth = max(1, options.dispatch_depth)
    self._timing_rows = timing_rows
    self._on_pack_failure = on_pack_failure
    self._deliver = deliver
    self._buffered = 0
    self._in_flight: 'collections.deque' = collections.deque()
    # Shared across a bucketed engine's packers: one poison set (the
    # caller doesn't know which bucket a ticket landed in) and one
    # global pack clock (every bucket's dispatches tick it) so the
    # starvation rule can measure "packs the OTHER buckets cut while my
    # tail sat buffered".
    self._poisoned: set = poisoned if poisoned is not None else set()
    self._pack_clock: List[int] = (
        pack_clock if pack_clock is not None else [0])
    # Wall stamp of the moment the current buffered tail started
    # waiting, for the pack_wait interval: how long rows sat buffered
    # before their pack was cut.
    self._t_buf_start = 0.0
    # The runner's metrics registry, when it has one (test stubs don't).
    self._obs = getattr(runner, 'obs', None)
    self.n_packs = 0
    self.n_pack_rows = 0
    self.n_pad_rows = 0
    self.n_starvation_flushes = 0
    self.n_flush_pad_rows = 0
    self.n_oom_bisections = 0
    self.n_device_faults = 0
    self.n_dispatch_timeouts = 0
    # Pack buffers ever allocated (pool misses); the ragged packer has
    # no pool and stays at 0.
    self.n_pack_buffers_allocated = 0
    self.model_wall = 0.0

  def poison(self, ticket: Ticket) -> None:
    """Fault injection: the pack containing this ticket fails at
    dispatch (simulates a window payload that breaks the model stage —
    DCTPU_FAULT_POISON_WINDOW)."""
    self._poisoned.add(id(ticket))

  def _raise_if_poisoned(self, tickets: List[Ticket], what: str) -> None:
    if not self._poisoned:
      return
    hit = [t for t in tickets if id(t) in self._poisoned]
    if hit:
      for t in hit:
        self._poisoned.discard(id(t))
      # dclint: allow=typed-faults (fault-injection hook: must be
      # a bare RuntimeError so it trips the pack-failure path the
      # same way a real dispatch error would)
      raise RuntimeError(
          'injected poison window payload '
          f'({faults_lib.ENV_POISON_WINDOW}; {len(hit)} window(s) '
          f'in {what})')

  def _stamp_pack_wait(self, bucket: int, n_rows: int) -> None:
    """The pack_wait interval of the pack just cut: from the moment its
    first row was buffered (or the previous cut) to now. A wait, not
    work: consecutive ones tile the timeline by construction."""
    t_cut = time.time()
    obs_lib.record_stage(
        self._obs, obs_lib.trace.STAGE_PACK_WAIT,
        self._t_buf_start or t_cut, t_cut, cat=obs_lib.trace.CAT_WAIT,
        bucket=bucket, n_rows=n_rows)
    # Any leftover tail starts a fresh wait from this cut.
    self._t_buf_start = t_cut

  def _deliver_pack(self, entry, pred_ids: np.ndarray,
                    quality: np.ndarray, t0: float) -> None:
    """One drained pack to its tickets. `entry` is what the subclass
    keeps per window of the pack (tickets, or ragged placements)."""
    n_rows = len(entry)
    with obs_lib.stage(self._obs, obs_lib.trace.STAGE_DELIVER,
                       n_rows=n_rows):
      # uint8 transport into the stitch plane (values are 0..4 / 0..93).
      ids_u8 = pred_ids.astype(np.uint8)
      quals_u8 = quality.astype(np.uint8)
      elapsed = time.time() - t0
      self.model_wall += elapsed
      self._deliver_rows(entry, ids_u8, quals_u8)
    self._timing_rows.append(dict(
        stage='run_model', runtime=elapsed, n_zmws=0,
        n_examples=n_rows, n_subreads=0))

  def flush(self, drain: bool = True) -> None:
    """Cuts the buffered tail as final (padded) packs — the only
    partial packs of a run; with drain, also resolves every in-flight
    pack (end of input)."""
    self._cut_packs(flush=True)
    while drain and self._in_flight:
      self._drain_one()

  @property
  def has_work(self) -> bool:
    return bool(self._buffered or self._in_flight)


# ----------------------------------------------------------------------
# Cross-batch window packer


class _WindowPacker(_PackerBase):
  """Cross-batch window packer feeding the fixed-shape compiled forward.

  The packer owns batch-sized compact pack buffers (`main_u8`
  [batch, total_rows - 4, L, 1] uint8 and `sn` [batch, 4] float32: what
  the device receives, models/data.py). Every submitted window is
  written once, at its final row of the pack being filled, clipped and
  cast on the way (data.fill_pack); a pack is cut and dispatched the
  moment it is full, so in steady state the forward never runs padded
  and the dispatch pipeline never drains at submission seams. A partial
  pack stays in its buffer until the next submission; only flush()
  dispatches one, its unfilled rows zeroed. Up to dispatch_depth packs
  stay in flight; draining the oldest hands its (ids, quals) rows to
  deliver(), one call per ticket.

  Buffers come from a free list and go back to it only when their pack
  has been drained and delivered, or failed and routed: the transfer is
  asynchronous (and may alias host memory on the CPU backend), and
  under on_device_error=degrade a device fault bisects or resubmits the
  pack from its buffer. At most dispatch_depth + 1 buffers are alive at
  once (dispatch_depth in flight and the one being filled);
  n_pack_buffers_allocated counts them.

  A pack that fails to dispatch or finalize is routed to
  on_pack_failure(tickets, pack_seq, error) — ticket bookkeeping plus
  any quarantine policy live with the caller. Under
  on_device_error=degrade, typed device faults are absorbed first:
  RESOURCE_EXHAUSTED bisects the pack (retry at half batch), a
  lost/halted device rebuilds the mesh one dp step down and resubmits
  everything that was in flight, in featurize order.
  """

  def __init__(self, runner, options, timing_rows: List[Dict[str, Any]],
               on_pack_failure: PackFailureFn, deliver: DeliverFn,
               poisoned: Optional[set] = None,
               pack_clock: Optional[List[int]] = None):
    super().__init__(runner, options, timing_rows, on_pack_failure,
                     deliver, poisoned, pack_clock)
    self._batch = options.batch_size
    self._degrade = getattr(options, 'on_device_error', 'fail') == 'degrade'
    # The pack being filled, (main_u8, sn): rows [:_buffered] are
    # written, for the tickets in _tickets.
    self._buf: Optional[PackBuffer] = None
    self._tickets: List[Ticket] = []
    self._free: List[PackBuffer] = []
    # Clock reading when the current buffered tail started waiting.
    self._starve_mark = 0

  def add(self, windows, tickets: Sequence[Ticket],
          layout: 'data_lib.PackLayout') -> None:
    """Writes one submission's windows (a list of [H, L, 1] tensors or a
    [k, H, L, 1] array, one width, aligned with tickets) into the pack
    being filled, dispatching every pack the moment it is full."""
    n = len(windows)
    done = 0
    while done < n:
      if not self._buffered:
        self._starve_mark = self._pack_clock[0]
        self._t_buf_start = time.time()
      take = min(n - done, self._batch - self._buffered)
      with obs_lib.stage(self._obs, obs_lib.trace.STAGE_FORMAT) as st:
        if self._buf is None:
          self._buf = self._take_buffer(layout.n_main, windows[0].shape[1:])
        main_u8, sn = self._buf
        data_lib.fill_pack(windows[done:done + take], layout, main_u8, sn,
                           at=self._buffered)
        st.set(n_rows=take,
               bytes=take * (main_u8[0].nbytes + sn[0].nbytes),
               bytes_read=take * windows[0].nbytes)
      self._tickets.extend(tickets[done:done + take])
      self._buffered += take
      done += take
      self._cut_packs(flush=False)

  def _take_buffer(self, n_main: int, window_shape) -> PackBuffer:
    if self._free:
      return self._free.pop()
    self.n_pack_buffers_allocated += 1
    return (np.zeros((self._batch, n_main) + tuple(window_shape), np.uint8),
            np.zeros((self._batch, data_lib.SN_ROWS), np.float32))

  def maybe_flush_starved(self, limit: int) -> None:
    """Bucket starvation flush: if this packer's partial tail has sat
    buffered while the engine (all buckets together) cut >= limit
    packs, cut it now as a padded partial pack rather than holding its
    windows hostage to a bucket the input stream rarely feeds."""
    if self._buffered and self._pack_clock[0] - self._starve_mark >= limit:
      # Attribute the pad rows of this flush to starvation ONCE, here:
      # _cut_packs -> _dispatch adds the same pads to the general
      # n_pad_rows pool, and the end-of-input flush() cannot re-pad an
      # already-flushed tail (buffered is 0 after the cut), so neither
      # counter double-counts a bucket whose FINAL pack was a
      # starvation flush.
      self.n_starvation_flushes += 1
      self.n_flush_pad_rows += self._batch - self._buffered
      self._cut_packs(flush=True)

  def _cut_packs(self, flush: bool) -> None:
    """Cuts the pack being filled if it is full (or, on flush, begun):
    the buffer and its tickets leave the packer whole, nothing is
    copied. A short pack's unfilled rows are zeroed, since a reused
    buffer holds an earlier pack there."""
    if self._buffered < self._batch and not (flush and self._buffered):
      return
    with obs_lib.stage(self._obs, obs_lib.trace.STAGE_PACK_CUT) as st:
      n = self._buffered
      buf, self._buf = self._buf, None
      if n < self._batch:
        for plane in buf:
          plane[n:] = 0
      tickets, self._tickets = self._tickets, []
      self._buffered = 0
      st.set(n_rows=n, bytes_concatenated=0)
    self._dispatch(buf, n, tickets)

  def _dispatch(self, buf: PackBuffer, n: int,
                tickets: List[Ticket]) -> None:
    seq = self.n_packs
    self.n_packs += 1
    self._pack_clock[0] += 1
    self._starve_mark = self._pack_clock[0]
    self._stamp_pack_wait(int(buf[0].shape[2]), n)
    self.n_pack_rows += n
    self.n_pad_rows += self._batch - n
    try:
      self._raise_if_poisoned(tickets, f'pack {seq}')
      handle = self._runner.dispatch_pack(*buf, n_rows=n)
    except Exception as e:
      self._handle_pack_fault(buf, n, tickets, seq, e)
      self._free.append(buf)
      return
    # The entry holds the buffer until the pack is drained or routed: the
    # transfer may still be reading it, and degrade mode retries from it.
    self._in_flight.append((handle, tickets, seq, buf, n))
    while len(self._in_flight) > self._depth:
      self._drain_one()

  def _drain_one(self) -> None:
    handle, tickets, seq, buf, n = self._in_flight.popleft()
    t0 = time.time()
    try:
      pred_ids, quality = self._runner.finalize(handle)
    except Exception as e:
      self._handle_pack_fault(buf, n, tickets, seq, e)
    else:
      self._deliver_pack(tickets, pred_ids, quality, t0)
    self._free.append(buf)

  def _deliver_rows(self, tickets: List[Ticket], ids_u8: np.ndarray,
                    quals_u8: np.ndarray) -> None:
    for ticket, row_ids, row_quals in zip(tickets, ids_u8, quals_u8):
      self._deliver(ticket, row_ids, row_quals)

  def _handle_pack_fault(self, pack: PackBuffer, n: int,
                         tickets: List[Ticket], seq: int,
                         error: BaseException,
                         batch_size: Optional[int] = None) -> None:
    """Device-fault policy for one failed pack (`pack`: its buffer, or
    a bisected slice of it, the first `n` rows written).

    Classifies the error into the DeviceFault family; under
    on_device_error=degrade OOM bisects and a lost device degrades the
    mesh. Anything unrecovered routes to on_pack_failure with the
    classified error, so dead-letters carry the device-fault kind.
    """
    error = faults_lib.classify_device_error(error)
    if isinstance(error, faults_lib.DeviceFault):
      self.n_device_faults += 1
      if isinstance(error, faults_lib.DispatchTimeoutError):
        # The watchdog already bounded the loss; retrying a hung
        # device at the same (or any) shape would hang again.
        self.n_dispatch_timeouts += 1
      elif self._degrade:
        if isinstance(error, faults_lib.DeviceOomError):
          if self._bisect(pack, n, tickets, seq,
                          batch_size or self._batch):
            return
        elif isinstance(error, faults_lib.DeviceLostError):
          if self._try_degrade(pack, n, tickets, seq):
            return
    self._on_pack_failure(tickets, seq, error)

  def _bisect(self, pack: PackBuffer, n: int, tickets: List[Ticket],
              seq: int, batch_size: int) -> bool:
    """OOM bisection: retry the pack as halves at half batch shape.

    Floors at mesh-dp divisibility (the compiled batch must still
    split over the data axis); returns False when no smaller shape
    exists, handing the pack back to on_pack_failure.
    """
    dp = max(1, getattr(self._runner, 'mesh_dp', 0))
    half = batch_size // 2
    if half < 1 or half % dp:
      return False
    self.n_oom_bisections += 1
    for lo in range(0, n, half):
      self._run_pack_at(tuple(plane[lo:lo + half] for plane in pack),
                        min(half, n - lo), tickets[lo:lo + half], seq, half)
    return True

  def _try_degrade(self, pack: PackBuffer, n: int, tickets: List[Ticket],
                   seq: int) -> bool:
    """Mesh degradation: rebuild at the next lower dp and resubmit the
    failed pack plus everything else in flight (launched on the dead
    topology), in featurize (seq) order."""
    degrade = getattr(self._runner, 'degrade_mesh', None)
    if degrade is None or not degrade():
      return False
    pending = [(pack, n, tickets, seq)]
    while self._in_flight:
      _handle, ts, s, p, rows = self._in_flight.popleft()
      pending.append((p, rows, ts, s))
      # Resubmitted synchronously below; free for the next fill after.
      self._free.append(p)
    for p, rows, ts, s in sorted(pending, key=lambda entry: entry[3]):
      self._run_pack_at(p, rows, ts, s, self._batch)
    return True

  def _run_pack_at(self, pack: PackBuffer, n: int, tickets: List[Ticket],
                   seq: int, batch_size: int) -> None:
    """Synchronous retry of one (possibly bisected) pack at an explicit
    batch shape. Further faults recurse through _handle_pack_fault, so
    a bisected half can bisect again down to the dp floor."""
    t0 = time.time()
    try:
      handle = self._runner.dispatch_pack(*pack, n_rows=n,
                                          batch_size=batch_size)
      pred_ids, quality = self._runner.finalize(handle)
    except Exception as e:
      self._handle_pack_fault(pack, n, tickets, seq, e,
                              batch_size=batch_size)
      return
    self._deliver_pack(tickets, pred_ids, quality, t0)


# ----------------------------------------------------------------------
# Single-stream ragged packer (use_ragged_kernel)


class _RaggedPacker(_PackerBase):
  """One pack stream for every bucket width: mixed-width windows pack
  into fixed [n_slots, R, slot_len, 1] slots (slot_len = the largest
  bucket) with a per-slot int32 `lengths` vector, and dispatch through
  the runner's ragged forward (ModelRunner.dispatch_ragged). One
  compiled forward serves the whole run (n_forward_shapes == 1), so
  there is no per-bucket starvation and no starvation flush: packs cut
  only when every slot fills exactly (zero padding in steady state);
  partial, zero-length-padded slots appear only at end-of-input flush.

  Packing is greedy largest-first against the bucket divisibility
  chain (each bucket divides every larger one, enforced by the model's
  ragged path), so every placed window starts at a multiple of its own
  width and the device reshape-select recovers it exactly — per-window
  output stays byte-identical to the per-bucket packers.

  Fault policy is fail-only: typed device faults are classified and
  counted, then the whole pack routes to on_pack_failure. (Bisect /
  mesh-degrade recovery stays a bucketed-path feature; the ragged
  path's single compiled shape is the point.)
  """

  def __init__(self, runner, options, buckets: Tuple[int, ...],
               timing_rows: List[Dict[str, Any]],
               on_pack_failure: PackFailureFn, deliver: DeliverFn,
               poisoned: Optional[set] = None,
               pack_clock: Optional[List[int]] = None):
    buckets = tuple(sorted(int(b) for b in buckets))
    if not buckets or buckets[0] <= 0:
      # dclint: allow=typed-faults (configuration contract, not a
      # data-plane fault: buckets come from resolved model params)
      raise ValueError(f'ragged packing needs positive buckets: {buckets}')
    for small, large in zip(buckets, buckets[1:]):
      if large % small:
        # dclint: allow=typed-faults (same configuration contract —
        # mirrors ops.ragged_window_attention.validate_ragged_buckets
        # without importing jax into the engine)
        raise ValueError(
            'ragged packing needs a bucket divisibility chain '
            f'(each bucket divides the next): {buckets}')
    super().__init__(runner, options, timing_rows, on_pack_failure,
                     deliver, poisoned, pack_clock)
    self._buckets = buckets
    self._slot_len = buckets[-1]
    self._wps = self._slot_len // buckets[0]  # windows per slot, max
    batch = max(1, int(options.batch_size))
    n_slots = max(1, batch // self._wps)
    dp = int(getattr(runner, 'mesh_dp', 0) or 0)
    if dp > 1:
      # The compiled slot batch must split over the data axis.
      n_slots = ((n_slots + dp - 1) // dp) * dp
    self._n_slots = n_slots
    # Per-width FIFO queues of (rows [R, w, 1], ticket): within a
    # width, placement order == submission order, which is what the
    # byte-identity contract pins downstream.
    self._queues: Dict[int, 'collections.deque'] = {
        w: collections.deque() for w in buckets}
    # n_starvation_flushes / n_flush_pad_rows stay structurally zero on
    # the single-stream path (no starvation flush); the engine
    # aggregates them uniformly.

  @property
  def slot_len(self) -> int:
    return self._slot_len

  @property
  def n_slots(self) -> int:
    return self._n_slots

  @property
  def windows_per_slot(self) -> int:
    return self._wps

  def add(self, rows: np.ndarray, tickets: Sequence[Ticket]) -> None:
    """Buffers one submission's formatted rows ([k, R, w, 1], one
    bucket width, aligned with tickets) and cuts every pack whose
    n_slots slots can now be filled exactly."""
    width = int(rows.shape[2])
    queue = self._queues.get(width)
    if queue is None:
      # dclint: allow=typed-faults (caller shape contract: windows
      # must arrive pre-padded to a configured bucket)
      raise ValueError(
          f'window width {width} not in window buckets {self._buckets}')
    if not self._buffered:
      self._t_buf_start = time.time()
    for row, ticket in zip(rows, tickets):
      queue.append((row, ticket))
    self._buffered += len(rows)
    self._cut_packs(flush=False)

  def maybe_flush_starved(self, limit: int) -> None:
    """No-op: one pack stream serves every width, so no bucket's tail
    can starve behind another's traffic."""
    del limit

  def _plan(self, allow_partial: bool) -> Optional[List[Tuple[int, int, int]]]:
    """Greedy largest-first slot plan: [(slot, offset, width), ...] in
    per-width FIFO order, or None when the slots cannot all be filled
    exactly (and partial packs are not allowed). With the divisibility
    chain, any remaining slot capacity is a multiple of every smaller
    bucket, so largest-first never strands capacity a different order
    could have filled."""
    counts = {w: len(q) for w, q in self._queues.items()}
    plan: List[Tuple[int, int, int]] = []
    for slot in range(self._n_slots):
      remaining = self._slot_len
      while remaining:
        width = next(
            (w for w in reversed(self._buckets)
             if w <= remaining and counts[w]), None)
        if width is None:
          if allow_partial:
            break
          return None
        counts[width] -= 1
        plan.append((slot, self._slot_len - remaining, width))
        remaining -= width
      if allow_partial and not any(counts.values()):
        break
    return plan

  def _cut_packs(self, flush: bool) -> None:
    while self._cut_one(allow_partial=False):
      pass
    while flush and self._buffered:
      self._cut_one(allow_partial=True)

  def _cut_one(self, allow_partial: bool) -> bool:
    """Plans one pack, gathers it and dispatches it; False when no pack
    can be cut. The `pack_cut` stage covers plan and gather (a plan that
    finds nothing to cut is one with n_rows 0), not the dispatch."""
    with obs_lib.stage(self._obs, obs_lib.trace.STAGE_PACK_CUT) as st:
      plan = self._plan(allow_partial=allow_partial)
      st.set(n_rows=len(plan or ()), bytes_concatenated=0)
      if plan is None:
        return False
      pack, lengths, placements, used = self._gather(plan)
      st.set(bytes_concatenated=pack.nbytes)
    self._dispatch(pack, lengths, placements, used)
    return True

  def _gather(self, plan: List[Tuple[int, int, int]]):
    """Materializes the pack from the plan, popping each width's FIFO."""
    first_row = self._queues[plan[0][2]][0][0]
    n_rows = first_row.shape[0]
    pack = np.zeros((self._n_slots, n_rows, self._slot_len, 1),
                    dtype=np.float32)
    lengths = np.zeros((self._n_slots, self._wps), dtype=np.int32)
    slot_fill = [0] * self._n_slots
    placements: List[Tuple[Ticket, int, int, int]] = []
    used = 0
    for slot, off, width in plan:
      row, ticket = self._queues[width].popleft()
      pack[slot, :, off:off + width] = row
      lengths[slot, slot_fill[slot]] = width
      slot_fill[slot] += 1
      placements.append((ticket, slot, off, width))
      used += width
    return pack, lengths, placements, used

  def _dispatch(self, pack: np.ndarray, lengths: np.ndarray,
                placements: List[Tuple[Ticket, int, int, int]],
                used: int) -> None:
    seq = self.n_packs
    self.n_packs += 1
    self._pack_clock[0] += 1
    self._stamp_pack_wait(self._slot_len, len(placements))
    self._buffered -= len(placements)
    self.n_pack_rows += len(placements)
    # Unused position capacity in min-bucket units: the windows a full
    # pack of the same shape could additionally have carried.
    self.n_pad_rows += (
        self._n_slots * self._slot_len - used) // self._buckets[0]
    try:
      self._raise_if_poisoned([p[0] for p in placements],
                              f'ragged pack {seq}')
      handle = self._runner.dispatch_ragged(pack, lengths)
    except Exception as e:
      self._handle_pack_fault(placements, seq, e)
      return
    self._in_flight.append((handle, placements, seq))
    while len(self._in_flight) > self._depth:
      self._drain_one()

  def _drain_one(self) -> None:
    handle, placements, seq = self._in_flight.popleft()
    t0 = time.time()
    try:
      pred_ids, quality = self._runner.finalize(handle)
    except Exception as e:
      self._handle_pack_fault(placements, seq, e)
      return
    self._deliver_pack(placements, pred_ids, quality, t0)

  def _deliver_rows(self, placements, ids_u8: np.ndarray,
                    quals_u8: np.ndarray) -> None:
    for ticket, slot, off, width in placements:
      self._deliver(ticket, ids_u8[slot, off:off + width],
                    quals_u8[slot, off:off + width])

  def _handle_pack_fault(self, placements, seq: int,
                         error: BaseException) -> None:
    error = faults_lib.classify_device_error(error)
    if isinstance(error, faults_lib.DeviceFault):
      self.n_device_faults += 1
      if isinstance(error, faults_lib.DispatchTimeoutError):
        self.n_dispatch_timeouts += 1
    self._on_pack_failure([p[0] for p in placements], seq, error)


# ----------------------------------------------------------------------
# The engine


def _raise_pack_failure(tickets, pack_seq: int, error: BaseException):
  del tickets, pack_seq
  raise error


class ConsensusEngine:
  """Submit featurized windows, receive finalized uint8 (ids, quals).

  Owns one window packer PER LENGTH BUCKET (params.window_buckets /
  options.window_buckets; single bucket = the historical fixed-shape
  engine), the dispatch depth, and (via the ModelRunner / model
  config) the fused-kernel vs XLA path choice — eligibility is
  per-bucket: traces at L <= the fused VMEM limit run the Pallas hot
  path, longer buckets the XLA fallback. Mixed-width submissions are
  grouped by trailing window width; within each bucket, delivery stays
  in featurize order, so per-bucket output is byte-identical to a
  single-bucket run over the same windows. See the module docstring
  for the contract; construct via __init__ with an existing
  ModelRunner or via from_checkpoint.
  """

  def __init__(self, runner, options, deliver: DeliverFn,
               on_pack_failure: Optional[PackFailureFn] = None,
               timing_rows: Optional[List[Dict[str, Any]]] = None):
    self.runner = runner
    self.options = options
    self.timing_rows = timing_rows if timing_rows is not None else []
    self._deliver_fn = deliver
    self._on_pack_failure = on_pack_failure or _raise_pack_failure
    self._buckets = self._resolve_buckets()
    # One packer per bucket, created on first window of that width;
    # all packers share the poison set and the global pack clock.
    self._packers: Dict[int, _WindowPacker] = {}
    self._poisoned: set = set()
    self._pack_clock: List[int] = [0]
    self._n_windows_by_bucket: Dict[int, int] = {}
    # use_ragged_kernel: ONE pack stream for every width — a single
    # _RaggedPacker replaces the per-bucket fleet, and every pack
    # dispatches at the same [n_slots, R, slot_len] shape.
    self._ragged = bool(getattr(options, 'use_ragged_kernel', False))
    self._ragged_packer: Optional[_RaggedPacker] = None
    # The runner's metrics registry, when it has one (test stubs don't).
    self._obs = getattr(runner, 'obs', None)

  def _resolve_buckets(self) -> Tuple[int, ...]:
    buckets = getattr(self.options, 'window_buckets', None)
    if buckets:
      return tuple(int(b) for b in buckets)
    params = getattr(self.runner, 'params', None)
    if params is not None:
      from deepconsensus_tpu.models import config as config_lib

      return config_lib.resolve_window_buckets(params)
    return (int(self.options.max_length),)

  @property
  def window_buckets(self) -> Tuple[int, ...]:
    return self._buckets

  def _packer_for(self, width: int):
    """The packer that takes windows of this width, made on first use;
    WindowBucketError for a width outside the configured buckets."""
    data_lib.check_window_bucket(width, self._buckets)
    if self._ragged:
      if self._ragged_packer is None:
        self._ragged_packer = _RaggedPacker(
            self.runner, self.options, self._buckets, self.timing_rows,
            lambda ts, seq, err: self._on_pack_failure(ts, seq, err),
            lambda t, ids, quals: self._deliver_fn(t, ids, quals),
            poisoned=self._poisoned, pack_clock=self._pack_clock)
      return self._ragged_packer
    packer = self._packers.get(width)
    if packer is None:
      packer = _WindowPacker(
          self.runner, self.options, self.timing_rows,
          # Indirection so predict_windows can swap the deliver sink
          # for every bucket at once.
          lambda ts, seq, err: self._on_pack_failure(ts, seq, err),
          lambda t, ids, quals: self._deliver_fn(t, ids, quals),
          poisoned=self._poisoned, pack_clock=self._pack_clock)
      self._packers[width] = packer
    return packer

  def _all_packers(self) -> List[Any]:
    """Every live packer: the per-bucket fleet, or the one ragged
    packer. Counter aggregation and flush iterate this so neither path
    double-counts."""
    if self._ragged:
      return [self._ragged_packer] if self._ragged_packer else []
    return [self._packers[w] for w in sorted(self._packers)]

  def _flush_starved(self) -> None:
    if self._ragged:
      return  # single pack stream: no bucket can starve
    limit = int(getattr(self.options, 'bucket_flush_packs', 0) or 0)
    if limit <= 0 or len(self._packers) < 2:
      return
    for width in sorted(self._packers):
      self._packers[width].maybe_flush_starved(limit)

  @staticmethod
  def _group_by_width(windows, tickets) -> Dict[int, Tuple[list, list]]:
    """Groups per-window tensors by trailing window width, preserving
    submission order within each group (delivery order within a bucket
    is what the byte-identity contract pins)."""
    groups: Dict[int, Tuple[list, list]] = {}
    for w, t in zip(windows, tickets):
      w = np.asarray(w)
      ws, ts = groups.setdefault(int(w.shape[-2]), ([], []))
      ws.append(w)
      ts.append(t)
    return groups

  @classmethod
  def from_checkpoint(cls, checkpoint_path: str, options,
                      deliver: DeliverFn,
                      on_pack_failure: Optional[PackFailureFn] = None,
                      timing_rows: Optional[List[Dict[str, Any]]] = None,
                      mesh=None) -> 'ConsensusEngine':
    from deepconsensus_tpu.inference import runner as runner_lib
    from deepconsensus_tpu.models import config as config_lib

    runner = runner_lib.ModelRunner.from_checkpoint(
        checkpoint_path, options, mesh=mesh)
    options.max_passes = runner.params.max_passes
    options.max_length = runner.params.max_length
    options.use_ccs_bq = runner.params.use_ccs_bq
    # Bucket-aware options: an explicit options.window_buckets must be
    # consistent with the checkpoint's base geometry; unset follows
    # params.window_buckets (single shape when that too is unset).
    options.window_buckets = config_lib.normalize_window_buckets(
        getattr(options, 'window_buckets', None) or
        getattr(runner.params, 'window_buckets', None),
        runner.params.max_length)
    return cls(runner, options, deliver,
               on_pack_failure=on_pack_failure, timing_rows=timing_rows)

  @property
  def params(self):
    return self.runner.params

  def submit(self, raw_windows,
             tickets: Sequence[Ticket]) -> None:
    """Feeds featurized window tensors (one ticket per window) through
    format -> pack -> dispatch. Accepts a uniform [k, total_rows, L, 1]
    array or a sequence of [total_rows, L, 1] tensors with mixed L;
    mixed widths are grouped per bucket. Full packs dispatch
    immediately; each bucket's tail waits for more windows, the
    starvation flush, or flush()."""
    self._submit(raw_windows, tickets, formatted=False)

  def submit_formatted(self, rows,
                       tickets: Sequence[Ticket]) -> None:
    """submit() for rows already through data.format_rows_batch (the
    serve retry path re-dispatches without re-formatting). Accepts a
    uniform [k, R, L, 1] array or a sequence of [R, L, 1] rows with
    mixed L."""
    self._submit(rows, tickets, formatted=True)

  def _submit(self, windows, tickets: Sequence[Ticket],
              formatted: bool) -> None:
    """Both submits: one `submit` stage on the caller's thread, and
    under it `stack_windows` (the grouping by width that a list costs),
    `format_rows` (each fill of a pack buffer) and the packer's
    `pack_cut` / `dispatch` / `finalize_drain` / `deliver`. Nothing of
    `windows` is referenced once this returns."""
    if len(windows) != len(tickets):
      # dclint: allow=typed-faults (caller API misuse guard, not a
      # data-plane fault: both args come from the same client code)
      raise ValueError(
          f'{len(windows)} {"rows" if formatted else "windows"} vs '
          f'{len(tickets)} tickets')
    if not len(windows):
      return
    with obs_lib.stage(self._obs, obs_lib.trace.STAGE_SUBMIT,
                       n_windows=len(tickets), formatted=int(formatted)):
      if isinstance(windows, np.ndarray) and windows.dtype != object:
        groups = [(int(windows.shape[-2]), (windows, list(tickets)))]
      else:
        with obs_lib.stage(self._obs, obs_lib.trace.STAGE_STACK,
                           n_rows=len(tickets), bytes=0):
          groups = sorted(self._group_by_width(windows, tickets).items())
      # Every width is checked before any window is written.
      packers = [self._packer_for(width) for width, _ in groups]
      for packer, (width, (ws, ts)) in zip(packers, groups):
        self._n_windows_by_bucket[width] = (
            self._n_windows_by_bucket.get(width, 0) + len(ws))
        if self._ragged:
          packer.add(self._ragged_rows(ws, formatted), ts)
        else:
          packer.add(ws, ts, data_lib.pack_layout(
              ws[0].shape[0], self.runner.params, formatted))
      self._flush_starved()

  def _ragged_rows(self, windows, formatted: bool) -> np.ndarray:
    """One width group as the ragged packer queues it, float32 rows:
    stacked when they came as a list, formatted when they came raw."""
    rows = windows
    if not isinstance(rows, np.ndarray):
      with obs_lib.stage(self._obs, obs_lib.trace.STAGE_STACK) as st:
        rows = np.stack(rows)
        st.set(n_rows=len(rows), bytes=rows.nbytes)
    if not formatted:
      with obs_lib.stage(self._obs, obs_lib.trace.STAGE_FORMAT) as st:
        rows = data_lib.format_rows_batch(
            rows, self.runner.params, window_buckets=self._buckets)
        st.set(n_rows=len(rows), bytes=rows.nbytes)
    return rows

  def flush(self, drain: bool = True) -> None:
    """Cuts every bucket's buffered tail as a padded pack; with drain,
    resolves every in-flight pack (every submitted ticket has been
    delivered or failed when this returns). Tails cut for all buckets
    before any drain so the end-of-input packs overlap on device."""
    with obs_lib.stage(self._obs, obs_lib.trace.STAGE_FLUSH,
                       drain=int(drain)):
      for packer in self._all_packers():
        packer.flush(drain=False)
      if drain:
        for packer in self._all_packers():
          packer.flush(drain=True)

  def poison_ticket(self, ticket: Ticket) -> None:
    # Shared across buckets: the caller doesn't know (or care) which
    # bucket the window landed in.
    self._poisoned.add(id(ticket))

  @property
  def has_work(self) -> bool:
    """True while any submitted window is still buffered or in flight."""
    return any(p.has_work for p in self._all_packers())

  def _agg(self, name: str):
    return sum(getattr(p, name) for p in self._all_packers())

  @property
  def n_packs(self) -> int:
    return self._agg('n_packs')

  @property
  def n_pack_rows(self) -> int:
    return self._agg('n_pack_rows')

  @property
  def n_pad_rows(self) -> int:
    return self._agg('n_pad_rows')

  @property
  def model_wall(self) -> float:
    return self._agg('model_wall')

  @property
  def n_oom_bisections(self) -> int:
    return self._agg('n_oom_bisections')

  @property
  def n_device_faults(self) -> int:
    return self._agg('n_device_faults')

  @property
  def n_dispatch_timeouts(self) -> int:
    return self._agg('n_dispatch_timeouts')

  @property
  def n_starvation_flushes(self) -> int:
    return self._agg('n_starvation_flushes')

  @property
  def n_packs_by_bucket(self) -> Dict[int, int]:
    if self._ragged:
      packer = self._ragged_packer
      return {packer.slot_len: packer.n_packs} if packer else {}
    return {w: self._packers[w].n_packs for w in sorted(self._packers)}

  @property
  def flush_padding_fraction(self) -> float:
    """Fraction of all dispatched positions that were starvation-flush
    padding: sum_b(n_flush_pad_rows_b * L_b) / sum_b(n_packs_b * B * L_b).
    Separates the cost of the bucket_flush_packs policy from ordinary
    end-of-input padding; structurally 0.0 on the ragged path."""
    dispatched = 0
    flushed = 0
    for width, packer in sorted(self._packers.items()):
      dispatched += packer.n_packs * packer._batch * width
      flushed += packer.n_flush_pad_rows * width
    return flushed / dispatched if dispatched else 0.0

  @property
  def padding_fraction(self) -> float:
    """Fraction of positions a pad-to-max policy would have dispatched
    on top of the bucketed dispatch: 1 - sum(n_b * L_b) / (N * L_max).
    0.0 with a single bucket or before any window arrives."""
    total = sum(self._n_windows_by_bucket.values())
    if not total or len(self._buckets) < 2:
      return 0.0
    bucketed = sum(
        n * w for w, n in self._n_windows_by_bucket.items())
    return 1.0 - bucketed / (total * max(self._buckets))

  def stats(self) -> Dict[str, Any]:
    out = {
        'n_model_packs': self.n_packs,
        'n_model_pack_rows': self.n_pack_rows,
        'n_model_pad_rows': self.n_pad_rows,
        'n_starvation_flushes': self.n_starvation_flushes,
        'flush_padding_fraction': round(self.flush_padding_fraction, 4),
        'model_wall_s': round(self.model_wall, 3),
        'n_oom_bisections': self.n_oom_bisections,
        'n_device_faults': self.n_device_faults,
        'n_dispatch_timeouts': self.n_dispatch_timeouts,
        # All buckets together: at most dispatch_depth + 1 a bucket.
        'n_pack_buffers_allocated': self._agg('n_pack_buffers_allocated'),
    }
    # Sharded-dispatch / transfer-overlap counters (stub runners in
    # tests may not implement the full dispatch contract).
    dispatch_stats = getattr(self.runner, 'dispatch_stats', None)
    if dispatch_stats is not None:
      out.update(dispatch_stats())
    # Bucketed-dispatch counters (after the runner merge: the engine's
    # per-packer view is authoritative for pack accounting).
    out['window_buckets'] = list(self._buckets)
    out['use_ragged_kernel'] = int(self._ragged)
    out['n_packs_by_bucket'] = self.n_packs_by_bucket
    out['n_windows_by_bucket'] = {
        w: self._n_windows_by_bucket[w]
        for w in sorted(self._n_windows_by_bucket)}
    out['padding_fraction'] = round(self.padding_fraction, 4)
    return out

  def predict_windows(
      self, raw_windows
  ) -> Tuple[Any, Any]:
    """Synchronous convenience: featurized windows -> (ids, quals),
    in submission order. Flushes the pipeline, so only for tools/tests
    — streaming callers use submit()/flush() with tickets. Uniform
    widths return stacked arrays; mixed widths return aligned lists."""
    results: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    save = self._deliver_fn
    try:
      self._deliver_fn = (
          lambda ticket, ids, quals: results.__setitem__(
              ticket, (ids, quals)))
      self.submit(raw_windows, list(range(len(raw_windows))))
      self.flush()
    finally:
      self._deliver_fn = save
    ids = [results[i][0] for i in range(len(raw_windows))]
    quals = [results[i][1] for i in range(len(raw_windows))]
    if len({i.shape for i in ids}) <= 1:
      return np.stack(ids), np.stack(quals)
    return ids, quals
