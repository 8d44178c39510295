"""Input pipeline: TFRecord parsing, row formatting, batching.

TF-free equivalent of the reference's tf.data pipeline (reference:
deepconsensus/models/data_providers.py:41-425): examples parse into
numpy, PW/IP/SN rows are clipped, and batches are produced by a
lightweight shuffling loader that feeds jax.device_put directly.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import ml_collections
import numpy as np

from deepconsensus_tpu import constants
from deepconsensus_tpu.faults import CorruptInputError, WindowBucketError
from deepconsensus_tpu.io.example_proto import Example
from deepconsensus_tpu.models import config
from deepconsensus_tpu.io.tfrecord import read_tfrecords
from deepconsensus_tpu.preprocess.pileup import layout_from_shape, row_indices
from deepconsensus_tpu.utils import phred

log = logging.getLogger(__name__)


class OnShardError:
  """--on_shard_error policy values (StreamingDataset)."""

  FAIL = 'fail'
  SKIP = 'skip'

  CHOICES = (FAIL, SKIP)


def format_rows(
    subreads: np.ndarray,
    params: ml_collections.ConfigDict,
) -> np.ndarray:
  """Clips PW/IP/SN rows and crops passes to the model's max_passes
  (reference format_rows: data_providers.py:128-184)."""
  return format_rows_batch(subreads[None], params)[0]


def format_rows_batch(
    subreads: np.ndarray,
    params: ml_collections.ConfigDict,
    window_buckets: Sequence[int] = (),
    names: Sequence = (),
) -> np.ndarray:
  """format_rows over a whole window batch [N, H, L, 1] at once —
  one set of slice/clip/concat ops instead of N (the per-window calls
  were a measured host-side cost in the inference model stage).
  window_buckets overrides the allowed widths (callers whose buckets
  come from InferenceOptions rather than params). `names` (window ids,
  when the caller tracks them) only feeds the rejection message so an
  off-bucket window is attributable to its ZMW."""
  example_layout = layout_from_shape(subreads.shape[1:], params.use_ccs_bq)
  (base_r, pw_r, ip_r, strand_r, ccs_r, ccs_bq_r, sn_r) = row_indices(
      example_layout.max_passes, params.use_ccs_bq
  )
  keep = params.max_passes

  def rows_of(r, cap=None):
    block = subreads[:, r[0]:r[1]]
    return block[:, :cap] if cap else block

  features = [
      rows_of(base_r, keep),
      np.clip(rows_of(pw_r, keep), 0, params.PW_MAX),
      np.clip(rows_of(ip_r, keep), 0, params.IP_MAX),
      rows_of(strand_r, keep),
      rows_of(ccs_r),
  ]
  if params.use_ccs_bq:
    features.append(rows_of(ccs_bq_r))
  features.append(np.clip(rows_of(sn_r), 0, params.SN_MAX))
  rows = np.concatenate(features, axis=1)
  width = rows.shape[2]
  check_window_bucket(
      width, window_buckets or config.resolve_window_buckets(params), names)
  expected = (len(subreads), params.total_rows, width, 1)
  assert rows.shape == expected, rows.shape
  return rows


def check_window_bucket(width: int, buckets: Sequence[int],
                        names: Sequence = ()) -> None:
  """Raises WindowBucketError for a window width outside `buckets`."""
  buckets = tuple(buckets)
  if width in buckets:
    return
  who = ''
  if len(names):
    shown = [str(n) for n in list(names)[:3]]
    who = f' (window id(s) {shown}{"..." if len(names) > 3 else ""})'
  raise WindowBucketError(
      f'window width {width} not in window buckets {buckets}{who}; '
      f'triage the window into a bucket (pad) or run with '
      f'--on_shard_error=skip to quarantine it (n_width_rejected)')


# ----------------------------------------------------------------------
# The compact pack: what the device receives for a batch of windows.
# `main_u8` [B, total_rows - SN_ROWS, L, 1] uint8 holds every non-SN row
# (clip-bounded integers; ccs_bq biased by +1 because its spaced values
# include -1 sentinels), `sn` [B, SN_ROWS] float32 the per-window SN
# constants. ModelRunner._assemble_rows is the device-side inverse.

SN_ROWS = 4

# Float32 scratch of one fill chunk: a few tens of windows, so that the
# clip and the cast read what the gather just wrote from the cache.
_FILL_SCRATCH_BYTES = 1 << 20


@dataclasses.dataclass(frozen=True)
class PackLayout:
  """Which rows of a window go where in a compact pack: format_rows_batch
  and the runner's uint8 cast as one row map."""

  height: int  # rows of a source window
  # (dst_lo, dst_hi, src_lo): runs of source rows cast to main_u8 rows.
  runs: Tuple[Tuple[int, int, int], ...]
  # (src_lo, src_hi, max): source rows clipped to [0, max] before the cast.
  clips: Tuple[Tuple[int, int, float], ...]
  bq_src: Optional[int]  # source row biased by +1 before the cast
  sn_src: int  # first of the SN_ROWS source rows, read at column 0
  sn_max: float

  @property
  def n_main(self) -> int:
    return self.runs[-1][1]


def pack_layout(height: int, params: ml_collections.ConfigDict,
                formatted: bool = False) -> PackLayout:
  """The row map for windows of `height` rows: raw examples (passes
  cropped to params.max_passes when the example carries more) or rows
  already through format_rows_batch (the identity map; clipping again
  changes nothing)."""
  keep = params.max_passes
  if formatted:
    have = keep
    if height != params.total_rows:
      # dclint: allow=typed-faults (caller shape contract: formatted
      # rows come from format_rows_batch under the same params)
      raise ValueError(
          f'formatted rows have {height} rows, the model takes '
          f'{params.total_rows}')
  else:
    have = layout_from_shape((height, 0, 1), params.use_ccs_bq).max_passes
    if have < keep:
      # dclint: allow=typed-faults (configuration contract: the
      # featurizer's max_passes is set from the model's params)
      raise ValueError(
          f'windows carry {have} passes, the model takes {keep}')
  src = row_indices(have, params.use_ccs_bq)
  dst = row_indices(keep, params.use_ccs_bq)
  runs: List[List[int]] = []
  for (s_lo, _), (d_lo, d_hi) in zip(src[:6], dst[:6]):
    if d_hi == d_lo:
      continue
    if runs and runs[-1][1] == d_lo and (
        runs[-1][2] + runs[-1][1] - runs[-1][0] == s_lo):
      runs[-1][1] = d_hi
    else:
      runs.append([d_lo, d_hi, s_lo])
  pw, ip, bq = src[1], src[2], src[5]
  return PackLayout(
      height=height,
      runs=tuple(tuple(r) for r in runs),
      clips=((pw[0], pw[0] + keep, params.PW_MAX),
             (ip[0], ip[0] + keep, params.IP_MAX)),
      bq_src=bq[0] if params.use_ccs_bq else None,
      sn_src=src[6][0],
      sn_max=params.SN_MAX)


def fill_pack(windows, layout: PackLayout, main_u8: np.ndarray,
              sn: np.ndarray, at: int = 0) -> None:
  """Writes `windows` (a list of [height, L, 1] tensors, strided views or
  not, or one [k, height, L, 1] array) into rows at..at+k of a compact
  pack, each window read once and written once: to the bit what
  np.stack -> format_rows_batch -> the runner's uint8 cast and SN gather
  give, with no float32 intermediate beyond the scratch of one chunk.
  Nothing of `windows` is referenced afterwards."""
  k = len(windows)
  if not k:
    return
  shape = windows[0].shape
  if (shape[0], layout.n_main) + shape[1:] != (
      layout.height,) + main_u8.shape[1:]:
    # dclint: allow=typed-faults (caller shape contract: the layout and
    # the pack buffer are made by the same packer for this width)
    raise ValueError(
        f'window shape {shape} does not fit a pack of {main_u8.shape[1:]} '
        f'from {layout.height} rows')
  chunk = max(1, _FILL_SCRATCH_BYTES // (4 * int(np.prod(shape))))
  scratch = np.empty((min(chunk, k),) + shape, np.float32)
  is_array = isinstance(windows, np.ndarray)
  for lo in range(0, k, chunk):
    hi = min(k, lo + chunk)
    s = scratch[:hi - lo]
    if is_array:
      s[...] = windows[lo:hi]
    else:
      for j in range(hi - lo):
        window = windows[lo + j]
        if window.shape != shape:  # a [1, L, 1] would broadcast silently
          # dclint: allow=typed-faults (caller shape contract, what
          # np.stack raised for a list of unequal windows)
          raise ValueError(
              f'window {lo + j} has shape {window.shape}, not {shape}')
        s[j] = window
    for c_lo, c_hi, c_max in layout.clips:
      block = s[:, c_lo:c_hi]
      np.maximum(block, 0, out=block)
      np.minimum(block, c_max, out=block)
    if layout.bq_src is not None:
      s[:, layout.bq_src] += 1.0
    dst = main_u8[at + lo:at + hi]
    for d_lo, d_hi, s_lo in layout.runs:
      np.copyto(dst[:, d_lo:d_hi], s[:, s_lo:s_lo + d_hi - d_lo],
                casting='unsafe')
    np.clip(s[:, layout.sn_src:layout.sn_src + SN_ROWS, 0, 0], 0,
            layout.sn_max, out=sn[at + lo:at + hi])


# The only proto fields the training batch path needs; everything else
# (notably the 100-varint ccs_base_quality_scores walk) is skipped.
_MINIMAL_FIELDS = frozenset({
    'subreads/encoded', 'subreads/shape', 'label/encoded', 'label/shape',
})


_MINIMAL_FIELDS_WITH_NAME = _MINIMAL_FIELDS | {'name'}


def parse_example_minimal(
    raw: bytes, inference: bool = False, with_name: bool = False
) -> Dict[str, np.ndarray]:
  """Training/eval fast path: decodes only the subreads tensor (raw,
  unformatted) and the label. Row formatting and label gap-shifting
  are deferred to the batch level (format_rows_batch /
  phred.left_shift), which is ~4x cheaper per example than the
  per-example path (measured on the bundled train shard).

  with_name additionally decodes the window id ('name'), so the NaN
  sentinel's dead letters can attribute a diverged batch to its
  windows (params.track_window_ids)."""
  fields = _MINIMAL_FIELDS_WITH_NAME if with_name else _MINIMAL_FIELDS
  ex = Example.parse(raw, fields=fields)
  out = {
      'subreads': np.frombuffer(
          ex['subreads/encoded'][0], dtype=constants.NP_DATA_TYPE
      ).reshape(ex['subreads/shape'])
  }
  if with_name and 'name' in ex:
    out['name'] = ex['name'][0]
  if not inference:
    out['label'] = np.frombuffer(
        ex['label/encoded'][0], dtype=constants.NP_DATA_TYPE
    ).reshape(ex['label/shape'])
  return out


def _shard_reader_main(paths, inference: bool, seed: int, out_queue,
                       chunk: int = 64, on_shard_error: str = 'fail',
                       with_name: bool = False,
                       worker_idx: int = -1) -> None:
  """StreamingDataset worker: reads its shard subset forever (gzip +
  framing + minimal parse all inside this process) and ships parsed
  chunks to the parent as ('chunk', (worker_idx, parses)) tuples — the
  index feeds the parent's per-worker decode counters. A shard that
  fails
  to decode under on_shard_error='skip' is reported as a
  ('shard_error', description) tuple and the worker moves on; under
  'fail' the worker exits nonzero and the parent's liveness check
  raises. Terminated by the parent; blocking put keeps it idle when
  the consumer falls behind."""
  from deepconsensus_tpu import faults as faults_lib
  from deepconsensus_tpu.io.tfrecord import TFRecordReader

  rng = np.random.default_rng(seed)
  pending: List[Dict[str, np.ndarray]] = []
  while True:
    # One shard at a time (native whole-shard decode: memory per worker
    # is bounded by its largest shard); the parent's reservoir buffer
    # plus this per-epoch permutation provide the mixing.
    produced = False
    for i in rng.permutation(len(paths)):
      path = paths[i]
      faults_lib.maybe_kill_shard_reader(path)
      try:
        for raw in TFRecordReader(path, native_decode=True):
          try:
            parsed = parse_example_minimal(raw, inference, with_name)
          except Exception as e:  # noqa: BLE001 - policy-gated
            if on_shard_error != OnShardError.SKIP:
              raise
            # Record-local payload corruption (see the serial path).
            out_queue.put(
                ('corrupt_record', f'{path}: {type(e).__name__}: {e}'))
            continue
          pending.append(parsed)
          produced = True
          if len(pending) >= chunk:
            out_queue.put(('chunk', (worker_idx, pending)))
            pending = []
      except Exception as e:  # noqa: BLE001 - policy-gated
        if on_shard_error != OnShardError.SKIP:
          raise
        # Records decoded before the fault are good parses; keep them.
        # The corrupt flag lets the parent count decode-layer
        # corruption (n_corrupt_records) separately from other shard
        # failures in the faults metrics split.
        out_queue.put(
            ('shard_error', (f'{path}: {type(e).__name__}: {e}',
                             isinstance(e, faults_lib.CorruptInputError)))
        )
    if not produced and on_shard_error == OnShardError.SKIP:
      # dclint: allow=typed-faults (aggregate stop after every
      # per-shard fault was already routed to the counters; tests pin
      # RuntimeError('every shard failed ...'))
      raise RuntimeError(
          f'every shard failed to decode under on_shard_error=skip: '
          f'{paths}'
      )


def _window_width(parsed: Dict[str, np.ndarray]) -> int:
  """Window width of one minimal parse ([H, L, 1] subreads)."""
  return int(parsed['subreads'].shape[1])


def _pad_minimal(
    parsed: Dict[str, np.ndarray], pad_to: int
) -> Dict[str, np.ndarray]:
  """Pads one minimal parse's window axis up to its bucket width.

  Zero is the canonical absent value for every row family (gap base,
  no kinetics, UNKNOWN strand) and for the label (gap, shifted away by
  left_shift / ignored by the alignment loss), so padding a width-w
  window to its bucket is semantically a no-op — the same pad the
  featurize stage applies when a smart window comes up short."""
  w = _window_width(parsed)
  if w == pad_to:
    return parsed
  out = dict(parsed)
  out['subreads'] = np.pad(
      parsed['subreads'], ((0, 0), (0, pad_to - w), (0, 0)))
  if 'label' in parsed:
    out['label'] = np.pad(parsed['label'], (0, pad_to - w))
  return out


def _batch_from_minimal(
    chosen: List[Dict[str, np.ndarray]],
    params: ml_collections.ConfigDict,
    inference: bool,
    pad_to: int = 0,
) -> Dict[str, np.ndarray]:
  """Stacks minimal parses into a formatted (rows, label) batch.
  pad_to > 0 pads every window up to that bucket width first (the
  bucketed-training triage path)."""
  if pad_to:
    chosen = [_pad_minimal(c, pad_to) for c in chosen]
  names = ([c['name'] for c in chosen] if 'name' in chosen[0] else [])
  batch = {
      'rows': format_rows_batch(
          np.stack([c['subreads'] for c in chosen]), params, names=names
      )
  }
  if names:
    batch['name'] = np.asarray(names, dtype=object)
  if not inference:
    label = np.stack([c['label'] for c in chosen])
    if params.remove_label_gaps:
      label = phred.left_shift(label)
    batch['label'] = label
  return batch


@dataclasses.dataclass
class DatasetIterator:
  """Shuffled, repeating, fixed-batch iterator over TFRecord shards.

  Eagerly loads the shard contents once (training corpora stream via
  multiple shards; the bundled test sets fit in memory), then yields
  (rows, label) batches. drop_remainder semantics match the reference
  (data_providers.py:361).
  """

  patterns: Union[str, Sequence[str]]
  params: ml_collections.ConfigDict
  batch_size: int
  inference: bool = False
  seed: int = 1
  shuffle: bool = True
  drop_remainder: bool = True
  limit: int = -1

  def __post_init__(self):
    with_name = bool(self.params.get('track_window_ids', False))
    buckets = config.resolve_window_buckets(self.params)
    grouped: Dict[int, List[Dict[str, np.ndarray]]] = {}
    for i, raw in enumerate(read_tfrecords(self.patterns)):
      if 0 <= self.limit <= i:
        break
      parsed = parse_example_minimal(raw, self.inference, with_name)
      width = _window_width(parsed)
      bucket = config.bucket_for(width, buckets)
      if bucket is None:
        who = parsed.get('name')
        raise WindowBucketError(
            f'window width {width} overflows window buckets {buckets}'
            + (f' (window id {who!r})' if who is not None else ''))
      grouped.setdefault(bucket, []).append(parsed)
    if not grouped:
      # dclint: allow=typed-faults (startup config error: the operator
      # pointed the loader at an empty glob)
      raise ValueError(f'no examples matched {self.patterns!r}')
    # One formatted array group per occupied bucket, every window
    # padded to its bucket width; single-occupied-bucket corpora keep
    # the legacy flat rows/labels/names layout (and its exact sampling
    # order) so fixed-shape training is bit-identical to before.
    # Per-example pre-pad widths ride along for the padding-waste
    # counters.
    self._groups = {}
    for b in sorted(grouped):
      group = _batch_from_minimal(grouped[b], self.params,
                                  self.inference, pad_to=b)
      group['width'] = np.asarray(
          [_window_width(p) for p in grouped[b]], dtype=np.int64)
      self._groups[b] = group
    grouped.clear()
    self.counters: collections.Counter = collections.Counter()
    if len(self._groups) == 1:
      batch = next(iter(self._groups.values()))
      self.rows = batch['rows']
      self.labels = batch.get('label')
      self.names = batch.get('name')
    else:
      self.rows = self.labels = self.names = None
    self._rng = np.random.default_rng(self.seed)

  def __len__(self) -> int:
    return sum(len(g['rows']) for g in self._groups.values())

  @property
  def window_buckets_present(self) -> tuple:
    return tuple(sorted(self._groups))

  @property
  def steps_per_epoch(self) -> int:
    if self.drop_remainder:
      return sum(
          len(g['rows']) // self.batch_size
          for g in self._groups.values())
    return sum(
        -(-len(g['rows']) // self.batch_size)
        for g in self._groups.values())

  def _count_emit(self, bucket: int, widths: np.ndarray) -> None:
    self.counters[f'n_train_batches_by_bucket_{bucket}'] += 1
    self.counters['n_train_padded_positions'] += int(
        (bucket - widths).sum())
    self.counters['n_train_window_positions'] += int(
        bucket * len(widths))

  def epoch(self) -> Iterator[Dict[str, np.ndarray]]:
    if self.rows is not None:
      # Legacy single-shape path, untouched ordering.
      bucket, g = next(iter(self._groups.items()))
      order = np.arange(len(self.rows))
      if self.shuffle:
        self._rng.shuffle(order)
      n = len(order)
      stop = (
          n - n % self.batch_size if self.drop_remainder else n
      )
      for start in range(0, stop, self.batch_size):
        idx = order[start : start + self.batch_size]
        batch = {'rows': self.rows[idx]}
        if self.names is not None:
          batch['name'] = self.names[idx]
        if self.labels is not None:
          batch['label'] = self.labels[idx]
        self._count_emit(bucket, g['width'][idx])
        yield batch
      return
    # Bucketed epoch: shuffle within each bucket, then interleave the
    # per-bucket batch slots deterministically (seeded rng when
    # shuffling, narrow-to-wide otherwise) so resume/fast-forward
    # replays the identical batch sequence.
    slots: List[tuple] = []
    orders: Dict[int, np.ndarray] = {}
    for b in sorted(self._groups):
      g = self._groups[b]
      order = np.arange(len(g['rows']))
      if self.shuffle:
        self._rng.shuffle(order)
      orders[b] = order
      n = len(order)
      stop = n - n % self.batch_size if self.drop_remainder else n
      slots.extend((b, start) for start in range(0, stop, self.batch_size))
    if self.shuffle:
      self._rng.shuffle(slots)
    for b, start in slots:
      g = self._groups[b]
      idx = orders[b][start : start + self.batch_size]
      batch = {'rows': g['rows'][idx]}
      if g.get('name') is not None:
        batch['name'] = g['name'][idx]
      if g.get('label') is not None:
        batch['label'] = g['label'][idx]
      self._count_emit(b, g['width'][idx])
      yield batch

  def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
    while True:
      yield from self.epoch()


@dataclasses.dataclass
class StreamingDataset:
  """Shard-interleaved streaming loader with a shuffle buffer.

  For corpora too large for memory (the reference trains on ~100M
  examples): shards are read round-robin on a background thread, parsed
  examples fill a reservoir shuffle buffer, and fixed-size batches are
  drawn indefinitely (reference semantics: data_providers.py:395-425).
  """

  patterns: Union[str, Sequence[str]]
  params: ml_collections.ConfigDict
  batch_size: int
  buffer_size: int = 100_000
  seed: int = 1
  inference: bool = False
  # >0: decode raw records in worker processes (chunked imap). The
  # per-core decode ceiling is ~10k ex/s (measured, minimal parse);
  # dp>=8 training (~12k ex/s/host) needs either workers on a
  # many-core host or per-host input sharding (docs/training.md).
  workers: int = 0
  # 'fail' (default): a shard that fails to decode aborts training.
  # 'skip': log + count it and move on to the next shard — a single
  # corrupt shard out of thousands must not kill a multi-day run.
  on_shard_error: str = OnShardError.FAIL
  # Per-host shard assignment for pod-scale streaming: host `host_rank`
  # of `host_count` reads every host_count-th shard (round-robin over
  # the sorted glob). Default (0 of 1) reads everything — the
  # identical-batches mode the elastic identity drills rely on. An
  # elastic rebuild retargets the assignment via reassign_hosts().
  host_rank: int = 0
  host_count: int = 1

  def __post_init__(self):
    from deepconsensus_tpu.io.tfrecord import glob_paths

    if self.on_shard_error not in OnShardError.CHOICES:
      # dclint: allow=typed-faults (flag validation at startup)
      raise ValueError(
          f'on_shard_error must be one of {OnShardError.CHOICES}, '
          f'got {self.on_shard_error!r}'
      )
    if not 0 <= self.host_rank < max(self.host_count, 1):
      # dclint: allow=typed-faults (flag validation at startup)
      raise ValueError(
          f'host_rank={self.host_rank} out of range for '
          f'host_count={self.host_count}'
      )
    self._all_paths = glob_paths(self.patterns)
    if not self._all_paths:
      # dclint: allow=typed-faults (startup config error: the operator
      # pointed the loader at an empty glob)
      raise ValueError(f'no shards matched {self.patterns!r}')
    # dclint: lock-free (reassign_hosts replaces the whole list in one
    # reference assignment; the reader thread sees the old or the new
    # list, never a mix)
    self._paths = self._assigned_paths(self.host_rank, self.host_count)
    self._rng = np.random.default_rng(self.seed)
    self._with_name = bool(self.params.get('track_window_ids', False))
    self._buckets = config.resolve_window_buckets(self.params)
    # Fault counters (n_shard_errors, ...) survive the iterator so the
    # training driver can report them at end of run.
    # dclint: lock-free (the reader thread and the consuming train loop
    # increment DISJOINT key sets — producer: shard/record decode
    # faults; consumer: per-bucket emission counters — and each
    # Counter bump is a single GIL-atomic dict op per key)
    self.counters: collections.Counter = collections.Counter()

  def _assigned_paths(self, rank: int, count: int) -> list:
    """Round-robin shard assignment for one host. A host whose slot is
    empty (more hosts than shards) falls back to the full set — reading
    duplicate data beats deadlocking an admitted member with no
    input."""
    assigned = self._all_paths[rank::max(count, 1)]
    if not assigned:
      log.warning(
          'host %d/%d has no shards under round-robin assignment of '
          '%d path(s); falling back to the full shard set',
          rank, count, len(self._all_paths))
      return list(self._all_paths)
    return assigned

  def reassign_hosts(self, rank: int, count: int) -> None:
    """Retargets the per-host shard assignment after an elastic
    membership change (rebuild shrinks host_count, re-admission grows
    it back). Takes effect at the next epoch's shard permutation — the
    shard currently being read finishes under the old assignment. The
    swap is a single reference assignment, so the reader thread sees
    either the old or the new list, never a mix."""
    # dclint: lock-free (host_rank/host_count are written only here,
    # on the consuming thread; the reader thread takes the companion
    # self._paths swap below — these two scalars only feed logging and
    # this no-op check)
    if (rank, count) == (self.host_rank, self.host_count):
      return
    self.host_rank, self.host_count = int(rank), int(count)
    self._paths = self._assigned_paths(self.host_rank, self.host_count)
    self.counters['n_shard_reassignments'] += 1
    log.warning('streaming shards reassigned: host %d/%d now owns %d '
                'of %d shard(s)', rank, count, len(self._paths),
                len(self._all_paths))

  def _raw_stream(self) -> Iterator[bytes]:
    """Shards in a fresh random order each epoch, consumed ONE AT A
    TIME with whole-shard native decode (memory stays bounded by the
    largest single shard; an interleave across open native readers
    would hold every shard's records at once). Cross-shard mixing is
    the reference's shuffle-files + shuffle-buffer recipe: per-epoch
    shard permutation here, reservoir buffer in __iter__
    (data_providers.py:395-425)."""
    from deepconsensus_tpu.io.tfrecord import TFRecordReader

    while True:
      produced = False
      # Snapshot the assignment for this epoch: reassign_hosts swaps
      # self._paths from the training thread, and indexing a shrunk
      # list with a stale permutation would walk off the end.
      paths = self._paths
      for i in self._rng.permutation(len(paths)):
        path = paths[i]
        try:
          for raw in TFRecordReader(path, native_decode=True):
            produced = True
            yield raw
        except Exception as e:  # noqa: BLE001 - policy-gated below
          if self.on_shard_error != OnShardError.SKIP:
            raise
          self.counters['n_shard_errors'] += 1
          if isinstance(e, CorruptInputError):
            self.counters['n_corrupt_records'] += 1
          log.warning('on_shard_error=skip: skipping shard %s (%s: %s)',
                      path, type(e).__name__, e)
      if not produced:
        # All shards bad: without this the skip policy would spin
        # forever yielding nothing while the consumer waits.
        # dclint: allow=typed-faults (aggregate stop after every
        # per-shard fault was already routed to the counters; tests
        # pin RuntimeError('every shard failed ...'))
        raise RuntimeError(
            f'every shard failed to decode under on_shard_error=skip: '
            f'{self._paths}'
        )

  def _minimal_stream(self, stop) -> Iterator[Dict[str, np.ndarray]]:
    """Raw records -> minimal parses, optionally via worker processes.

    workers>0 assigns each worker a round-robin subset of the SHARDS,
    so gzip decompression + record framing (the measured single-core
    bottleneck, ~10k rec/s) parallelizes along with the proto parse;
    the parent only drains parsed chunks. Cross-worker mixing comes
    from the caller's reservoir shuffle buffer.
    """
    if self.workers <= 0:
      for raw in self._raw_stream():
        if stop.is_set():
          return
        try:
          parsed = parse_example_minimal(raw, self.inference,
                                         self._with_name)
        except Exception as e:  # noqa: BLE001 - policy-gated
          if self.on_shard_error != OnShardError.SKIP:
            raise
          # Frame-intact but undecodable payload: the streaming loader
          # skips payload CRCs for speed, so bit rot inside a record
          # surfaces here at proto-parse time. Record-local — skip just
          # this record, not the shard.
          self.counters['n_corrupt_records'] += 1
          log.warning('on_shard_error=skip: undecodable record '
                      '(%s: %s)', type(e).__name__, e)
          continue
        yield parsed
      return
    import multiprocessing
    import queue as queue_lib

    n_workers = min(self.workers, len(self._paths))
    # spawn, not fork: the parent is multi-threaded (producer threads)
    # and typically has a TPU backend initialized by the time training
    # iterates the dataset — forking that process can deadlock the
    # child on an inherited lock. Workers only need numpy + the
    # TFRecord/proto codecs, so a fresh interpreter is cheap.
    ctx = multiprocessing.get_context('spawn')
    out_queue = ctx.Queue(maxsize=64)  # of <=64-parse chunks (~2 MB each)
    procs = []
    worker_paths = [self._paths[w::n_workers] for w in range(n_workers)]
    for w in range(n_workers):
      proc = ctx.Process(
          target=_shard_reader_main,
          args=(worker_paths[w], self.inference, self.seed + w, out_queue,
                64, self.on_shard_error, self._with_name, w),
          daemon=True,
      )
      proc.start()
      procs.append(proc)
    def check_liveness():
      # A worker that died cleanly (exit 0) simply exhausted its
      # repeat-forever stream early — impossible in practice, so treat
      # ANY dead worker with a nonzero code as fatal: letting training
      # continue on the survivors' shard subsets silently skews the
      # data distribution. Checked on EVERY drain iteration, not just
      # when the queue runs dry — survivors can keep the queue fed
      # forever, which is exactly the silent-skew case.
      crashed = [
          (w, p.exitcode)
          for w, p in enumerate(procs)
          if not p.is_alive() and p.exitcode not in (0, None)
      ]
      if crashed:
        # Name the dead workers' shard subsets: 'worker 1 crashed' is
        # undebuggable, 'worker 1 owned these 3 files' points straight
        # at the corrupt shard.
        detail = '; '.join(
            f'worker {w} (exit code {code}) owned shards '
            f'{worker_paths[w]}'
            for w, code in crashed
        )
        # dclint: allow=typed-faults (worker-process death is an infra
        # failure, not an input fault; tests pin the RuntimeError
        # message naming the dead worker's owned shards)
        raise RuntimeError(
            f'StreamingDataset worker(s) crashed ({len(crashed)} of '
            f'{n_workers}): {detail}; check shard paths/integrity '
            f'(corrupt shard or OOM)'
        )
      if not any(p.is_alive() for p in procs):
        codes = [p.exitcode for p in procs]
        # dclint: allow=typed-faults (worker-process death is an infra
        # failure, not an input fault)
        raise RuntimeError(
            f'all {n_workers} StreamingDataset workers exited '
            f'(exit codes {codes}); check shard paths/integrity'
        )

    try:
      while not stop.is_set():
        check_liveness()
        try:
          kind, payload = out_queue.get(timeout=5)
        except queue_lib.Empty:
          continue
        if kind == 'shard_error':
          message, corrupt = payload
          self.counters['n_shard_errors'] += 1
          if corrupt:
            self.counters['n_corrupt_records'] += 1
          log.warning('on_shard_error=skip: worker skipped shard (%s)',
                      message)
          continue
        if kind == 'corrupt_record':
          self.counters['n_corrupt_records'] += 1
          log.warning('on_shard_error=skip: worker skipped record (%s)',
                      payload)
          continue
        w_idx, parses = payload
        # Per-worker decode counters: with N workers on an M-core host
        # these prove (or disprove) that the decode load actually
        # splits ~evenly — the evidence behind any "N workers -> ~N x
        # throughput" extrapolation (docs/training.md).
        self.counters[f'n_parsed_worker_{w_idx}'] += len(parses)
        yield from parses
    finally:
      for proc in procs:
        proc.terminate()
      for proc in procs:
        proc.join(timeout=5)

  def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
    import queue as queue_lib
    import threading

    parsed_queue: 'queue_lib.Queue' = queue_lib.Queue(maxsize=4096)
    stop = threading.Event()

    def producer():
      # Decode errors (bad shard, dead workers) must surface at the
      # consumer, not die with this thread: forward them as items.
      try:
        for parsed in self._minimal_stream(stop):
          while not stop.is_set():
            try:
              parsed_queue.put(('item', parsed), timeout=0.5)
              break
            except queue_lib.Full:
              continue
          if stop.is_set():
            return
      except BaseException as e:  # noqa: BLE001 - re-raised at consumer
        while not stop.is_set():
          try:
            parsed_queue.put(('error', e), timeout=0.5)
            return
          except queue_lib.Full:
            continue

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()

    def next_parsed():
      kind, payload = parsed_queue.get()
      if kind == 'error':
        raise payload
      return payload

    try:
      if len(self._buckets) == 1:
        # Legacy fixed-shape reservoir. The rng draw sequence is
        # bit-identical to the pre-bucketing loader for on-bucket
        # corpora (triage only intervenes on narrow windows, which pad,
        # and overflow widths, which quarantine under skip).
        bucket = self._buckets[0]
        buffer: List[Dict[str, np.ndarray]] = []
        fill_target = max(self.buffer_size, self.batch_size * 2)
        while True:
          while len(buffer) < fill_target:
            triaged = self._triage(next_parsed())
            if triaged is not None:
              buffer.append(triaged[1])
          idx = self._rng.choice(len(buffer), self.batch_size,
                                 replace=False)
          idx_set = set(idx.tolist())
          chosen = [buffer[i] for i in idx]
          buffer = [b for i, b in enumerate(buffer) if i not in idx_set]
          self._count_emit(bucket, chosen)
          yield _batch_from_minimal(chosen, self.params, self.inference,
                                    pad_to=bucket)
      else:
        yield from self._bucketed_batches(next_parsed)
    finally:
      # Stop the producer when the consumer abandons the iterator
      # (GeneratorExit) so retries don't accumulate blocked threads.
      # Then JOIN it: its own finally terminates+joins the worker
      # processes, so returning before it finishes would leave workers
      # decoding (and competing for cores) into whatever runs next.
      # Bounded so a wedged worker can't hang the consumer; daemons
      # die with the interpreter in that case.
      stop.set()
      thread.join(timeout=15)

  def _triage(self, parsed: Dict[str, np.ndarray]):
    """(bucket, parse) for the smallest bucket that fits the window, or
    None after quarantining an overflow width (on_shard_error=skip +
    n_width_rejected; under 'fail' the typed fault names the window)."""
    width = _window_width(parsed)
    bucket = config.bucket_for(width, self._buckets)
    if bucket is not None:
      return bucket, parsed
    who = parsed.get('name')
    if self.on_shard_error != OnShardError.SKIP:
      raise WindowBucketError(
          f'window width {width} overflows window buckets '
          f'{self._buckets}'
          + (f' (window id {who!r})' if who is not None else '')
          + '; widen window_buckets or run with --on_shard_error=skip '
          'to quarantine it')
    self.counters['n_width_rejected'] += 1
    log.warning(
        'on_shard_error=skip: window width %d overflows buckets %s%s; '
        'rejected (n_width_rejected)', width, self._buckets,
        f' (window id {who!r})' if who is not None else '')
    return None

  def _count_emit(self, bucket: int, chosen: List[Dict]) -> None:
    """Per-bucket emission counters. The padded/total position pair is
    what the trainer turns into train_padding_fraction."""
    self.counters[f'n_train_batches_by_bucket_{bucket}'] += 1
    pad = sum(bucket - _window_width(c) for c in chosen)
    self.counters['n_train_padded_positions'] += pad
    self.counters['n_train_window_positions'] += bucket * len(chosen)

  def _bucketed_batches(self, next_parsed) -> Iterator[Dict[str, np.ndarray]]:
    """Multi-bucket consumer: per-bucket accumulation under a shared
    batch clock, mirroring the PR-12 inference engine's per-bucket
    packers.

    Every parse is triaged into the smallest fitting bucket's buffer.
    A bucket emits when it holds a full batch (largest buffer first —
    the backlog drain rule); a bucket whose oldest pending window has
    waited `bucket_starvation_batches` clock ticks without filling is
    flushed by PROMOTING windows from narrower buffers (any window fits
    a wider bucket at the cost of more padding), so rare wide windows
    never go stale and every emitted batch still carries batch_size
    real windows — a fixed per-bucket geometry, never a partial batch
    that would retrace the jitted step. The whole schedule is a
    deterministic function of the parse stream and the seeded rng, so
    skip-based resume/fast-forward replays the identical batch
    sequence."""
    batch = self.batch_size
    buckets = self._buckets
    starvation = int(
        self.params.get('bucket_starvation_batches', 8) or 8)
    fill_target = max(self.buffer_size, batch * 2 * len(buckets))
    buffers: Dict[int, List[Dict[str, np.ndarray]]] = {
        b: [] for b in buckets}
    # Clock tick at which each bucket's current backlog started
    # waiting; -1 = empty.
    waiting = {b: -1 for b in buckets}
    clock = 0

    def ready():
      return [b for b in buckets if len(buffers[b]) >= batch]

    def starved():
      out = []
      for b in buckets:
        if waiting[b] < 0 or clock - waiting[b] < starvation:
          continue
        # Flushable only if promotion from narrower buckets can top the
        # batch up to full size.
        if sum(len(buffers[x]) for x in buckets if x <= b) >= batch:
          out.append(b)
      return out

    def draw(bucket, take):
      pool = buffers[bucket]
      idx = self._rng.choice(len(pool), take, replace=False)
      idx_set = set(idx.tolist())
      chosen = [pool[i] for i in idx]
      buffers[bucket] = [p for i, p in enumerate(pool)
                         if i not in idx_set]
      return chosen

    while True:
      while True:
        total = sum(len(v) for v in buffers.values())
        if (ready() or starved()) and total >= fill_target:
          break
        triaged = self._triage(next_parsed())
        if triaged is None:
          continue
        b, parsed = triaged
        buffers[b].append(parsed)
        if waiting[b] < 0:
          waiting[b] = clock
      star = starved()
      if star:
        # Widest starving bucket first: its windows cannot be promoted
        # anywhere else, so it is the one at risk of going stale. (A
        # starved bucket that meanwhile filled up just emits a normal
        # full draw — the promotion loop below is a no-op.)
        bucket = max(star)
        chosen = draw(bucket, min(len(buffers[bucket]), batch))
        if len(chosen) < batch:
          self.counters['n_train_starvation_flushes'] += 1
          for nb in sorted((x for x in buckets if x < bucket),
                           reverse=True):
            need = batch - len(chosen)
            if not need:
              break
            take = min(need, len(buffers[nb]))
            if take:
              chosen.extend(draw(nb, take))
              self.counters['n_train_promoted_windows'] += take
      else:
        # Largest backlog first (ties to the wider bucket) keeps every
        # buffer bounded instead of letting the dominant width starve
        # the rest of reservoir space.
        bucket = max(ready(), key=lambda b: (len(buffers[b]), b))
        chosen = draw(bucket, batch)
      clock += 1
      for b in buckets:
        if not buffers[b]:
          waiting[b] = -1
      waiting[bucket] = clock if buffers[bucket] else -1
      self._count_emit(bucket, chosen)
      yield _batch_from_minimal(chosen, self.params, self.inference,
                                pad_to=bucket)


def prefetch_iterator(iterator, depth: int = 2):
  """Runs `iterator` in a background thread, keeping up to `depth`
  batches ready, so host-side decode/shuffle/stacking overlaps device
  compute (the reference gets this from tf.data prefetch;
  data_providers.py uses AUTOTUNE). Exceptions re-raise at the
  consumer; closing the generator stops the producer.
  """
  import queue
  import threading

  q: 'queue.Queue' = queue.Queue(maxsize=depth)
  stop = threading.Event()
  _END = object()

  def producer():
    try:
      for item in iterator:
        while not stop.is_set():
          try:
            q.put(('item', item), timeout=0.2)
            break
          except queue.Full:
            continue
        if stop.is_set():
          return
      while not stop.is_set():
        try:
          q.put(('end', _END), timeout=0.2)
          return
        except queue.Full:
          continue
    except BaseException as e:  # noqa: BLE001 - surfaced to consumer
      # Same retry-until-stopped discipline as item puts: dropping the
      # sentinel on a momentarily-full queue would leave the consumer
      # blocked on q.get() forever instead of seeing the error.
      while not stop.is_set():
        try:
          q.put(('error', e), timeout=0.2)
          return
        except queue.Full:
          continue

  thread = threading.Thread(target=producer, daemon=True)
  thread.start()
  try:
    while True:
      kind, payload = q.get()
      if kind == 'end':
        return
      if kind == 'error':
        raise payload
      yield payload
  finally:
    stop.set()
    # Drain so a blocked producer can observe stop and exit.
    while not q.empty():
      try:
        q.get_nowait()
      except queue.Empty:
        break
    thread.join(timeout=10)


# Complement map over SEQ_VOCAB ' ATCG': gap fixed, A<->T, C<->G.
_COMPLEMENT_LUT = np.array([0, 2, 1, 4, 3], dtype=constants.NP_DATA_TYPE)
# Strand values (constants.Strand): UNKNOWN fixed, FORWARD<->REVERSE.
_STRAND_FLIP_LUT = np.array([0, 2, 1], dtype=constants.NP_DATA_TYPE)
# SN rows are per-channel [A, C, G, T]; under reverse-complement each
# base is read as its partner, so channels swap A<->T, C<->G.
_SN_RC_ORDER = np.array([3, 2, 1, 0])


def augment_batch(
    batch: Dict[str, np.ndarray],
    params: ml_collections.ConfigDict,
    rng: np.random.Generator,
) -> Dict[str, np.ndarray]:
  """Training-time window augmentation over a formatted (rows, label)
  batch. No reference counterpart: the reference trains on ~100M unique
  windows (train_tpu_model.md:234-239) while small corpora re-show the
  same ones, so augmentation substitutes for data diversity. Four
  independent per-example transforms, each gated by its
  params.augment_*_prob:

    * subread permutation — shuffle the order of present subreads
      (consensus is order-invariant; the model should be too);
    * subread downsampling — keep a random >= half subset, compacted
      to the front (simulates lower-pass ZMWs);
    * reverse-complement — flip the occupied extent of every row along
      the window, complement bases/ccs/label, swap strand and SN
      channels (the same molecule read in the other orientation);
    * PW/IP jitter — +/-1 on a quarter of nonzero kinetics entries,
      clipped back to [1, PW_MAX/IP_MAX].

  Returns a new batch; never mutates the input. Presence of a subread
  is read off its strand row (absent rows are all-zero = UNKNOWN).
  """
  rows = batch['rows'].copy()  # [B, H, L, 1]
  label = batch['label'].copy() if batch.get('label') is not None else None
  b, _, length, _ = rows.shape
  p = params.max_passes
  blocks = rows[:, : 4 * p, :, 0].reshape(b, 4, p, length)  # views rows
  bases, pw, ip, strand = (blocks[:, i] for i in range(4))
  present = strand.max(axis=2) > 0  # [B, P]
  n_present = present.sum(axis=1)  # [B]

  # --- subread permutation + downsampling (one combined gather) ---
  perm_on = rng.random(b) < params.get('augment_perm_prob', 0.0)
  drop_on = rng.random(b) < params.get('augment_drop_prob', 0.0)
  keep = np.where(
      drop_on & (n_present > 1),
      rng.integers(np.maximum(1, -(-n_present // 2)),
                   np.maximum(n_present, 1) + 1),
      n_present,
  )
  # Which subreads survive: a RANDOM size-`keep` subset of the present
  # ones (selection must be random even when the independent
  # permutation transform does not fire, or every drop would remove
  # the trailing subreads and bias the augmented distribution).
  sel_keys = np.where(present, rng.random((b, p)), 2.0)
  sel_rank = np.argsort(np.argsort(sel_keys, axis=1), axis=1)
  kept = (sel_rank < keep[:, None]) & present
  # Output order: random when permuting, original subread order
  # otherwise; non-kept rows sort to the end.
  order_keys = np.where(
      perm_on[:, None], rng.random((b, p)), np.arange(p)[None, :] / p
  )
  order_keys = np.where(kept, order_keys, 2.0)
  order = np.argsort(order_keys, axis=1, kind='stable')  # [B, P]
  fired = perm_on | (keep < n_present)  # [B]
  if fired.any():
    sel = np.take_along_axis(
        blocks, order[:, None, :, None], axis=2
    )  # [B, 4, P, L]
    # Zero out dropped tail (and previously-absent rows stay zero).
    live = np.arange(p)[None, :] < keep[:, None]  # [B, P]
    sel = np.where(live[:, None, :, None], sel, 0.0)
    # Gate the write per-example: for an example where neither
    # transform fired, the gather is only the identity if its present
    # subreads are front-compacted — an example with an interior
    # all-zero row would be silently compacted by the batch-wide write.
    sel = np.where(fired[:, None, None, None], sel, blocks)
    rows[:, : 4 * p, :, 0] = sel.reshape(b, 4 * p, length)
    blocks = rows[:, : 4 * p, :, 0].reshape(b, 4, p, length)
    bases, pw, ip, strand = (blocks[:, i] for i in range(4))

  # --- reverse-complement ---
  rc_on = rng.random(b) < params.get('augment_rc_prob', 0.0)
  if rc_on.any():
    ccs_row = 4 * p
    sn_start = 4 * p + 1 + (1 if params.use_ccs_bq else 0)
    # Occupied extent: last column with any base content (subreads or
    # ccs); reversal happens inside it so tail padding stays the tail.
    content = (bases.max(axis=1) > 0) | (rows[:, ccs_row, :, 0] > 0)
    width = length - np.argmax(content[:, ::-1], axis=1)  # [B]
    width = np.where(content.any(axis=1), width, 0)
    rev_idx = np.arange(length)[None, :]  # [B, L] source index map
    rev_idx = np.where(
        rev_idx < width[:, None], width[:, None] - 1 - rev_idx, rev_idx
    )
    flip = rc_on[:, None]

    def rev(block):  # [B, R, L] reverse occupied extent where rc_on
      rev_b = np.take_along_axis(block, rev_idx[:, None, :], axis=2)
      return np.where(flip[:, :, None] if block.ndim == 3 else flip,
                      rev_b, block)

    comp = _COMPLEMENT_LUT
    new_bases = rev(comp[bases.astype(np.int64)])
    rows[:, :p, :, 0] = np.where(flip[:, :, None], new_bases, bases)
    rows[:, p : 2 * p, :, 0] = rev(pw)
    rows[:, 2 * p : 3 * p, :, 0] = rev(ip)
    flipped_strand = _STRAND_FLIP_LUT[strand.astype(np.int64)]
    rows[:, 3 * p : 4 * p, :, 0] = np.where(
        flip[:, :, None], flipped_strand, strand
    )
    ccs = rows[:, ccs_row : ccs_row + 1, :, 0]
    # Fall-through must be the ORIGINAL row: rev()'s internal where
    # would otherwise hand non-flipped examples the complemented (but
    # unreversed) ccs.
    ccs_rc = np.take_along_axis(
        comp[ccs.astype(np.int64)], rev_idx[:, None, :], axis=2
    )
    rows[:, ccs_row : ccs_row + 1, :, 0] = np.where(
        flip[:, :, None], ccs_rc, ccs
    )
    if params.use_ccs_bq:
      rows[:, ccs_row + 1 : ccs_row + 2, :, 0] = rev(
          rows[:, ccs_row + 1 : ccs_row + 2, :, 0]
      )
    sn = rows[:, sn_start : sn_start + 4, :, 0]
    rows[:, sn_start : sn_start + 4, :, 0] = np.where(
        flip[:, :, None], sn[:, _SN_RC_ORDER], sn
    )
    if label is not None and label.size:
      # The loss treats the label as a gap-collapsible SEQUENCE
      # (left_shift_sequence), so a full reverse + complement is exact;
      # leading gaps are shifted away by the loss.
      lab_rc = _COMPLEMENT_LUT[label.astype(np.int64)][:, ::-1]
      label = np.where(rc_on[:, None], lab_rc, label).astype(label.dtype)

  # --- PW/IP jitter ---
  jit_on = rng.random(b) < params.get('augment_jitter_prob', 0.0)
  if jit_on.any():
    blocks = rows[:, : 4 * p, :, 0].reshape(b, 4, p, length)
    for bi, cap in ((1, params.PW_MAX), (2, params.IP_MAX)):
      block = blocks[:, bi]
      # Draw from {-1, +1}: integers(-1, 2) would include 0 and silently
      # cut the effective jitter rate to ~17% of entries.
      delta = (rng.integers(0, 2, size=block.shape) * 2 - 1).astype(
          rows.dtype
      )
      mask = (
          jit_on[:, None, None]
          & (block > 0)
          & (rng.random(block.shape) < 0.25)
      )
      blocks[:, bi] = np.where(
          mask, np.clip(block + delta, 1, cap), block
      )
    rows[:, : 4 * p, :, 0] = blocks.reshape(b, 4 * p, length)

  out = dict(batch)
  out['rows'] = rows
  if label is not None:
    out['label'] = label
  return out
