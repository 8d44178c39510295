"""Batched inference: BAM -> windows -> jitted model -> stitched FASTQ.

TPU-native re-design of the reference's quick_inference driver
(reference: deepconsensus/inference/quick_inference.py:68-984):

* Featurization runs the vectorized preprocess core (no per-base Python
  loops), so the host keeps up with the accelerator without a process
  pool for moderate workloads; a pool can still fan it out.
* The model step is one jitted function over fixed-shape batches
  (padded final batch) returning argmax bases and max probabilities,
  so only two small arrays cross the device boundary per batch.
* Window skip triage (CCS quality above threshold, overflow windows)
  happens on host exactly like the reference, including CCS-quality
  calibration of skipped windows.
* Per-stage wall-time is recorded and dumped to <output>.runtime.csv.
"""
from __future__ import annotations

import sys
import time

# The `import_runner` span (recorded at the foot of this module): what it
# costs a process to import the runner and all it pulls in, jax included
# unless the caller had it already.
_T_IMPORT = time.time()
_JAX_PRELOADED = 'jax' in sys.modules

import collections
import csv
import dataclasses
import itertools
import json
import logging
import atexit
import os
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The forward donates its input buffers (see _jit_forward); backends
# that can't reuse a given donated buffer (host CPU, notably) warn per
# dispatch, which would flood batch runs.
warnings.filterwarnings(
    'ignore', message='Some donated buffers were not usable')

from deepconsensus_tpu import obs as obs_lib
from deepconsensus_tpu.obs import compiles as compiles_lib
from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.inference import engine as engine_lib
from deepconsensus_tpu.inference import faults
from deepconsensus_tpu.io import bam as bam_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import output_plane
from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.postprocess import stitch
from deepconsensus_tpu.preprocess import (
    FeatureLayout,
    create_proc_feeder,
    reads_to_pileup,
)
from deepconsensus_tpu.preprocess.pileup import row_indices

log = logging.getLogger(__name__)


@dataclasses.dataclass
class InferenceOptions:
  """Knobs shared across inference stages
  (reference: quick_inference.py:243-275)."""

  max_length: int = config_lib.DEFAULT_MAX_LENGTH
  max_passes: int = 20
  min_quality: int = 20
  min_length: int = 0
  batch_size: int = 1024
  batch_zmws: int = 100
  use_ccs_bq: bool = False
  skip_windows_above: int = 45
  ins_trim: int = 5
  use_ccs_smart_windows: bool = False
  # Window length buckets (config.resolve_window_buckets): None = follow
  # params.window_buckets / single-shape at max_length. Each smart
  # window pads to the smallest bucket that fits; the engine packs and
  # dispatches each bucket separately (one compiled shape per bucket).
  window_buckets: Optional[Tuple[int, ...]] = None
  # Bucket starvation flush: when one bucket's partial tail has sat
  # buffered while the other buckets cut this many full packs, the
  # starved tail is cut as a padded partial pack so a rare bucket's
  # windows cannot be held back indefinitely behind a busy one
  # (0 disables; tails always flush at end-of-input regardless).
  bucket_flush_packs: int = 8
  # Single-pack-stream ragged dispatch: mixed-width windows pack
  # back-to-back into fixed [n_slots, R, slot_len] slots (slot_len =
  # the largest bucket) with a per-slot int32 lengths vector, and ONE
  # compiled ragged forward serves every width (n_forward_shapes == 1;
  # no per-bucket packer fleet, no starvation flush — partial packs
  # exist only at end-of-input). Requires the buckets to form a
  # divisibility chain (each bucket divides the next); the bucketed
  # path remains the byte-identical fallback when False.
  use_ragged_kernel: bool = False
  max_base_quality: int = 93
  limit: int = 0
  # (i, n): keep only ZMWs with zm % n == i — single-flag fleet scaling
  # over one shared BAM (the reference's shard-the-BAM pattern without
  # the external splitting step).
  shard: Optional[Tuple[int, int]] = None
  # >0: featurization worker pool. Caveat: shipping featurized windows
  # between processes is IPC-bound (~6 MB/ZMW), so on fast hosts the
  # serial path may win (its rate against the pool is not measured);
  # scale across chips by sharding input BAMs into separate runs like
  # the reference's 500-shard pattern.
  cpus: int = 0
  # Max batches in flight on the device before the oldest is drained.
  # A deeper pipeline overlaps the transfer latency of batches
  # i+1..i+k with the compute of batch i. Device-side cost per in-flight batch is one
  # uint8 input buffer (~21 MB at b1024) + tiny outputs.
  dispatch_depth: int = 8
  # Bounded hand-off queue between the model stage and the stitch/emit
  # worker thread, in featurize batches. Deeper absorbs longer emit
  # stalls (slow disk) before the device pipeline feels them; each
  # queued batch holds its windows' output arrays (~2*L bytes/window).
  emit_queue_depth: int = 4
  # Fault tolerance (inference/faults.py). on_zmw_error governs the
  # per-ZMW quarantine: 'fail' keeps historical fail-fast semantics,
  # 'skip' drops the ZMW (dead-lettered), 'ccs-fallback' emits the
  # draft CCS read with its original base qualities instead.
  on_zmw_error: str = 'fail'  # fail | skip | ccs-fallback
  # Per-record allocation cap for the hardened BAM decoders
  # (io/bam.py): a record claiming more than this is treated as
  # corrupt — quarantined under on_zmw_error=skip — never allocated.
  max_record_bytes: int = 64 << 20
  # >0: per-batch watchdog timeout (s) on the featurization pool; a
  # hung/SIGKILLed worker surfaces as a timeout, triggering pool
  # re-spawn + bounded retry (batch_retries) before quarantine.
  batch_timeout: float = 0.0
  batch_retries: int = 2
  # Device fault domain (the sharded counterpart of on_zmw_error).
  # 'fail' keeps bare propagation of device-runtime errors; 'degrade'
  # turns RESOURCE_EXHAUSTED into pack bisection (retry at half batch,
  # floored at dp divisibility) and repeated permanent device faults
  # into mesh degradation (rebuild at the next lower dp, re-place
  # weights, resubmit the failed pack in featurize order).
  on_device_error: str = 'fail'  # fail | degrade
  # >0: dispatch watchdog — bound the blocking finalize of each
  # in-flight pack to this many seconds; a hung forward surfaces as a
  # DispatchTimeoutError through pack-failure attribution instead of
  # wedging the model loop.
  dispatch_timeout: float = 0.0
  # Resume an interrupted run from <output>.progress.json + <output>.tmp.
  resume: bool = False
  # Quantized-inference levers (models/quantize.py), applied once at
  # checkpoint load BEFORE device placement so sharded weight
  # transfers ship the shrunken bytes. inference_dtype: None keeps the
  # checkpoint's dtype; 'bfloat16' casts weights + runs activations
  # bf16 end-to-end. quantize_matmuls: None/'none' off; 'int8'
  # per-channel weight quantization of the encoder matmuls.
  inference_dtype: Optional[str] = None
  quantize_matmuls: Optional[str] = None
  # Device-resident output plane (ops/output_plane.py): the forward
  # emits the final uint8 (base ids, Phred quality) planes on device —
  # argmax plus a threshold-table quality byte-identical to the host
  # epilogue — so finalize becomes a pure 2-bytes/position drain (vs 8
  # for int32 ids + f32 max_prob). Tri-state: None (auto) turns it on
  # for checkpoints and follows the artifact metadata for exported
  # runs; an explicit True/False is enforced — disagreeing with an
  # exported artifact raises ExportedArtifactMismatchError. Falls back
  # to the host path (with a warning) when the calibration is not
  # device-representable (non-monotone, or top quality past uint8).
  device_epilogue: Optional[bool] = None
  # Debug stage truncation (reference DebugStage: quick_inference.py:68-75).
  end_after_stage: str = 'full'  # dc_input | tf_examples | run_model | full
  dc_calibration_values: calibration_lib.QualityCalibrationValues = (
      dataclasses.field(
          default_factory=lambda: calibration_lib.parse_calibration_string(
              'skip'
          )
      )
  )
  ccs_calibration_values: calibration_lib.QualityCalibrationValues = (
      dataclasses.field(
          default_factory=lambda: calibration_lib.parse_calibration_string(
              'skip'
          )
      )
  )


_SN_ROWS = data_lib.SN_ROWS  # trailing rows: per-window SN constants


def _assemble_rows(main_u8: jnp.ndarray, sn: jnp.ndarray,
                   bq_row: Optional[int] = None) -> jnp.ndarray:
  """Device-side inverse of dispatch()'s compact split: uint8 rows ->
  f32, SN scalars re-broadcast across the window.

  bq_row: index of the ccs_bq row inside main_u8, if the model uses
  one. That row travels biased by +1 (its spaced values include -1
  sentinels at gap columns / padded tails, which a plain uint8 cast
  would wrap to 255); undo the bias here.
  """
  b, _, l, _ = main_u8.shape
  main = main_u8.astype(jnp.float32)
  if bq_row is not None:
    main = main.at[:, bq_row].add(-1.0)
  sn_rows = jnp.broadcast_to(
      sn.astype(jnp.float32)[:, :, None, None], (b, _SN_ROWS, l, 1)
  )
  return jnp.concatenate([main, sn_rows], axis=1)


def _assemble_rows_ragged(main_u8: jnp.ndarray, sn_w: jnp.ndarray,
                          lengths: jnp.ndarray,
                          bq_row: Optional[int] = None) -> jnp.ndarray:
  """_assemble_rows for ragged slots: SN constants vary per WINDOW
  within a slot, so sn_w carries [B, wps, 4] per-window scalars and
  each position gathers its own window's values through the
  lengths-derived segment map (same slot_geometry the mask uses).
  Positions past the packed windows get zero SN (they are masked out
  of attention and sliced away at delivery)."""
  from deepconsensus_tpu.ops import ragged_window_attention as ragged_ops

  b, _, l, _ = main_u8.shape
  main = main_u8.astype(jnp.float32)
  if bq_row is not None:
    main = main.at[:, bq_row].add(-1.0)
  seg, _start, _width, valid = ragged_ops.slot_geometry(lengths, l)
  # seg is always in [0, wps) (invalid positions keep segment 0), so
  # the gather needs no clip; valid zeroes what it fetched there.
  sn_pos = jnp.take_along_axis(
      sn_w.astype(jnp.float32), seg[:, :, None], axis=1)  # [B, l, 4]
  sn_pos = jnp.where(valid[:, :, None], sn_pos, 0.0)
  sn_rows = jnp.transpose(sn_pos, (0, 2, 1))[:, :, :, None]
  return jnp.concatenate([main, sn_rows], axis=1)


def _bq_row_index(params) -> Optional[int]:
  """Row index of the ccs_bq row within the non-SN block, taken from
  the canonical layout (pileup.row_indices) rather than re-derived.

  Also guards the compact-transport assumption: every non-SN row must
  fit 0..255 after the ccs_bq +1 bias, and PW_MAX/IP_MAX are
  config-tunable, so fail loudly instead of silently truncating.
  """
  from deepconsensus_tpu.preprocess import pileup

  if params.PW_MAX > 255 or params.IP_MAX > 255:
    # dclint: allow=typed-faults (model-config validation at startup,
    # surfaced as operator error by the CLI, not a data-plane fault)
    raise ValueError(
        f'compact uint8 dispatch requires PW_MAX/IP_MAX <= 255, got '
        f'{params.PW_MAX}/{params.IP_MAX}'
    )
  if not params.use_ccs_bq:
    return None
  bq_lo, _bq_hi = pileup.row_indices(params.max_passes, True)[5]
  return bq_lo


def _apply_quant_levers(params, options: 'InferenceOptions') -> None:
  """Fold the CLI quantization levers into a loaded params config.

  inference_dtype also overrides the compute dtype so activations run
  end-to-end in the requested precision; attn_softmax_dtype is left
  alone (the independent f32 escape hatch). The actual weight
  cast/quantization happens in ModelRunner.__init__ via
  models/quantize.py, before any device placement.
  """
  with params.unlocked():
    if options.inference_dtype:
      params.inference_dtype = options.inference_dtype
      params.dtype = options.inference_dtype
    if options.quantize_matmuls and options.quantize_matmuls != 'none':
      params.quantize_matmuls = options.quantize_matmuls


def _check_exported_levers(meta, options: 'InferenceOptions',
                           export_dir: str) -> None:
  """Exported artifacts bake the quantization levers into the compiled
  program; an explicitly requested lever that disagrees with the
  artifact metadata is a serving mismatch, not a silent override."""
  checks = (
      ('inference_dtype', options.inference_dtype,
       meta.get('inference_dtype') or 'float32', '--inference_dtype'),
      ('quantize_matmuls', options.quantize_matmuls,
       meta.get('quantize_matmuls') or 'none', '--quantize_matmuls'),
  )
  mismatches = [
      (name, requested, baked, flag)
      for name, requested, baked, flag in checks
      if requested is not None and requested != baked
  ]
  if not mismatches:
    return
  detail = ', '.join(
      f'{name}: artifact has {baked!r}, requested {requested!r}'
      for name, requested, baked, _flag in mismatches)
  flags = ' '.join(
      f'{flag} {requested}' for _name, requested, _baked, flag in mismatches)
  raise faults.ExportedArtifactMismatchError(
      f'exported artifact quantization mismatch ({detail})',
      reexport_command=(
          f'dctpu export --checkpoint <orbax_ckpt> '
          f'--output {export_dir} {flags}'
      ),
  )


def _check_exported_epilogue(meta, options: 'InferenceOptions',
                             export_dir: str) -> None:
  """The output plane is compiled into exported artifacts: an epilogue
  artifact always emits uint8 (ids, quals) with its baked calibration
  and clamp, a pre-epilogue artifact can only feed the host quality
  path. An explicit --device_epilogue/--no_device_epilogue — or a
  quality knob disagreeing with what an epilogue artifact baked — is a
  serving mismatch, not a silent override (same contract as
  _check_exported_levers)."""
  baked = bool(meta.get('device_epilogue'))
  requested = options.device_epilogue
  if requested is not None and requested != baked:
    flag = '--device_epilogue' if requested else '--no_device_epilogue'
    raise faults.ExportedArtifactMismatchError(
        f'exported artifact output-plane mismatch (artifact has '
        f'device_epilogue={baked}, requested {flag})',
        reexport_command=(
            f'dctpu export --checkpoint <orbax_ckpt> '
            f'--output {export_dir} {flag}'
        ),
    )
  if not baked:
    return
  baked_maxq = int(meta.get('max_base_quality', 93))
  if int(options.max_base_quality) != baked_maxq:
    raise faults.ExportedArtifactMismatchError(
        f'exported artifact bakes max_base_quality={baked_maxq} into '
        f'its device epilogue, requested {options.max_base_quality}',
        reexport_command=(
            f'dctpu export --checkpoint <orbax_ckpt> '
            f'--output {export_dir} '
            f'--max_base_quality {options.max_base_quality}'
        ),
    )
  baked_cal_str = meta.get('dc_calibration') or 'skip'
  baked_cal = calibration_lib.parse_calibration_string(baked_cal_str)
  if options.dc_calibration_values != baked_cal:
    requested_cal = calibration_lib.calibration_string(
        options.dc_calibration_values)
    raise faults.ExportedArtifactMismatchError(
        f'exported artifact bakes dc-calibration {baked_cal_str!r} '
        f'into its device epilogue, requested {requested_cal!r}',
        reexport_command=(
            f'dctpu export --checkpoint <orbax_ckpt> '
            f'--output {export_dir} --dc_calibration {requested_cal}'
        ),
    )


def _check_sparse_experts_served(params, mesh) -> None:
  """What a block kind with sparse experts cannot run yet, refused by
  name before anything is placed (ROADMAP R-a)."""
  kind = config_lib.block_kind_of(params)
  if params.get('quantize_matmuls', None) not in (None, 'none'):
    # dclint: allow=typed-faults (model-config validation at startup,
    # surfaced as operator error by the CLI, not a data-plane fault)
    raise ValueError(
        f'block kind {kind!r} is not served with '
        f'quantize_matmuls={params.quantize_matmuls!r}: models/quantize.py '
        'has no per-expert scales')
  if mesh is not None:
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    if int(mesh.shape.get(mesh_lib.MODEL_AXIS, 1)) > 1:
      # dclint: allow=typed-faults (startup config validation: the
      # operator asked for a mesh axis the kind cannot be split over)
      raise ValueError(
          f'block kind {kind!r} is not served with --tp: '
          'parallel/partition_rules.py has no expert axis')


def _check_dp_divisible(options: 'InferenceOptions', mesh) -> int:
  """The compiled batch splits evenly over the mesh data axis; returns
  the data-axis size."""
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  dp = mesh.shape[mesh_lib.DATA_AXIS]
  if options.batch_size % dp:
    # dclint: allow=typed-faults (startup config validation: operator
    # picked a batch size the mesh cannot split)
    raise ValueError(
        f'batch_size={options.batch_size} not divisible by the mesh '
        f'data axis ({dp} devices)'
    )
  return dp


class _DispatchHandle:
  """One in-flight pack: the runner's dispatch contract.

  dispatch() returns one of these holding the pack's (dp-sharded)
  device inputs in the transfer slot; the matching forward launches
  either when the NEXT pack dispatches (so pack N+1's host->device
  transfer overlaps pack N's compute) or on demand in
  raw_outputs()/finalize(). A launch error is stored here and
  re-raised at finalize time, so the engine's pack-failure routing
  attributes it to the pack that actually failed, not the pack whose
  dispatch happened to trigger the launch.
  """

  __slots__ = ('inputs', 'n', 'outputs', 'error', 'seq', 'hang_s',
               't_launch', 'bucket', 'ragged')

  def __init__(self, inputs, n: int):
    self.inputs = inputs  # device input tuple; cleared at launch
    self.n = n
    self.outputs = None  # (pred_ids_dev, max_prob_dev) once launched
    self.error = None
    self.seq = 0  # 1-based dispatch ordinal (fault-injection target)
    self.hang_s = 0.0  # injected finalize hang (watchdog drills)
    self.t_launch = 0.0  # forward-launch wall stamp (device_compute span)
    self.bucket = 0  # window width / slot length (straggler context)
    self.ragged = False  # routes the launch to the ragged forward

  @property
  def launched(self) -> bool:
    return self.outputs is not None or self.error is not None


# Watchdog workers abandoned past their deadline. Joined (briefly) at
# interpreter exit: a daemon thread still inside an XLA sync when
# CPython tears down the runtime segfaults the process, so the exit
# hook trades a bounded wait for a clean exit code. Slow-but-alive
# packs finish inside the grace; a truly wedged device still exits
# after it (and may then crash teardown — unavoidable without killing
# the thread, which CPython cannot do safely).
_abandoned_watchdogs: List[threading.Thread] = []
_ABANDON_GRACE_S = 15.0


def _join_abandoned_watchdogs() -> None:
  deadline = time.monotonic() + _ABANDON_GRACE_S
  for t in list(_abandoned_watchdogs):
    t.join(max(0.0, deadline - time.monotonic()))


atexit.register(_join_abandoned_watchdogs)


def _finalize_with_watchdog(finalize_fn, dispatched, timeout: float):
  """Bounds a blocking finalize: runs finalize_fn(dispatched) in a
  worker thread and waits at most `timeout` seconds.

  A device-side hang (wedged transfer, halted chip mid-collective)
  otherwise blocks np.asarray forever and wedges the model loop; here
  it surfaces as a DispatchTimeoutError that the engine's pack-failure
  routing attributes to the hung pack's tickets. The worker is a
  daemon: if the device never answers, the thread is abandoned with
  its pack rather than keeping the process alive.

  Module-level (not a ModelRunner method) on purpose: the runner's
  dispatch state stays single-threaded — this helper owns the only
  cross-thread hand-off, a single-producer result cell.
  """
  # dclint: lock-free (single-producer result cell: exactly one worker
  # thread appends once; the waiter reads only after a successful join)
  box = []

  def worker():
    try:
      box.append(('ok', finalize_fn(dispatched)))
    # dclint: allow=typed-faults (error capture for the cross-thread
    # hand-off: the waiter re-raises it verbatim on the model loop)
    except BaseException as e:
      box.append(('error', e))

  t = threading.Thread(
      target=worker, name='dctpu-finalize-watchdog', daemon=True)
  t.start()
  t.join(timeout)
  if t.is_alive() or not box:
    if t.is_alive():
      _abandoned_watchdogs.append(t)
    raise faults.DispatchTimeoutError(
        f'pack finalize produced no result within '
        f'dispatch_timeout={timeout}s')
  status, value = box[0]
  if status == 'error':
    raise value
  return value


class ModelRunner:
  """Jitted forward pass producing (bases, quality scores) per window.

  With a mesh, the window batch is sharded over the mesh's data axis
  (weights replicated), so one process drives every chip — the
  multi-chip counterpart of the reference's shard-the-BAM pattern
  (quick_inference.py 500-shard runs)."""

  def __init__(self, params, variables, options: InferenceOptions,
               mesh=None):
    self._init_obs()
    with obs_lib.stage(self.obs, obs_lib.trace.STAGE_RUNNER_INIT) as st:
      self._build(params, variables, options, mesh)
      st.set(weight_bytes=self._weight_bytes, block_kind=self._block_kind,
             mesh_dp=self.mesh_dp)

  def _init_obs(self) -> None:
    """One metrics registry per runner process; the engine, service and
    batch driver all observe into this same registry so /metricz and
    the run sidecar read one coherent view (obs/metrics.py). The
    process's compile events count into the registry bound last
    (obs/compiles.py), and it is told what importing this module cost."""
    self.obs = obs_lib.MetricsRegistry()
    compiles_lib.install(self.obs)
    self.obs.observe(
        obs_lib.stage_histogram_name(obs_lib.trace.STAGE_IMPORT_RUNNER),
        IMPORT_S)

  def _build(self, params, variables, options: InferenceOptions,
             mesh) -> None:
    self.params = params
    # Whether the forward routes tokens to experts and returns their
    # per-pack counts beside the predictions.
    sparse_experts = config_lib.holds_experts(params)
    if sparse_experts:
      _check_sparse_experts_served(params, mesh)
    # Quantize/cast once on the host BEFORE any device placement, so
    # the weight transfer below ships the shrunken bf16/int8 bytes
    # (and degrade_mesh()'s re-placement keeps shipping them).
    self._n_quantized_matmuls = 0
    if variables:
      from deepconsensus_tpu.models import quantize as quantize_lib

      with obs_lib.stage(self.obs, obs_lib.trace.STAGE_WEIGHTS_PREPARE):
        variables, self._n_quantized_matmuls = (
            quantize_lib.prepare_inference_variables(variables, params))
    self.variables = variables
    self.options = options
    self.mesh = mesh
    if mesh is not None:
      _check_dp_divisible(options, mesh)
    if variables:
      with obs_lib.stage(self.obs, obs_lib.trace.STAGE_WEIGHTS_PLACE):
        self.variables = self._place_weights(variables, mesh)
    model = model_lib.get_model(params)
    self._bq_row = _bq_row_index(params)
    bq_row = self._bq_row
    self._configure_epilogue()
    thresholds = self._epilogue_thresholds

    def forward_for(single_device):
      # A function named `forward`: the compiled program is found in the
      # device trace as `jit_forward`.
      def forward(variables, main_u8, sn):
        rows = _assemble_rows(main_u8, sn, bq_row)
        counts = ()
        # Without a mesh the program is inference for one device, and
        # the model may take kernels on its own
        # (model_lib.kernel_paths).
        with pallas_util.single_device_inference(single_device):
          if sparse_experts:
            # Beside the predictions, the assignments every held expert
            # took this pack, [expert layers, experts held] (finalize
            # counts them).
            preds, sown = model.apply(variables, rows,
                                      mutable=['moe_counts'])
            counts = (model_lib.expert_assignments(sown['moe_counts']),)
          else:
            preds = model.apply(variables, rows)
        if thresholds is not None:
          return output_plane.phred_epilogue(preds, thresholds) + counts
        pred_ids = jnp.argmax(preds, axis=-1).astype(jnp.int32)
        max_prob = jnp.max(preds, axis=-1)
        return (pred_ids, max_prob) + counts

      return forward

    def ragged_forward(variables, main_u8, sn_w, lengths):
      rows = _assemble_rows_ragged(main_u8, sn_w, lengths, bq_row)
      preds = model.apply(variables, rows, window_lengths=lengths)
      if thresholds is not None:
        return output_plane.phred_epilogue(preds, thresholds)
      pred_ids = jnp.argmax(preds, axis=-1).astype(jnp.int32)
      max_prob = jnp.max(preds, axis=-1)
      return pred_ids, max_prob

    # Retained so degrade_mesh() can recompile the same forward for a
    # rebuilt (smaller) mesh.
    self._make_forward = lambda m: self._jit_forward(
        forward_for(m is None), m, n_replicated_outputs=int(sparse_experts))
    self._forward = self._make_forward(mesh)
    # The ragged forward compiles lazily at its first dispatch_ragged,
    # so wiring it up always costs nothing when use_ragged_kernel is
    # off (jit() does not trace).
    self._make_ragged_forward = (
        lambda m: self._jit_ragged_forward(ragged_forward, m))
    self._ragged_forward = self._make_ragged_forward(mesh)
    self._init_dispatch_state(mesh)

  @staticmethod
  def _place_weights(variables, mesh):
    """The weights (and the quant collections) on the device(s), once,
    and there when this returns: placing is asynchronous, and without
    the wait `weights_place` would read the enqueue and the first
    forward pay the copy."""
    if mesh is not None:
      from deepconsensus_tpu.parallel import mesh as mesh_lib

      # Otherwise every forward re-broadcasts host arrays to all
      # devices. param_shardings shards attention heads / FFN filters
      # on the model axis under tp>1 and degenerates to replication at
      # tp=1 (same rules as training); the non-params collections
      # always replicate.
      placed = {
          key: jax.device_put(
              value,
              mesh_lib.param_shardings(mesh, value)
              if key == 'params' else mesh_lib.replicated(mesh),
          )
          for key, value in variables.items()
      }
    else:
      # Single-device residency: same as the mesh branch — otherwise
      # every forward re-transfers the host arrays, leaving a host gap
      # between consecutive packs' device_compute spans. With the input
      # buffers donated, the steady-state pack loop then touches the
      # host only for the uint8 pack in and the uint8 (ids, quals)
      # planes out.
      placed = jax.device_put(variables)
    return jax.block_until_ready(placed)

  def _configure_epilogue(self) -> None:
    """Resolves the tri-state device_epilogue option against the
    quality knobs: builds the exact threshold table
    (ops/output_plane.py) when the device output plane is on, or
    records the host fallback — warning when the operator asked for
    the device path but the prob->quality map is not
    device-representable."""
    opts = self.options
    want = opts.device_epilogue
    if want is None:
      want = True  # default on for checkpoint-loaded runners
    self._device_epilogue = False
    self._epilogue_thresholds = None
    if not want:
      return
    thresholds = output_plane.quality_thresholds(
        opts.dc_calibration_values, opts.max_base_quality)
    if thresholds is None:
      log.warning(
          'device epilogue unavailable for this dc-calibration/'
          'max_base_quality (non-monotone calibration, or top quality '
          'past the uint8 plane); falling back to host quality math')
      return
    self._device_epilogue = True
    self._epilogue_thresholds = thresholds

  def _init_dispatch_state(self, mesh) -> None:
    """Dispatch-contract state shared by __init__ and from_exported
    (which builds the runner via cls.__new__)."""
    if mesh is not None:
      from deepconsensus_tpu.parallel import mesh as mesh_lib

      self._input_sharding = mesh_lib.batch_sharding(mesh)
    else:
      self._input_sharding = None
    # What the forward holds and computes, for the forward_launch span
    # and the registry: the encoder block kind (the model family where
    # there is no encoder), and the resident parameter bytes by leaf
    # dtype (0 for an exported artifact, whose weights are baked in). A
    # second copy or an upcast of the weights shows here.
    self._block_kind = (
        config_lib.block_kind_of(self.params)
        if 'transformer' in self.params.model_name
        else str(self.params.model_name))
    # Whether the jitted forward was traced as inference for one device
    # (checkpoint runners without a mesh): the model's rule for the
    # attention sublayer kernel reads it, and so does the span.
    self._single_device = (
        mesh is None and getattr(self, 'variables', None) is not None)
    self._weight_bytes = sum(
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(self.variables))
    self.obs.set_gauge('model_weight_bytes', self._weight_bytes)
    self._n_forward_positions = self.obs.counter('n_forward_positions')
    # What `forward_launch` says of the stack, the same for every pack.
    self._launch_fields = model_lib.describe_stack(self.params)
    self._sparse_experts = config_lib.holds_experts(self.params)
    if self._sparse_experts:
      # Assignments the router made (positions x k x expert layers), those
      # that fell on held experts, and the most any one held expert took
      # in a pack of one layer.
      self._moe_total = self.obs.counter('moe_assignments_total')
      self._moe_held = self.obs.counter('moe_assignments_held')
      self._moe_load_max = 0
      self.obs.set_gauge('moe_expert_load_max', 0)
    # dclint: lock-free (single transfer slot: the model-loop thread
    # is the sole device owner — dispatch/finalize are never called
    # concurrently, per the engine's single-thread contract)
    self._pending: Optional[_DispatchHandle] = None
    self._n_dispatched = 0
    self._n_dispatched_sharded = 0
    self._pack_shard_devices = 0
    self._n_overlapped_launches = 0
    self._n_direct_launches = 0
    # Mesh-degradation ladder state: the dp we started with, and how
    # many times degrade_mesh() stepped down.
    if mesh is not None:
      from deepconsensus_tpu.parallel import mesh as mesh_lib

      self._initial_dp = int(mesh.shape[mesh_lib.DATA_AXIS])
    else:
      self._initial_dp = 0
    self._n_degraded = 0
    # Quantization lever labels for /metricz and the run sidecar.
    # from_exported builds the runner via cls.__new__ and never applies
    # the levers itself (they are baked into the artifact), so default
    # the counter here instead of in __init__.
    self._n_quantized_matmuls = getattr(self, '_n_quantized_matmuls', 0)
    self._inference_dtype_label = str(
        self.params.get('inference_dtype', None) or 'float32')
    # Output-plane state: checkpoint __init__ resolves it in
    # _configure_epilogue before reaching here; from_exported sets it
    # from the artifact metadata (the epilogue is compiled in, no
    # threshold table needed host-side). Same getattr pattern as
    # _n_quantized_matmuls.
    self._device_epilogue = getattr(self, '_device_epilogue', False)
    self._epilogue_thresholds = getattr(self, '_epilogue_thresholds', None)
    self._n_epilogue_packs = 0
    # Measured at the first finalize drain (actual device-array bytes
    # pulled host-side per pack), for /metricz and the bench A/B.
    self._d2h_bytes_per_pack = 0
    # Bucketed-dispatch accounting: the distinct (batch, L) input shapes
    # this runner has dispatched, which is how many executables the
    # per-bucket compile-once contract allows the jitted forward (what
    # XLA really compiled is `n_xla_compiles`, obs/compiles.py); the
    # per-bucket dict counts dispatches (including bisection retries,
    # unlike the engine's per-packer n_packs).
    self._forward_shapes: set = set()
    self._n_dispatched_by_bucket: Dict[int, int] = {}
    # Ragged dispatch contract: absent on exported-artifact runners
    # (the baked program has no lengths input), present on checkpoint
    # runners regardless of the gate (jit never traces unless called).
    self._ragged_forward = getattr(self, '_ragged_forward', None)
    self._make_ragged_forward = getattr(self, '_make_ragged_forward', None)

  @staticmethod
  def _jit_forward(forward, mesh, n_replicated_outputs: int = 0):
    # donate_argnums: the uint8 pack and SN buffers are dead after the
    # forward (finalize only touches the outputs), so steady state
    # reuses their device memory instead of growing the arena by one
    # pack per in-flight dispatch.
    if mesh is None:
      return jax.jit(forward, donate_argnums=(1, 2))
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    batch_sh = mesh_lib.batch_sharding(mesh)
    return jax.jit(
        forward,
        # Variables keep the placement __init__ gave them (replicated,
        # or model-axis sharded under tp>1).
        in_shardings=(None, batch_sh, batch_sh),
        # The two planes by window; what follows them (a pack's counts)
        # whole on every device.
        out_shardings=(batch_sh, batch_sh) + (
            mesh_lib.replicated(mesh),) * n_replicated_outputs,
        donate_argnums=(1, 2),
    )

  @staticmethod
  def _jit_ragged_forward(forward, mesh):
    # Same donation contract as _jit_forward, with the lengths vector
    # riding along: all three pack buffers (uint8 rows, per-window SN,
    # int32 lengths) are dead after the forward, so steady state
    # cycles ONE set of donated device buffers across packs.
    if mesh is None:
      return jax.jit(forward, donate_argnums=(1, 2, 3))
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    batch_sh = mesh_lib.batch_sharding(mesh)
    return jax.jit(
        forward,
        in_shardings=(None, batch_sh, batch_sh, batch_sh),
        out_shardings=(batch_sh, batch_sh),
        donate_argnums=(1, 2, 3),
    )

  @classmethod
  def from_checkpoint(cls, checkpoint_path: str,
                      options: InferenceOptions,
                      mesh=None) -> 'ModelRunner':
    """Loads either an orbax checkpoint or an exported StableHLO
    artifact directory (the reference's SavedModel-vs-checkpoint
    detection: quick_inference.py:797-800,512-529)."""
    import os

    from deepconsensus_tpu.models import export as export_lib
    from deepconsensus_tpu.models.checkpoints import load_params

    if os.path.isdir(checkpoint_path) and os.path.exists(
        os.path.join(checkpoint_path, export_lib.ARTIFACT_NAME)
    ):
      return cls.from_exported(checkpoint_path, options, mesh=mesh)

    params = config_lib.read_params_from_json(checkpoint_path)
    config_lib.finalize_params(params, is_training=False)
    _apply_quant_levers(params, options)
    t0 = time.time()
    loaded = load_params(checkpoint_path)
    t1 = time.time()
    runner = cls(params, {'params': loaded}, options, mesh=mesh)
    # Stamped once there is a registry to hold its histogram.
    obs_lib.record_stage(
        runner.obs, obs_lib.trace.STAGE_CHECKPOINT_LOAD, t0, t1,
        bytes=sum(int(leaf.nbytes)
                  for leaf in jax.tree_util.tree_leaves(loaded)))
    return runner

  @classmethod
  def from_exported(cls, export_dir: str,
                    options: InferenceOptions,
                    mesh=None) -> 'ModelRunner':
    """Serves an exported StableHLO artifact (params baked in).

    With a mesh, the single-device program serves data-parallel: each
    device runs the artifact on its batch shard under shard_map (the
    batch-polymorphic export accepts the per-device shape), matching
    the reference's any-topology SavedModel serving. Requires a
    polymorphic artifact and a pure-DP mesh — the baked program can't
    be re-sharded on the model axis.
    """
    from deepconsensus_tpu.models import export as export_lib

    t0 = time.time()
    serving, meta = export_lib.load_exported(export_dir)
    t1 = time.time()
    params = config_lib.read_params_from_json(export_dir)
    config_lib.finalize_params(params, is_training=False)
    _check_exported_levers(meta, options, export_dir)
    _check_exported_epilogue(meta, options, export_dir)
    baked_epilogue = bool(meta.get('device_epilogue'))
    runner = cls.__new__(cls)
    runner._init_obs()
    obs_lib.record_stage(
        runner.obs, obs_lib.trace.STAGE_CHECKPOINT_LOAD, t0, t1,
        bytes=os.path.getsize(
            os.path.join(export_dir, export_lib.ARTIFACT_NAME)))
    runner.params = params
    runner.variables = None
    # The output plane is part of the compiled program: when baked, the
    # serving call already returns the uint8 (ids, quals) planes and
    # finalize is a pure drain; no host-side threshold table exists.
    runner._device_epilogue = baked_epilogue
    runner._epilogue_thresholds = None
    if not meta.get('polymorphic_batch'):
      # Fixed-batch artifact: the compiled shape wins over the flag.
      if mesh is not None:
        raise faults.ExportedArtifactMismatchError(
            'mesh/--dp serving of an exported artifact requires a '
            'batch-polymorphic export (this artifact is fixed-batch; '
            're-export with polymorphic_batch=True)',
            reexport_command=(
                'dctpu export --checkpoint <orbax_ckpt> '
                f'--output {export_dir} --strict_polymorphic'
            ),
        )
      options.batch_size = int(meta['batch_size'])
    runner.options = options
    runner.mesh = mesh
    runner._bq_row = _bq_row_index(params)
    bq_row = runner._bq_row

    def apply_serving(main_u8, sn):
      out = serving(_assemble_rows(main_u8, sn, bq_row))
      if baked_epilogue:
        # Epilogue artifact: `out` already is the uint8 (ids, quals)
        # tuple — the whole output plane ran inside the baked program.
        return tuple(out)
      preds = out
      return (
          jnp.argmax(preds, axis=-1).astype(jnp.int32),
          jnp.max(preds, axis=-1),
      )

    if mesh is None:
      runner._forward = jax.jit(
          lambda _variables, main_u8, sn: apply_serving(main_u8, sn),
          donate_argnums=(1, 2))
      # No mesh, no degradation ladder: degrade_mesh() bails before
      # ever recompiling, so the identity rebuild is never called.
      runner._make_forward = lambda _m: runner._forward
      runner._init_dispatch_state(mesh)
      return runner

    from jax import shard_map
    from jax.sharding import PartitionSpec
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    if mesh_lib.MODEL_AXIS in mesh.shape and (
        mesh.shape[mesh_lib.MODEL_AXIS] > 1):
      raise faults.ExportedArtifactMismatchError(
          'exported artifacts serve data-parallel only (the compiled '
          'program cannot be re-sharded on the model axis); use tp=1 '
          'or an orbax checkpoint'
      )
    _check_dp_divisible(options, mesh)
    batch_spec = PartitionSpec(mesh_lib.DATA_AXIS)

    def make_forward(m):
      sharded_serving = shard_map(
          apply_serving, mesh=m,
          in_specs=(batch_spec, batch_spec),
          out_specs=(batch_spec, batch_spec),
          # The exported-call primitive has no varying-manual-axes rule;
          # both specs are fully dp-sharded anyway, so there is nothing
          # for the checker to prove.
          check_vma=False,
      )
      return jax.jit(
          lambda _variables, main_u8, sn: sharded_serving(main_u8, sn),
          donate_argnums=(1, 2))

    runner._make_forward = make_forward
    runner._forward = make_forward(mesh)
    runner._init_dispatch_state(mesh)
    return runner

  def dispatch(self, rows: np.ndarray,
               batch_size: Optional[int] = None) -> _DispatchHandle:
    """dispatch_pack() for float rows [B, R, L, 1] already through
    data.format_rows_batch: casts them to the compact pack first. The
    entry of callers that hold float rows (predict, the per-batch
    pipeline, benches); the engine fills compact packs itself
    (data.fill_pack) and calls dispatch_pack directly."""
    return self.dispatch_pack(
        self._cast_main_u8(rows),
        np.ascontiguousarray(rows[:, -_SN_ROWS:, 0, 0].astype(np.float32)),
        batch_size=batch_size)

  def dispatch_pack(self, main_u8: np.ndarray, sn: np.ndarray,
                    n_rows: Optional[int] = None,
                    batch_size: Optional[int] = None) -> _DispatchHandle:
    """Async sharded dispatch of one compact pack -> _DispatchHandle.

    Transfer is compact: every non-SN row holds clip-bounded integers
    (bases/ccs 0-4, pw/ip <= PW_MAX/IP_MAX = 255, strand 0-2, ccs_bq
    -1..93 shipped biased by +1), and the 4 SN rows are per-window
    constants, so the batch ships as uint8 rows `main_u8` [B, R - 4, L,
    1] + `sn` [B, 4] float SN scalars (~4x less than f32 rows over
    PCIe) and reassembles losslessly on device (_assemble_rows undoes
    the ccs_bq bias).

    Pads to the fixed compiled batch shape if the pack is shorter,
    places it on the device(s) with an async `jax.device_put`
    (dp-sharded over the mesh data axis when a mesh is configured), and
    returns a handle holding the in-flight transfer slot. The matching
    forward is double-buffered: it launches when the NEXT pack
    dispatches — so this pack's compute overlaps that pack's
    host->device transfer — or on demand in finalize(). The forward
    donates the input buffers, so steady state reuses device memory.
    The transfer may still be reading the host arrays when this
    returns: the caller keeps them unchanged until finalize().

    n_rows: how many leading rows are windows (the rest is zero
    padding the caller already wrote); default all of them.
    batch_size overrides the compiled batch shape for this pack only
    (OOM bisection retries at half batch; jit's per-shape cache keeps
    one executable per distinct size).
    """
    n = len(main_u8) if n_rows is None else n_rows
    batch = batch_size or self.options.batch_size
    width = int(main_u8.shape[2])
    with obs_lib.stage(self.obs, obs_lib.trace.STAGE_DISPATCH,
                       pack=self._n_dispatched + 1, bucket=width, n_rows=n):
      # What is left of the cast: the pad of a pack that came short.
      with obs_lib.stage(self.obs, obs_lib.trace.STAGE_PACK_CAST) as st:
        bytes_in = main_u8.nbytes + sn.nbytes
        short = batch - len(main_u8)
        if short > 0:
          main_u8, sn = (
              np.concatenate(
                  [plane, np.zeros((short,) + plane.shape[1:], plane.dtype)])
              for plane in (main_u8, sn))
        st.set(bytes_in=bytes_in, bytes_out=main_u8.nbytes + sn.nbytes)
      # Per-bucket compile-once accounting: jit keeps one executable per
      # distinct (batch, L).
      return self._place_pack((main_u8, sn), n=n, n_rows=n, bucket=width,
                              shape_key=(batch, width))

  def _cast_main_u8(self, rows: np.ndarray) -> np.ndarray:
    """The non-SN rows of a pack as uint8, ccs_bq biased by +1: spaced
    ccs_bq holds -1 sentinels, so 0..94 makes the cast lossless (the
    device side subtracts 1 back; zero pad positions round-trip
    0 -> 1 -> 0)."""
    main = rows[:, :-_SN_ROWS]
    main_u8 = main.astype(np.uint8)
    if self._bq_row is not None:
      main_u8[:, self._bq_row] = (main[:, self._bq_row] + 1.0).astype(
          np.uint8)
    return main_u8

  def _place_pack(self, host_arrays, n: int, n_rows: int, bucket: int,
                  shape_key, ragged: bool = False) -> _DispatchHandle:
    """The half of a dispatch that both pack layouts share: launch the
    previous pack's forward, start this pack's async transfer, count,
    and leave the handle in the transfer slot."""
    # Launch the previous pack's forward BEFORE starting this pack's
    # transfer, so the device_put below overlaps its compute.
    self._launch_pending()
    with obs_lib.stage(
        self.obs, obs_lib.trace.STAGE_H2D, pack=self._n_dispatched + 1,
        bucket=bucket, dp=self.mesh_dp, n_rows=n_rows,
        bytes=sum(a.nbytes for a in host_arrays)):
      if self._input_sharding is not None:
        placed = tuple(jax.device_put(a, self._input_sharding)
                       for a in host_arrays)
        self._n_dispatched_sharded += 1
      else:
        placed = tuple(jax.device_put(a) for a in host_arrays)
      self._n_dispatched += 1
      # Distinct devices holding a shard of the placed pack (metadata
      # only, no sync): dp=4 must read 4 here, not 1.
      self._pack_shard_devices = len(
          {shard.device for shard in placed[0].addressable_shards})
    if self._device_epilogue:
      self._n_epilogue_packs += 1
    self._forward_shapes.add(shape_key)
    self._n_dispatched_by_bucket[bucket] = (
        self._n_dispatched_by_bucket.get(bucket, 0) + 1)
    handle = _DispatchHandle(placed, n)
    handle.seq = self._n_dispatched
    handle.bucket = bucket
    handle.ragged = ragged
    self._pending = handle
    return handle

  def dispatch_ragged(self, rows: np.ndarray,
                      lengths: np.ndarray) -> _DispatchHandle:
    """dispatch() for the single ragged pack stream: rows
    [n_slots, R, slot_len, 1] with mixed-width windows packed
    back-to-back per slot, lengths [n_slots, wps] int32 window widths
    (0 = unused capacity). Same compact uint8 transport and
    double-buffered launch as dispatch(), with the SN plane shipped as
    PER-WINDOW scalars ([n_slots, wps, 4], sampled at each window's
    start column) that _assemble_rows_ragged re-broadcasts through the
    lengths-derived segment map. Every pack has the same shape, so the
    jitted ragged forward compiles exactly once (n_forward_shapes
    stays 1 for the whole run)."""
    if self._ragged_forward is None:
      # dclint: allow=typed-faults (serving contract: exported
      # artifacts bake a fixed-shape program with no lengths input)
      raise ValueError(
          'ragged dispatch is not available on this runner (exported '
          'artifacts serve the bucketed path only)')
    n_slots = int(rows.shape[0])
    slot_len = int(rows.shape[2])
    n_windows = int((np.asarray(lengths) > 0).sum())
    with obs_lib.stage(self.obs, obs_lib.trace.STAGE_DISPATCH,
                       pack=self._n_dispatched + 1, bucket=slot_len,
                       n_rows=n_windows):
      with obs_lib.stage(self.obs, obs_lib.trace.STAGE_PACK_CAST) as st:
        lengths = np.ascontiguousarray(np.asarray(lengths, dtype=np.int32))
        main_u8 = self._cast_main_u8(rows)
        # Per-window SN scalars, sampled at each window's start column
        # (the packer broadcast them across the window, like the raw
        # feature layout). Empty window slots carry zeros.
        starts = np.zeros_like(lengths)
        starts[:, 1:] = np.cumsum(lengths[:, :-1], axis=1)
        sn_planes = rows[:, -_SN_ROWS:, :, 0]  # [n_slots, 4, slot_len]
        sn_w = np.take_along_axis(
            sn_planes, np.clip(starts, 0, slot_len - 1)[:, None, :], axis=2)
        sn_w = sn_w.transpose(0, 2, 1) * (lengths > 0)[:, :, None]
        sn_w = np.ascontiguousarray(sn_w.astype(np.float32))
        st.set(bytes_in=rows.nbytes,
               bytes_out=main_u8.nbytes + sn_w.nbytes + lengths.nbytes)
      # One shape for the whole run: the collapse the ragged path buys.
      return self._place_pack(
          (main_u8, sn_w, lengths), n=n_slots, n_rows=n_windows,
          bucket=slot_len, shape_key=('ragged', n_slots, slot_len),
          ragged=True)

  def _launch_pending(self) -> None:
    """Launches the forward for the pack currently in the transfer
    slot, if any (the overlapped half of the double buffer)."""
    handle, self._pending = self._pending, None
    if handle is None or handle.launched:
      return
    self._launch(handle)
    self._n_overlapped_launches += 1

  def _launch(self, handle: _DispatchHandle) -> None:
    """Runs the jitted forward on a handle's device inputs. An error is
    stored on the handle (re-raised by raw_outputs/finalize) so the
    engine attributes it to the failing pack, not to whichever later
    dispatch happened to trigger this launch."""
    inputs = handle.inputs
    # Drop our references before the call: the jit donates these
    # buffers, so they must not be reachable (or reused) afterwards.
    handle.inputs = None
    # Launch stamp: the device_compute span runs launch -> drain, and
    # launch-before-finalize ordering is the span-derived overlap
    # signal dctpu trace reconciles against the counters.
    handle.t_launch = time.time()
    fwd = self._ragged_forward if handle.ragged else self._forward
    # Positions the forward computes: the compiled pack's rows x width.
    n_positions = inputs[0].shape[0] * inputs[0].shape[2]
    self._n_forward_positions.inc(n_positions)
    # What the compiled forward takes of the kernels the model chooses by
    # itself, asked under the declaration it was traced under.
    with pallas_util.single_device_inference(self._single_device):
      paths = model_lib.kernel_paths(
          self.params, batch=inputs[0].shape[0], length=inputs[0].shape[2],
          ragged=handle.ragged)
    # Host time to enqueue the forward: where a full runtime queue
    # would block.
    with obs_lib.stage(self.obs, obs_lib.trace.STAGE_LAUNCH,
                       pack=handle.seq, block_kind=self._block_kind,
                       n_positions=n_positions,
                       weight_bytes=self._weight_bytes,
                       **paths, **self._launch_fields):
      try:
        faults.injected_device_fault(handle.seq)
        handle.hang_s = faults.injected_device_hang(handle.seq)
        handle.outputs = fwd(self.variables, *inputs)
      # dclint: allow=typed-faults (deferred-launch error capture: the
      # classified error is re-raised at finalize time, where
      # pack-failure routing can attribute it to the right tickets)
      except Exception as e:
        handle.error = faults.classify_device_error(e)

  def raw_outputs(self, dispatched: _DispatchHandle):
    """Device arrays (pred_ids, max_prob, n) for a dispatch handle —
    (ids_u8, quals_u8, n) when the device epilogue is on — launching
    its forward now if no later dispatch overlapped it."""
    handle = dispatched
    if not handle.launched:
      if self._pending is handle:
        self._pending = None
      self._launch(handle)
      self._n_direct_launches += 1
    if handle.error is not None:
      raise handle.error
    pred_ids, max_prob = handle.outputs[:2]
    return pred_ids, max_prob, handle.n

  def dispatch_stats(self) -> Dict[str, Any]:
    """Transfer/overlap counters for /metricz and the bench stages."""
    launches = self._n_overlapped_launches + self._n_direct_launches
    return {
        'n_packs_dispatched_sharded': self._n_dispatched_sharded,
        'n_transfer_overlapped': self._n_overlapped_launches,
        'n_transfer_direct': self._n_direct_launches,
        'transfer_overlap_fraction': (
            round(self._n_overlapped_launches / launches, 4)
            if launches else 0.0),
        'n_mesh_degradations': self._n_degraded,
        'mesh_dp': self.mesh_dp,
        'pack_shard_devices': self._pack_shard_devices,
        'inference_dtype': self._inference_dtype_label,
        'n_quantized_matmuls': self._n_quantized_matmuls,
        'device_epilogue': int(self._device_epilogue),
        'n_epilogue_packs': self._n_epilogue_packs,
        'd2h_bytes_per_pack': self._d2h_bytes_per_pack,
        'n_forward_shapes': len(self._forward_shapes),
        # What XLA compiled, or read from the persistent cache, in this
        # process since this runner was built (obs/compiles.py).
        'n_xla_compiles': self.obs.counter('xla_compiles_total').value,
        'n_xla_cache_hits': self.obs.counter('xla_cache_hits_total').value,
        'block_kind': self._block_kind,
        'model_weight_bytes': self._weight_bytes,
        'n_forward_positions': self._n_forward_positions.value,
        **self._expert_stats(),
        'n_dispatched_by_bucket': {
            w: self._n_dispatched_by_bucket[w]
            for w in sorted(self._n_dispatched_by_bucket)},
    }

  def _expert_stats(self) -> Dict[str, int]:
    if not self._sparse_experts:
      return {}
    return {'moe_assignments_total': self._moe_total.value,
            'moe_assignments_held': self._moe_held.value,
            'moe_expert_load_max': self._moe_load_max}

  @property
  def mesh_dp(self) -> int:
    """Current data-axis width (0 without a mesh)."""
    if self.mesh is None:
      return 0
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    return int(self.mesh.shape[mesh_lib.DATA_AXIS])

  @property
  def is_degraded(self) -> bool:
    """True once degrade_mesh() stepped below the launch topology."""
    return self._n_degraded > 0

  def degrade_mesh(self) -> Optional[int]:
    """Rebuilds the mesh at the next lower dp (8 -> 4 -> 2 -> 1) after
    a permanent device fault; returns the new dp, or None when no
    smaller topology exists (single device, or no mesh at all).

    Re-places the weights on the surviving devices and recompiles the
    forward (jit caches per mesh, so a later un-degrade would be
    cheap). The caller owns resubmission of whatever was in flight on
    the old mesh; the stale transfer slot is abandoned here — its
    buffers lived on the dead topology.
    """
    if self.mesh is None:
      return None
    from deepconsensus_tpu.parallel import mesh as mesh_lib

    dp = int(self.mesh.shape[mesh_lib.DATA_AXIS])
    tp = int(self.mesh.shape.get(mesh_lib.MODEL_AXIS, 1))
    new_dp = dp // 2
    # The compiled batch must still split evenly over the data axis.
    while new_dp >= 1 and self.options.batch_size % new_dp:
      new_dp //= 2
    if new_dp < 1 or new_dp >= dp:
      return None
    devices = np.asarray(self.mesh.devices).reshape(-1)[:new_dp * tp]
    mesh = mesh_lib.make_mesh(dp=new_dp, tp=tp, devices=list(devices))
    if self.variables:
      self.variables = self._place_weights(self.variables, mesh)
    self.mesh = mesh
    self._forward = self._make_forward(mesh)
    if self._make_ragged_forward is not None:
      self._ragged_forward = self._make_ragged_forward(mesh)
    self._input_sharding = mesh_lib.batch_sharding(mesh)
    self._pending = None
    self._n_degraded += 1
    log.warning('mesh degraded to dp=%d (step %d of the ladder)',
                new_dp, self._n_degraded)
    return new_dp

  def finalize(self, dispatched) -> Tuple[np.ndarray, np.ndarray]:
    """Resolves a dispatch into (base ids [n, L], quality [n, L]).

    With --dispatch_timeout > 0 the blocking device sync is bounded by
    the dispatch watchdog; a hang becomes DispatchTimeoutError.
    """
    timeout = self.options.dispatch_timeout
    if timeout and timeout > 0:
      return _finalize_with_watchdog(self._finalize_sync, dispatched,
                                     timeout)
    return self._finalize_sync(dispatched)

  def _finalize_sync(self, dispatched) -> Tuple[np.ndarray, np.ndarray]:
    """Timing shell around the blocking drain: emits the pack's
    finalize_drain span, and a device_compute span running from the
    forward-launch stamp to drain completion. The two spans' start
    ordering is the span-derived overlap fraction: an overlapped pack
    was launched by a later dispatch (launch stamp BEFORE finalize
    began); a direct launch happens inside finalize."""
    handle = dispatched
    try:
      with obs_lib.stage(self.obs, obs_lib.trace.STAGE_FINALIZE,
                         pack=handle.seq) as st:
        try:
          return self._drain_sync(handle)
        finally:
          st.set(bytes=self._d2h_bytes_per_pack,
                 **self._count_expert_assignments(handle))
    finally:
      if handle.t_launch:
        # A wait, not work: launch to the end of the drain on the host's
        # clock. At dispatch depth d it spans about d pack periods, so
        # it is no measure of device time.
        obs_lib.record_stage(
            self.obs, obs_lib.trace.STAGE_DEVICE_COMPUTE,
            handle.t_launch, time.time(), cat=obs_lib.trace.CAT_WAIT,
            pack=handle.seq, bucket=handle.bucket, dp=self.mesh_dp,
            n_rows=handle.n)

  def _count_expert_assignments(self, handle) -> Dict[str, int]:
    """Adds a drained pack's per-expert assignment counts (the forward's
    third output, [layers, experts held]) to the registry; returns what
    the pack's `finalize_drain` span says of them. Nothing for a forward
    without sparse experts, or one that failed."""
    if not self._sparse_experts or handle.error is not None or (
        handle.outputs is None):
      return {}
    # dclint: allow=jit-hazards (finalize IS the sync point, and the
    # planes of this pack have just been drained)
    counts = np.asarray(handle.outputs[2])
    # Every position of the compiled pack is routed, padding included.
    total = (handle.outputs[0].size * int(self.params.num_experts_per_tok)
             * counts.shape[0])
    held, load_max = int(counts.sum()), int(counts.max())
    self._moe_total.inc(total)
    self._moe_held.inc(held)
    self._moe_load_max = max(self._moe_load_max, load_max)
    self.obs.set_gauge('moe_expert_load_max', self._moe_load_max)
    return {'moe_assignments_total': total, 'moe_assignments_held': held,
            'moe_expert_load_max': load_max,
            'moe_expert_load_min': int(counts.min())}

  def _drain_sync(self, dispatched) -> Tuple[np.ndarray, np.ndarray]:
    """The blocking half of finalize: device sync, plus host quality
    math only on the fallback path (with the device epilogue on, the
    quality integers already left the device final — this is a pure
    uint8 drain)."""
    out_a, out_b, n = self.raw_outputs(dispatched)
    hang_s = getattr(dispatched, 'hang_s', 0.0)
    if hang_s:
      # Injected device hang (ENV_DEVICE_HANG_AT_PACK): simulate a
      # wedged sync so the watchdog path is provable on CPU.
      dispatched.hang_s = 0.0
      time.sleep(hang_s)
    if not self._d2h_bytes_per_pack:
      # Actual drain size: 2 uint8 planes with the epilogue, int32 ids
      # + f32 max_prob without (the bench A/B's measured numerator).
      self._d2h_bytes_per_pack = int(out_a.nbytes + out_b.nbytes)
    # Slice on the host: indexing the device array with a varying [:n]
    # would lower (and cache) a fresh jitted slice per tail size.
    # dclint: allow=jit-hazards (finalize IS the sync point: results
    # must land on the host here, after the async dispatch window)
    out_a = np.asarray(out_a)[:n]
    # dclint: allow=jit-hazards (same deliberate sync as out_a)
    out_b = np.asarray(out_b)[:n]
    if self._device_epilogue:
      return out_a, out_b  # (ids_u8, quals_u8): nothing left to compute
    pred_ids, max_prob = out_a, out_b
    error_prob = np.maximum(1.0 - max_prob, 1e-12)
    quality = -10.0 * np.log10(error_prob)
    opts = self.options
    if opts.dc_calibration_values.enabled:
      quality = calibration_lib.calibrate_quality_scores(
          quality, opts.dc_calibration_values
      )
    quality = np.minimum(quality, opts.max_base_quality)
    quality = np.round(quality, decimals=0).astype(np.int32)
    quality = np.maximum(quality, 0)
    return pred_ids, quality

  def predict(self, rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Synchronous convenience wrapper."""
    return self.finalize(self.dispatch(rows))


def preprocess_zmw(
    zmw_input, options: InferenceOptions
) -> Tuple[List[Dict[str, Any]], collections.Counter]:
  """One ZMW -> list of window feature dicts
  (reference: quick_inference.py:535-564)."""
  subreads, name, layout, _split, window_widths = zmw_input
  pileup = reads_to_pileup(subreads, name, layout, window_widths)
  features = list(pileup.iter_window_features())
  return features, pileup.counter


# Feature-dict fields shipped as plain pickled metadata by the shm
# transport (everything except the bulk 'subreads' tensor).
_SHM_META_FIELDS = (
    'subreads/num_passes', 'name', 'window_pos',
    'ccs_base_quality_scores', 'overflow', 'ec', 'np_num_passes', 'rq',
    'rg',
)


def _create_shm(size: int, prefix: Optional[str] = None):
  """One shm segment, named under `prefix` when given so the watchdog
  can reclaim a killed worker's orphans by glob (faults
  .reclaim_shm_segments) without touching other batches' segments."""
  from multiprocessing import shared_memory

  if not prefix:
    return shared_memory.SharedMemory(create=True, size=size)
  for attempt in itertools.count():
    name = f'{prefix}{os.getpid()}_{attempt}'
    try:
      return shared_memory.SharedMemory(name=name, create=True, size=size)
    except FileExistsError:
      continue


def preprocess_zmw_shm(zmw_input, options: InferenceOptions,
                       shm_prefix: Optional[str] = None):
  """Pool-worker variant: the bulk window tensors travel through one
  POSIX shared-memory segment per ZMW instead of the result pickle.

  The pickle channel is the measured bottleneck of the worker pool
  (~6 MB/ZMW through a pipe); with shm the pickle carries only names
  and offsets. Returns (shm_name, window_metadata, counter); the
  parent re-views the tensors with _features_from_shm and owns the
  segment's lifetime (workers unregister from their resource tracker).
  """
  from multiprocessing import resource_tracker

  features, counter = preprocess_zmw(zmw_input, options)
  total = sum(f['subreads'].nbytes for f in features)
  if not total:
    return None, [{k: f[k] for k in _SHM_META_FIELDS} for f in features
                  ], counter
  shm = _create_shm(total, shm_prefix)
  try:
    meta = []
    offset = 0
    for f in features:
      arr = f['subreads']
      flat = np.ndarray(arr.shape, arr.dtype, buffer=shm.buf,
                        offset=offset)
      flat[...] = arr
      entry = {k: f[k] for k in _SHM_META_FIELDS}
      # bq values fit int16 (-1..93); int64 would dominate the metadata
      # pickle (~120 KB/ZMW of the ~130 KB total).
      entry['ccs_base_quality_scores'] = (
          entry['ccs_base_quality_scores'].astype(np.int16)
      )
      entry['_shape'] = arr.shape
      entry['_dtype'] = arr.dtype.str
      entry['_offset'] = offset
      offset += arr.nbytes
      meta.append(entry)
  except BaseException:
    # Packing failed: this worker still owns the segment.
    shm.close()
    shm.unlink()
    raise
  name = shm.name
  shm.close()
  # The worker's resource tracker would unlink the segment when the
  # worker exits; ownership transfers to the parent instead.
  try:
    resource_tracker.unregister(f'/{name}', 'shared_memory')
  # dclint: allow=typed-faults (best-effort unregister: on failure the
  # tracker merely logs a spurious leak warning at exit)
  except Exception:  # pragma: no cover - tracker internals shifted
    pass
  return name, meta, counter


_POOL_ENV_PREFIXES = ('DCTPU_', 'DC_TPU_')


def _pool_env() -> Dict[str, str]:
  return {k: v for k, v in os.environ.items()
          if k.startswith(_POOL_ENV_PREFIXES)}


def _pool_init(env: Dict[str, str]) -> None:
  """Pool initializer: fork-server workers inherit the environment the
  server started with, so the repo's own knobs (fault hooks, the
  native off-switch) are carried over from the pool's creator."""
  os.environ.update(env)


def _pool_worker(zmw_input, options: InferenceOptions,
                 shm_prefix: Optional[str] = None):
  """starmap payload: never raises, so the parent always receives every
  created shm name (a raising task would make starmap discard ALL
  results, orphaning the successful workers' segments forever)."""
  try:
    name = zmw_input[1] if len(zmw_input) > 1 else None
    if isinstance(name, str):
      faults.maybe_kill_worker(name)
    return 'ok', preprocess_zmw_shm(zmw_input, options, shm_prefix)
  # dclint: allow=typed-faults (routes the error to the parent as an
  # ('error', traceback) result; raising would make starmap discard
  # the whole batch and orphan sibling shm segments)
  except BaseException:
    import traceback

    return 'error', traceback.format_exc()


def _features_from_shm(result):
  """Parent-side inverse of preprocess_zmw_shm.

  Returns (features, counter, shm_handle_or_None); the caller must
  close+unlink the handle once the features are consumed.
  """
  from multiprocessing import shared_memory

  shm_name, meta, counter = result
  shm = None
  features = []
  if shm_name is not None:
    shm = shared_memory.SharedMemory(name=shm_name)
  for entry in meta:
    f = {k: entry[k] for k in _SHM_META_FIELDS}
    f['ccs_base_quality_scores'] = (
        f['ccs_base_quality_scores'].astype(np.int64)
    )
    if shm is not None:
      f['subreads'] = np.ndarray(
          entry['_shape'], np.dtype(entry['_dtype']), buffer=shm.buf,
          offset=entry['_offset'],
      )
    features.append(f)
  return features, counter, shm


# The model stage (triage -> pack -> dispatch -> finalize) lives in
# inference/engine.py as ConsensusEngine; this pipeline is one of its
# thin clients (the serve daemon is the other). Aliases keep the
# historical runner.py names importable.
_ccs_quals_array = engine_lib.ccs_quals_array
skipped_window_arrays = engine_lib.skipped_window_arrays
_triage_windows = engine_lib.triage_windows
_WindowPacker = engine_lib._WindowPacker
ConsensusEngine = engine_lib.ConsensusEngine


class _MolState:
  """One molecule's windows accumulating toward stitch/emit.

  Entries are appended in the legacy prediction order (skip windows
  first, then model windows, each in featurize order) so the stable
  in-stitch sort reproduces the string plane's byte-exact output.
  Model windows are appended as placeholders and filled in when their
  pack finalizes; model_entries keeps each one's draft-CCS copy so a
  failed pack can adopt the CCS without the (released) feature tensor.
  """

  __slots__ = ('name', 'batch', 'meta', 'pos', 'ids', 'quals',
               'model_entries', 'status')

  def __init__(self, name: str, batch: '_BatchState', meta: Tuple):
    self.name = name
    self.batch = batch
    self.meta = meta  # (ec, np_num_passes, rq, rg)
    self.pos: List[int] = []
    self.ids: List[Optional[np.ndarray]] = []
    self.quals: List[Optional[np.ndarray]] = []
    self.model_entries: List[Tuple[int, np.ndarray, np.ndarray]] = []
    self.status = 'ok'  # ok | adopted (ccs-fallback) | dropped

  def append_resolved(self, window_pos: int, ids: np.ndarray,
                      quals: np.ndarray) -> None:
    self.pos.append(window_pos)
    self.ids.append(ids)
    self.quals.append(quals)

  def append_pending(self, window_pos: int, ccs_ids: np.ndarray,
                     ccs_bq: np.ndarray) -> int:
    idx = len(self.pos)
    self.pos.append(window_pos)
    self.ids.append(None)
    self.quals.append(None)
    self.model_entries.append((idx, ccs_ids, ccs_bq))
    self.batch.pending += 1
    return idx

  def set_result(self, idx: int, ids: Optional[np.ndarray],
                 quals: Optional[np.ndarray]) -> None:
    """Resolves one model slot (ids=None marks a failed pack's slot).
    Always decrements the batch's pending count, even for molecules
    already adopted/dropped by an earlier pack failure."""
    if self.status == 'ok' and ids is not None:
      self.ids[idx] = ids
      self.quals[idx] = quals
    self.batch.pending -= 1

  def adopt_ccs(self, options: InferenceOptions) -> bool:
    """ccs-fallback for a model-stage fault: every model window (in
    this pack, other packs, resolved or not) adopts its draft CCS so
    the molecule degrades consistently, like the string plane's whole-
    molecule fallback."""
    for idx, ccs_ids, ccs_bq in self.model_entries:
      self.ids[idx] = ccs_ids
      self.quals[idx] = _ccs_quals_array(ccs_bq, options)
    return True


class _BatchState:
  """Completion tracker for one featurize batch flowing through the
  packed model stage toward the stitch/emit worker."""

  __slots__ = ('feat', 'mols', 'pending', 'featurized', 'n_windows')

  def __init__(self, feat: Dict[str, Any]):
    self.feat = feat
    self.mols: Dict[str, _MolState] = {}
    self.pending = 0
    self.featurized = False
    self.n_windows = 0

  def mol(self, fd: Dict[str, Any]) -> _MolState:
    name = (fd['name'] if isinstance(fd['name'], str)
            else fd['name'].decode())
    state = self.mols.get(name)
    if state is None:
      state = self.mols[name] = _MolState(
          name, self,
          (fd['ec'], fd['np_num_passes'], fd['rq'], fd['rg']))
    return state

  @property
  def complete(self) -> bool:
    return self.featurized and self.pending == 0


def run_inference(
    subreads_to_ccs: str,
    ccs_bam: Optional[str],
    checkpoint: Optional[str],
    output: str,
    options: Optional[InferenceOptions] = None,
    runner: Optional[ModelRunner] = None,
    ccs_fasta: Optional[str] = None,
    mesh=None,
) -> Dict[str, Any]:
  """Full inference pipeline; returns the counters dict
  (reference run(): quick_inference.py:794-963).

  Fault tolerance (inference/faults.py): with options.on_zmw_error !=
  'fail', per-ZMW failures in any stage are quarantined to
  <output>.failed.jsonl — optionally emitting the draft CCS read —
  instead of aborting the run; the featurization pool runs under a
  watchdog (batch_timeout/batch_retries); and output streams into
  <output>.tmp with a crash-consistent progress manifest, renamed into
  place only on success. options.resume replays the feeder past the
  committed groups of an interrupted run.
  """
  options = options or InferenceOptions()
  # Run-scoped tracing: honor DCTPU_TRACE unless the CLI already
  # configured a writer, and stamp every span (and dead letter) from
  # this run's threads with one minted trace id. Before the runner is
  # built, so that its start-up spans carry the id too.
  if not obs_lib.trace.enabled():
    obs_lib.trace.configure_from_env(tier='run')
  run_trace_id = obs_lib.trace.mint_trace_id()
  obs_lib.trace.set_trace_id(run_trace_id)
  if runner is None:
    if checkpoint is None:
      # dclint: allow=typed-faults (API misuse by the caller, not a
      # data-plane fault; the CLI maps it to exit code 2)
      raise ValueError('need checkpoint or runner')
    runner = ModelRunner.from_checkpoint(checkpoint, options, mesh=mesh)
  params = runner.params
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  # Bucket-aware geometry: an explicit options.window_buckets (CLI
  # --window_buckets) must be consistent with the checkpoint's base
  # max_length; unset follows params.window_buckets (single shape when
  # that too is unset).
  options.window_buckets = config_lib.normalize_window_buckets(
      options.window_buckets or getattr(params, 'window_buckets', None),
      params.max_length)

  fail_fast = options.on_zmw_error == faults.OnZmwError.FAIL
  dead_letter: Optional[faults.DeadLetterWriter] = None
  quarantine: Optional[faults.Quarantine] = None

  # Atomic, resumable output: everything streams into <output>.tmp; the
  # manifest records (feeder groups committed, flushed tmp size) after
  # every consumed batch, and the tmp file is renamed into place only
  # when the run completes. A crashed run never leaves a plausible-
  # looking final output, and --resume truncates the tmp file to the
  # last committed byte and replays the feeder past committed groups.
  manifest = faults.ProgressManifest(output + '.progress.json')
  source = {
      'subreads_to_ccs': subreads_to_ccs,
      'ccs_bam': ccs_bam,
      'ccs_fasta': ccs_fasta,
      'output': output,
      'shard': list(options.shard) if options.shard else None,
  }
  out_tmp = output + '.tmp'
  resume_skip_groups = 0
  resuming = False
  if options.resume and options.end_after_stage == 'full':
    state = manifest.load()
    if state is None:
      log.info('--resume: no usable progress manifest; starting fresh')
    else:
      faults.validate_resume_source(state, source)
      committed = int(state['tmp_size'])
      if os.path.exists(out_tmp) and os.path.getsize(out_tmp) >= committed:
        with open(out_tmp, 'r+b') as f:
          f.truncate(committed)
        resume_skip_groups = int(state['groups_done'])
        resuming = True
        log.info(
            'resuming after %d committed feeder group(s); %s truncated '
            'to %d bytes', resume_skip_groups, out_tmp, committed)
      else:
        log.warning(
            '--resume: %s missing or shorter than the committed %d '
            'bytes; restarting from scratch', out_tmp, committed)

  if not fail_fast:
    dead_letter = faults.DeadLetterWriter(output + '.failed.jsonl',
                                          append=resuming)
    quarantine = faults.Quarantine(options.on_zmw_error, dead_letter)

  layout = FeatureLayout(
      max_passes=options.max_passes,
      max_length=options.max_length,
      use_ccs_bq=options.use_ccs_bq,
      window_buckets=options.window_buckets,
  )
  # dclint: lock-free (producer thread owns the feeder's counter while
  # it runs; the main thread merges into it only after the join)
  feeder, counter = create_proc_feeder(
      subreads_to_ccs=subreads_to_ccs,
      ccs_bam=ccs_bam,
      ccs_fasta=ccs_fasta,
      layout=layout,
      ins_trim=options.ins_trim,
      use_ccs_smart_windows=options.use_ccs_smart_windows,
      limit=options.limit,
      shard=options.shard,
      quarantine=quarantine,
      resume_skip_groups=resume_skip_groups,
      max_record_bytes=options.max_record_bytes,
  )
  watchdog: Optional[faults.PoolWatchdog] = None
  if (options.cpus and options.cpus > 1
      and options.end_after_stage != 'dc_input'):
    # dc_input runs never featurize; forking idle workers would only
    # pollute the stage timing the flag exists to measure.
    import multiprocessing

    # This process already holds the accelerator (the runner above
    # placed the weights), and the watchdog re-creates the pool
    # mid-run: workers come from a fork server that never touched the
    # device, not from a fork of this process.
    pool_ctx = multiprocessing.get_context('forkserver')
    pool_ctx.set_forkserver_preload([__name__])
    watchdog = faults.PoolWatchdog(
        lambda: pool_ctx.Pool(options.cpus, initializer=_pool_init,
                              initargs=(_pool_env(),)),
        timeout=options.batch_timeout,
        retries=options.batch_retries,
        quarantine=quarantine,
    )
  # Per-batch shm namespace: pool segments are created under
  # <run>b<seq>_ so a SIGKILLed worker's orphans can be reclaimed by
  # prefix without touching other in-flight batches' segments.
  shm_run_prefix = f'dctpu_{os.getpid()}_'
  outcome = stitch.OutcomeCounter()
  # dclint: lock-free (emit worker owns it while running; the main
  # thread writes only the disjoint n_model_pack* keys, merges after
  # the join — see the counter-discipline note in the main loop)
  window_counter: collections.Counter = collections.Counter()
  # dclint: lock-free (list.append is atomic under the GIL; rows are
  # only aggregated after both worker threads have joined)
  timing_rows: List[Dict[str, Any]] = []
  # dclint: lock-free (single writer: the emit worker via nonlocal;
  # the main thread reads it after the emit queue drains)
  fastq_lines = 0

  if output.endswith('.bam'):
    from deepconsensus_tpu.io.bam_writer import BamWriter

    # Carry the CCS BAM header (RG/PG lines) into the output so the
    # per-read RG:Z tags reference declared read groups, as the
    # reference does by opening the writer with template=ccs
    # (quick_inference.py:894-897). Falls back to a bare @HD when no
    # CCS BAM is in play (ccs_fasta mode).
    header_text = '@HD\tVN:1.5\tSO:unknown\n'
    if ccs_bam:
      with bam_lib.BamReader(
          ccs_bam, max_record_bytes=options.max_record_bytes) as ccs_reader:
        if ccs_reader.header_text:
          header_text = ccs_reader.header_text
          if not header_text.endswith('\n'):
            header_text += '\n'
    writer = BamWriter(out_tmp, header_text=header_text, append=resuming)

    def emit_read(name: str, seq: bytes, quals: np.ndarray, meta) -> None:
      ec, np_passes, rq, rg = meta
      tags = {}
      if ec is not None:
        tags['ec'] = float(ec)
      if np_passes is not None:
        tags['np'] = int(np_passes)
      if rq is not None:
        tags['rq'] = float(rq)
      if rg is not None:
        tags['RG'] = str(rg)
      # Non-PacBio names (e.g. ccs_fasta inputs with plain names) have
      # no movie/zmw/type structure; omit the zm tag rather than crash.
      parts = name.split('/')
      if len(parts) >= 2:
        try:
          tags['zm'] = int(parts[1])
        except ValueError:
          pass
      writer.write(
          name,
          seq.decode('ascii'),
          np.asarray(quals, dtype=np.uint8),
          tags=tags,
      )

    close_out = writer.close
    sink_flush = writer.flush
    sink_tell = writer.tell
  else:
    writer = open(out_tmp, 'ab' if resuming else 'wb')

    def emit_read(name: str, seq: bytes, quals: np.ndarray, meta) -> None:
      del meta
      writer.write(stitch.format_fastq_bytes(name, seq, quals))

    close_out = writer.close
    sink_flush = writer.flush
    sink_tell = writer.tell

  partial = True
  counters: Dict[str, Any] = {}
  try:
    try:

      def featurize_batch(zmw_batch, shm_prefix=''):
        """Producer-side: BAM records -> window features for one batch."""
        t0 = time.time()
        fallbacks = [
            z for z in zmw_batch if isinstance(z, faults.CcsFallback)
        ]
        zmws = [
            z for z in zmw_batch if not isinstance(z, faults.CcsFallback)
        ]
        all_windows: List[Dict[str, Any]] = []
        zmw_counters = []
        shm_handles = []
        n_subreads = 0
        pairs = []  # (zmw_input, features, per-zmw counter)

        def quarantine_featurize(zmw_input, error):
          ccs_read = zmw_input[0][-1]
          item = quarantine.handle(
              zmw_input[1], 'featurize', error,
              fallback=lambda r=ccs_read: faults.fallback_from_ccs_read(r),
          )
          if item is not None:
            fallbacks.append(item)

        if watchdog is not None:
          # Bulk tensors travel via shared memory; the result pickle
          # carries only names/offsets (the pipe was the bottleneck).
          # _pool_worker never raises, so starmap always returns and the
          # parent always sees every created shm name (a raising task
          # would discard ALL results, orphaning sibling segments).
          try:
            raw = watchdog.run_batch(
                _pool_worker,
                [(z, options, shm_prefix) for z in zmws],
                chunksize=4,
                shm_prefix=shm_prefix,
            )
          except faults.WatchdogTimeout as e:
            if quarantine is None:
              raise
            # The whole batch exhausted the watchdog; quarantine every
            # ZMW in it (the pool is already re-spawned and the batch's
            # shm segments reclaimed).
            for z in zmws:
              quarantine_featurize(z, e)
            raw = []
          try:
            for zmw_input, (status, payload) in zip(zmws, raw):
              if status != 'ok':
                if quarantine is None:
                  zmw_name = (zmw_input[1]
                              if len(zmw_input) > 1 else None)
                  raise faults.ZmwFault(
                      zmw_name if isinstance(zmw_name, str) else None,
                      'featurize', faults.classify_error(payload),
                      f'featurization worker failed:\n{payload}'
                  )
                quarantine_featurize(
                    zmw_input,
                    f'featurization worker failed:\n{payload}',
                )
                continue
              features, zmw_counter, shm = _features_from_shm(payload)
              pairs.append((zmw_input, features, zmw_counter))
              if shm is not None:
                shm_handles.append(shm)
          except BaseException:
            # Workers unregistered the segments from their resource
            # tracker, so this is the only cleanup: unlink every segment
            # named in raw (attached or not) before propagating.
            from multiprocessing import shared_memory

            attached = {s.name for s in shm_handles}
            for shm in shm_handles:
              try:
                shm.close()
                shm.unlink()
              except OSError:
                pass
            for status, payload in raw:
              if (status == 'ok' and payload[0] is not None
                  and payload[0] not in attached):
                try:
                  leaked = shared_memory.SharedMemory(name=payload[0])
                  leaked.close()
                  leaked.unlink()
                except OSError:
                  pass
            faults.reclaim_shm_segments(shm_prefix)
            raise
        else:
          for z in zmws:
            try:
              features, zmw_counter = preprocess_zmw(z, options)
            except Exception as e:
              if quarantine is None:
                raise
              quarantine_featurize(z, e)
              continue
            pairs.append((z, features, zmw_counter))
        for zmw_input, features, zmw_counter in pairs:
          n_subreads += len(zmw_input[0]) - 1
          zmw_counters.append(zmw_counter)
          all_windows.extend(features)
        t_end = time.time()
        obs_lib.record_stage(runner.obs, obs_lib.trace.STAGE_FEATURIZE,
                             t0, t_end, n_zmws=len(zmw_batch),
                             n_windows=len(all_windows))
        return {
            'windows': all_windows,
            'counters': zmw_counters,
            'n_subreads': n_subreads,
            'n_zmws': len(zmw_batch),
            'preprocess_time': t_end - t0,
            'shm_handles': shm_handles,
            'fallbacks': fallbacks,
        }

      def release_shm(feat):
        for shm in feat.get('shm_handles', ()):
          try:
            shm.close()
            shm.unlink()
          except (FileNotFoundError, OSError):
            pass
        feat['shm_handles'] = []

      def emit_fallback(fb) -> None:
        """Emits a quarantined ZMW's draft CCS read (ccs-fallback)."""
        nonlocal fastq_lines
        result = stitch.fallback_to_arrays(
            fb.molecule_name,
            fb.sequence,
            fb.quality_scores,
            min_quality=options.min_quality,
            min_length=options.min_length,
            max_base_quality=options.max_base_quality,
            counter=window_counter,
        )
        if result is None:
          return
        emit_read(fb.molecule_name, result[0], result[1],
                  (fb.ec, fb.np_num_passes, fb.rq, fb.rg))
        fastq_lines += 1

      # Three-stage pipeline: featurize (producer thread) -> model
      # (main thread: triage + cross-batch packer + dispatch pipeline)
      # -> stitch/emit (dedicated worker thread behind a bounded
      # queue), so device forwards never wait on postprocess or disk.
      # Counter discipline: the producer owns the feeder's `counter`;
      # the main thread updates window triage counts, the emit worker
      # updates outcome/fallback counts (disjoint keys), and everything
      # merges in the sidecar epilogue.
      import queue as queue_lib
      import threading

      feat_queue: 'queue_lib.Queue' = queue_lib.Queue(maxsize=2)
      stop = threading.Event()
      skip_featurize = options.end_after_stage == 'dc_input'

      def put(item) -> bool:
        """Bounded put that aborts when the consumer has bailed."""
        while not stop.is_set():
          try:
            feat_queue.put(item, timeout=0.5)
            return True
          except queue_lib.Full:
            continue
        return False

      def producer():
        obs_lib.trace.set_trace_id(run_trace_id)  # thread-local
        try:
          def flush(zmw_batch) -> bool:
            if not zmw_batch:
              return True
            if skip_featurize:
              # dc_input stage: measure BAM decode/grouping only, so the
              # runtime CSV still carries one row per batch.
              timing_rows.append(
                  dict(stage='dc_input',
                       runtime=time.time() - flush.t_start,
                       n_zmws=len(zmw_batch), n_examples=0,
                       n_subreads=sum(
                           len(z[0]) - 1 for z in zmw_batch
                           if not isinstance(z, faults.CcsFallback))))
              flush.t_start = time.time()
              return True
            feat = featurize_batch(
                zmw_batch, f'{shm_run_prefix}b{flush.seq}_')
            flush.seq += 1
            # Resume bookkeeping: how far the feeder had advanced when
            # this batch was cut (includes skipped/sharded-out groups,
            # which the resume replay skips the same way).
            feat['groups_end'] = counter['n_zmw_processed']
            last = zmw_batch[-1]
            feat['last_zmw'] = (
                last.molecule_name
                if isinstance(last, faults.CcsFallback) else last[1]
            )
            ok = put(('batch', feat))
            if not ok:
              # Consumer bailed mid-flight: this batch will never be
              # consumed, and its shm segments have no other owner.
              release_shm(feat)
            return ok

          flush.t_start = time.time()
          flush.seq = 0
          zmw_batch = []
          for zmw_input in feeder():
            zmw_batch.append(zmw_input)
            if options.batch_zmws and len(zmw_batch) >= options.batch_zmws:
              if not flush(zmw_batch):
                return
              zmw_batch = []
          if not flush(zmw_batch):
            return
          put(('done', None))
        except BaseException as e:  # surface worker failures to the main thread
          put(('error', e))

      full_mode = options.end_after_stage == 'full'
      model_mode = options.end_after_stage in ('run_model', 'full')
      crash_after = faults.injected_crash_after_batches()
      ccs_row = row_indices(options.max_passes, options.use_ccs_bq)[4][0]
      states: 'collections.deque[_BatchState]' = collections.deque()

      def on_pack_failure(slots, pack_seq: int, error) -> None:
        """Attributes a packed-batch failure to its member molecules:
        each affected molecule is quarantined once (adopting its draft
        CCS under ccs-fallback, or dropped under skip), with the pack id
        and its window count recorded in the dead-letter entry."""
        for mol, idx in slots:
          mol.set_result(idx, None, None)
        if quarantine is None:
          raise error
        members: Dict[_MolState, int] = {}
        for mol, _ in slots:
          members[mol] = members.get(mol, 0) + 1
        for mol, n_in_pack in members.items():
          if mol.status != 'ok':
            continue  # already quarantined by an earlier failed pack
          adopted = quarantine.handle(
              mol.name, 'model', error,
              fallback=lambda m=mol: m.adopt_ccs(options),
              extra={'model_pack': pack_seq,
                     'n_windows_in_pack': n_in_pack},
          )
          mol.status = 'adopted' if adopted else 'dropped'

      engine: Optional[ConsensusEngine] = None
      if model_mode:
        # Tickets are (mol, idx) slots; a delivered row resolves its
        # molecule's pending window directly.
        engine = ConsensusEngine(
            runner, options,
            deliver=lambda slot, ids, quals: slot[0].set_result(
                slot[1], ids, quals),
            on_pack_failure=on_pack_failure,
            timing_rows=timing_rows)

      def ingest_batch(feat) -> None:
        """Main-thread stage: triage a featurize batch, copy what the
        emit stage will need out of shm, and feed model windows to the
        packer. The batch's _BatchState completes (and becomes eligible
        for emit) once every pack containing its windows has drained."""
        for zmw_counter in feat['counters']:
          window_counter.update(zmw_counter)
        all_windows = feat['windows']
        timing_rows.append(
            dict(stage='preprocess', runtime=feat['preprocess_time'],
                 n_zmws=feat['n_zmws'], n_examples=len(all_windows),
                 n_subreads=feat['n_subreads']))
        if not model_mode:  # tf_examples: featurization was the point
          return
        state = _BatchState(feat)
        state.n_windows = len(all_windows)
        to_model, to_skip = _triage_windows(all_windows, options,
                                            window_counter)
        for fd in to_skip:
          state.mol(fd).append_resolved(
              fd['window_pos'], *skipped_window_arrays(fd, options))
        slots: List[Tuple[_MolState, int]] = []
        for fd in to_model:
          mol = state.mol(fd)
          # Copies: the feature tensors may live in shm segments that
          # are released as soon as this function returns.
          ccs_ids = fd['subreads'][ccs_row, :, 0].astype(np.uint8)
          ccs_bq = np.array(fd['ccs_base_quality_scores'])
          slots.append(
              (mol,
               mol.append_pending(fd['window_pos'], ccs_ids, ccs_bq)))
        if to_model:
          # A list (not a stacked array): widths may mix across buckets;
          # the engine groups per bucket preserving featurize order.
          engine.submit([fd['subreads'] for fd in to_model], slots)
        feat['windows'] = None
        state.featurized = True
        states.append(state)

      emit_queue: Optional['queue_lib.Queue'] = None
      emit_thread: Optional[threading.Thread] = None
      # dclint: lock-free (single-writer cell: only the emit worker
      # stores into it; the main thread polls it via check_emit)
      emit_error: List[Optional[BaseException]] = [None]
      emit_stop = threading.Event()

      def check_emit() -> None:
        if emit_error[0] is not None:
          raise emit_error[0]

      def emit_batch_state(state: _BatchState) -> None:
        """Emit-worker stage: stitch + filter + write one featurize
        batch's molecules (sorted by name, matching the string plane's
        global (name, pos) sort order), then its ccs-fallback reads,
        then commit the progress manifest — only after the sink flushed
        this batch's bytes, preserving the durability contract."""
        nonlocal fastq_lines
        feat = state.feat
        t0 = time.time()
        for name in sorted(state.mols):
          mol = state.mols[name]
          if mol.status == 'dropped':
            continue
          try:
            result = stitch.stitch_arrays(
                name,
                np.asarray(mol.pos, dtype=np.int64),
                mol.ids,
                mol.quals,
                max_length=options.max_length,
                min_quality=options.min_quality,
                min_length=options.min_length,
                outcome_counter=outcome,
            )
            if result is not None:
              emit_read(name, result[0], result[1], mol.meta)
              fastq_lines += 1
          except Exception as e:
            if quarantine is None:
              raise
            # No draft CCS survives to this stage; stitch faults can
            # only skip the molecule.
            quarantine.handle(name, 'stitch', e, fallback=None)
        for fb in feat.get('fallbacks', ()):
          emit_fallback(fb)
        t_end = time.time()
        obs_lib.record_stage(runner.obs, obs_lib.trace.STAGE_STITCH,
                             t0, t_end, n_zmws=feat['n_zmws'],
                             n_windows=state.n_windows)
        timing_rows.append(
            dict(stage='stitch_and_write_fastq',
                 runtime=t_end - t0, n_zmws=feat['n_zmws'],
                 n_examples=state.n_windows,
                 n_subreads=feat['n_subreads']))
        if 'groups_end' in feat:
          # Durability point: flush the sink so the manifest's
          # (groups_done, tmp_size) pair names a valid output prefix
          # that --resume can truncate back to.
          sink_flush()
          manifest.commit(
              groups_done=feat['groups_end'],
              tmp_size=sink_tell(),
              source=source,
              last_zmw=feat.get('last_zmw'),
          )

      def emit_worker() -> None:
        obs_lib.trace.set_trace_id(run_trace_id)  # thread-local
        emitted = 0
        try:
          while not emit_stop.is_set():
            try:
              state = emit_queue.get(timeout=0.2)
            except queue_lib.Empty:
              continue
            if state is None:
              return
            emit_batch_state(state)
            emitted += 1
            if crash_after and emitted >= crash_after:
              # dclint: allow=typed-faults (fault-injection hook: the
              # resilience tests expect a bare RuntimeError crash)
              raise RuntimeError(
                  f'injected crash after {emitted} batch(es) '
                  f'({faults.ENV_CRASH_AFTER_BATCHES})'
              )
        # dclint: allow=typed-faults (routes the error to the main
        # thread through the emit_error cell; check_emit() re-raises)
        except BaseException as e:  # surfaced via check_emit()
          emit_error[0] = e

      def emit_put(state) -> None:
        """Bounded put that surfaces an emit-worker death instead of
        blocking forever on its abandoned queue."""
        while True:
          check_emit()
          try:
            emit_queue.put(state, timeout=0.5)
            return
          except queue_lib.Full:
            continue

      def pop_ready() -> None:
        """Hands completed featurize batches to the emit worker, in
        featurize order (pack completion is monotone in that order
        because packs drain FIFO, so per-batch emission order — and
        resume byte-identity — are preserved)."""
        while states and states[0].complete:
          state = states.popleft()
          if emit_thread is not None:
            emit_put(state)

      if full_mode:
        emit_queue = queue_lib.Queue(
            maxsize=max(1, options.emit_queue_depth))
        emit_thread = threading.Thread(target=emit_worker, daemon=True)
        emit_thread.start()

      thread = threading.Thread(target=producer, daemon=True)
      thread.start()
      batches_ingested = 0
      startup_logged = False

      def log_startup() -> None:
        """One line, once the first forward has been launched: start-up,
        that forward's compile included, is behind us (read from the
        registry, so with tracing off too)."""
        nonlocal startup_logged
        if startup_logged or not runner.obs.histogram(
            obs_lib.stage_histogram_name(
                obs_lib.trace.STAGE_LAUNCH)).snapshot()['count']:
          return
        startup_logged = True
        log.info('%s', compiles_lib.format_startup(
            compiles_lib.startup_split(runner.obs)))

      try:
        while True:
          kind, payload = feat_queue.get()
          if kind == 'done':
            break
          if kind == 'error':
            raise payload
          try:
            check_emit()
            ingest_batch(payload)
          finally:
            release_shm(payload)
          pop_ready()
          batches_ingested += 1
          log_startup()
          if (crash_after and emit_thread is None
              and batches_ingested >= crash_after):
            # Without an emit stage the main thread is the whole
            # consumer; with one, the injection moves there so the
            # crash still lands just after a manifest commit (see
            # emit_worker).
            # dclint: allow=typed-faults (fault-injection hook: the
            # resilience tests expect a bare RuntimeError crash)
            raise RuntimeError(
                f'injected crash after {batches_ingested} batch(es) '
                f'({faults.ENV_CRASH_AFTER_BATCHES})'
            )
        if engine is not None:
          engine.flush()  # end of input: cut the tail pack, drain all
        log_startup()  # an input of one pack launches only here
        pop_ready()
        if states:
          # dclint: allow=typed-faults (internal invariant violation —
          # a packer accounting bug, not an input or request fault)
          raise RuntimeError(
              f'{len(states)} featurize batch(es) never completed the '
              'model stage (packer accounting bug)')
        if emit_thread is not None:
          emit_put(None)
          emit_thread.join()
          check_emit()
      finally:
        stop.set()
        emit_stop.set()
        thread.join(timeout=30)
        if emit_thread is not None:
          emit_thread.join(timeout=30)
        if engine is not None:
          window_counter['n_model_packs'] = engine.n_packs
          window_counter['n_model_pack_rows'] = engine.n_pack_rows
          window_counter['n_model_pad_rows'] = engine.n_pad_rows
          window_counter['n_starvation_flushes'] = (
              engine.n_starvation_flushes)
          window_counter['flush_padding_fraction'] = round(
              engine.flush_padding_fraction, 4)
          window_counter['n_oom_bisections'] = engine.n_oom_bisections
          window_counter['n_device_faults'] = engine.n_device_faults
          window_counter['n_dispatch_timeouts'] = (
              engine.n_dispatch_timeouts)
          dispatch_stats = getattr(runner, 'dispatch_stats', None)
          if dispatch_stats is not None:
            for key, value in dispatch_stats().items():
              window_counter[key] = value
          # The device this run really used and how its Pallas calls
          # resolved (compiled vs interpreter), for the sidecar.
          for key, value in pallas_util.execution_report().items():
            window_counter[key] = value
        if thread.is_alive():
          # Draining now would race the producer's put(); anything it
          # enqueues after our drain would leak its shm segments.
          log.warning(
              'producer thread still alive after 30s join; skipping '
              'queue drain (shm segments may leak until exit)')
        else:
          # Producer confirmed dead: drain queued batches (error paths)
          # without racing a concurrent put().
          while True:
            try:
              kind, payload = feat_queue.get_nowait()
            except queue_lib.Empty:
              break
            if kind == 'batch':
              release_shm(payload)
    finally:
      close_out()
      if watchdog is not None:
        watchdog.close()
    # Success: promote <output>.tmp to its final name atomically and
    # drop the progress manifest.
    os.replace(out_tmp, output)
    manifest.delete()
    partial = False
  finally:
    if dead_letter is not None:
      dead_letter.close()
    # dispatch_stats() carries non-numeric labels (inference_dtype);
    # Counter.update would try to add them to 0, so merge those by
    # assignment and keep the numeric tally semantics for the rest.
    for key, value in window_counter.items():
      if isinstance(value, (int, float)):
        counter[key] += value
      else:
        counter[key] = value
    if quarantine is not None:
      counter.update(quarantine.counters)
    # Sidecar outputs (reference: quick_inference.py:777-791,961-962),
    # written on failure too but stamped "partial": true so downstream
    # tooling can't mistake a crashed run for a complete one.
    counters = dict(counter)
    counters.update(dataclasses.asdict(outcome))
    if partial:
      counters['partial'] = True
    try:
      with open(output + '.runtime.csv', 'w', newline='') as f:
        csv_writer = csv.DictWriter(
            f, fieldnames=['stage', 'runtime', 'n_zmws', 'n_examples',
                           'n_subreads']
        )
        csv_writer.writeheader()
        csv_writer.writerows(timing_rows)
      with open(output + '.inference.json', 'w') as f:
        json.dump(counters, f, indent=2, sort_keys=True)
    # dclint: allow=typed-faults (sidecar stats are best-effort: a
    # failed write is logged, never masks the run's own outcome)
    except Exception:  # never mask the run's own error with sidecar IO
      log.exception('failed to write sidecar outputs for %s', output)
  if not outcome.success and options.end_after_stage == 'full':
    log.warning('No reads passed filters; outcome=%s', outcome)
  return counters


IMPORT_S = time.time() - _T_IMPORT


def record_import_span() -> None:
  """The `import_runner` span, as stamped at this module's scope (a test
  that emptied the start-up record puts it back with this)."""
  obs_lib.record_stage(None, obs_lib.trace.STAGE_IMPORT_RUNNER, _T_IMPORT,
                       _T_IMPORT + IMPORT_S, jax_preloaded=_JAX_PRELOADED)


record_import_span()
# From here on JAX's trace, lower and compile events are spans and
# counts (a program may jit before it builds a runner).
compiles_lib.install()
