"""Training loop: optax LAMB + SPMD data/tensor parallelism + orbax.

TPU-native re-design of the reference's custom tf.distribute loop
(reference: deepconsensus/models/model_train_custom_loop.py:93-358,
model_utils.py:478-669): one jitted train_step with sharded inputs over
a jax.sharding.Mesh, LAMB with warmup+polynomial decay, periodic eval
with checkpointing, best-checkpoint tracking by eval accuracy, a
checkpoint_metrics.tsv sidecar, and crash-resumable state.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import logging
import os
import queue as queue_lib
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import ml_collections
import numpy as np
import optax
from flax import struct
from flax.training import train_state as ts_lib
import orbax.checkpoint as ocp

from deepconsensus_tpu import constants
from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu import obs as obs_lib
from deepconsensus_tpu.models import checkpoints as checkpoints_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import losses as losses_lib
from deepconsensus_tpu.models import metrics as metrics_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.parallel import mesh as mesh_lib
from deepconsensus_tpu.parallel import partition_rules
from deepconsensus_tpu.parallel import ring_attention as ring_lib
from deepconsensus_tpu.preprocess.pileup import row_indices
from deepconsensus_tpu.utils import compile_cache


class TrainState(ts_lib.TrainState):
  dropout_rng: jax.Array = struct.field(pytree_node=True, default=None)
  # Non-trainable variable collections (e.g. BatchNorm batch_stats for
  # the conv family); empty dict for purely-functional models.
  model_state: Any = struct.field(pytree_node=True, default_factory=dict)


def create_learning_rate_fn(
    params: ml_collections.ConfigDict, decay_steps: int
):
  """Linear warmup into polynomial (power 1) decay, matching tf-models'
  LinearWarmup(PolynomialDecay) (reference model_utils.py:621-669)."""
  decay_steps = max(int(decay_steps), 1)
  poly = optax.polynomial_schedule(
      init_value=params.initial_learning_rate,
      end_value=params.end_learning_rate,
      power=1.0,
      transition_steps=decay_steps,
  )
  warmup_steps = int(params.warmup_steps)
  if warmup_steps <= 0:
    return poly

  def schedule(step):
    warm = poly(warmup_steps) * (step + 1) / warmup_steps
    return jnp.where(step < warmup_steps, warm, poly(step))

  return schedule


def _weight_decay_mask(params):
  """Excludes biases and layer-norm/rezero parameters from decay
  (reference exclude list: model_utils.py:641-648)."""

  def keep(path, leaf):
    del leaf
    parts = [getattr(k, 'key', getattr(k, 'name', str(k))) for k in path]
    path_str = '/'.join(parts).lower()
    if parts and parts[-1] in ('bias', 'alpha'):
      return False
    if 'layer_norm' in path_str or 'norm' in path_str:
      return False
    return True

  return jax.tree_util.tree_map_with_path(keep, params)


def create_optimizer(
    params: ml_collections.ConfigDict, decay_steps: int
) -> optax.GradientTransformation:
  lr_fn = create_learning_rate_fn(params, decay_steps)
  return optax.lamb(
      learning_rate=lr_fn,
      b1=params.beta_1,
      b2=params.beta_2,
      eps=params.epsilon,
      weight_decay=params.weight_decay_rate,
      mask=_weight_decay_mask,
  )


def resolve_pallas_wavefront(params: ml_collections.ConfigDict) -> bool:
  """None = auto: the Pallas DP wins on a real TPU backend (measured
  1.24x the scan DP on v5e); everywhere else the scan DP is faster
  than the interpreted kernel."""
  flag = params.get('use_pallas_wavefront', None)
  if flag is None:
    return jax.default_backend() == 'tpu'
  return bool(flag)


def make_loss(params: ml_collections.ConfigDict,
              mesh=None) -> losses_lib.AlignmentLoss:
  """mesh: the mesh the calling step is partitioned over (None for a
  single-device step); the Pallas scorers need it to run per shard."""
  width = params.get('band_width', None)
  return losses_lib.AlignmentLoss(
      del_cost=params.del_cost,
      loss_reg=params.loss_reg,
      width=width,
      use_pallas=resolve_pallas_wavefront(params),
      mesh=mesh,
      batch_axis=mesh_lib.DATA_AXIS,
  )


def ccs_row_from_batch(rows: jnp.ndarray, params) -> jnp.ndarray:
  """Extracts the CCS base row from the stacked input tensor."""
  ccs_range = row_indices(params.max_passes, params.use_ccs_bq)[4]
  return rows[:, ccs_range[0], :, 0]


@dataclasses.dataclass
class Trainer:
  """Owns jitted steps, checkpointing, and the metrics sidecars."""

  params: ml_collections.ConfigDict
  out_dir: str
  mesh: Optional[Any] = None
  # Elastic pod membership endpoint (parallel/elastic.py). When set,
  # the mesh is host-local, cross-host reduction runs through the pod's
  # bounded step_sync, and "the one writer" means the pod LEADER (lowest
  # live host id — survives leader loss) rather than jax process 0.
  pod: Optional[Any] = None
  # False when each pod member streams its OWN shard subset
  # (elastic_config['shard_streams']): batches are then host-local data,
  # not slices of a replicated global batch, so localize_batch must not
  # re-slice them.
  pod_slices_batches: bool = True

  def __post_init__(self):
    model_lib.refuse_inference_only_kind(self.params, 'train')
    # Bucketed training compiles one pjit step per bucket width over a
    # single param tree, so the bucket SET must be valid at
    # construction (strictly ascending, smallest == max_length — the
    # normalizer's contract) and the model family must be
    # length-agnostic: the FC head sizes its output Dense by
    # max_length, so one param tree cannot serve two widths there.
    try:
      buckets = config_lib.resolve_window_buckets(self.params)
    except ValueError as e:
      raise faults_lib.WindowBucketError(str(e)) from e
    if (len(buckets) > 1
        and not str(self.params.model_name).startswith('transformer')):
      raise faults_lib.WindowBucketError(
          f'window_buckets={tuple(buckets)} needs a length-agnostic '
          f'model, but model_name={self.params.model_name!r} has '
          'window-width-dependent parameter shapes (the FC head is '
          'sized by max_length); use a transformer config for bucketed '
          'training'
      )
    self.window_buckets = buckets
    # Distinct train-step traces (== compiled batch geometries). One
    # per bucket width on a clean bucketed run; mesh degradation
    # legitimately re-traces.
    self.n_train_forward_shapes = 0
    os.makedirs(self.out_dir, exist_ok=True)
    compile_cache.enable()
    self.model = model_lib.get_model(self.params)
    self.alignment_metric = metrics_lib.AlignmentMetric()
    if self.mesh is None:
      self.mesh = mesh_lib.make_mesh()
    self.loss_fn = make_loss(self.params, mesh=self.mesh)
    self._ckpt_dir = os.path.join(os.path.abspath(self.out_dir), 'checkpoints')
    self._checkpointer = ocp.StandardCheckpointer()
    self._metrics_tsv = os.path.join(self.out_dir, 'checkpoint_metrics.tsv')
    self._best_file = os.path.join(self.out_dir, 'best_checkpoint.txt')
    self._metrics_jsonl = os.path.join(self.out_dir, 'metrics.jsonl')
    # Central metrics registry (obs/): the metrics sidecar mirrors every
    # logged scalar into typed gauges and the training loop feeds the
    # step-time histogram, so `obs.metrics` sees train the same way it
    # sees serve/router/featurize tiers.
    self.obs = obs_lib.MetricsRegistry(tier='train')
    self.step_time_hist = self.obs.histogram(
        'train_step_s', help='wall time per training step')
    # Which eval metric selects best_checkpoint.txt. The reference pins
    # per_example_accuracy (whole-window exact match); on small or
    # held-out eval sets that metric can tie at 0.0 for every
    # checkpoint (observed on the bundled eval split), so it is
    # configurable — eval/identity_pred is the right selector
    # there.
    self._best_metric_name = self.params.get(
        'best_checkpoint_metric', constants.MAIN_EVAL_METRIC_NAME
    ) or constants.MAIN_EVAL_METRIC_NAME
    self._best_metric = -1.0
    self._tsv_columns = None
    # Recover best-metric and header state across restarts.
    if os.path.exists(self._metrics_tsv):
      with open(self._metrics_tsv) as f:
        header = f.readline().strip().split('\t')
        self._tsv_columns = header[1:]
        if self._best_metric_name in self._tsv_columns:
          idx = 1 + self._tsv_columns.index(self._best_metric_name)
          for line in f:
            parts = line.strip().split('\t')
            try:
              self._best_metric = max(self._best_metric, float(parts[idx]))
            except (IndexError, ValueError):
              continue

  # ---- state ---------------------------------------------------------
  def init_state(self, steps_total: int, seed: Optional[int] = None
                 ) -> TrainState:
    seed = self.params.seed if seed is None else seed
    rng = jax.random.PRNGKey(seed)
    rows = jnp.zeros(
        (1, self.params.total_rows, self.params.max_length, 1), jnp.float32
    )
    variables = self.model.init(rng, rows)
    tx = create_optimizer(self.params, steps_total)
    model_state = {k: v for k, v in variables.items() if k != 'params'}
    state = TrainState.create(
        apply_fn=self.model.apply,
        params=variables['params'],
        tx=tx,
        dropout_rng=jax.random.fold_in(rng, 1),
        model_state=model_state,
    )
    with open(os.path.join(self.out_dir, 'model_summary.txt'), 'w') as f:
      f.write(model_lib.summarize_params(variables['params']))
    # Place the WHOLE state by the declarative rule table: the LAMB
    # moments mirror the param tree, so one re.search pass shards them
    # exactly like their parameters (partition_rules.py), and scalars
    # (step counts, schedule state) replicate.
    return jax.device_put(state, self.state_shardings(state))

  def state_shardings(self, state):
    """Rule-table NamedShardings for a full TrainState (params,
    optimizer moments, model_state, rng, scalars) on this mesh — the
    single source train/eval/distill pjit steps compile against."""
    return partition_rules.tree_shardings(self.mesh, state)

  def _is_writer(self) -> bool:
    """Whether THIS host owns the shared-filesystem mutations
    (checkpoint manifests, TSV/best sidecars, metrics.jsonl,
    quarantine). Elastic pods elect the leader; legacy multi-host keeps
    the fixed process-0 convention."""
    if self.pod is not None:
      return self.pod.is_leader
    return jax.process_index() == 0

  def _manifest_extra(self) -> Optional[Dict[str, Any]]:
    """Elastic provenance for the checkpoint manifest: which member-set
    epoch wrote it (so a post-mortem can tell a degraded-pod checkpoint
    from a full-strength one)."""
    if self.pod is None:
      return None
    return {'pod_epoch': int(self.pod.epoch),
            'pod_members': [int(m) for m in self.pod.members]}

  # ---- steps ---------------------------------------------------------
  def train_step_fn(self, state: Optional[TrainState] = None):
    loss_obj = self.loss_fn

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
      # Python body == one pjit trace. jit caches one executable per
      # batch geometry, so over a bucketed stream this counts exactly
      # n_buckets traces (surfaced as n_train_forward_shapes; the
      # compile-once tests pin it — a value above the bucket count
      # means mid-run recompiles).
      self.n_train_forward_shapes += 1
      rng = jax.random.fold_in(state.dropout_rng, state.step)
      mutable = list(state.model_state.keys())

      def loss_of(p):
        if mutable:
          preds, new_model_state = state.apply_fn(
              {'params': p, **state.model_state},
              batch['rows'], train=True, rngs={'dropout': rng},
              mutable=mutable,
          )
        else:
          preds = state.apply_fn(
              {'params': p}, batch['rows'], train=True,
              rngs={'dropout': rng},
          )
          new_model_state = {}
        return loss_obj(batch['label'], preds), (preds, new_model_state)

      (loss, (preds, new_model_state)), grads = jax.value_and_grad(
          loss_of, has_aux=True
      )(state.params)
      new_state = state.apply_gradients(
          grads=grads, model_state=new_model_state
      ) if mutable else state.apply_gradients(grads=grads)
      correct, total = metrics_lib.per_example_accuracy_counts(
          batch['label'], preds
      )
      metrics = {
          'loss': loss,
          # Exposed for the NaN/Inf sentinel: a non-finite gradient can
          # poison the params even when this step's loss still computes
          # finite, so divergence is judged on both.
          'grad_norm': optax.global_norm(grads),
          'accuracy_correct': correct,
          'accuracy_total': total,
      }
      return new_state, metrics

    batch_sh = self._batch_sharding()
    # With a concrete state the step is an explicit-sharding pjit: the
    # donated input state and the returned state both carry the rule-
    # table shardings, so XLA keeps every optimizer update in place
    # (no gather/scatter around the step). Without one (legacy/bench
    # callers) the state sharding is inferred from the arguments.
    state_sh = None if state is None else self.state_shardings(state)
    return partition_rules.compile_parallel(
        step,
        in_shardings=(state_sh, {'rows': batch_sh, 'label': batch_sh}),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )

  def grad_step_fn(self, state: Optional[TrainState] = None):
    """First half of the elastic-pod data plane: forward+backward on
    this host's batch slice only, returning (grads, new_model_state,
    metrics) WITHOUT applying, so the pod's bounded weighted-mean
    allreduce (ElasticPod.step_sync) runs between compute and update.
    No donation and no pinned batch sharding: the same state re-enters
    apply_step_fn (and re-enters here when a lost-host rebuild replays
    the step), and the batch's leading dim changes with membership, so
    shapes/shardings are inferred per call."""
    del state  # shardings inferred from the concrete (placed) arguments
    loss_obj = self.loss_fn

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
      rng = jax.random.fold_in(state.dropout_rng, state.step)
      mutable = list(state.model_state.keys())

      def loss_of(p):
        if mutable:
          preds, new_model_state = state.apply_fn(
              {'params': p, **state.model_state},
              batch['rows'], train=True, rngs={'dropout': rng},
              mutable=mutable,
          )
        else:
          preds = state.apply_fn(
              {'params': p}, batch['rows'], train=True,
              rngs={'dropout': rng},
          )
          new_model_state = {}
        return loss_obj(batch['label'], preds), (preds, new_model_state)

      (loss, (preds, new_model_state)), grads = jax.value_and_grad(
          loss_of, has_aux=True
      )(state.params)
      correct, total = metrics_lib.per_example_accuracy_counts(
          batch['label'], preds
      )
      metrics = {
          'loss': loss,
          'accuracy_correct': correct,
          'accuracy_total': total,
      }
      return grads, new_model_state, metrics

    return partition_rules.compile_parallel(step)

  def apply_step_fn(self, state: Optional[TrainState] = None):
    """Second half: applies the pod-averaged gradients (and merged
    model_state) to the local state replica. Every member applies the
    SAME averaged arrays to the SAME state, so replicas stay in sync
    without any cross-host state transfer. grad_norm is computed on the
    averaged gradients — the same quantity the fused single-mesh step
    reports for the whole global batch."""
    del state
    def step(state: TrainState, grads, new_model_state):
      if new_model_state:
        new_state = state.apply_gradients(
            grads=grads, model_state=new_model_state
        )
      else:
        new_state = state.apply_gradients(grads=grads)
      return new_state, optax.global_norm(grads)

    return partition_rules.compile_parallel(step, donate_argnums=(0,))

  def _batch_sharding(self, n: Optional[int] = None):
    """Shard the batch over the data axis when divisible, else
    replicate (tiny test batches, uneven elastic member slices). `n`
    overrides the configured global batch size — elastic pod members
    feed membership-dependent slices whose length params.batch_size no
    longer describes."""
    dp = self.mesh.shape[mesh_lib.DATA_AXIS]
    n = int(self.params.batch_size) if n is None else int(n)
    if n % dp == 0:
      return mesh_lib.batch_sharding(self.mesh)
    return mesh_lib.replicated(self.mesh)

  def globalize_batch(self, batch):
    """Multi-host batch assembly: every host loads the SAME global
    batch (same files, same seed), takes its `local_batch_slice`, and
    the slices are stitched into one globally-sharded array
    (reference reaches pods via TPUStrategy's per-replica dataset:
    model_train_custom_loop.py:333-343). No-op single-process."""
    if jax.process_count() == 1:
      return batch
    from deepconsensus_tpu.parallel import distributed

    spec = self._batch_sharding().spec
    if not len(spec):  # replicated: all hosts feed identical arrays
      return {
          k: distributed.host_local_to_global(self.mesh, spec, v)
          for k, v in batch.items()
      }
    n = next(iter(batch.values())).shape[0]
    sl = distributed.local_batch_slice(n)
    return {
        k: distributed.host_local_to_global(self.mesh, spec, v[sl])
        for k, v in batch.items()
    }

  def localize_batch(self, batch):
    """The training-input view of one loaded batch on THIS host.

    Elastic pod: every member loads the SAME global batch (same files,
    same seed) and trains on its member_batch_slice — the union covers
    every row exactly once at ANY member count, so a pod of one
    degrades to the full batch and survivor training matches the
    undisturbed run. With shard_streams the batch is already host-local
    data and passes through. Legacy multi-host delegates to
    globalize_batch; single everything is a no-op.
    """
    if self.pod is None:
      return self.globalize_batch(batch)
    if not self.pod_slices_batches:
      return batch
    members = self.pod.members
    if len(members) <= 1:
      return batch
    from deepconsensus_tpu.parallel import distributed

    n = next(iter(batch.values())).shape[0]
    sl = distributed.member_batch_slice(
        n, len(members), sorted(members).index(self.pod.host_id))
    return {k: v[sl] for k, v in batch.items()}

  def eval_step_fn(self, state: Optional[TrainState] = None):
    loss_obj = self.loss_fn
    params_cfg = self.params
    metric = self.alignment_metric

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
      preds = state.apply_fn(
          {'params': state.params, **state.model_state}, batch['rows']
      )
      loss = loss_obj(batch['label'], preds)
      correct, total = metrics_lib.per_example_accuracy_counts(
          batch['label'], preds
      )
      ccs = ccs_row_from_batch(batch['rows'], params_cfg)
      id_ccs, id_pred = metrics_lib.batch_identity_ccs_pred(
          ccs, preds, batch['label'], metric
      )
      out = {
          'loss': loss,
          'accuracy_correct': correct,
          'accuracy_total': total,
          'identity_ccs': id_ccs,
          'identity_pred': id_pred,
      }
      for cls in range(constants.SEQ_VOCAB_SIZE):
        c, t = metrics_lib.per_class_accuracy_counts(
            batch['label'], preds, cls
        )
        out[f'class{cls}_correct'] = c
        out[f'class{cls}_total'] = t
      return out

    batch_sh = self._batch_sharding()
    state_sh = None if state is None else self.state_shardings(state)
    return partition_rules.compile_parallel(
        step,
        in_shardings=(state_sh, {'rows': batch_sh, 'label': batch_sh}),
    )

  def run_eval(self, state, eval_ds) -> Dict[str, float]:
    """One full eval epoch aggregated to the eval/* metric dict.

    The single aggregation used by BOTH run_training and distill, so
    their TSVs carry the same metric key set and
    params.best_checkpoint_metric means the same thing everywhere."""
    if getattr(self, '_cached_eval_step', None) is None:
      self._cached_eval_step = self.eval_step_fn(state)
    eval_step = self._cached_eval_step
    sums: Dict[str, float] = {}
    batches = 0
    yield_metric = metrics_lib.YieldOverCCS()
    for batch in eval_ds.epoch():
      # Window ids (params.track_window_ids) are training-loop
      # forensics; the jitted eval step shards (rows, label) only.
      batch = {k: v for k, v in batch.items() if k != 'name'}
      batch = self.globalize_batch(batch)
      out = {k: float(v) for k, v in eval_step(state, batch).items()}
      yield_metric.update(out['identity_ccs'], out['identity_pred'])
      for k, v in out.items():
        sums[k] = sums.get(k, 0.0) + v
      batches += 1
    if not batches:
      return {}
    acc = sums['accuracy_correct'] / max(sums['accuracy_total'], 1)
    result = {
        'eval/loss': sums['loss'] / batches,
        constants.MAIN_EVAL_METRIC_NAME: acc,
        'eval/identity_ccs': sums['identity_ccs'] / batches,
        'eval/identity_pred': sums['identity_pred'] / batches,
        'eval/yield_over_ccs': yield_metric.result(),
    }
    # Emit every class key unconditionally so the metric key set (and
    # the TSV header) stays stable across evals.
    for cls in range(constants.SEQ_VOCAB_SIZE):
      total = sums.get(f'class{cls}_total', 0.0)
      result[f'eval/class{cls}_accuracy'] = (
          sums[f'class{cls}_correct'] / total if total else 0.0
      )
    return result

  # ---- checkpoints ---------------------------------------------------
  def save_checkpoint(self, state: TrainState, step: int,
                      eval_metrics: Dict[str, float]) -> str:
    path = os.path.join(self._ckpt_dir, f'checkpoint-{step}')
    saved = {
        'params': jax.device_get(state.params),
        'opt_state': jax.device_get(state.opt_state),
        'model_state': jax.device_get(state.model_state),
        'step': step,
    }
    def do_save():
      self._checkpointer.save(path, saved, force=True)
      # Block until the async write finalizes so a crash right after
      # this point never leaves a half-written latest checkpoint.
      wait = getattr(self._checkpointer, 'wait_until_finished', None)
      if wait is not None:
        wait()

    if self.pod is not None:
      # Elastic pod: each member is its own single-process jax runtime
      # sharing out_dir, so orbax's multihost protocol does not apply —
      # the leader writes alone and a bounded pod barrier aligns the
      # rest (deadline scaled well above the step barrier: checkpoint
      # IO legitimately takes longer than a gradient sync).
      if self._is_writer():
        do_save()
      if len(self.pod.members) > 1:
        self.pod.barrier(
            f'ckpt-{step}',
            timeout_s=max(60.0, 4.0 * self.pod.barrier_timeout))
    elif jax.process_count() > 1:
      # Legacy multi-host: EVERY process calls save — orbax's multihost
      # protocol barriers across processes and writes from the primary
      # only. Bounded (the PR-18 rule: no collective waits forever): a
      # peer dying inside the save barrier surfaces as HostLostError
      # for the retry wrapper instead of hanging every survivor.
      from deepconsensus_tpu.parallel import elastic as elastic_lib

      elastic_lib.bounded_call(
          do_save, self._save_timeout(), f'orbax-save-{step}')
    else:
      do_save()
    if not self._is_writer():
      # Metric sidecars (TSV, best-checkpoint) and manifests have one
      # writer.
      return path
    # Commit the integrity manifest only now that the checkpoint is
    # fully on disk: its presence marks the directory as complete, and
    # its file inventory lets latest_valid_checkpoint detect truncation
    # without loading arrays.
    checkpoints_lib.write_manifest(
        path, step, digest=checkpoints_lib.tree_digest(saved),
        extra=self._manifest_extra(),
    )
    if not eval_metrics:
      # Emergency (preemption) saves carry no eval pass; skip the
      # metric sidecars rather than writing an empty TSV header.
      return path
    header_needed = not os.path.exists(self._metrics_tsv)
    if header_needed:
      self._tsv_columns = sorted(eval_metrics)
      with open(self._metrics_tsv, 'a') as f:
        f.write('checkpoint\t' + '\t'.join(self._tsv_columns) + '\n')
    with open(self._metrics_tsv, 'a') as f:
      # Align values to the header captured at first write; metric key
      # sets are stable by construction (all keys always emitted).
      f.write(
          f'checkpoint-{step}\t'
          + '\t'.join(
              str(eval_metrics.get(k, 'nan')) for k in self._tsv_columns
          )
          + '\n'
      )
    if self._best_metric_name not in eval_metrics:
      # A typo'd metric name would otherwise silently never update
      # best_checkpoint.txt (get() returning -1.0 forever).
      logging.getLogger(__name__).warning(
          'best_checkpoint_metric %r not among eval metrics %s; '
          'best_checkpoint.txt will not update',
          self._best_metric_name, sorted(eval_metrics))
    main = eval_metrics.get(self._best_metric_name, -1.0)
    if main > self._best_metric:
      self._best_metric = main
      with open(self._best_file, 'w') as f:
        f.write(f'checkpoint-{step}\n')
    return path

  def _save_timeout(self) -> float:
    """Deadline for the legacy multi-host orbax save barrier: generous
    (checkpoint IO is slow) but finite."""
    base = float(
        self.params.get('elastic_barrier_timeout', 30.0) or 30.0)
    return max(300.0, 10.0 * base)

  def restore_checkpoint(self, state: TrainState, path: str,
                         params_only: bool = False) -> TrainState:
    """Restores training state; full resume includes optimizer state
    and LR-schedule position (the reference restores the whole
    tf.train.Checkpoint: model_utils.py:511-540)."""
    if params_only:
      # Warm-start source checkpoints are usually full TrainStates
      # (params + opt_state + step); a params-only typed target makes
      # orbax raise a structure mismatch, so select the subtree from
      # an untyped restore (same approach as checkpoints.load_params,
      # which inference/export use). The template keeps restore-time
      # structure/shape validation and casts to the model's dtype.
      from deepconsensus_tpu.models.checkpoints import load_params

      return state.replace(params=load_params(
          path, params_template=jax.device_get(state.params)))
    restored = self._checkpointer.restore(
        path,
        target={
            'params': jax.device_get(state.params),
            'opt_state': jax.device_get(state.opt_state),
            'model_state': jax.device_get(state.model_state),
            'step': 0,
        },
    )
    return state.replace(
        params=restored['params'],
        opt_state=restored['opt_state'],
        model_state=restored['model_state'],
        step=jnp.asarray(restored['step']),
    )

  def latest_valid_checkpoint(self) -> Optional[str]:
    """Newest checkpoint that passes integrity validation; corrupt or
    uncommitted (manifest-less) directories are quarantined to
    checkpoints/.quarantine/ and the scan falls back to the next
    valid one. Replaces the old latest_checkpoint(), which compared
    step numbers only and would happily resume onto a half-written
    directory."""
    return checkpoints_lib.latest_valid_checkpoint(
        self._ckpt_dir, quarantine=self._is_writer()
    )

  # Backward-compatible name; validation semantics included.
  latest_checkpoint = latest_valid_checkpoint

  def log_metrics(self, step: int, split: str, metrics: Dict[str, float]):
    if not self._is_writer():
      return
    for name, value in metrics.items():
      try:
        self.obs.set_gauge(f'{split}/{name}', float(value))
      except (TypeError, ValueError):
        continue
    entry = {'step': step, 'split': split, 'time': time.time(), **metrics}
    with open(self._metrics_jsonl, 'a') as f:
      f.write(json.dumps(entry) + '\n')
    self._write_tensorboard(step, split, metrics)

  def _write_tensorboard(self, step: int, split: str,
                         metrics: Dict[str, float]):
    """Optional TensorBoard scalars (reference writes TB summaries:
    model_train_custom_loop.py:164-166). No-op without tensorflow."""
    if not hasattr(self, '_tb_writers'):
      self._tb_writers = {}
    if split not in self._tb_writers:
      try:
        import tensorflow as tf  # noqa: F401

        self._tb_writers[split] = tf.summary.create_file_writer(
            os.path.join(self.out_dir, 'tensorboard', split)
        )
      except ImportError:
        self._tb_writers[split] = None
    writer = self._tb_writers[split]
    if writer is None:
      return
    import tensorflow as tf

    with writer.as_default():
      for name, value in metrics.items():
        try:
          tf.summary.scalar(name, float(value), step=step)
        except (TypeError, ValueError):
          continue
      writer.flush()


class _PrefetchedBatch:
  """One in-flight training batch: host arrays (kept for the NaN
  sentinel and for re-placement after a mesh degrade), the async
  device transfer, and the mesh generation the transfer targeted."""

  __slots__ = ('names', 'host', 'device', 'generation', 'error')

  def __init__(self):
    self.names = None
    self.host = None
    self.device = None
    self.generation = 0
    self.error: Optional[BaseException] = None


class TrainBatchPrefetcher:
  """Double-buffered training-batch transfer: the PR-8 dispatch
  pattern applied to input.

  A producer thread pulls host batches (already host-prefetched by
  data.prefetch_iterator), applies the batch fault-injection hooks,
  and issues batch N+1's ASYNC sharded jax.device_put while the device
  runs step N — jax.device_put returns before the copy completes, so
  the H2D transfer rides under compute instead of serializing in the
  jitted call's argument placement. The queue holds one ready handle
  and the consumer holds another: depth-2 double buffering, same as
  the inference dispatch pipeline.

  Counters (surfaced in the metrics sidecar's `faults` split):
  `n_batches_prefetched` counts launches issued while an earlier
  batch's step was in flight (every launch after the first — the
  depth-1 queue guarantees launch k happens only after the consumer
  took batch k-1, i.e. during step k-1's async window);
  `train_transfer_overlap_fraction` is that count over all launches,
  so a clean run reports (steps-1)/steps.

  Mesh degrades retarget the prefetcher: `retarget()` bumps the mesh
  generation, and a handle whose transfer targeted a retired mesh is
  re-placed from its host copy at consumption time.
  """

  def __init__(self, batches, trainer: Trainer, poison_base_step: int = 0):
    self._trainer = trainer
    self._batches = batches
    self._poison_base = poison_base_step
    self._lock = threading.Lock()
    self._generation = 0  # guarded by: self._lock
    self._n_launched = 0  # guarded by: self._lock
    self._n_overlapped = 0  # guarded by: self._lock
    self._n_replaced = 0  # guarded by: self._lock
    self._stop = threading.Event()
    self._queue: queue_lib.Queue = queue_lib.Queue(maxsize=1)
    self._thread = threading.Thread(
        target=self._produce, daemon=True, name='train-batch-prefetch'
    )
    self._thread.start()

  # ---- producer thread ----------------------------------------------
  def _produce(self):
    ordinal = self._poison_base
    try:
      for batch in self._batches:
        if self._stop.is_set():
          break
        item = _PrefetchedBatch()
        item.names = batch.pop('name', None)
        ordinal += 1
        # Injection ordinal = the step this batch is consumed at on the
        # no-rollback path (rollbacks replay step numbers but never
        # batches; _fire_once keeps hooks consume-once either way).
        faults_lib.maybe_poison_batch(ordinal, batch)
        item.host = dict(batch)
        item.generation, item.device = self._launch(item.host)
        if not self._put(item):
          break
    # dclint-style routing: the error crosses threads via the handle
    # and re-raises at the consumer, like data.prefetch_iterator.
    except BaseException as e:  # pylint: disable=broad-except
      item = _PrefetchedBatch()
      item.error = e
      self._put(item)
    else:
      self._put(None)
    finally:
      close = getattr(self._batches, 'close', None)
      if close is not None:
        try:
          close()
        except Exception:  # pragma: no cover - best-effort shutdown
          pass

  def _launch(self, host: Dict[str, np.ndarray]):
    """Issues the async sharded H2D transfer for one host batch and
    returns (mesh generation, device arrays)."""
    gbatch = self._trainer.localize_batch(dict(host))
    sh = self._trainer._batch_sharding(
        n=next(iter(gbatch.values())).shape[0])
    with self._lock:
      gen = self._generation
      self._n_launched += 1
      if self._n_launched > 1:
        self._n_overlapped += 1
    return gen, jax.device_put(gbatch, {k: sh for k in gbatch})

  def _put(self, item) -> bool:
    while not self._stop.is_set():
      try:
        self._queue.put(item, timeout=0.1)
        return True
      except queue_lib.Full:
        continue
    return False

  # ---- consumer (training loop) -------------------------------------
  def __iter__(self):
    return self

  def __next__(self):
    item = self._queue.get()
    if item is None:
      raise StopIteration
    if item.error is not None:
      raise item.error
    with self._lock:
      gen = self._generation
    if item.generation != gen:
      # The transfer targeted a mesh that has since been degraded;
      # re-place from the host copy onto the current mesh.
      item.device = self.place(item.host)
      item.generation = gen
    return item.names, item.host, item.device

  def place(self, host: Dict[str, np.ndarray]):
    """Direct (non-overlapped) placement of a host batch on the
    CURRENT mesh (and, for elastic pods, the CURRENT membership —
    re-placing after a rebuild re-slices the same host batch for the
    surviving member set) — used to re-dispatch the failed batch after
    a degrade/rebuild and to refresh stale prefetched transfers."""
    gbatch = self._trainer.localize_batch(dict(host))
    sh = self._trainer._batch_sharding(
        n=next(iter(gbatch.values())).shape[0])
    with self._lock:
      self._n_replaced += 1
    return jax.device_put(gbatch, {k: sh for k in gbatch})

  def retarget(self) -> None:
    """Invalidates in-flight transfers after a mesh rebuild: bumps the
    generation so stale handles re-place at consumption."""
    with self._lock:
      self._generation += 1

  def stats(self) -> Dict[str, float]:
    with self._lock:
      launched = self._n_launched
      overlapped = self._n_overlapped
      replaced = self._n_replaced
    return {
        'n_batch_launches': float(launched),
        'n_batches_prefetched': float(overlapped),
        'n_batches_replaced': float(replaced),
        'train_transfer_overlap_fraction': (
            round(overlapped / launched, 4) if launched else 0.0
        ),
    }

  def close(self) -> None:
    self._stop.set()
    # Drain so a producer blocked in _put can observe the stop flag.
    try:
      while True:
        self._queue.get_nowait()
    except queue_lib.Empty:
      pass
    self._thread.join(timeout=5.0)


class PreemptionGuard:
  """SIGTERM/SIGINT -> emergency checkpoint at the next step boundary.

  TPU-VM preemption delivers SIGTERM with a short grace period; a
  Ctrl-C during a long local run deserves the same treatment. The
  handler only sets a flag — the training loop polls requested() once
  per step and performs the (collective) checkpoint save itself, so the
  save never runs inside a signal handler or mid-step. A second signal
  aborts immediately (raises KeyboardInterrupt) for operators who
  really mean it.

  Multi-host: the decision to stop must be unanimous — the orbax save
  is collective, so one host checkpointing alone would deadlock the
  rest. requested() allgathers the local flags and trips when ANY host
  saw a signal. The vote is BOUNDED (PR 18): a peer that died before
  voting surfaces as HostLostError after barrier_timeout instead of
  wedging every survivor inside process_allgather forever. Elastic
  pods skip the collective entirely — they piggyback `local()` on the
  per-step sync, which is already bounded.
  """

  def __init__(self, barrier_timeout: float = 30.0):
    self._event = threading.Event()
    self._prev: Dict[int, Any] = {}
    self.signum: Optional[int] = None
    self.barrier_timeout = float(barrier_timeout)

  def install(self) -> 'PreemptionGuard':
    import signal

    for sig in (signal.SIGTERM, signal.SIGINT):
      try:
        self._prev[sig] = signal.signal(sig, self._handle)
      except ValueError:
        # Not the main thread (e.g. training driven from a worker
        # thread in tests): preemption safety degrades to the default
        # handlers rather than breaking training.
        pass
    return self

  def _handle(self, signum, frame):
    del frame
    if self._event.is_set():
      raise KeyboardInterrupt(
          f'second signal {signum} during checkpoint-and-exit'
      )
    self.signum = signum
    self._event.set()
    logging.getLogger(__name__).warning(
        'signal %s received; will checkpoint and exit at the next step '
        'boundary (send again to abort immediately)', signum,
    )

  def local(self) -> bool:
    """This host's own stop flag, no collective — what the elastic pod
    piggybacks as its stop vote on step_sync."""
    return self._event.is_set()

  def requested(self) -> bool:
    local = self._event.is_set()
    if jax.process_count() == 1:
      return local
    from jax.experimental import multihost_utils

    from deepconsensus_tpu.parallel import elastic as elastic_lib

    def vote():
      return multihost_utils.process_allgather(
          np.asarray([local], dtype=np.int32)
      )

    flags = elastic_lib.bounded_call(
        vote, self.barrier_timeout, 'preemption-stop-vote')
    return bool(np.any(flags))

  def restore(self) -> None:
    import signal

    for sig, prev in self._prev.items():
      try:
        signal.signal(sig, prev)
      except ValueError:
        pass
    self._prev = {}


class NanSentinel:
  """Watches per-step loss/grad-norm finiteness; after `limit`
  consecutive non-finite steps, rolls training back to the last valid
  checkpoint (the train step donates and overwrites its input state, so
  a NaN update poisons the live params irreversibly — rollback is the
  only recovery). Every non-finite step is dead-lettered with the
  offending batch's window ids (params.track_window_ids) or a content
  fingerprint, in the PR 1 sidecar format, to <out_dir>/training.failed.jsonl.

  Verdicts are read one step late: float(metrics) blocks on the device,
  so checking step k while step k+1 is dispatching preserves the
  async-dispatch pipeline. The one extra contaminated step costs
  nothing — rollback discards it either way. The exception is a save
  boundary (eval checkpoint, emergency preemption save, final save):
  there the loop force-resolves the pending verdict and refuses to
  checkpoint while `consecutive > 0`, so a poisoned state can never
  become the "last valid checkpoint" the rollback restores.
  """

  def __init__(self, params: ml_collections.ConfigDict, out_dir: str,
               writer: Optional[bool] = None):
    self.limit = int(params.get('nan_sentinel_steps', 3) or 0)
    self.max_rollbacks = int(params.get('nan_max_rollbacks', 2) or 0)
    self.enabled = self.limit > 0
    self.consecutive = 0
    self.rollbacks = 0
    self.counters: collections.Counter = collections.Counter()
    self._dead_letter = None
    if writer is None:
      # Legacy convention; elastic runs pass the leader verdict so the
      # shared dead-letter file keeps one writer across pod epochs.
      writer = jax.process_index() == 0
    if self.enabled and writer:
      self._dead_letter = faults_lib.DeadLetterWriter(
          os.path.join(out_dir, 'training.failed.jsonl'), append=True
      )

  def observe(self, step: int, metrics: Dict[str, Any],
              names, batch: Optional[Dict[str, np.ndarray]]) -> bool:
    """Returns True (and records a dead letter) when this step's loss
    or grad norm is non-finite."""
    loss = float(metrics['loss'])
    grad_norm = float(metrics.get('grad_norm', 0.0))
    if np.isfinite(loss) and np.isfinite(grad_norm):
      self.consecutive = 0
      return False
    self.consecutive += 1
    self.counters['n_nonfinite_steps'] += 1
    extra: Dict[str, Any] = {
        'step': step, 'loss': loss, 'grad_norm': grad_norm,
    }
    if names is not None:
      extra['window_ids'] = [
          n.decode('utf-8', 'replace') if isinstance(n, bytes) else str(n)
          for n in names
      ]
    elif batch is not None and 'rows' in batch:
      extra['batch_sha1'] = hashlib.sha1(
          np.ascontiguousarray(batch['rows']).tobytes()
      ).hexdigest()[:16]
    will_roll = self.consecutive >= self.limit
    if self._dead_letter is not None:
      self._dead_letter.record(
          None, 'train', faults_lib.FaultKind.TRANSIENT,
          f'non-finite training step: loss={loss} grad_norm={grad_norm}',
          'rollback' if will_roll else 'recorded', extra=extra,
      )
    logging.getLogger(__name__).warning(
        'non-finite training step %d (loss=%s grad_norm=%s; %d/%d '
        'consecutive)', step, loss, grad_norm, self.consecutive,
        self.limit,
    )
    return True

  def should_rollback(self) -> bool:
    return self.enabled and self.consecutive >= self.limit

  def rolled_back(self, checkpoint: str) -> None:
    self.rollbacks += 1
    self.consecutive = 0
    self.counters['n_nan_rollbacks'] += 1
    logging.getLogger(__name__).warning(
        'NaN sentinel: rolled back to %s (rollback %d/%d)',
        checkpoint, self.rollbacks, self.max_rollbacks,
    )

  def close(self) -> None:
    if self._dead_letter is not None:
      self._dead_letter.close()


def run_training(
    params: ml_collections.ConfigDict,
    out_dir: str,
    train_patterns=None,
    eval_patterns=None,
    num_epochs: Optional[int] = None,
    mesh=None,
    eval_every: Optional[int] = None,
    warm_start: Optional[str] = None,
    distributed_config: Optional[Dict[str, Any]] = None,
    elastic_config: Optional[Dict[str, Any]] = None,
    preemption_guard: Optional['PreemptionGuard'] = None,
) -> Dict[str, float]:
  """End-to-end training driver. Returns final eval metrics.

  Multi-host: pass distributed_config (coordinator_address,
  num_processes, process_id — or {} for pod auto-detection) to
  initialize jax.distributed before the mesh is built; every host then
  feeds its local slice of the global batch (globalize_batch) and only
  process 0 writes checkpoints/metrics. out_dir must be shared (or at
  least readable) across hosts for crash-resume.

  Elastic multi-host: pass elastic_config (host_id, n_hosts, plus
  optional barrier_timeout / on_host_error / readmit /
  heartbeat_interval / shard_streams / defer_join_until_step) instead.
  Each host runs its own single-process jax over a LOCAL mesh; the
  membership layer (parallel/elastic.py) forms the pod in
  <out_dir>/.pod/, gradients cross hosts through the bounded per-step
  weighted-mean sync, and a lost host triggers the coordinated rebuild
  (agreement round, epoch bump, batch re-slice, step replay) instead
  of a hang. docs/training.md "Elastic multi-host training".
  """
  if distributed_config is not None:
    from deepconsensus_tpu.parallel import distributed

    distributed.initialize(**distributed_config)
  pod = None
  pod_start = None
  shard_streams = False
  on_host_error = 'degrade'
  if elastic_config:
    from deepconsensus_tpu.parallel import elastic as elastic_lib

    shard_streams = bool(elastic_config.get('shard_streams', False))
    on_host_error = str(
        elastic_config.get('on_host_error')
        or params.get('on_host_error', 'degrade') or 'degrade')
    defer = int(elastic_config.get('defer_join_until_step', 0) or 0)
    if not defer:
      # Subprocess fault drills arm the rejoin hook via the restarted
      # process's environment (scripts/inject_faults.py host).
      defer = faults_lib.host_rejoin_step()
    pod = elastic_lib.ElasticPod(
        os.path.join(os.path.abspath(out_dir), '.pod'),
        host_id=int(elastic_config['host_id']),
        n_hosts=int(elastic_config['n_hosts']),
        barrier_timeout=float(
            elastic_config.get('barrier_timeout')
            or params.get('elastic_barrier_timeout', 30.0) or 30.0),
        heartbeat_interval=float(
            elastic_config.get('heartbeat_interval', 0.25) or 0.25),
        readmit=bool(elastic_config.get('readmit', True)),
        defer_join_until_step=defer,
    )
  train_patterns = train_patterns or list(params.train_path)
  eval_patterns = eval_patterns or list(params.eval_path)
  num_epochs = num_epochs or params.num_epochs

  streaming = bool(params.get('streaming', False))
  train_ds = None
  if streaming:
    # Shard-interleaved streaming with a shuffle buffer; "epochs"
    # become fixed step counts (n_examples_train / batch). The dataset
    # itself is constructed after checkpoint restore so the stream can
    # be reseeded by resume position.
    n_train = int(params.get('n_examples_train', 0) or 0)
    if n_train < params.batch_size:
      raise ValueError(
          'streaming training requires params.n_examples_train (>= one '
          'batch) to size the step budget'
      )
    steps_per_epoch = n_train // params.batch_size
  else:
    train_ds = data_lib.DatasetIterator(
        patterns=train_patterns,
        params=params,
        batch_size=params.batch_size,
        seed=params.seed,
    )
    steps_per_epoch = train_ds.steps_per_epoch
  eval_ds = data_lib.DatasetIterator(
      patterns=eval_patterns,
      params=params,
      batch_size=params.batch_size,
      shuffle=False,
  )
  decay_steps = steps_per_epoch * params.get('num_epochs_for_decay',
                                             num_epochs)
  if pod is not None and mesh is None:
    # The jit-visible mesh of an elastic member never spans processes;
    # cross-host reduction happens at host level through step_sync.
    mesh = mesh_lib.local_mesh(tp=int(params.get('tp', 1) or 1))
  trainer = Trainer(params=params, out_dir=out_dir, mesh=mesh, pod=pod,
                    pod_slices_batches=not shard_streams)
  if pod is not None:
    # Form (or join) the pod BEFORE any shared-filesystem writes so
    # writer gating (_is_writer == pod leader) is meaningful.
    pod_start = pod.start()
  if trainer._is_writer():
    config_lib.save_params_as_json(out_dir, params)
  state = trainer.init_state(steps_total=decay_steps)
  resume_from = trainer.latest_valid_checkpoint()
  if warm_start and resume_from is not None:
    logging.getLogger(__name__).warning(
        'warm_start=%s ignored: %s already has checkpoints; resuming '
        'from the latest instead', warm_start, out_dir,
    )
  if warm_start and resume_from is None:
    # Warm start adopts weights only; optimizer starts fresh
    # (reference --checkpoint warm start: model_train_custom_loop.py:119-124).
    # Applies only to the very first start: once this run has its own
    # checkpoints, crash-resume below must win or a preempted
    # warm-started run would restart from step 0.
    state = trainer.restore_checkpoint(state, warm_start, params_only=True)
  eval_every = eval_every or params.get('eval_every_n_steps', 3000)

  def run_eval(state) -> Dict[str, float]:
    return trainer.run_eval(state, eval_ds)

  # Crash-resume: pick up from the newest VALID checkpoint in out_dir
  # (reference resumable training: model_utils.py:511-540) — a
  # half-written or truncated latest checkpoint is quarantined by
  # latest_valid_checkpoint and the previous one wins.
  # The out_dir's own latest checkpoint always wins over warm_start:
  # warm_start seeds only the very first start, so a preempted
  # warm-started run resumes its own progress instead of resetting.
  step = 0
  if pod_start is not None and pod_start.joined:
    # Re-admission: adopt the leader's LIVE snapshot (state re-placed
    # outward at the admission boundary), which supersedes any local
    # checkpoint — the pod has advanced past what disk remembers.
    if pod_start.state is None:
      raise faults_lib.ElasticRebuildError(
          f'host {pod.host_id} was admitted at epoch {pod_start.epoch} '
          'but no state snapshot exists for that epoch in the pod dir')
    host_state = jax.device_get(state)
    leaves, treedef = jax.tree_util.tree_flatten(host_state)
    if len(pod_start.state) != len(leaves):
      raise faults_lib.ElasticRebuildError(
          f'pod snapshot carries {len(pod_start.state)} leaves but the '
          f'local state template has {len(leaves)}; the rejoining host '
          'is running a different model/optimizer config than the pod')
    state = jax.tree_util.tree_unflatten(
        treedef, [np.asarray(snap_leaf, dtype=np.asarray(tmpl).dtype)
                  for snap_leaf, tmpl in zip(pod_start.state, leaves)])
    step = int(pod_start.step)
    state = jax.device_put(state, trainer.state_shardings(state))
  elif resume_from:
    state = trainer.restore_checkpoint(state, resume_from)
    step = int(state.step)
    # Restore materializes host arrays; re-place under the rule table
    # so the donated pjit step below sees committed sharded inputs.
    state = jax.device_put(state, trainer.state_shardings(state))
  # Compiled against the concrete (placed) state: explicit rule-table
  # in/out shardings plus donation keep the optimizer update in place.
  # Elastic pods split the step instead (grad compute / bounded
  # host-level allreduce / apply), so the compiled graph never contains
  # a cross-host collective a dead peer could wedge.
  train_step = grad_step = apply_step = None
  if pod is None:
    train_step = trainer.train_step_fn(state)
  else:
    grad_step = trainer.grad_step_fn(state)
    apply_step = trainer.apply_step_fn(state)

  # Fleet tracing + on-demand profiler: spans and dead letters from
  # this run carry one minted trace id; SIGUSR2 triggers a short
  # jax.profiler capture into <out_dir>/profile — the batch-side
  # counterpart of serve's /debugz/profile endpoint.
  obs_lib.trace.configure_from_env(tier='train')
  obs_lib.trace.set_trace_id(obs_lib.trace.mint_trace_id())
  obs_lib.profiler.install_sigusr2(os.path.join(out_dir, 'profile'))
  # Snapshot the module-global blockwise-attention trace count so the
  # end-of-run delta attributes ring routing to THIS run (tests train
  # several models per process).
  ring_traces_start = ring_lib.n_blockwise_traces

  profile_dir = params.get('profile_dir', None)
  if profile_dir:
    jax.profiler.start_trace(profile_dir)

  stream_ds = None
  if streaming:
    # Constructed here (after checkpoint restore) so the stream can be
    # reseeded by resume position: a restarted run draws fresh
    # (differently-shuffled) data instead of replaying the head of the
    # corpus. Held in a variable so its fault counters (skipped shards
    # etc.) survive the iterator for the end-of-run summary.
    if pod is not None and not shard_streams and pod.readmit:
      logging.getLogger(__name__).warning(
          'elastic + streaming without shard_streams: a re-admitted '
          'host reseeds its stream by resume position and so draws '
          'approximately (not exactly) the batches its peers hold; '
          'pass shard_streams for per-host shard ownership, or use '
          'the non-streaming loader for exact replicated batches')
    stream_ds = data_lib.StreamingDataset(
        patterns=train_patterns,
        params=params,
        batch_size=params.batch_size,
        **({'buffer_size': params.buffer_size}
           if 'buffer_size' in params else {}),
        **({'host_rank': sorted(pod.members).index(pod.host_id),
            'host_count': len(pod.members)}
           if (pod is not None and shard_streams) else {}),
        workers=params.get('loader_workers', 0),
        seed=params.seed + step,
        on_shard_error=params.get('on_shard_error', 'fail'),
    )

  def train_batches():
    if streaming:
      it = iter(stream_ds)
      try:
        for _ in range(max(steps_per_epoch * num_epochs - step, 0)):
          yield next(it)
      finally:
        it.close()
    else:
      steps_to_skip = step
      for _ in range(num_epochs):
        for batch in train_ds.epoch():
          if steps_to_skip > 0:
            # Skip batches already covered by the restored checkpoint.
            steps_to_skip -= 1
            continue
          yield batch

  def maybe_augmented():
    # Training-time window augmentation (params.augment; applied to
    # training batches only — eval batches go through run_eval
    # untouched). Seeded off params.seed + resume step so a resumed
    # run draws a fresh augmentation stream instead of replaying one.
    if not params.get('augment', False):
      return train_batches()
    aug_rng = np.random.default_rng(params.seed + 7919 * (step + 1))
    return (
        data_lib.augment_batch(b, params, aug_rng)
        for b in train_batches()
    )

  # An orchestrator (models/flywheel.py) that owns the process-wide
  # signal handlers passes its guard in; we only install (and later
  # restore) our own when running standalone.
  owns_guard = preemption_guard is None
  guard = preemption_guard or PreemptionGuard(
      barrier_timeout=float(
          params.get('elastic_barrier_timeout', 30.0) or 30.0)
  ).install()
  sentinel = NanSentinel(
      params, out_dir,
      writer=trainer._is_writer() if pod is not None else None)
  # The sentinel reads verdicts one step late (see NanSentinel);
  # pending holds (step, metrics, window ids, host batch) for the step
  # whose device result is not yet known.
  pending = None

  def rollback():
    nonlocal state, step, pending
    if sentinel.rollbacks >= sentinel.max_rollbacks:
      raise faults_lib.NonFiniteTrainingError(
          f'training diverged: non-finite steps persisted through '
          f'{sentinel.rollbacks} rollback(s); refusing to roll back '
          f'again (params.nan_max_rollbacks={sentinel.max_rollbacks})'
      )
    latest = trainer.latest_valid_checkpoint()
    if latest is None:
      raise faults_lib.NonFiniteTrainingError(
          f'training diverged after {sentinel.consecutive} consecutive '
          f'non-finite step(s) at step {step} and no valid checkpoint '
          f'exists to roll back to'
      )
    # The contaminated state is still a valid restore template (same
    # tree/shapes); its values are fully overwritten.
    state = trainer.restore_checkpoint(state, latest)
    step = int(state.step)
    state = jax.device_put(state, trainer.state_shardings(state))
    pending = None
    if pod is not None:
      # Every member judges the same merged metrics, so all roll back
      # at the same step; bumping the barrier round in lockstep keeps
      # the replayed step numbers out of their first pass's stale
      # payload files.
      pod.advance_round()
    sentinel.rolled_back(latest)

  # Training degradation ladder (--on_device_error=degrade): the
  # inference-side dp ladder (runner.degrade_mesh) applied to training.
  # A permanent DeviceLostError mid-step rebuilds the mesh one dp step
  # down over the surviving devices, re-places the live state from
  # memory (checkpoint rollback only when the state itself is
  # unreadable, i.e. died with the device), recompiles the pjit step,
  # retargets in-flight prefetched transfers, and re-runs the failed
  # batch — the run completes instead of crash-looping at fixed dp.
  on_device_error = params.get('on_device_error', 'fail')
  n_train_degraded = 0
  prefetcher: Optional[TrainBatchPrefetcher] = None

  def degrade_mesh() -> bool:
    nonlocal state, step, pending, train_step, n_train_degraded
    dp = int(trainer.mesh.shape[mesh_lib.DATA_AXIS])
    tp = int(trainer.mesh.shape.get(mesh_lib.MODEL_AXIS, 1))
    new_dp = dp // 2
    # The global batch must still split evenly over the data axis.
    while new_dp >= 1 and params.batch_size % new_dp:
      new_dp //= 2
    if new_dp < 1 or new_dp >= dp or jax.process_count() > 1:
      # Single device (nothing smaller) or multi-host (the mesh spans
      # processes; shrinking it here would desync the others).
      return False
    # Pull the live state to host BEFORE abandoning the old mesh: when
    # the read succeeds the run continues from the exact last step (no
    # rollback); when the state died with the device, rebuild and fall
    # back to the last valid checkpoint.
    contaminated = False
    host_state = None
    try:
      host_state = jax.device_get(state)
    except Exception:  # pylint: disable=broad-except
      contaminated = True
    devices = np.asarray(trainer.mesh.devices).reshape(-1)[:new_dp * tp]
    trainer.mesh = mesh_lib.make_mesh(dp=new_dp, tp=tp,
                                      devices=list(devices))
    trainer._cached_eval_step = None  # eval recompiles on the new mesh
    trainer.loss_fn = make_loss(trainer.params, mesh=trainer.mesh)
    if contaminated:
      latest = trainer.latest_valid_checkpoint()
      if latest is None:
        return False
      state = trainer.init_state(steps_total=decay_steps)
      state = trainer.restore_checkpoint(state, latest)
      step = int(state.step)
      pending = None
    else:
      state = host_state
    state = jax.device_put(state, trainer.state_shardings(state))
    train_step = trainer.train_step_fn(state)
    if prefetcher is not None:
      prefetcher.retarget()
    n_train_degraded += 1
    logging.getLogger(__name__).warning(
        'training mesh degraded to dp=%d after a device loss (step %d '
        'of the ladder)%s', new_dp, n_train_degraded,
        '; rolled back to the last valid checkpoint' if contaminated
        else '; state carried over in memory',
    )
    return True

  # Elastic host-loss handling (--on_host_error=degrade): the pod-scale
  # sibling of degrade_mesh. A HostLostError from any bounded barrier
  # triggers the survivor-side agreement round; the member set shrinks,
  # the epoch bumps, batches re-slice over the survivors, and the
  # failed step replays under the new epoch's barrier namespace.
  def rebuild_after_host_loss(err: Exception) -> bool:
    """Returns True when this host adopted a peer's AHEAD state: the
    lost host died inside a step barrier some members had already
    collected, so the pod split across a step boundary; the
    most-advanced member snapshots its live state and the rest adopt
    it — forward reconciliation, never a checkpoint rollback (that is
    reserved for state that died with a host, mirroring the PR-14
    degrade rule)."""
    nonlocal state, step, pending
    t0 = time.time()
    old_members = pod.members
    members = ()
    got = None
    for _ in range(max(pod.rebuild_attempts, 1)):
      members = pod.rebuild()
      try:
        got = pod.allgather('resume', {'step': int(step)})
        break
      except faults_lib.HostLostError as resume_err:
        # Another member died between the agreement round and the
        # resume exchange; rebuild again without it.
        err = resume_err
    if got is None:
      raise faults_lib.ElasticRebuildError(
          f'pod resume exchange never converged after '
          f'{pod.rebuild_attempts} rebuild(s); last error: {err}')
    steps = {int(h): int(meta['step']) for h, (meta, _) in got.items()}
    max_step = max(steps.values())
    adopted = False
    if len(set(steps.values())) > 1:
      max_host = min(h for h, s in steps.items() if s == max_step)
      if pod.host_id == max_host:
        pod.write_state_snapshot(
            pod.epoch, max_step,
            [np.asarray(x) for x in
             jax.tree_util.tree_flatten(jax.device_get(state))[0]])
      pod.barrier('resume-adopt')
      if steps[pod.host_id] < max_step:
        snap = pod.read_state_snapshot(pod.epoch)
        if snap is None:
          raise faults_lib.ElasticRebuildError(
              f'resume snapshot for epoch {pod.epoch} missing after '
              'the adopt barrier; pod dir inconsistent')
        leaves, treedef = jax.tree_util.tree_flatten(
            jax.device_get(state))
        state = jax.tree_util.tree_unflatten(
            treedef,
            [np.asarray(s_leaf, dtype=np.asarray(t).dtype)
             for s_leaf, t in zip(snap, leaves)])
        step = max_step
        pending = None
        adopted = True
    # Re-place the live TrainState by the rule table. The mesh is
    # host-local and unchanged, so this is cheap — placement is only
    # actually rebuilt for host-materialized (adopted) leaves.
    state = jax.device_put(state, trainer.state_shardings(state))
    if jax.process_count() > 1:
      # Real multi-controller pod: re-enter initialize_distributed
      # semantics at the agreed process count.
      from deepconsensus_tpu.parallel import distributed

      distributed.reinitialize(
          num_processes=len(members),
          process_id=sorted(members).index(pod.host_id))
    if prefetcher is not None:
      prefetcher.retarget()
    if stream_ds is not None and shard_streams:
      stream_ds.reassign_hosts(
          sorted(members).index(pod.host_id), len(members))
    obs_lib.trace.complete_event('host_rebuild', 'train', t0, time.time(), {
        'epoch': pod.epoch,
        'missing': [int(h) for h in getattr(err, 'missing', ()) or ()],
        'members_before': len(old_members),
        'members_after': len(members),
        'adopted_peer_state': adopted,
    })
    logging.getLogger(__name__).warning(
        'pod rebuilt after host loss (%s): members %s -> %s, epoch %d%s',
        err, sorted(old_members), sorted(members), pod.epoch,
        '; adopted the most-advanced survivor state' if adopted else '')
    return adopted

  def admit_joiners(joiners, at_step: int) -> None:
    """Survivor side of re-admission, at a step boundary: snapshot the
    live state outward, agree on the expanded member set, retarget the
    input pipeline to the new membership."""
    t0 = time.time()
    members = pod.admit(
        joiners,
        [np.asarray(x) for x in
         jax.tree_util.tree_flatten(jax.device_get(state))[0]],
        at_step)
    if jax.process_count() > 1:
      from deepconsensus_tpu.parallel import distributed

      distributed.reinitialize(
          num_processes=len(members),
          process_id=sorted(members).index(pod.host_id))
    if prefetcher is not None:
      prefetcher.retarget()
    if stream_ds is not None and shard_streams:
      stream_ds.reassign_hosts(
          sorted(members).index(pod.host_id), len(members))
    obs_lib.trace.complete_event(
        'host_readmit', 'train', t0, time.time(),
        {'epoch': pod.epoch, 'joiners': [int(j) for j in joiners],
         'members': len(members), 'step': int(at_step)})
    logging.getLogger(__name__).warning(
        'pod re-admitted %s at the step %d boundary: members now %s '
        '(epoch %d)', sorted(joiners), at_step, sorted(members),
        pod.epoch)

  def elastic_step(batch):
    """One pod-synchronized training step: local grads on this host's
    batch slice, bounded weighted-mean allreduce across members,
    identical apply everywhere. Returns (merged metrics, StepSync)."""
    nonlocal state
    grads, new_mstate, m_local = grad_step(state, batch)
    g_leaves, g_treedef = jax.tree_util.tree_flatten(
        jax.device_get((grads, new_mstate)))
    sync = pod.step_sync(
        step + 1,
        [np.asarray(leaf, np.float32) for leaf in g_leaves],
        weight=float(next(iter(batch.values())).shape[0]),
        meta={
            'loss': float(m_local['loss']),
            'acc_correct': float(m_local['accuracy_correct']),
            'acc_total': float(m_local['accuracy_total']),
        },
        stop_vote=guard.local(),
    )
    avg_grads, avg_mstate = jax.tree_util.tree_unflatten(
        g_treedef, sync.arrays)
    state, grad_norm = apply_step(state, avg_grads, avg_mstate)
    total = sync.weight_total
    merged = {
        # Per-host losses are slice means; their weighted mean is the
        # exact global-batch mean. Accuracy counts just sum.
        'loss': sum(meta['loss'] * meta['weight']
                    for meta in sync.metas.values()) / total,
        'grad_norm': grad_norm,
        'accuracy_correct': sum(
            meta['acc_correct'] for meta in sync.metas.values()),
        'accuracy_total': sum(
            meta['acc_total'] for meta in sync.metas.values()),
    }
    return merged, sync

  def pod_safe_save(at_step: int, metrics: Dict[str, float]) -> None:
    """save_checkpoint, with a peer death inside the checkpoint barrier
    handled like any other host loss (the leader's write is already
    intact or will be redone at the next boundary)."""
    try:
      trainer.save_checkpoint(state, at_step, metrics)
    except faults_lib.HostLostError as host_err:
      if pod is None or on_host_error != 'degrade':
        raise
      rebuild_after_host_loss(host_err)

  preempted = False
  final_metrics: Dict[str, float] = {}
  try:
    # Two prefetch layers: data.prefetch_iterator overlaps host-side
    # decode/shuffle/stacking with the device step (reference
    # counterpart: tf.data prefetch(AUTOTUNE) in data_providers.py),
    # and TrainBatchPrefetcher overlaps the sharded H2D transfer of
    # batch i+1 with the device's step i.
    prefetcher = TrainBatchPrefetcher(
        data_lib.prefetch_iterator(maybe_augmented()),
        trainer,
        poison_base_step=step,
    )
    t_step = time.time()
    for names, host_batch, batch in prefetcher:
      sync = None
      if pod is not None:
        # The host-loss drill hook fires BEFORE the step so the death
        # lands mid-barrier for the survivors, like a real SIGKILL.
        faults_lib.maybe_host_lost(step + 1, pod.host_id, pod.abandon)
        m = None
        attempts = 0
        while True:
          try:
            with jax.profiler.StepTraceAnnotation('train', step_num=step):
              m, sync = elastic_step(batch)
            break
          except faults_lib.HostLostError as host_err:
            attempts += 1
            if (on_host_error != 'degrade'
                or attempts > pod.rebuild_attempts):
              raise
            if rebuild_after_host_loss(host_err):
              # This host adopted a peer state AHEAD of its own, so
              # the batch in hand was already applied pod-wide; drop
              # it (adoption advanced `step`) and realign on the next.
              break
            # The failed step never committed (apply only runs after a
            # full collect): re-slice this same host batch for the
            # surviving member set and replay it under the new epoch.
            batch = prefetcher.place(host_batch)
        if m is None:
          continue
      else:
        try:
          faults_lib.injected_train_device_fault(step + 1)
          with jax.profiler.StepTraceAnnotation('train', step_num=step):
            state, m = train_step(state, batch)
        except Exception as e:  # pylint: disable=broad-except
          err = faults_lib.classify_device_error(e)
          if (on_device_error != 'degrade'
              or not isinstance(err, faults_lib.DeviceLostError)):
            raise
          if not degrade_mesh():
            raise err
          # The failed batch was consumed from the pipeline but never
          # applied: re-place it on the rebuilt mesh and re-run.
          batch = prefetcher.place(host_batch)
          with jax.profiler.StepTraceAnnotation('train', step_num=step):
            state, m = train_step(state, batch)
      step += 1
      # Per-iteration wall time (dispatch-to-dispatch, which converges
      # to device step time once the pipeline fills) feeds the registry
      # histogram and — when DCTPU_TRACE is set — a train_step span.
      t_now = time.time()
      trainer.step_time_hist.observe(t_now - t_step)
      obs_lib.trace.complete_event('train_step', 'train', t_step, t_now,
                                   {'step': step})
      t_step = t_now
      faults_lib.maybe_kill_train_at_step(step)
      faults_lib.maybe_sigterm_at_step(step)
      if pod is not None and sync is not None and sync.join_requests:
        # Re-admission lands exactly at a step boundary: every member
        # saw the same join requests piggybacked on this step's sync.
        admit_joiners(sync.join_requests, step)
      if sentinel.enabled:
        if pending is not None and sentinel.observe(*pending):
          if sentinel.should_rollback():
            rollback()
            continue
        pending = (step, m, names, host_batch)
      if step % params.get('log_every_n_steps', 100) == 0:
        m_host = {k: float(v) for k, v in m.items()}
        m_host['train/accuracy'] = m_host['accuracy_correct'] / max(
            m_host['accuracy_total'], 1
        )
        trainer.log_metrics(step, 'train', m_host)
      if step % eval_every == 0:
        # Force-resolve the delayed verdict before checkpointing: a
        # save boundary crossed while the state is contaminated would
        # persist NaN params, and the rollback path would then "heal"
        # onto the poisoned checkpoint. The extra device sync is free
        # here — eval blocks on the device anyway.
        if sentinel.enabled and pending is not None:
          sentinel.observe(*pending)
          pending = None
        if sentinel.should_rollback():
          rollback()
          continue
        if sentinel.consecutive:
          logging.getLogger(__name__).warning(
              'skipping eval/checkpoint at step %d: state contaminated '
              'by a non-finite update (%d/%d consecutive)',
              step, sentinel.consecutive, sentinel.limit,
          )
        else:
          final_metrics = run_eval(state)
          trainer.log_metrics(step, 'eval', final_metrics)
          pod_safe_save(step, final_metrics)
      # Elastic pods read the stop decision off the step sync (bounded,
      # unanimous-by-construction: every member merged the same votes);
      # legacy runs take the allgather vote, now also bounded.
      stop_requested = (bool(sync is not None and sync.stop)
                        if pod is not None else guard.requested())
      if stop_requested:
        # Emergency checkpoint at the step boundary, then a clean
        # return: the retry wrapper / scheduler restarts from it.
        # Same contamination guard as above: resuming from a NaN
        # emergency save would be worse than losing a few steps.
        if sentinel.enabled and pending is not None:
          sentinel.observe(*pending)
          pending = None
        if sentinel.consecutive:
          logging.getLogger(__name__).warning(
              'skipping emergency checkpoint at step %d: state '
              'contaminated by a non-finite update; resume will fall '
              'back to the last valid checkpoint', step,
          )
        else:
          pod_safe_save(step, {})
        final_metrics = {'preempted': 1.0, 'stop_step': float(step)}
        preempted = True
        logging.getLogger(__name__).warning(
            'preemption checkpoint saved at step %d; exiting cleanly',
            step,
        )
        break
    if not preempted:
      if sentinel.enabled and pending is not None:
        sentinel.observe(*pending)
        pending = None
      if sentinel.enabled and sentinel.consecutive:
        # Out of data with contaminated params: roll back even below
        # the threshold rather than finish (and save) a NaN state.
        rollback()
      final_metrics = run_eval(state)
      trainer.log_metrics(step, 'eval', final_metrics)
      pod_safe_save(step, final_metrics)
  finally:
    if prefetcher is not None:
      prefetcher.close()
    if owns_guard:
      guard.restore()
    sentinel.close()
    fault_counters: Dict[str, float] = dict(sentinel.counters)
    if pod is not None:
      # pod_epoch / n_host_rebuilds / n_host_readmissions /
      # n_barrier_timeouts land in the same `faults` split the other
      # resilience counters use.
      fault_counters.update(pod.counters())
      pod.close()
    if stream_ds is not None:
      fault_counters.update(stream_ds.counters)
    if train_ds is not None:
      fault_counters.update(train_ds.counters)
    # Bucketed-training observability: distinct compiled step
    # geometries (clean run: == n buckets), ring-attention routing for
    # long-insert widths, and the padding waste of bucket triage.
    fault_counters['n_train_forward_shapes'] = float(
        trainer.n_train_forward_shapes)
    ring_traces = ring_lib.n_blockwise_traces - ring_traces_start
    if ring_traces:
      fault_counters['n_ring_attention_traces'] = float(ring_traces)
    total_pos = float(fault_counters.get('n_train_window_positions', 0))
    if total_pos:
      fault_counters['train_padding_fraction'] = (
          float(fault_counters.get('n_train_padded_positions', 0))
          / total_pos)
    if prefetcher is not None:
      # Transfer-overlap observability: a clean N-step run reports
      # train_transfer_overlap_fraction == (N-1)/N (every launch after
      # the first rides under the previous step's compute).
      fault_counters.update(prefetcher.stats())
    if n_train_degraded:
      fault_counters['n_train_degraded'] = float(n_train_degraded)
    step_times = trainer.step_time_hist.percentiles()
    if step_times['count']:
      fault_counters['train_step_p50_s'] = step_times['p50']
      fault_counters['train_step_p99_s'] = step_times['p99']
    if fault_counters:
      trainer.log_metrics(step, 'faults', fault_counters)
    # The device this run really used, the resolved loss path and how
    # its Pallas calls resolved (compiled vs interpreter).
    trainer.log_metrics(step, 'device', {
        **pallas_util.execution_report(),
        'use_pallas_wavefront': int(resolve_pallas_wavefront(params)),
        'n_model_axis_sharded_params': mesh_lib.count_model_sharded(
            jax.tree.map(lambda a: a.sharding, state.params)),
    })
    if profile_dir:
      jax.profiler.stop_trace()
  if jax.process_count() > 1:
    # Writes happen on process 0 only; without this sync the other
    # hosts exit first and the distributed shutdown barrier times out
    # while process 0 is still checkpointing.
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices('dc_tpu_end_of_training')
  return final_metrics


_UNSET = object()


def run_training_with_retry(
    *args,
    max_retries: int = 1_000_000,
    backoff_base: float = 0.5,
    backoff_max: float = 60.0,
    max_stalled_restarts: int = 3,
    **kwargs,
):
  """Retries training on transient failures (TPU preemption,
  device-unavailable), resuming from the latest valid checkpoint
  (reference retry-forever loop: model_train_custom_loop.py:333-347) —
  with three brakes the reference lacks:

  * only TRANSIENT errors retry (shared taxonomy,
    deepconsensus_tpu/faults.classify_error); a permanent error (bad
    config, bad data, diverged model) raises on the first attempt
    instead of looping forever;
  * exponential backoff between attempts (backoff_base * 2^k, capped
    at backoff_max) so a flapping device isn't hammered;
  * a crash-loop breaker: when the resume step fails to advance across
    max_stalled_restarts consecutive restarts, retrying cannot help
    (the failure precedes the first new checkpoint every time) and
    CrashLoopError aborts the loop.
  """
  log = logging.getLogger(__name__)
  out_dir = kwargs.get('out_dir')
  if out_dir is None and len(args) >= 2 and isinstance(args[1], str):
    out_dir = args[1]
  attempts = 0
  last_step = _UNSET
  stalled = 0
  while True:
    try:
      return run_training(*args, **kwargs)
    except Exception as e:  # pylint: disable=broad-except
      message = f'{type(e).__name__}: {e}'
      attempts += 1
      if faults_lib.classify_error(message) != faults_lib.FaultKind.TRANSIENT:
        raise
      if attempts > max_retries:
        raise
      if out_dir is not None:
        # Crash-loop detection needs the resume position; read it
        # without quarantining (run_training owns that mutation).
        resume_step = checkpoints_lib.latest_valid_step(
            os.path.join(os.path.abspath(out_dir), 'checkpoints')
        )
        if last_step is not _UNSET and resume_step == last_step:
          stalled += 1
          if stalled >= max_stalled_restarts:
            raise faults_lib.CrashLoopError(
                f'training failed {stalled + 1} consecutive time(s) '
                f'without the resume step advancing past '
                f'{resume_step}; aborting instead of crash-looping '
                f'(last error: {message.splitlines()[0]})'
            ) from e
        else:
          stalled = 0
        last_step = resume_step
      delay = min(backoff_max, backoff_base * (2 ** (attempts - 1)))
      log.warning(
          'transient failure (%s); restarting from latest valid '
          'checkpoint in %.1fs (attempt %d)',
          message.splitlines()[0], delay, attempts,
      )
      time.sleep(delay)
