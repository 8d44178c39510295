"""Knowledge distillation: frozen teacher -> smaller student.

Mirrors the reference's distillation trainer (reference:
deepconsensus/models/model_distillation.py:104-420): the student is
initialized from a teacher layer map, then trained with
student_alpha * AlignmentLoss + distill_alpha * logit-space loss while
the teacher runs inference-only. Both models share one jitted step.

As a flywheel stage (models/flywheel.py), distillation is durable:
mid-run checkpoints every params.checkpoint_every_n_steps, crash/
preemption resume from the latest valid checkpoint (fast-forwarding
the deterministic data stream so the replayed prefix is dropped, not
re-applied), a shared PreemptionGuard so SIGTERM checkpoints and
returns {'preempted': 1, 'stop_step': N} like run_training, and an
elastic-pod-lite mode (grads cross hosts through the bounded
step_sync; a HostLostError propagates to the flywheel's stage retry,
which degrades the pod, rather than rebuilding in place).
"""
from __future__ import annotations

import logging
import os
from typing import Dict, Optional

import jax
import ml_collections
import numpy as np

from deepconsensus_tpu import faults as faults_lib
from deepconsensus_tpu.models import checkpoints as checkpoints_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import data as data_lib
from deepconsensus_tpu.models import losses as losses_lib
from deepconsensus_tpu.models import metrics as metrics_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.models import train as train_lib
from deepconsensus_tpu.parallel import mesh as mesh_lib
from deepconsensus_tpu.parallel import partition_rules

log = logging.getLogger(__name__)


def init_student_from_teacher(
    student_params: Dict,
    teacher_params: Dict,
    cfg: ml_collections.ConfigDict,
) -> Dict:
  """Copies teacher weights into the student per the layer maps
  (reference: model_distillation.py:104-144)."""
  student = jax.tree_util.tree_map(lambda x: x, student_params)  # copy

  if cfg.get('init_nonencoder_layers', True):
    for key in student:
      if key != 'encoder' and key in teacher_params:
        student[key] = jax.tree_util.tree_map(
            lambda x: x, teacher_params[key]
        )

  if cfg.get('init_encoder_stack', True):
    t_layers = list(cfg.teacher_encoder_layers)
    s_layers = list(cfg.student_encoder_layers)
    enc_s = dict(student['encoder'])
    enc_t = teacher_params['encoder']
    for t, s in zip(t_layers, s_layers):
      for stem in ('self_attention', 'attention_wrapper', 'ffn',
                   'ffn_wrapper'):
        src = f'{stem}_{t}'
        dst = f'{stem}_{s}'
        if src in enc_t and dst in enc_s:
          enc_s[dst] = jax.tree_util.tree_map(lambda x: x, enc_t[src])
    if 'output_normalization' in enc_t:
      enc_s['output_normalization'] = jax.tree_util.tree_map(
          lambda x: x, enc_t['output_normalization']
      )
    student['encoder'] = enc_s
  return student


def run_distillation(
    params: ml_collections.ConfigDict,
    teacher_params_cfg: ml_collections.ConfigDict,
    teacher_variables: Dict,
    out_dir: str,
    train_patterns=None,
    eval_patterns=None,
    num_epochs: Optional[int] = None,
    mesh=None,
    elastic_config: Optional[Dict] = None,
    preemption_guard=None,
) -> Dict[str, float]:
  """Distillation training driver; returns final eval metrics.

  A preemption (SIGTERM/SIGINT via the guard, or a pod stop vote)
  checkpoints at the step boundary and returns
  {'preempted': 1.0, 'stop_step': N}; a rerun on the same out_dir
  resumes from that checkpoint. elastic_config (host_id, n_hosts,
  barrier_timeout) is the pod-lite version of run_training's: grads
  cross hosts through parallel/elastic.py step_sync on a local mesh,
  but a HostLostError propagates to the caller (the flywheel's stage
  retry degrades the pod) instead of an in-place rebuild.
  """
  for side in (params, teacher_params_cfg):
    model_lib.refuse_inference_only_kind(side, 'distill')
  train_patterns = train_patterns or list(params.train_path)
  eval_patterns = eval_patterns or list(params.eval_path)
  num_epochs = num_epochs or params.num_epochs

  pod = None
  if elastic_config and int(elastic_config.get('n_hosts', 1) or 1) > 1:
    from deepconsensus_tpu.parallel import elastic as elastic_lib

    pod = elastic_lib.ElasticPod(
        os.path.join(os.path.abspath(out_dir), '.pod'),
        host_id=int(elastic_config['host_id']),
        n_hosts=int(elastic_config['n_hosts']),
        barrier_timeout=float(
            elastic_config.get('barrier_timeout')
            or params.get('elastic_barrier_timeout', 30.0) or 30.0),
        heartbeat_interval=float(
            elastic_config.get('heartbeat_interval', 0.25) or 0.25),
        readmit=False,
    )
  if pod is not None and mesh is None:
    mesh = mesh_lib.local_mesh(tp=int(params.get('tp', 1) or 1))

  owns_guard = preemption_guard is None
  guard = preemption_guard or train_lib.PreemptionGuard(
      barrier_timeout=float(
          params.get('elastic_barrier_timeout', 30.0) or 30.0)
  ).install()

  teacher_model = model_lib.get_model(teacher_params_cfg)
  student_model = model_lib.get_model(params)

  train_ds = data_lib.DatasetIterator(
      patterns=train_patterns, params=params,
      batch_size=params.batch_size, seed=params.seed,
  )
  eval_ds = data_lib.DatasetIterator(
      patterns=eval_patterns, params=params,
      batch_size=params.batch_size, shuffle=False,
  )
  decay_steps = train_ds.steps_per_epoch * params.get(
      'num_epochs_for_decay', num_epochs
  )
  trainer = train_lib.Trainer(params=params, out_dir=out_dir, mesh=mesh,
                              pod=pod)
  if pod is not None:
    pod.start()
  if trainer._is_writer():
    config_lib.save_params_as_json(out_dir, params)
  state = trainer.init_state(steps_total=max(decay_steps, 1))
  # Crash/preemption resume: a valid checkpoint under this out_dir
  # means a previous distill attempt got that far — restore it (full
  # state: params + LAMB moments + LR position) and fast-forward the
  # deterministic data stream past the applied prefix. Only a fresh
  # start initializes from the teacher layer map.
  resume_from = trainer.latest_valid_checkpoint()
  start_step = 0
  if resume_from is not None:
    state = trainer.restore_checkpoint(state, resume_from)
    start_step = checkpoints_lib.checkpoint_step(resume_from)
    log.warning('distill: resuming from %s (step %d)', resume_from,
                start_step)
  else:
    state = state.replace(
        params=init_student_from_teacher(
            state.params, teacher_variables['params'], params
        )
    )

  align_loss = trainer.loss_fn
  student_alpha = float(params.student_alpha)
  distill_alpha = float(params.distill_alpha)
  temperature = float(params.temperature)
  logit_loss = params.get('logit_loss_identifier', 'mean_squared_error')

  def grads_and_metrics(state, batch):
    rng = jax.random.fold_in(state.dropout_rng, state.step)
    teacher_out = teacher_model.apply(
        teacher_variables, batch['rows'],
        method=teacher_model.apply_with_intermediates,
    )

    def loss_of(p):
      out = student_model.apply(
          {'params': p}, batch['rows'], train=True,
          rngs={'dropout': rng},
          method=student_model.apply_with_intermediates,
      )
      l_student = align_loss(batch['label'], out['preds'])
      l_distill = losses_lib.distillation_loss(
          teacher_out['logits'], out['logits'],
          temperature=temperature, kind=logit_loss,
      )
      total = student_alpha * l_student + distill_alpha * l_distill
      return total, (l_student, l_distill, out['preds'])

    (loss, (l_s, l_d, preds)), grads = jax.value_and_grad(
        loss_of, has_aux=True
    )(state.params)
    correct, total = metrics_lib.per_example_accuracy_counts(
        batch['label'], preds
    )
    return grads, {
        'loss': loss,
        'student_loss': l_s,
        'distill_loss': l_d,
        'accuracy_correct': correct,
        'accuracy_total': total,
    }

  # Trace count == distinct compiled batch geometries: a bucketed
  # corpus (DatasetIterator emits per-bucket batches) compiles one
  # teacher+student step per bucket width over the shared param trees,
  # exactly like run_training's n_train_forward_shapes.
  n_forward_shapes = [0]

  def step(state, batch):
    n_forward_shapes[0] += 1
    grads, m = grads_and_metrics(state, batch)
    return state.apply_gradients(grads=grads), m

  # Same declarative rule table as run_training: the student state
  # (params + LAMB moments) shards by partition_rules.DEFAULT_RULES and
  # the batch over the data axis, so distillation scales on the same
  # meshes as training without its own sharding map. compile_parallel
  # is jax.jit underneath: one executable is cached per bucket width,
  # with no mid-run recompiles for a fixed bucket set.
  state_sh = trainer.state_shardings(state)
  batch_sh = trainer._batch_sharding()
  train_step = partition_rules.compile_parallel(
      step,
      in_shardings=(state_sh, {'rows': batch_sh, 'label': batch_sh}),
      out_shardings=(state_sh, None),
      donate_argnums=(0,),
  )
  # Pod-lite split: local grads, host-level bounded allreduce, local
  # apply — every member applies the same weighted-mean grads, so the
  # states evolve identically (same LAMB update, same fold_in rng).
  grad_step = partition_rules.compile_parallel(
      grads_and_metrics,
      in_shardings=(state_sh, {'rows': batch_sh, 'label': batch_sh}),
  )

  log_every = params.get('log_every_n_steps', 100)
  checkpoint_every = int(params.get('checkpoint_every_n_steps', 0) or 0)
  step_count = 0
  try:
    for _ in range(num_epochs):
      for batch in train_ds.epoch():
        batch.pop('name', None)
        step_count += 1
        if step_count <= start_step:
          # Resume fast-forward: the data stream is deterministic
          # (same patterns, same seed, same epoch order), so skipping
          # the first start_step batches replays the stream position
          # without re-applying the already-checkpointed prefix.
          continue
        sync = None
        if pod is not None:
          local = trainer.localize_batch(batch)
          grads, m = grad_step(state, local)
          g_leaves, treedef = jax.tree_util.tree_flatten(
              jax.device_get(grads))
          sync = pod.step_sync(
              step_count,
              [np.asarray(leaf, np.float32) for leaf in g_leaves],
              weight=float(next(iter(local.values())).shape[0]),
              meta={'loss': float(m['loss'])},
              stop_vote=guard.local(),
          )
          avg = jax.tree_util.tree_unflatten(treedef, sync.arrays)
          state = state.apply_gradients(grads=avg)
        else:
          state, m = train_step(state, batch)
        if step_count % log_every == 0:
          trainer.log_metrics(
              step_count, 'train', {k: float(v) for k, v in m.items()}
          )
        if checkpoint_every and step_count % checkpoint_every == 0:
          trainer.save_checkpoint(state, step_count, {})
        stop = sync.stop if sync is not None else guard.requested()
        if stop:
          # Preemption: commit the step boundary and hand control back
          # (the flywheel marks its journal `interrupted` and exits 0;
          # the next --resume run restores from this checkpoint).
          trainer.save_checkpoint(state, step_count, {})
          return {'preempted': 1.0, 'stop_step': float(step_count)}
    # Final eval + checkpoint, through the same aggregation as
    # run_training so the metric key set (identity_pred, class
    # accuracies, yield) and best_checkpoint_metric behave identically.
    # The bucket telemetry (batches per width, padding fraction,
    # compile-once proof) rides the same 'faults' sidecar channel.
    fault_counters = {k: float(v) for k, v in train_ds.counters.items()}
    fault_counters['n_train_forward_shapes'] = float(n_forward_shapes[0])
    total_pos = fault_counters.get('n_train_window_positions', 0.0)
    if total_pos:
      fault_counters['train_padding_fraction'] = (
          fault_counters.get('n_train_padded_positions', 0.0) / total_pos)
    trainer.log_metrics(step_count, 'faults', fault_counters)
    final = trainer.run_eval(state, eval_ds)
    trainer.save_checkpoint(state, step_count, final)
    return final
  finally:
    if pod is not None:
      pod.close()
    if owns_guard:
      guard.restore()
