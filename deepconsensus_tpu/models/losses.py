"""Differentiable alignment loss and related losses (JAX).

AlignmentLoss is the reference's soft edit-distance training objective
(reference: deepconsensus/models/losses_and_metrics.py:263-609): a
wavefront DP over cross-entropy substitution/insertion costs with a
constant deletion cost and a logsumexp soft minimum, optionally
band-restricted. Here the DP is a lax.scan (ops/wavefront) and the
whole loss jits and differentiates end-to-end on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deepconsensus_tpu import constants
from deepconsensus_tpu.ops import wavefront

Array = jnp.ndarray


def left_shift_sequence(y: Array) -> Array:
  """Moves internal gaps to the end per row via the two-stage sort trick
  (reference: losses_and_metrics.py:92-115)."""
  seq_length = y.shape[1]
  ixs = jnp.broadcast_to(jnp.arange(seq_length), y.shape)
  sort_order = jnp.sort(
      jnp.where(y != constants.GAP_INT, ixs, seq_length + ixs), axis=1
  )
  sort_order = jnp.where(
      sort_order < seq_length, sort_order, sort_order - seq_length
  )
  return jnp.take_along_axis(y, sort_order, axis=1)


def xentropy_subs_cost(y_true: Array, y_pred: Array,
                       eps: float = 1e-7) -> Array:
  """[B, m, n] pairwise cross-entropy costs for integer labels
  (reference: losses_and_metrics.py:123-143).

  Computed as an exact vocab gather rather than a one-hot matmul: on
  TPU a default-precision matmul would round the log-probs to bfloat16.
  """
  log_p = jnp.log(jnp.clip(y_pred, eps, 1 - eps))  # [B, n, V]
  b, n, _ = y_pred.shape
  bi = jnp.arange(b)[:, None, None]
  ji = jnp.arange(n)[None, None, :]
  return -log_p[bi, ji, y_true[:, :, None]]


def xentropy_ins_cost(y_pred: Array, eps: float = 1e-7) -> Array:
  """[B, n] insertion costs: -log P(gap)
  (reference: losses_and_metrics.py:191-207)."""
  return -jnp.log(jnp.clip(y_pred[..., constants.GAP_INT], eps, 1 - eps))


class AlignmentLoss:
  """Soft alignment loss; callable returns the mean over the batch."""

  def __init__(
      self,
      del_cost: float = 1.0,
      loss_reg: Optional[float] = 1.0,
      width: Optional[int] = None,
      eps: float = 1e-7,
      inf: float = 1e9,
      use_pallas: bool = False,
      mesh=None,
      batch_axis: str = 'data',
  ):
    self.del_cost = del_cost
    self.loss_reg = loss_reg
    self.width = width
    self.eps = eps
    self.inf = inf
    # Whole-DP Pallas kernels (ops/wavefront_pallas): forward scorer +
    # custom-VJP backward, so training differentiates through Pallas.
    self.use_pallas = use_pallas
    # The mesh the surrounding step is partitioned over, if any. XLA
    # cannot partition a Mosaic kernel by itself ("wrap the call in a
    # shard_map"), and the DP is independent per example, so on a
    # multi-device mesh the Pallas scorers run under a shard_map over
    # the batch axis (replicated over every other axis).
    self.mesh = mesh
    self.batch_axis = batch_axis

  def _pallas_scores(self, scorer, subs_costs, ins_costs, seq_lens, *static):
    call = lambda subs, ins, lens: scorer(subs, ins, lens, *static)
    if self.mesh is not None and self.mesh.size > 1:
      spec = jax.sharding.PartitionSpec(self.batch_axis)
      call = jax.shard_map(
          call, mesh=self.mesh, in_specs=(spec, spec, spec), out_specs=spec,
          check_vma=False)
    return call(subs_costs, ins_costs, seq_lens)

  def per_example(self, y_true: Array, y_pred: Array) -> Array:
    """[B] loss values for y_true [B, m] ints and y_pred [B, n, V]."""
    y_true = left_shift_sequence(y_true.astype(jnp.int32))
    seq_lens = jnp.sum(
        (y_true != constants.GAP_INT).astype(jnp.int32), axis=-1
    )
    y_pred = y_pred / jnp.sum(y_pred, axis=-1, keepdims=True)

    subs_costs = xentropy_subs_cost(y_true, y_pred, self.eps)
    ins_costs = xentropy_ins_cost(y_pred, self.eps)
    del_cost = jnp.asarray(self.del_cost, y_pred.dtype)

    if self.loss_reg is None:
      minop = lambda t: jnp.min(t, axis=0)
    else:
      reg = jnp.asarray(self.loss_reg, y_pred.dtype)
      minop = lambda t: -reg * jax.nn.logsumexp(-t / reg, axis=0)

    if self.width is None:
      if self.use_pallas:
        from deepconsensus_tpu.ops import wavefront_pallas

        return self._pallas_scores(
            wavefront_pallas.alignment_scores_vjp,
            subs_costs, ins_costs, seq_lens, self.del_cost,
            self.loss_reg, self.inf,
        )
      return wavefront.alignment_scan(
          subs_costs, ins_costs, del_cost, seq_lens, minop, self.inf
      )
    if self.use_pallas:
      from deepconsensus_tpu.ops import wavefront_pallas

      return self._pallas_scores(
          wavefront_pallas.banded_alignment_scores_vjp,
          subs_costs, ins_costs, seq_lens, self.del_cost,
          self.loss_reg, int(self.width), self.inf,
      )
    return wavefront.banded_alignment_scan(
        subs_costs, ins_costs, del_cost, seq_lens, int(self.width), minop,
        self.inf,
    )

  def __call__(self, y_true: Array, y_pred: Array) -> Array:
    return jnp.mean(self.per_example(y_true, y_pred))


def distillation_loss(
    teacher_logits: Array,
    student_logits: Array,
    temperature: float = 1.0,
    kind: str = 'mean_squared_error',
) -> Array:
  """Temperature-scaled prob-space loss between teacher and student
  (reference DistillationLoss: losses_and_metrics.py:1170-1213)."""
  teacher = jax.nn.softmax(teacher_logits / temperature, axis=-1)
  student = jax.nn.softmax(student_logits / temperature, axis=-1)
  if kind == 'mean_squared_error':
    per_pos = jnp.mean((teacher - student) ** 2, axis=-1)
  elif kind == 'kl_divergence':
    per_pos = jnp.sum(
        teacher * (jnp.log(jnp.clip(teacher, 1e-10, 1.0))
                   - jnp.log(jnp.clip(student, 1e-10, 1.0))),
        axis=-1,
    )
  else:
    raise ValueError(f'unknown distillation loss {kind!r}')
  return jnp.mean(per_pos)
