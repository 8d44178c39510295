"""Pallas TPU kernels: pipelined whole-DP wavefront alignment scorer.

The alignment score is an anti-diagonal DP with a sequential dependence
over k = i + j. The TPU-native formulation here makes the *grid* the
diagonal axis: each grid step consumes a streamed block of `unroll`
diagonals of the wavefrontified cost tensors (Pallas double-buffers
the HBM->VMEM DMAs automatically) and updates carry rows held in VMEM
scratch that persist across grid steps. The full batch rides the
vector lanes of every step, so per-step work is `unroll` [B, m+1]
vector ops instead of the [batch_tile, m+1] slice a whole-DP-in-VMEM
kernel is limited to, and VMEM holds a few diagonal blocks instead of
the entire cost tensor.

`alignment_scores` is the forward scorer matching
ops/wavefront.alignment_scan semantics exactly; `alignment_scores_vjp`
wraps it in a jax.custom_vjp: the forward rule streams every DP row
V[k] to HBM and saves them as residuals, and the backward runs one
reverse-order adjoint sweep whose blocks walk the diagonals backwards
(soft-min weights recomputed per diagonal from the saved rows), so
AlignmentLoss trains through Pallas end-to-end in two DP sweeps per
step (the reference trains through this DP:
losses_and_metrics.py:346-411). Validated against alignment_scan
values and jax.grad in interpret mode and on TPU hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.ops import wavefront

Array = jnp.ndarray

# Max diagonals computed per grid step in the forward kernel. Each
# diagonal's vector work ([B, m+1]) is tiny next to a grid step's fixed
# overhead, so unrolling amortizes the ~m+n sequential steps that
# dominate this DP's runtime. VMEM cost grows linearly with unroll
# (Pallas double-buffers the streamed [unroll, B, m]/[B, m+1] blocks,
# and emit_rows streams an [unroll, B, m+1] output block too), so the
# effective unroll is capped per call by _auto_unroll to keep streamed
# blocks inside a VMEM budget.
PALLAS_UNROLL = 8

# Streamed-block VMEM budget (bytes). ~16 MB/core total; leave room
# for the three [B, m+1] scratch rows and the non-streamed operands.
_VMEM_STREAM_BUDGET = 8 * 1024 * 1024


def _auto_unroll(requested, batch, lanes):
  """Largest unroll <= requested whose double-buffered streamed blocks
  fit in _VMEM_STREAM_BUDGET. `lanes` is the summed last-dim width of
  every [unroll, B, lanes_i] block the kernel streams (inputs and
  outputs), so per-diagonal bytes = 2 (double-buffer) * 4 (f32) * B *
  lanes."""
  per_diag = 2 * 4 * batch * lanes
  fit = max(1, _VMEM_STREAM_BUDGET // max(per_diag, 1))
  return max(1, min(requested, fit))


def _make_minop(loss_reg):
  if loss_reg is None:
    return lambda t: jnp.min(t, axis=0)
  reg = jnp.float32(loss_reg)
  return lambda t: -reg * jax.nn.logsumexp(-t / reg, axis=0)


def _init_rows(b, m, ins0, del_cost, inf):
  """DP rows V[0], V[1] as full [B, m+1] vectors (cells (i, k-i))."""
  row0 = jnp.concatenate(
      [jnp.zeros((b, 1), jnp.float32),
       jnp.full((b, m), inf, jnp.float32)], axis=1,
  )
  row1 = jnp.concatenate(
      [ins0[:, :1],
       jnp.full((b, 1), del_cost, jnp.float32),
       jnp.full((b, m - 1), inf, jnp.float32)], axis=1,
  )
  return row0, row1


def _dp_step(k, v_p2, v_p1, subs_k, ins_k, *, i_range, n, del_cost,
             minop, inf):
  """One anti-diagonal update (forward scorer; the backward recomputes
  its soft-min weights from the rows this step produced, so drift here
  would silently decouple loss values from gradients)."""
  valid = (k - i_range >= 0) & (k - i_range <= n)
  o_m = v_p2 + subs_k
  o_i = v_p1 + ins_k
  v_p2_next = v_p1[:, :-1]
  o_d = v_p2_next + del_cost
  body_vals = minop(jnp.stack([o_m, o_i[:, 1:], o_d]))
  v_new = jnp.where(
      valid, jnp.concatenate([o_i[:, :1], body_vals], axis=1), inf
  )
  return v_p2_next, v_new


def _recompute_band(k, rows_p2, rows_p1, subs_k, ins_k, del_cost,
                    loss_reg):
  """Option stack + soft-min weights at diagonal k (backward pass)."""
  t = jnp.stack([
      rows_p2[:, :-1] + subs_k,
      rows_p1[:, 1:] + ins_k[:, 1:],
      rows_p1[:, :-1] + del_cost,
  ])
  if loss_reg is None:
    tmin = jnp.min(t, axis=0, keepdims=True)
    eq = (t == tmin).astype(jnp.float32)
    w = eq / jnp.sum(eq, axis=0, keepdims=True)
  else:
    w = jax.nn.softmax(-t / jnp.float32(loss_reg), axis=0)
  return w


def _fwd_kernel(subs_ref, ins_ref, ins0_ref, lens_ref, out_ref, rows_ref,
                v_p2_ref, v_p1_ref, v_opt_ref, *, m, n, del_cost,
                loss_reg, inf, unroll):
  """Grid step g computes diagonals k = g*unroll + u + 2, u = 0..unroll-1.

  Streams subs[k-2] and ins[k-1] in blocks of `unroll` diagonals;
  carries V[k-2], V[k-1] in VMEM scratch across grid steps. The
  per-diagonal vector work ([B, m+1]) is far smaller than a grid
  step's fixed cost, so unrolling several diagonals per step amortizes
  the sequential-grid overhead that dominates this DP. Diagonals past
  m+n (grid padding) are masked invalid by the k-range check inside
  _dp_step. With emit_rows (rows_ref not None), every V[k] streams
  back to HBM for the backward sweep.
  """
  g = pl.program_id(0)
  b = v_p1_ref.shape[0]
  i_range = jax.lax.broadcasted_iota(jnp.int32, (1, m + 1), 1)
  minop = _make_minop(loss_reg)
  lens = lens_ref[:, 0]
  k_end = lens + n
  onehot_len = (
      jax.lax.broadcasted_iota(jnp.int32, (b, m + 1), 1) == lens[:, None]
  ).astype(jnp.float32)

  @pl.when(g == 0)
  def _init():
    row0, row1 = _init_rows(b, m, ins0_ref[:], del_cost, inf)
    v_p2_ref[:] = row0
    v_p1_ref[:] = row1
    v_opt_ref[:] = jnp.full((b, 1), inf, jnp.float32)

  v_p2 = v_p2_ref[:]
  v_p1 = v_p1_ref[:]
  v_opt = v_opt_ref[:]
  for u in range(unroll):
    k = g * unroll + u + 2
    v_p2_next, v_new = _dp_step(
        k, v_p2[:, :m], v_p1, subs_ref[u], ins_ref[u],
        i_range=i_range, n=n, del_cost=del_cost, minop=minop, inf=inf,
    )
    if rows_ref is not None:
      rows_ref[u] = v_new
    v_at_len = jnp.sum(v_new * onehot_len, axis=1, keepdims=True)
    hit = (k_end == k)[:, None].astype(jnp.float32)
    v_opt = v_opt * (1.0 - hit) + v_at_len * hit
    v_p2 = jnp.concatenate(
        [v_p2_next, jnp.full((b, 1), inf, jnp.float32)], axis=1
    )
    v_p1 = v_new
  v_p2_ref[:] = v_p2
  v_p1_ref[:] = v_p1
  v_opt_ref[:] = v_opt
  out_ref[:] = v_opt


def _pad_diagonals(t, n_pad, front=False):
  """Zero-pads a [K, ...]-leading diagonal stream to n_pad entries.

  front=True pads before entry 0, which keeps reverse-order block
  sweeps block-aligned (the backward kernel's block g covers the
  highest-k diagonals when g = 0)."""
  k_dim = t.shape[0]
  if k_dim == n_pad:
    return t
  pad = (n_pad - k_dim, 0) if front else (0, n_pad - k_dim)
  pad_widths = [pad] + [(0, 0)] * (t.ndim - 1)
  return jnp.pad(t, pad_widths)


def _fwd_call(subs_w, ins_w, seq_lens, m, n, del_cost, loss_reg, inf,
              interpret, emit_rows, unroll):
  k_dim = subs_w.shape[0]  # m + n - 1
  batch = subs_w.shape[1]
  lanes = 2 * m + 1 + ((m + 1) if emit_rows else 0)
  unroll = _auto_unroll(unroll, batch, lanes)
  unroll = max(1, min(unroll, k_dim))
  n_blocks = -(-k_dim // unroll)
  n_pad = n_blocks * unroll
  ins0 = ins_w[0]  # [B, m+1]
  subs_pad = _pad_diagonals(subs_w, n_pad)
  # ins diagonal for k lives at ins_w[k-1]; shift so entry j serves
  # k = j + 2, aligning ins blocks with subs blocks.
  ins_shift = _pad_diagonals(ins_w[1:], n_pad)
  impl = functools.partial(
      _fwd_kernel, m=m, n=n, del_cost=float(del_cost),
      loss_reg=None if loss_reg is None else float(loss_reg),
      inf=float(inf), unroll=unroll,
  )
  if emit_rows:
    kernel = impl
  else:
    def kernel(subs, ins, ins0_r, lens, out, s1, s2, s3):
      impl(subs, ins, ins0_r, lens, out, None, s1, s2, s3)
  out_specs = [
      pl.BlockSpec((batch, 1), lambda g: (0, 0),
                   memory_space=pltpu.VMEM),
  ]
  out_shape = [jax.ShapeDtypeStruct((batch, 1), jnp.float32)]
  if emit_rows:
    # rows[k] for k = 2..m+n; rows[0:2] are closed-form, filled XLA-side.
    out_specs.append(
        pl.BlockSpec((unroll, batch, m + 1), lambda g: (g, 0, 0),
                     memory_space=pltpu.VMEM)
    )
    out_shape.append(
        jax.ShapeDtypeStruct((n_pad, batch, m + 1), jnp.float32)
    )
  results = pl.pallas_call(
      kernel,
      grid=(n_blocks,),
      in_specs=[
          pl.BlockSpec((unroll, batch, m), lambda g: (g, 0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((unroll, batch, m + 1), lambda g: (g, 0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, m + 1), lambda g: (0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, 1), lambda g: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[
          pltpu.VMEM((batch, m + 1), jnp.float32),
          pltpu.VMEM((batch, m + 1), jnp.float32),
          pltpu.VMEM((batch, 1), jnp.float32),
      ],
      interpret=interpret,
  )(subs_pad, ins_shift, ins0, seq_lens.astype(jnp.int32)[:, None])
  if emit_rows:
    return results[0], results[1][:k_dim]
  return results


def _scores_and_rows(subs_costs, ins_costs, del_cost, seq_lens, loss_reg,
                     inf, interpret, emit_rows, unroll=None):
  """Shared forward pipeline (wavefrontify + kernel call) for the plain
  scorer and the custom-VJP fwd rule — one copy, so the rule's output
  can never drift from the primal's. Returns (scores, rows|None)."""
  _, m, n = subs_costs.shape
  subs_w = wavefrontify32(subs_costs)  # [K, B, m]
  ins_w = wavefrontify_vec32(ins_costs, m + 1)  # [K+1, B, m+1]
  res = _fwd_call(
      subs_w, ins_w, seq_lens, m, n, del_cost, loss_reg, inf,
      interpret, emit_rows=emit_rows,
      unroll=PALLAS_UNROLL if unroll is None else unroll,
  )
  if emit_rows:
    out, rows = res
    return out[:, 0], rows
  (out,) = res
  return out[:, 0], None


def alignment_scores(
    subs_costs: Array,
    ins_costs: Array,
    del_cost: float,
    seq_lens: Array,
    loss_reg: Optional[float] = None,
    inf: float = 1e9,
    interpret: bool = False,
    unroll: Optional[int] = None,
) -> Array:
  """Pallas twin of wavefront.alignment_scan (same args/semantics)."""
  out, _ = _scores_and_rows(
      subs_costs, ins_costs, del_cost, seq_lens, loss_reg, inf,
      interpret, emit_rows=False, unroll=unroll,
  )
  return out


def wavefrontify32(t: Array) -> Array:
  return wavefront.wavefrontify(t).astype(jnp.float32)


def wavefrontify_vec32(v: Array, len1: int) -> Array:
  return wavefront.wavefrontify_vec(v, len1).astype(jnp.float32)


def _unwavefrontify(t_w: Array, n: int) -> Array:
  """Inverse of wavefront.wavefrontify: [K, B, m] -> [B, m, n] with
  out[b, i, j] = t_w[i+j, b, i] (the forward map is one-to-one)."""
  _, _, m = t_w.shape
  i = jnp.arange(m)[:, None]
  j = jnp.arange(n)[None, :]
  return jnp.transpose(t_w, (1, 0, 2))[:, i + j, i]


def _unwavefrontify_vec_grad(v_w: Array, n: int) -> Array:
  """Adjoint of wavefront.wavefrontify_vec: [K2, B, L] -> [B, n].

  The forward broadcasts v[b, j] to every slot (k=i+j, i), so the
  adjoint sums over i: out[b, j] = sum_i v_w[i+j, b, i].
  """
  _, _, length = v_w.shape
  i = jnp.arange(length)[:, None]
  j = jnp.arange(n)[None, :]
  return jnp.sum(jnp.transpose(v_w, (1, 0, 2))[:, i + j, i], axis=1)


def _bwd_kernel(subs_ref, ins_ref, rows_p2_ref, rows_p1_ref, lens_ref,
                g_ref, dsubs_ref, dins_ref, dv1_ref, dA_ref, dB_ref, *,
                m, n, del_cost, loss_reg, inf, k_total, unroll):
  """Reverse adjoint sweep; grid step g handles diagonals
  k = j + 2 for j = (k_total-1) - (g+1)*unroll + u, u descending.

  Every stream (subs[k-2], ins[k-1], recorded DP rows V[k-2], V[k-1],
  and the emitted gradients) is indexed by j = k - 2 and front-padded
  to a multiple of `unroll`, so the reverse sweep walks whole blocks
  from the high-k end (block index n_blocks-1-g) and stays
  block-aligned. Front-padding entries have k < 2; their carry
  updates are masked out (their block writes land in the padding,
  sliced off by the caller). Carry: dA = adjoint of V[k], dB =
  adjoint of V[k-1]. Step k spreads dA onto the three predecessor
  rows weighted by the recomputed soft-min weights and emits the
  cost-gradient diagonals dsubs[k-2], dins[k-1].
  """
  del inf
  g = pl.program_id(0)
  b = dA_ref.shape[0]
  i_range = jax.lax.broadcasted_iota(jnp.int32, (1, m + 1), 1)
  lens = lens_ref[:, 0]
  k_end = lens + n
  onehot_len = (
      jax.lax.broadcasted_iota(jnp.int32, (b, m + 1), 1) == lens[:, None]
  ).astype(jnp.float32)

  @pl.when(g == 0)
  def _init():
    dA_ref[:] = jnp.zeros((b, m + 1), jnp.float32)
    dB_ref[:] = jnp.zeros((b, m + 1), jnp.float32)
    dv1_ref[:] = jnp.zeros((b, m + 1), jnp.float32)

  dA_c = dA_ref[:]
  dB_c = dB_ref[:]
  dv1 = dv1_ref[:]
  zero_col = jnp.zeros((b, 1), jnp.float32)
  for u in reversed(range(unroll)):
    k = (k_total - 1) - (g + 1) * unroll + u + 2
    valid = (k - i_range >= 0) & (k - i_range <= n)
    inject = g_ref[:, :1] * onehot_len * (k_end == k)[:, None].astype(
        jnp.float32
    )
    dA = jnp.where(valid, dA_c + inject, 0.0)

    w = _recompute_band(
        k, rows_p2_ref[u], rows_p1_ref[u], subs_ref[u], ins_ref[u],
        del_cost, loss_reg,
    )
    dbody = dA[:, 1:]
    d_m = w[0] * dbody
    d_i1 = w[1] * dbody
    d_d = w[2] * dbody
    dsubs_ref[u] = d_m
    dins_row = jnp.concatenate([dA[:, :1], d_i1], axis=1)
    dins_ref[u] = dins_row
    dB_new = dB_c + dins_row + jnp.concatenate([d_d, zero_col], axis=1)
    # Front-padding diagonals (k < 2) must not advance the carry: the
    # final dv1 (written at k = 2) is the closed-form dV[1] adjoint.
    ok = k >= 2
    dA_c = jnp.where(ok, dB_new, dA_c)
    dB_c = jnp.where(
        ok, jnp.concatenate([d_m, zero_col], axis=1), dB_c
    )
    dv1 = jnp.where(ok, dB_new, dv1)
  dA_ref[:] = dA_c
  dB_ref[:] = dB_c
  dv1_ref[:] = dv1


def _scores_fwd_impl(subs_costs, ins_costs, seq_lens, del_cost, loss_reg,
                     inf, interpret, emit_rows=False, unroll=None):
  return _scores_and_rows(
      subs_costs, ins_costs, del_cost, seq_lens, loss_reg, inf,
      pallas_util.resolve_interpret(interpret), emit_rows=emit_rows,
      unroll=unroll,
  )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def alignment_scores_vjp(
    subs_costs: Array,
    ins_costs: Array,
    seq_lens: Array,
    del_cost: float,
    loss_reg: Optional[float],
    inf: float = 1e9,
    interpret: Optional[bool] = None,
    unroll: Optional[int] = None,
) -> Array:
  """Differentiable Pallas twin of wavefront.alignment_scan.

  Same scores as `alignment_scores`; gradients w.r.t. subs_costs and
  ins_costs come from the pipelined backward kernels. `unroll` caps the
  per-grid-step diagonal unroll for both sweeps (None = PALLAS_UNROLL;
  the VMEM fit still applies, so forward and backward may resolve to
  different effective unrolls — results are unroll-invariant either
  way).
  """
  out, _ = _scores_fwd_impl(
      subs_costs, ins_costs, seq_lens, del_cost, loss_reg, inf,
      interpret, unroll=unroll,
  )
  return out


def _vjp_fwd(subs_costs, ins_costs, seq_lens, del_cost, loss_reg, inf,
             interpret, unroll):
  # Run the forward with emit_rows=True and save every DP row V[k] as
  # a residual: the backward then starts directly at the reverse
  # adjoint sweep instead of re-running the whole forward DP (one of
  # three otherwise-equal-cost sweeps per training step). The rows
  # residual is [m+n+1, B, m+1] f32 in HBM — ~110 MB at B=1024,
  # m=121, well inside a v5e's 16 GB. The cost tensors are saved in
  # their original [B, m, n] layout/dtype; the backward re-derives the
  # wavefrontified views (a cheap XLA gather next to the DP sweep).
  out, rows_kernel = _scores_fwd_impl(
      subs_costs, ins_costs, seq_lens, del_cost, loss_reg, inf,
      interpret, emit_rows=True, unroll=unroll,
  )
  return out, (subs_costs, ins_costs, seq_lens, rows_kernel)


def _vjp_bwd(del_cost, loss_reg, inf, interpret, unroll, res, g):
  import numpy as np

  subs_costs, ins_costs, seq_lens, rows_kernel = res
  batch, m, n = subs_costs.shape
  subs_w = wavefrontify32(subs_costs)
  ins_w = wavefrontify_vec32(ins_costs, m + 1)
  k_dim = subs_w.shape[0]  # m + n - 1
  interp = pallas_util.resolve_interpret(interpret)
  k_total = m + n

  row0, row1 = _init_rows(batch, m, ins_w[0], float(del_cost), float(inf))
  rows = jnp.concatenate(
      [row0[None], row1[None], rows_kernel], axis=0
  )  # [m+n+1, B, m+1], rows[k] = V[k]

  # Pass 2: reverse sweep in blocks of `unroll` diagonals. Every
  # stream is re-indexed by j = k - 2 (subs[j], ins_w[j+1], V[j],
  # V[j+1], gradients) and front-padded to a block multiple, so block
  # n_blocks-1-g holds the g-th-from-the-top group of diagonals and
  # the kernel walks u descending inside it.
  # Backward streams 6 [unroll, B, ~m] blocks per diagonal (4 in,
  # 2 out), so the VMEM-fitted unroll is smaller than the forward's.
  unroll = _auto_unroll(
      PALLAS_UNROLL if unroll is None else unroll, batch, 6 * m + 4
  )
  unroll = max(1, min(unroll, k_dim))
  n_blocks = -(-k_dim // unroll)
  n_pad = n_blocks * unroll
  subs_b = _pad_diagonals(subs_w, n_pad, front=True)
  ins_b = _pad_diagonals(ins_w[1:], n_pad, front=True)
  rows_p2_b = _pad_diagonals(rows[:-2], n_pad, front=True)
  rows_p1_b = _pad_diagonals(rows[1:-1], n_pad, front=True)
  rev_spec_m = pl.BlockSpec(
      (unroll, batch, m), lambda gi: (n_blocks - 1 - gi, 0, 0),
      memory_space=pltpu.VMEM)
  rev_spec_m1 = pl.BlockSpec(
      (unroll, batch, m + 1), lambda gi: (n_blocks - 1 - gi, 0, 0),
      memory_space=pltpu.VMEM)
  d_subs_pad, d_ins_pad, dv1 = pl.pallas_call(
      functools.partial(
          _bwd_kernel, m=m, n=n, del_cost=float(del_cost),
          loss_reg=None if loss_reg is None else float(loss_reg),
          inf=float(inf), k_total=k_total, unroll=unroll,
      ),
      grid=(n_blocks,),
      in_specs=[
          rev_spec_m,
          rev_spec_m1,
          rev_spec_m1,
          rev_spec_m1,
          pl.BlockSpec((batch, 1), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, 1), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_specs=[
          rev_spec_m,
          rev_spec_m1,
          pl.BlockSpec((batch, m + 1), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((n_pad, batch, m), jnp.float32),
          jax.ShapeDtypeStruct((n_pad, batch, m + 1), jnp.float32),
          jax.ShapeDtypeStruct((batch, m + 1), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((batch, m + 1), jnp.float32),
          pltpu.VMEM((batch, m + 1), jnp.float32),
      ],
      interpret=interp,
  )(subs_b, ins_b, rows_p2_b, rows_p1_b,
    seq_lens.astype(jnp.int32)[:, None], g.astype(jnp.float32)[:, None])

  d_subs_w = d_subs_pad[n_pad - k_dim:]
  # The kernel emits dins at j = k - 2 >= 0, i.e. ins_w entries 1..;
  # V[1][0] = ins_w[0][:, 0] is the only input-dependent init entry,
  # so dins[0] comes from the dV[1] carry.
  d_ins_w = jnp.concatenate(
      [jnp.concatenate(
          [dv1[:, :1], jnp.zeros((batch, m), jnp.float32)], axis=1
      )[None],
       d_ins_pad[n_pad - k_dim:]], axis=0
  )
  d_subs = _unwavefrontify(d_subs_w, n).astype(subs_costs.dtype)
  d_ins = _unwavefrontify_vec_grad(d_ins_w, n).astype(ins_costs.dtype)
  d_lens = np.zeros(seq_lens.shape, jax.dtypes.float0)
  return d_subs, d_ins, d_lens


alignment_scores_vjp.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# Banded wavefront DP: Pallas twins of wavefront.banded_alignment_scan.
#
# Band coordinates: cell (x, y) of the [m+1, m+1] DP matrix lives at
# (k = x + y, d = y - x + width); odd-parity slots hold no cell and
# stay at `inf` (the cost streams put `inf` there, and valid slots only
# ever read same-parity predecessors). The grid walks k = 2..2m with
# [B, 2*width+1] carries in VMEM scratch — the band-space analogue of
# the unbanded diagonal-grid kernel above, reusing its streaming /
# unroll / rows-as-residuals design (reference banded recursion:
# losses_and_metrics.py:413-547).
# ---------------------------------------------------------------------------


def _band_cost_streams(subs_costs, ins_costs, width, inf):
  """Per-diagonal cost bands for k = 2..2m: ([K, B, n_diag],) * 2 f32
  with K = 2m - 1 — the vectorized form of banded_alignment_scan's
  subs_at/ins_at gathers, computed once XLA-side and streamed."""
  batch, m, n = subs_costs.shape
  n_diag = 2 * width + 1
  d = jnp.arange(n_diag)
  ks = jnp.arange(2, 2 * m + 1)
  x2 = ks[:, None] - d[None, :] + width  # [K, n_diag]
  y2 = ks[:, None] + d[None, :] - width
  s_valid = (
      (x2 % 2 == 0) & (x2 >= 2) & (y2 >= 2) & (x2 <= 2 * m) & (y2 <= 2 * n)
  )
  xi = jnp.clip(x2 // 2 - 1, 0, m - 1)
  yi = jnp.clip(y2 // 2 - 1, 0, n - 1)
  subs_band = jnp.where(
      s_valid[None], subs_costs[:, xi, yi], inf
  )  # [B, K, n_diag]
  i_valid = (x2 % 2 == 0) & (x2 >= 0) & (y2 >= 0)
  y = jnp.clip(y2 // 2, 0, n)
  ins_pad = jnp.concatenate(
      [jnp.zeros((batch, 1), ins_costs.dtype), ins_costs], axis=1
  )
  ins_band = jnp.where(i_valid[None], ins_pad[:, y], inf)
  return (
      jnp.transpose(subs_band, (1, 0, 2)).astype(jnp.float32),
      jnp.transpose(ins_band, (1, 0, 2)).astype(jnp.float32),
  )


def _band_init_rows(b, n_diag, width, ins0, del_cost, inf):
  """Band rows at k=0 (only cell (0,0)=0) and k=1 (cells (1,0)=del and
  (0,1)=ins[0]), as [B, n_diag] f32."""
  d = jax.lax.broadcasted_iota(jnp.int32, (b, n_diag), 1)
  row0 = jnp.where(d == width, 0.0, jnp.float32(inf))
  row1 = jnp.full((b, n_diag), inf, jnp.float32)
  row1 = jnp.where(d == width - 1, jnp.float32(del_cost), row1)
  row1 = jnp.where(d == width + 1, ins0, row1)
  return row0, row1


def _band_ends(lens, n, width):
  """Band evaluation cell (reference index_ending_band):
  (x, y) = (lens, min(n, lens + width)) -> (k_end, d_end)."""
  y_end = jnp.minimum(n, lens + width)
  return lens + y_end, y_end - lens + width


def _band_step(p2, p1, subs_k, ins_k, del_cost, minop, inf, b):
  """One band diagonal update (identical algebra to the scan step)."""
  inf_col = jnp.full((b, 1), inf, jnp.float32)
  o_m = p2 + subs_k
  o_d = jnp.concatenate([p1[:, 1:], inf_col], axis=1) + del_cost
  o_i = jnp.concatenate([inf_col, p1[:, :-1]], axis=1) + ins_k
  return minop(jnp.stack([o_m, o_d, o_i]))


def _band_fwd_kernel(subs_ref, ins_ref, ins0_ref, lens_ref, out_ref,
                     rows_ref, p2_ref, p1_ref, opt_ref, *, m, width,
                     del_cost, loss_reg, inf, unroll):
  """Grid step g computes band diagonals k = g*unroll + u + 2."""
  g = pl.program_id(0)
  b = p1_ref.shape[0]
  n_diag = 2 * width + 1
  minop = _make_minop(loss_reg)
  lens = lens_ref[:, 0]
  k_end, d_end = _band_ends(lens, m, width)
  onehot_d = (
      jax.lax.broadcasted_iota(jnp.int32, (b, n_diag), 1) == d_end[:, None]
  ).astype(jnp.float32)

  @pl.when(g == 0)
  def _init():
    row0, row1 = _band_init_rows(
        b, n_diag, width, ins0_ref[:, :1], del_cost, inf
    )
    p2_ref[:] = row0
    p1_ref[:] = row1
    # k_end < 2 never fires inside the streamed loop; latch the
    # closed-form rows here (k_end = 0 needs width = 0 or an empty
    # window; k_end = 1 happens at lens = 0, width = 1).
    opt = jnp.full((b, 1), inf, jnp.float32)
    opt0 = jnp.sum(row0 * onehot_d, axis=1, keepdims=True)
    opt1 = jnp.sum(row1 * onehot_d, axis=1, keepdims=True)
    opt = jnp.where((k_end == 0)[:, None], opt0, opt)
    opt = jnp.where((k_end == 1)[:, None], opt1, opt)
    opt_ref[:] = opt

  p2 = p2_ref[:]
  p1 = p1_ref[:]
  opt = opt_ref[:]
  for u in range(unroll):
    k = g * unroll + u + 2
    new = _band_step(p2, p1, subs_ref[u], ins_ref[u], del_cost, minop,
                     inf, b)
    if rows_ref is not None:
      rows_ref[u] = new
    hit = (k_end == k)[:, None].astype(jnp.float32)
    v_at = jnp.sum(new * onehot_d, axis=1, keepdims=True)
    opt = opt * (1.0 - hit) + v_at * hit
    p2 = p1
    p1 = new
  p2_ref[:] = p2
  p1_ref[:] = p1
  opt_ref[:] = opt
  out_ref[:] = opt


def _band_fwd_call(subs_band, ins_band, ins0, seq_lens, m, width,
                   del_cost, loss_reg, inf, interpret, emit_rows, unroll):
  k_dim = subs_band.shape[0]  # 2m - 1
  batch = subs_band.shape[1]
  n_diag = 2 * width + 1
  lanes = 2 * n_diag + (n_diag if emit_rows else 0)
  unroll = _auto_unroll(unroll, batch, lanes)
  unroll = max(1, min(unroll, k_dim))
  n_blocks = -(-k_dim // unroll)
  n_pad = n_blocks * unroll
  subs_pad = _pad_diagonals(subs_band, n_pad)
  ins_pad = _pad_diagonals(ins_band, n_pad)
  impl = functools.partial(
      _band_fwd_kernel, m=m, width=width, del_cost=float(del_cost),
      loss_reg=None if loss_reg is None else float(loss_reg),
      inf=float(inf), unroll=unroll,
  )
  if emit_rows:
    kernel = impl
  else:
    def kernel(subs, ins, ins0_r, lens, out, s1, s2, s3):
      impl(subs, ins, ins0_r, lens, out, None, s1, s2, s3)
  out_specs = [
      pl.BlockSpec((batch, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
  ]
  out_shape = [jax.ShapeDtypeStruct((batch, 1), jnp.float32)]
  if emit_rows:
    out_specs.append(
        pl.BlockSpec((unroll, batch, n_diag), lambda g: (g, 0, 0),
                     memory_space=pltpu.VMEM)
    )
    out_shape.append(
        jax.ShapeDtypeStruct((n_pad, batch, n_diag), jnp.float32)
    )
  results = pl.pallas_call(
      kernel,
      grid=(n_blocks,),
      in_specs=[
          pl.BlockSpec((unroll, batch, n_diag), lambda g: (g, 0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((unroll, batch, n_diag), lambda g: (g, 0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, 1), lambda g: (0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, 1), lambda g: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[
          pltpu.VMEM((batch, n_diag), jnp.float32),
          pltpu.VMEM((batch, n_diag), jnp.float32),
          pltpu.VMEM((batch, 1), jnp.float32),
      ],
      interpret=interpret,
  )(subs_pad, ins_pad, ins0, seq_lens.astype(jnp.int32)[:, None])
  if emit_rows:
    return results[0], results[1][:k_dim]
  return results


def _banded_scores_and_rows(subs_costs, ins_costs, del_cost, seq_lens,
                            width, loss_reg, inf, interpret, emit_rows,
                            unroll=None):
  batch, m, n = subs_costs.shape
  if m != n:
    raise ValueError('banded alignment requires m == n')
  if width < 1:
    raise ValueError('band width must be >= 1')
  subs_band, ins_band = _band_cost_streams(
      subs_costs, ins_costs, width, float(inf)
  )
  ins0 = ins_costs[:, :1].astype(jnp.float32)
  res = _band_fwd_call(
      subs_band, ins_band, ins0, seq_lens, m, width, del_cost, loss_reg,
      inf, interpret, emit_rows=emit_rows,
      unroll=PALLAS_UNROLL if unroll is None else unroll,
  )
  if emit_rows:
    out, rows = res
    return out[:, 0], rows
  (out,) = res
  return out[:, 0], None


def banded_alignment_scores(
    subs_costs: Array,
    ins_costs: Array,
    del_cost: float,
    seq_lens: Array,
    width: int,
    loss_reg: Optional[float] = None,
    inf: float = 1e9,
    interpret: bool = False,
    unroll: Optional[int] = None,
) -> Array:
  """Pallas twin of wavefront.banded_alignment_scan (same semantics)."""
  out, _ = _banded_scores_and_rows(
      subs_costs, ins_costs, del_cost, seq_lens, int(width), loss_reg,
      inf, interpret, emit_rows=False, unroll=unroll,
  )
  return out


def _band_bwd_kernel(subs_ref, ins_ref, rows_p2_ref, rows_p1_ref,
                     lens_ref, g_ref, dsubs_ref, dins_ref, dv1_ref,
                     dA_ref, dB_ref, *, m, width, del_cost, loss_reg,
                     inf, k_total, unroll):
  """Reverse adjoint sweep over band diagonals (block-aligned like the
  unbanded backward: streams are front-padded, block g covers the
  (g+1)-th-from-the-top group of diagonals, u walks descending).

  Carry: dA = adjoint of band[k], dB = adjoint of band[k-1]. A step
  spreads dA over the three predecessors with the recomputed soft-min
  weights: match -> band[k-2][d], delete -> band[k-1][d+1], insert ->
  band[k-1][d-1]; emits dsubs[k], dins[k] cost-band gradients."""
  g = pl.program_id(0)
  b = dA_ref.shape[0]
  n_diag = 2 * width + 1
  lens = lens_ref[:, 0]
  k_end, d_end = _band_ends(lens, m, width)
  onehot_d = (
      jax.lax.broadcasted_iota(jnp.int32, (b, n_diag), 1) == d_end[:, None]
  ).astype(jnp.float32)

  @pl.when(g == 0)
  def _init():
    dA_ref[:] = jnp.zeros((b, n_diag), jnp.float32)
    dB_ref[:] = jnp.zeros((b, n_diag), jnp.float32)
    dv1_ref[:] = jnp.zeros((b, n_diag), jnp.float32)

  dA_c = dA_ref[:]
  dB_c = dB_ref[:]
  dv1 = dv1_ref[:]
  zero_col = jnp.zeros((b, 1), jnp.float32)
  for u in reversed(range(unroll)):
    k = (k_total - 1) - (g + 1) * unroll + u + 2
    inject = g_ref[:, :1] * onehot_d * (k_end == k)[:, None].astype(
        jnp.float32
    )
    dA = dA_c + inject

    p2 = rows_p2_ref[u]
    p1 = rows_p1_ref[u]
    inf_col = jnp.full((b, 1), inf, jnp.float32)
    t = jnp.stack([
        p2 + subs_ref[u],
        jnp.concatenate([p1[:, 1:], inf_col], axis=1) + del_cost,
        jnp.concatenate([inf_col, p1[:, :-1]], axis=1) + ins_ref[u],
    ])
    if loss_reg is None:
      tmin = jnp.min(t, axis=0, keepdims=True)
      eq = (t == tmin).astype(jnp.float32)
      w = eq / jnp.sum(eq, axis=0, keepdims=True)
    else:
      w = jax.nn.softmax(-t / jnp.float32(loss_reg), axis=0)

    d_m = w[0] * dA
    a_del = w[1] * dA
    b_ins = w[2] * dA
    dsubs_ref[u] = d_m
    dins_ref[u] = b_ins
    dp1 = (
        dB_c
        + jnp.concatenate([zero_col, a_del[:, :-1]], axis=1)
        + jnp.concatenate([b_ins[:, 1:], zero_col], axis=1)
    )
    ok = k >= 2
    dA_c = jnp.where(ok, dp1, dA_c)
    dB_c = jnp.where(ok, d_m, dB_c)
    dv1 = jnp.where(ok, dp1, dv1)
  dA_ref[:] = dA_c
  dB_ref[:] = dB_c
  dv1_ref[:] = dv1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def banded_alignment_scores_vjp(
    subs_costs: Array,
    ins_costs: Array,
    seq_lens: Array,
    del_cost: float,
    loss_reg: Optional[float],
    width: int,
    inf: float = 1e9,
    interpret: Optional[bool] = None,
    unroll: Optional[int] = None,
) -> Array:
  """Differentiable Pallas twin of wavefront.banded_alignment_scan."""
  out, _ = _banded_scores_and_rows(
      subs_costs, ins_costs, del_cost, seq_lens, int(width), loss_reg,
      inf, pallas_util.resolve_interpret(interpret), emit_rows=False,
      unroll=unroll,
  )
  return out


def _banded_vjp_fwd(subs_costs, ins_costs, seq_lens, del_cost, loss_reg,
                    width, inf, interpret, unroll):
  out, rows_kernel = _banded_scores_and_rows(
      subs_costs, ins_costs, del_cost, seq_lens, int(width), loss_reg,
      inf, pallas_util.resolve_interpret(interpret), emit_rows=True,
      unroll=unroll,
  )
  return out, (subs_costs, ins_costs, seq_lens, rows_kernel)


def _banded_vjp_bwd(del_cost, loss_reg, width, inf, interpret, unroll,
                    res, g):
  import numpy as np

  subs_costs, ins_costs, seq_lens, rows_kernel = res
  batch, m, n = subs_costs.shape
  width = int(width)
  n_diag = 2 * width + 1
  interp = pallas_util.resolve_interpret(interpret)
  subs_band, ins_band = _band_cost_streams(
      subs_costs, ins_costs, width, float(inf)
  )
  k_dim = subs_band.shape[0]  # 2m - 1
  k_total = 2 * m  # maximum band diagonal (k runs 2..2m)

  ins0 = ins_costs[:, :1].astype(jnp.float32)
  row0, row1 = _band_init_rows(
      batch, n_diag, width, ins0, float(del_cost), float(inf)
  )
  rows = jnp.concatenate([row0[None], row1[None], rows_kernel], axis=0)

  unroll_eff = _auto_unroll(
      PALLAS_UNROLL if unroll is None else unroll, batch, 6 * n_diag
  )
  unroll_eff = max(1, min(unroll_eff, k_dim))
  n_blocks = -(-k_dim // unroll_eff)
  n_pad = n_blocks * unroll_eff
  subs_b = _pad_diagonals(subs_band, n_pad, front=True)
  ins_b = _pad_diagonals(ins_band, n_pad, front=True)
  rows_p2_b = _pad_diagonals(rows[:-2], n_pad, front=True)
  rows_p1_b = _pad_diagonals(rows[1:-1], n_pad, front=True)
  rev_spec = pl.BlockSpec(
      (unroll_eff, batch, n_diag), lambda gi: (n_blocks - 1 - gi, 0, 0),
      memory_space=pltpu.VMEM)
  d_subs_pad, d_ins_pad, dv1 = pl.pallas_call(
      functools.partial(
          _band_bwd_kernel, m=m, width=width, del_cost=float(del_cost),
          loss_reg=None if loss_reg is None else float(loss_reg),
          inf=float(inf), k_total=k_total, unroll=unroll_eff,
      ),
      grid=(n_blocks,),
      in_specs=[
          rev_spec,
          rev_spec,
          rev_spec,
          rev_spec,
          pl.BlockSpec((batch, 1), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
          pl.BlockSpec((batch, 1), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_specs=[
          rev_spec,
          rev_spec,
          pl.BlockSpec((batch, n_diag), lambda gi: (0, 0),
                       memory_space=pltpu.VMEM),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((n_pad, batch, n_diag), jnp.float32),
          jax.ShapeDtypeStruct((n_pad, batch, n_diag), jnp.float32),
          jax.ShapeDtypeStruct((batch, n_diag), jnp.float32),
      ],
      scratch_shapes=[
          pltpu.VMEM((batch, n_diag), jnp.float32),
          pltpu.VMEM((batch, n_diag), jnp.float32),
      ],
      interpret=interp,
  )(subs_b, ins_b, rows_p2_b, rows_p1_b,
    seq_lens.astype(jnp.int32)[:, None], g.astype(jnp.float32)[:, None])

  d_subs_band = d_subs_pad[n_pad - k_dim:]  # [K, B, n_diag], K index = k-2
  d_ins_band = d_ins_pad[n_pad - k_dim:]

  # Un-band dsubs: cell (i, j) of subs_costs was consumed by slot
  # (k = i + j + 2, d = j - i + width) iff inside the band.
  i = jnp.arange(m)[:, None]
  j = jnp.arange(n)[None, :]
  kidx = i + j  # stream index k - 2
  didx = j - i + width
  s_ok = (didx >= 0) & (didx < n_diag)
  d_subs = jnp.where(
      s_ok[None],
      jnp.transpose(d_subs_band, (1, 0, 2))[
          :, kidx, jnp.clip(didx, 0, n_diag - 1)
      ],
      0.0,
  )

  # Un-band dins: ins_costs[:, y-1] was consumed by every band slot
  # with that y: (k = x + y, d = y - x + width) for x = 0..m in band —
  # plus the k = 1 init slot (0, 1), whose adjoint is dv1[width+1].
  xs = jnp.arange(m + 1)[None, :]  # [1, m+1]
  ys = jnp.arange(1, n + 1)[:, None]  # [n, 1] (y = j + 1)
  kidx_i = xs + ys - 2  # stream index k - 2
  didx_i = ys - xs + width
  i_ok = (kidx_i >= 0) & (kidx_i < k_dim) & (didx_i >= 0) & (
      didx_i < n_diag
  )
  gathered = jnp.transpose(d_ins_band, (1, 0, 2))[
      :, jnp.clip(kidx_i, 0, k_dim - 1), jnp.clip(didx_i, 0, n_diag - 1)
  ]  # [B, n, m+1]
  d_ins = jnp.sum(jnp.where(i_ok[None], gathered, 0.0), axis=2)
  d_ins = d_ins.at[:, 0].add(dv1[:, width + 1])

  d_lens = np.zeros(seq_lens.shape, jax.dtypes.float0)
  return (
      d_subs.astype(subs_costs.dtype),
      d_ins.astype(ins_costs.dtype),
      d_lens,
  )


banded_alignment_scores_vjp.defvjp(_banded_vjp_fwd, _banded_vjp_bwd)
