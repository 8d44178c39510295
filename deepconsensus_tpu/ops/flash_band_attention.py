"""Pallas TPU kernel: block-banded flash attention for long windows.

The short-window kernel (ops/banded_attention.py) holds the full
[G, L, L] logits in VMEM, which is ideal at the pileup default L=100
but caps out near L~512 and wastes MXU work on masked-out tiles. This
kernel makes the band structural instead: the grid walks
(batch*head groups, query blocks, key blocks *within the band*), so
compute and VMEM scale with L*band instead of L^2. Keys/values are
zero-padded by one block on each side so the banded index map never
clamps (out-of-range tiles are killed by the mask, never revisited),
and the online-softmax state (row max, row sum, output accumulator)
lives in VMEM scratch across the sequential key-block axis.

Semantics match ops/banded_attention.reference_banded_attention (the
reference's band_part mask + softmax: attention_layer.py:112-120,207);
validated against it in interpret mode and, at L=100, against the
short-window kernel. Forward-only by design: the flagship training
window is L=100 where the short-window VJP kernels already train; this
kernel serves long-window inference and composes with
parallel/ring_attention.py for cross-device sequence parallelism.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepconsensus_tpu.ops import pallas_util

Array = jnp.ndarray

_NEG = -1e9

# Above this window length the whole-L kernel (banded_attention.py)
# stops being the right tool — its [G, L, L] VMEM block grows past
# what fits/compiles — and callers should switch to this kernel.
WHOLE_L_LIMIT = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            attn_win_size, length, block_q, block_k, n_kblocks,
            w_blocks, lse_ref=None):
  j = pl.program_id(2)
  qi = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    m_ref[:] = jnp.full_like(m_ref, _NEG)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

  q = q_ref[:].astype(jnp.float32)  # [G, BQ, D]
  k = k_ref[:].astype(jnp.float32)  # [G, BK, D]
  s = jax.lax.dot_general(
      q, k, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )  # [G, BQ, BK]
  # Global coordinates: rows from the query block, cols from the key
  # block's position in the *unpadded* sequence (the padded array is
  # shifted right by w_blocks*block_k).
  rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
  if attn_win_size is None:
    col_start = j * block_k  # index map (g, j): plain key-block walk
  else:
    col_start = qi * block_q - w_blocks * block_k + j * block_k
  cols = col_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
  valid = (cols >= 0) & (cols < length)
  if attn_win_size is not None:
    valid = valid & (jnp.abs(rows - cols) <= attn_win_size)
  s = jnp.where(valid, s, _NEG)

  m_prev = m_ref[:]                      # [G, BQ]
  m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
  alpha = jnp.exp(m_prev - m_new)        # rescale of previous state
  p = jnp.exp(s - m_new[:, :, None])     # [G, BQ, BK]
  # Fully-masked tiles (all _NEG) must contribute exactly zero even
  # when the running max is still _NEG (exp(0)=1 otherwise).
  p = jnp.where(valid, p, 0.0)
  l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=2)
  acc_ref[:] = (
      acc_ref[:] * alpha[:, :, None]
      + jax.lax.dot_general(
          p, v_ref[:].astype(jnp.float32),
          (((2,), (1,)), ((0,), (0,))),
          preferred_element_type=jnp.float32,
      )
  )
  m_ref[:] = m_new

  @pl.when(j == n_kblocks - 1)
  def _finalize():
    denom = l_ref[:]
    denom = jnp.where(denom == 0.0, 1.0, denom)  # padded query rows
    o_ref[:] = (acc_ref[:] / denom[:, :, None]).astype(o_ref.dtype)
    if lse_ref is not None:
      # Safe logsumexp per row; fully-masked rows get 0 (their w in
      # the backward is forced to 0 by the same validity mask).
      lse_ref[:] = jnp.where(
          l_ref[:] == 0.0, 0.0, m_ref[:] + jnp.log(denom)
      )


class _Plan:
  """Shared blocking geometry for the forward and backward kernels."""

  def __init__(self, b, l, h, d, attn_win_size, block_q, group):
    self.l, self.d = l, d
    self.n = b * h
    self.group = min(group, self.n)
    while self.n % self.group:
      self.group -= 1
    self.block_q = min(block_q, _round_up(l, 128))
    self.block_k = self.block_q
    self.lq = _round_up(l, self.block_q)
    if attn_win_size is None:
      self.w_blocks = 0
      self.n_kblocks = self.lq // self.block_k
      self.pad = 0
    else:
      self.w_blocks = -(-attn_win_size // self.block_k)  # ceil
      self.n_kblocks = 2 * self.w_blocks + 1
      self.pad = self.w_blocks * self.block_k

  def to_blocks(self, x, pad_lo, pad_hi):
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(self.n, self.l, self.d)
    return jnp.pad(x, ((0, 0), (pad_lo, pad_hi), (0, 0)))

  def from_blocks(self, x, b, h):
    x = x[:, : self.l]
    return jnp.transpose(x.reshape(b, h, self.l, self.d), (0, 2, 1, 3))

  def spec(self, index_map, block_len=None, rank2=False):
    block_len = block_len or self.block_q
    if rank2:
      return pl.BlockSpec((self.group, block_len), index_map,
                          memory_space=pltpu.VMEM)
    return pl.BlockSpec((self.group, block_len, self.d), index_map,
                        memory_space=pltpu.VMEM)


def _forward(q, k, v, attn_win_size, interpret, emit_lse):
  b, l, h, d = q.shape
  plan = _Plan(b, l, h, d, attn_win_size, 128, 8)
  qb = plan.to_blocks(q, 0, plan.lq - l)
  # Keys/values get w_blocks blocks of zeros each side so the banded
  # index map stays in range for every (qi, j); the mask kills them.
  kv_hi = (plan.lq - l) + plan.pad
  kb = plan.to_blocks(k, plan.pad, kv_hi)
  vb = plan.to_blocks(v, plan.pad, kv_hi)

  q_spec = plan.spec(lambda g, i, j: (g, i, 0))
  if attn_win_size is None:
    kv_index = lambda g, i, j: (g, j, 0)
  else:
    # Padded block 0 sits w_blocks blocks left of query block 0.
    kv_index = lambda g, i, j: (g, i + j, 0)
  kv_spec = plan.spec(kv_index)
  kwargs = dict(
      attn_win_size=attn_win_size, length=l, block_q=plan.block_q,
      block_k=plan.block_k, n_kblocks=plan.n_kblocks,
      w_blocks=plan.w_blocks,
  )
  if emit_lse:
    kernel = functools.partial(_kernel_with_lse, **kwargs)
    out_shape = [
        jax.ShapeDtypeStruct((plan.n, plan.lq, d), q.dtype),
        jax.ShapeDtypeStruct((plan.n, plan.lq), jnp.float32),
    ]
    out_specs = [q_spec, plan.spec(lambda g, i, j: (g, i), rank2=True)]
  else:
    kernel = functools.partial(_kernel, **kwargs)
    out_shape = jax.ShapeDtypeStruct((plan.n, plan.lq, d), q.dtype)
    out_specs = q_spec
  result = pl.pallas_call(
      kernel,
      grid=(plan.n // plan.group, plan.lq // plan.block_q,
            plan.n_kblocks),
      in_specs=[q_spec, kv_spec, kv_spec],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[
          pltpu.VMEM((plan.group, plan.block_q), jnp.float32),
          pltpu.VMEM((plan.group, plan.block_q), jnp.float32),
          pltpu.VMEM((plan.group, plan.block_q, d), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel', 'arbitrary'),
      ),
      interpret=pallas_util.resolve_interpret(interpret),
  )(qb, kb, vb)
  if emit_lse:
    out, lse = result
    return plan.from_blocks(out, b, h), lse
  return plan.from_blocks(result, b, h)


def _kernel_with_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                     acc_ref, **kwargs):
  _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
          lse_ref=lse_ref, **kwargs)


def flash_band_attention(
    q: Array,
    k: Array,
    v: Array,
    attn_win_size: Optional[int],
    interpret: Optional[bool] = None,
) -> Array:
  """Banded flash attention. q,k,v: [B, L, H, D], q pre-scaled.

  attn_win_size None means full (unbanded) attention; the key-block
  loop then covers the whole sequence.
  """
  return _forward(q, k, v, attn_win_size, interpret, emit_lse=False)


def _round_up(x: int, m: int) -> int:
  return -(-x // m) * m


def _recompute_w(q, k, lse, rows, cols, attn_win_size, length):
  """Softmax weights for one (q-block, k-block) tile from the saved
  row logsumexp; fully-masked positions get exactly 0."""
  s = jax.lax.dot_general(
      q, k, (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )
  valid = (cols >= 0) & (cols < length) & (rows < length)
  if attn_win_size is not None:
    valid = valid & (jnp.abs(rows - cols) <= attn_win_size)
  return jnp.where(valid, jnp.exp(s - lse[:, :, None]), 0.0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, attn_win_size, length, block_q,
                   block_k, n_kblocks, w_blocks):
  j = pl.program_id(2)
  qi = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    acc_ref[:] = jnp.zeros_like(acc_ref)

  q = q_ref[:].astype(jnp.float32)
  k = k_ref[:].astype(jnp.float32)
  rows = qi * block_q + jax.lax.broadcasted_iota(
      jnp.int32, (q.shape[0], block_q, block_k), 1)
  if attn_win_size is None:
    col_start = j * block_k
  else:
    col_start = qi * block_q - w_blocks * block_k + j * block_k
  cols = col_start + jax.lax.broadcasted_iota(
      jnp.int32, (q.shape[0], block_q, block_k), 2)
  w = _recompute_w(q, k, lse_ref[:], rows, cols, attn_win_size, length)
  dw = jax.lax.dot_general(
      do_ref[:].astype(jnp.float32), v_ref[:].astype(jnp.float32),
      (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )
  ds = w * (dw - delta_ref[:][:, :, None])
  acc_ref[:] += jax.lax.dot_general(
      ds, k, (((2,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )

  @pl.when(j == n_kblocks - 1)
  def _finalize():
    dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, attn_win_size,
                    length, block_q, block_k, n_qblocks, w_blocks):
  jq = pl.program_id(2)
  ki = pl.program_id(1)

  @pl.when(jq == 0)
  def _init():
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

  q = q_ref[:].astype(jnp.float32)
  k = k_ref[:].astype(jnp.float32)
  do = do_ref[:].astype(jnp.float32)
  if attn_win_size is None:
    row_start = jq * block_q
  else:
    row_start = ki * block_k - w_blocks * block_q + jq * block_q
  g = q.shape[0]
  rows = row_start + jax.lax.broadcasted_iota(
      jnp.int32, (g, block_q, block_k), 1)
  cols = ki * block_k + jax.lax.broadcasted_iota(
      jnp.int32, (g, block_q, block_k), 2)
  w = _recompute_w(q, k, lse_ref[:], rows, cols, attn_win_size, length)
  dv_acc[:] += jax.lax.dot_general(
      w, do, (((1,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )
  dw = jax.lax.dot_general(
      do, v_ref[:].astype(jnp.float32),
      (((2,), (2,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )
  ds = w * (dw - delta_ref[:][:, :, None])
  dk_acc[:] += jax.lax.dot_general(
      ds, q, (((1,), (1,)), ((0,), (0,))),
      preferred_element_type=jnp.float32,
  )

  @pl.when(jq == n_qblocks - 1)
  def _finalize():
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_band_attention_vjp(q, k, v, attn_win_size, interpret=None):
  """Differentiable banded flash attention (same semantics as
  flash_band_attention; flash-attention-style backward: the forward
  saves the per-row logsumexp, the backward recomputes weight tiles
  and accumulates dq over key blocks and dk/dv over the query blocks
  whose band reaches each key block)."""
  return _forward(q, k, v, attn_win_size, interpret, emit_lse=False)


def _vjp_fwd(q, k, v, attn_win_size, interpret):
  out, lse = _forward(q, k, v, attn_win_size, interpret, emit_lse=True)
  return out, (q, k, v, out, lse)


def _vjp_bwd(attn_win_size, interpret, res, do):
  q, k, v, out, lse = res
  b, l, h, d = q.shape
  plan = _Plan(b, l, h, d, attn_win_size, 128, 8)
  interp = pallas_util.resolve_interpret(interpret)
  pad, lq = plan.pad, plan.lq

  # delta[f] = sum_d do[f, d] * out[f, d], rows beyond l are dead.
  delta = jnp.sum(
      do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
  )  # [B, L, H]
  delta_b = jnp.pad(
      jnp.transpose(delta, (0, 2, 1)).reshape(plan.n, l),
      ((0, 0), (0, lq - l)),
  )
  lse_b = lse  # already [n, lq] from the forward

  qb = plan.to_blocks(q, 0, lq - l)
  dob = plan.to_blocks(do, 0, lq - l)
  kv_hi = (lq - l) + pad
  kb = plan.to_blocks(k, pad, kv_hi)
  vb = plan.to_blocks(v, pad, kv_hi)

  q_spec = plan.spec(lambda g, i, j: (g, i, 0))
  if attn_win_size is None:
    kv_index = lambda g, i, j: (g, j, 0)
  else:
    kv_index = lambda g, i, j: (g, i + j, 0)
  kv_spec = plan.spec(kv_index)
  rank2_q = plan.spec(lambda g, i, j: (g, i), rank2=True)
  dq = pl.pallas_call(
      functools.partial(
          _bwd_dq_kernel, attn_win_size=attn_win_size, length=l,
          block_q=plan.block_q, block_k=plan.block_k,
          n_kblocks=plan.n_kblocks, w_blocks=plan.w_blocks,
      ),
      grid=(plan.n // plan.group, lq // plan.block_q, plan.n_kblocks),
      in_specs=[q_spec, kv_spec, kv_spec, q_spec, rank2_q, rank2_q],
      out_specs=q_spec,
      out_shape=jax.ShapeDtypeStruct((plan.n, lq, d), q.dtype),
      scratch_shapes=[pltpu.VMEM((plan.group, plan.block_q, d),
                                 jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel', 'arbitrary'),
      ),
      interpret=interp,
  )(qb, kb, vb, dob, lse_b, delta_b)

  # dk/dv: key block ki attends from query blocks ki-w..ki+w, so pad
  # the query-side arrays by w_blocks blocks on each side (mirror of
  # the forward's key-side padding).
  if attn_win_size is None:
    n_qblocks = lq // plan.block_q
    q_pad_lo = 0
    qk_index = lambda g, i, j: (g, j, 0)
    qk_index2 = lambda g, i, j: (g, j)
  else:
    n_qblocks = 2 * plan.w_blocks + 1
    q_pad_lo = pad
    qk_index = lambda g, i, j: (g, i + j, 0)
    qk_index2 = lambda g, i, j: (g, i + j)
  q_hi = (lq - l) + q_pad_lo
  qb2 = plan.to_blocks(q, q_pad_lo, q_hi)
  dob2 = plan.to_blocks(do, q_pad_lo, q_hi)
  kb2 = plan.to_blocks(k, 0, lq - l)
  vb2 = plan.to_blocks(v, 0, lq - l)
  # Mirror qb2/dob2's two-sided padding so every (g, i+j) block index
  # is in range: lse_b/delta_b are already lq wide (high-padded by
  # lq-l), so add q_pad_lo on both sides rather than relying on
  # Pallas' OOB block clamping for the trailing masked tiles.
  pad2 = ((0, 0), (q_pad_lo, q_pad_lo))
  lse2 = jnp.pad(lse_b, pad2)
  delta2 = jnp.pad(delta_b, pad2)

  k_spec = plan.spec(lambda g, i, j: (g, i, 0), block_len=plan.block_k)
  qd_spec = plan.spec(qk_index)
  rank2_spec = plan.spec(qk_index2, rank2=True)
  dk, dv = pl.pallas_call(
      functools.partial(
          _bwd_dkv_kernel, attn_win_size=attn_win_size, length=l,
          block_q=plan.block_q, block_k=plan.block_k,
          n_qblocks=n_qblocks, w_blocks=plan.w_blocks,
      ),
      grid=(plan.n // plan.group, lq // plan.block_k, n_qblocks),
      in_specs=[qd_spec, k_spec, k_spec, qd_spec, rank2_spec,
                rank2_spec],
      out_specs=[k_spec, k_spec],
      out_shape=[jax.ShapeDtypeStruct((plan.n, lq, d), q.dtype)] * 2,
      scratch_shapes=[
          pltpu.VMEM((plan.group, plan.block_k, d), jnp.float32),
          pltpu.VMEM((plan.group, plan.block_k, d), jnp.float32),
      ],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=('parallel', 'parallel', 'arbitrary'),
      ),
      interpret=interp,
  )(qb2, kb2, vb2, dob2, lse2, delta2)

  return (plan.from_blocks(dq, b, h), plan.from_blocks(dk, b, h),
          plan.from_blocks(dv, b, h))


flash_band_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
