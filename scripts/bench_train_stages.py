"""Train-step stage shares: model fwd/bwd + optimizer vs alignment DP.

VERDICT r2 #4 asked how the train step splits between the model and
the AlignmentLoss wavefront DP. Rather than parsing jax.profiler
traces, this times jitted step variants back-to-back in one process:

  step_dp   - the real train step (model fwd/bwd + AlignmentLoss DP +
              LAMB), the same construction as scripts/bench_train_scaling.py
  step_xent - identical step with the DP loss swapped for a cheap
              masked per-position cross-entropy, so model fwd/bwd +
              optimizer cost is intact and (step_dp - step_xent)
              estimates the DP's share (forward + backward + cost
              construction)
  dp_grad   - jit(value_and_grad(AlignmentLoss)) alone on a fixed
              prediction tensor: the DP share measured directly. Its
              forward is the emit_rows=True kernel (streams DP rows
              to HBM as VJP residuals), so dp_grad covers the
              residual-streaming forward + the reverse adjoint sweep.
  dp_fwd    - jit(AlignmentLoss) forward only — the emit_rows=False
              scorer. dp_grad_over_fwd therefore compares the whole
              differentiated DP (row-streaming forward + backward)
              against the lean forward, not backward-vs-forward alone.

Prints one JSON line per (batch, dp-impl) with seconds per step and
derived shares. --scan-too also measures the lax.scan DP for the
kernel-vs-scan A/B at the same shapes.
"""
import argparse
import json
import time


def _timed(fn, args_, steps):
  import jax

  out = fn(*args_)
  jax.block_until_ready(out)
  t0 = time.perf_counter()
  for _ in range(steps):
    out = fn(*args_)
  jax.block_until_ready(out)
  return (time.perf_counter() - t0) / steps


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batches', type=int, nargs='+', default=[256, 1024])
  ap.add_argument('--steps', type=int, default=6)
  ap.add_argument('--scan-too', action='store_true')
  ap.add_argument('--cpu', action='store_true')
  args = ap.parse_args()

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  import numpy as np

  from scripts import _bench_common

  dp_impls = ['pallas'] + (['scan'] if args.scan_too else [])
  for batch in args.batches:
    for dp_impl in dp_impls:
      trainer, state, rows_t, label = _bench_common.make_trainer_and_batch(
          batch, use_scan_dp=(dp_impl == 'scan'),
          out_dir='/tmp/dc_bench_train_stages',
      )
      loss_obj = trainer.loss_fn

      def masked_xent(y_true, y_pred):
        length = min(y_true.shape[1], y_pred.shape[1])
        yp = jnp.clip(y_pred[:, :length], 1e-7, 1.0)
        onehot = jax.nn.one_hot(y_true[:, :length], yp.shape[-1])
        return -jnp.mean(jnp.sum(onehot * jnp.log(yp), axis=-1))

      rng = np.random.default_rng(3)
      preds_fixed = jax.nn.softmax(jnp.asarray(
          rng.normal(
              size=(batch, trainer.params.max_length, 5)
          ).astype(np.float32)))
      dp_grad = jax.jit(jax.value_and_grad(
          lambda yp: loss_obj(label, yp)))
      dp_fwd = jax.jit(lambda yp: loss_obj(label, yp))

      row = {'batch': batch, 'dp': dp_impl}
      try:
        t_dp = _timed(
            _bench_common.make_scalar_step(state, loss_obj),
            (state, rows_t, label), args.steps)
        t_xent = _timed(
            _bench_common.make_scalar_step(state, masked_xent),
            (state, rows_t, label), args.steps)
        t_dpg = _timed(dp_grad, (preds_fixed,), args.steps)
        t_dpf = _timed(dp_fwd, (preds_fixed,), args.steps)
        row.update({
            'step_dp_s': round(t_dp, 4),
            'step_xent_s': round(t_xent, 4),
            'dp_grad_s': round(t_dpg, 4),
            'dp_fwd_s': round(t_dpf, 4),
            'examples_per_sec': round(batch / t_dp, 1),
            'dp_share_of_step': round(max(0.0, t_dp - t_xent) / t_dp, 3),
            'model_opt_share': round(t_xent / t_dp, 3),
            'dp_grad_over_fwd': round(t_dpg / max(t_dpf, 1e-9), 2),
        })
      except Exception as e:  # keep earlier rows when a variant fails
        row['error'] = repr(e)[:200]
      print(json.dumps(row), flush=True)


if __name__ == '__main__':
  main()
