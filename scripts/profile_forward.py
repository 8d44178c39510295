"""Forward-pass attribution: where the non-MXU 79% goes (VERDICT r3 #5).

The measured forward MFU is 0.21 at b1024; this script attributes
wall-clock across the forward's stages without parsing profiler traces
(same strategy as bench_train_stages.py):
cumulative ablations of the real model — the embedding lookups alone, +
condenser, + encoder, + logits/softmax — timed back-to-back in one
process, plus standalone same-shape modules (one attention block, one
FFN block) for the within-encoder split, plus compiled-flops MFU for
every piece. --batches 1024 2048 also answers the r2-#8 b2048
regression with the same numbers. --trace DIR additionally dumps a
jax.profiler trace of the full forward for offline inspection.

Prints one JSON line per batch size.
"""
import argparse
import json
import time

REFERENCE_WINDOWS_PER_SEC = 114.0
PEAK_BF16_FLOPS = 197e12


def _timed(fn, args_, steps=10):
  import jax

  out = fn(*args_)
  jax.block_until_ready(out)
  t0 = time.perf_counter()
  for _ in range(steps):
    out = fn(*args_)
  jax.block_until_ready(out)
  return (time.perf_counter() - t0) / steps


def _flops(jitted, *args):
  try:
    cost = jitted.lower(*args).compile().cost_analysis()
    entry = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(entry.get('flops', 0.0)) or None
  except Exception:
    return None


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batches', type=int, nargs='+', default=[1024, 2048])
  ap.add_argument('--steps', type=int, default=10)
  ap.add_argument('--cpu', action='store_true')
  ap.add_argument('--trace', default=None,
                  help='directory for a jax.profiler trace of the full '
                  'forward (inspect offline with tensorboard/xprof)')
  ap.add_argument('--set', action='append', default=[], dest='overrides',
                  metavar='KEY=VALUE',
                  help='config override (e.g. '
                  'attn_softmax_dtype=bfloat16) for lever A/Bs')
  ap.add_argument('--config', default='transformer_learn_values+test',
                  help='config preset; use '
                  'transformer_learn_values_distill+test for the '
                  'quantized-student sweeps')
  args = ap.parse_args()

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  import numpy as np
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib
  from scripts._bench_common import make_rows

  params = config_lib.get_config(args.config)
  if args.overrides:
    from deepconsensus_tpu.cli import _apply_overrides

    _apply_overrides(params, args.overrides)
  if params.get('inference_dtype', None):
    # Mirror runner._apply_quant_levers: the inference dtype is also
    # the compute dtype, so activations follow the weights end-to-end.
    with params.unlocked():
      params.dtype = params.inference_dtype
  config_lib.finalize_params(params, is_training=False)
  model = model_lib.get_model(params)
  quant_levers = bool(
      params.get('inference_dtype', None)
      or (params.get('quantize_matmuls', None) or 'none') != 'none')

  for batch in args.batches:
    rows_np = make_rows(params, batch)
    rows = jnp.asarray(rows_np)
    variables = model.init(jax.random.PRNGKey(0), rows[:1])
    n_quantized = 0
    if quant_levers:
      # Same transform the runner applies at load: int8-quantize the
      # matmul weights (dequantized params + a 'quant' collection for
      # the fused kernels), then cast float leaves to inference_dtype.
      # Stage ablations below run the XLA methods on the transformed
      # tree, so their numbers attribute the levered model.
      from deepconsensus_tpu.models import quantize as quantize_lib

      variables, n_quantized = quantize_lib.prepare_inference_variables(
          variables, params)
    rows3 = jnp.squeeze(rows, -1)

    # -- cumulative ablations of the real model ------------------------
    full = jax.jit(lambda v, r: model.apply(v, r))
    embed = jax.jit(lambda v, r: model.apply(
        v, r, method=lambda m, rr: m._embed_rows(rr)))
    embed_condense = jax.jit(lambda v, r: model.apply(
        v, r, method=lambda m, rr: m.condenser(m._embed_rows(rr))))
    encoder_in = embed_condense(variables, rows3)
    encoder_only = jax.jit(lambda v, x: model.apply(
        v, x, method=lambda m, xx: m.encoder(xx, deterministic=True)))
    encoded = encoder_only(variables, encoder_in)
    logits_only = jax.jit(lambda v, x: model.apply(
        v, x, method=lambda m, xx: jax.nn.softmax(
            m.logits_layer(xx.astype(jnp.float32)), axis=-1)))

    stages = {}
    t_full = _timed(full, (variables, rows), args.steps)
    stages['full'] = t_full
    stages['embed'] = _timed(embed, (variables, rows3), args.steps)
    stages['embed_condense'] = _timed(
        embed_condense, (variables, rows3), args.steps)
    stages['encoder'] = _timed(
        encoder_only, (variables, encoder_in), args.steps)
    stages['logits_softmax'] = _timed(
        logits_only, (variables, encoded), args.steps)

    # -- standalone same-shape blocks for the within-encoder split -----
    dt = jnp.dtype(params.get('dtype', 'float32'))
    x_enc = encoder_in.astype(dt)
    attn = model_lib.BandedSelfAttention(
        hidden_size=params.hidden_size, num_heads=params.num_heads,
        dropout_rate=0.0, attn_win_size=params.attn_win_size, dtype=dt,
        use_pallas=params.get('use_pallas_attention', False),
        softmax_dtype=jnp.dtype(
            params.get('attn_softmax_dtype', None) or 'float32'))
    attn_vars = attn.init(jax.random.PRNGKey(1), x_enc, True)
    attn_fn = jax.jit(
        lambda v, x: attn.apply(v, x, True))
    stages['one_attention_block'] = _timed(
        attn_fn, (attn_vars, x_enc), args.steps)
    ffn = model_lib.FeedForward(
        hidden_size=params.hidden_size, filter_size=params.filter_size,
        dropout_rate=0.0, dtype=dt)
    ffn_vars = ffn.init(jax.random.PRNGKey(2), x_enc, True)
    ffn_fn = jax.jit(lambda v, x: ffn.apply(v, x, True))
    stages['one_ffn_block'] = _timed(ffn_fn, (ffn_vars, x_enc), args.steps)

    flops_full = _flops(full, variables, rows)
    result = {
        'batch': batch,
        'backend': jax.default_backend(),
        'windows_per_sec': round(batch / t_full, 1),
        'vs_baseline': round(batch / t_full / REFERENCE_WINDOWS_PER_SEC, 2),
        'stage_ms': {k: round(v * 1e3, 3) for k, v in stages.items()},
        'stage_share_of_full': {
            k: round(v / t_full, 3) for k, v in stages.items()
        },
        'n_layers': params.num_hidden_layers,
    }
    if quant_levers:
      result['inference_dtype'] = str(
          params.get('inference_dtype', None) or 'float32')
      result['quantize_matmuls'] = str(
          params.get('quantize_matmuls', None) or 'none')
      result['n_quantized_matmuls'] = n_quantized
    if flops_full:
      result['mfu'] = round(
          flops_full / t_full / PEAK_BF16_FLOPS, 4)
      result['flops_per_batch'] = flops_full
    for name, fn, fargs in (
        ('embed', embed, (variables, rows3)),
        ('encoder', encoder_only, (variables, encoder_in)),
        ('one_ffn_block', ffn_fn, (ffn_vars, x_enc)),
        ('one_attention_block', attn_fn, (attn_vars, x_enc)),
    ):
      f = _flops(fn, *fargs)
      if f and stages[name] > 0:
        result.setdefault('stage_mfu', {})[name] = round(
            f / stages[name] / PEAK_BF16_FLOPS, 4)
    print(json.dumps(result), flush=True)

    if args.trace:
      with jax.profiler.trace(args.trace):
        for _ in range(3):
          out = full(variables, rows)
        jax.block_until_ready(out)
      print(json.dumps({'trace_dir': args.trace, 'batch': batch}),
            flush=True)
  return 0


if __name__ == '__main__':
  raise SystemExit(main())
