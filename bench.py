"""Benchmark: model-forward, end-to-end and train-step throughput on the
attached TPU, in ONE process.

The process that runs this file holds the chip; it starts no JAX
children and never falls back to another backend. On anything but a
TPU it exits non-zero before printing a metric, and a stage that fails
fails the run. Every metric line names the device it was measured on.

Prints metric JSON lines as stages complete; the LAST line is the
primary result (end-to-end ZMW/s when that stage ran, best forward
windows/s otherwise). Per-stage details go to bench_out/ (git-ignored).

Honest baselines: the primary metric is END-TO-END ZMW/s against the
reference's published end-to-end anchor — 178 ZMWs in 234.95 s
(~0.76 ZMW/s) on an n1-standard-16 (reference
docs/quick_start.md:315-320). Model-forward windows/s lines compare
against the ~114 windows/s implied by that same run (~150 windows/ZMW)
and say so in their unit string.

The multi-device sweeps (scripts/bench_dp_scaling.py,
scripts/bench_train_scaling.py, scripts/bench_ragged.py) each own a
process and are run on their own, not from here.
"""
import argparse
import json
import os
import sys
import time

REFERENCE_WINDOWS_PER_SEC = 114.0
REFERENCE_E2E_ZMW_PER_SEC = 178 / 234.95  # ~0.757

# Peak dense bf16 matmul throughput per chip, keyed by
# jax.devices()[0].device_kind. Source: Google Cloud documentation,
# "TPU v5e" system architecture (197 TFLOP/s bf16 per chip). A kind
# that is not listed is an error, never a default.
PEAK_BF16_FLOPS_BY_DEVICE_KIND = {
    'TPU v5 lite': 197e12,
    'TPU v5e': 197e12,
}

_REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_REPO, 'bench_out')
_DETAILS_PATH = os.path.join(OUT_DIR, 'bench_details.json')


class NotATpuError(RuntimeError):
  """bench.py measures a TPU or nothing."""


def peak_bf16_flops(device_kind: str) -> float:
  try:
    return PEAK_BF16_FLOPS_BY_DEVICE_KIND[device_kind]
  except KeyError:
    raise NotATpuError(
        f'no published bf16 peak for device kind {device_kind!r}; add it '
        'to PEAK_BF16_FLOPS_BY_DEVICE_KIND with its source') from None


def require_tpu() -> dict:
  """The device block every metric line carries; raises off-TPU."""
  import jax

  dev = jax.devices()[0]
  if dev.platform != 'tpu':
    raise NotATpuError(
        f'bench.py needs a TPU backend, found {dev.platform!r}: a number '
        'taken elsewhere would sit under a device metric\'s name')
  return {'platform': dev.platform, 'kind': dev.device_kind,
          'count': len(jax.devices())}


def _write_details(details):
  os.makedirs(OUT_DIR, exist_ok=True)
  with open(_DETAILS_PATH, 'w') as f:
    json.dump(details, f, indent=1)


def _make_rows(params, batch, seed=0):
  import numpy as np

  rng = np.random.default_rng(seed)
  rows = np.zeros((batch, params.total_rows, params.max_length, 1),
                  np.float32)
  mp = params.max_passes
  rows[:, :mp] = rng.integers(0, 5, size=rows[:, :mp].shape)
  rows[:, mp:2 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 2 * mp:3 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 3 * mp:4 * mp] = rng.integers(0, 3, size=rows[:, :mp].shape)
  rows[:, 4 * mp] = rng.integers(0, 5, size=rows[:, 4 * mp].shape)
  rows[:, 4 * mp + 1:] = rng.integers(
      0, 501, size=rows[:, 4 * mp + 1:].shape)
  return rows


def _host_load():
  """1/5/15-min load averages, for attributing forward-throughput
  drift across runs to a busy host rather than a code change."""
  return [round(x, 2) for x in os.getloadavg()]


def _time_forward(model, variables, rows, n_iters=20, n_warmup=3):
  """Steady-state windows/s under a FIXED warmup discipline: one
  compile call plus n_warmup forced iterations before the timed region,
  identical every run. Inputs vary each iteration and the final result
  is forced to host. Returns (windows/s, compiled flops per batch)."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  @jax.jit
  def forward(variables, rows):
    preds = model.apply(variables, rows)
    return jnp.argmax(preds, -1), jnp.max(preds, -1)

  ids, _ = forward(variables, rows.at[0, 0, 0, 0].set(0.0))  # compile
  np.asarray(ids)
  for i in range(n_warmup):  # steady-state warmup, each forced to host
    ids, _ = forward(variables, rows.at[0, 0, 0, 0].set(float(-1 - i)))
    np.asarray(ids)
  t0 = time.perf_counter()
  last = None
  for i in range(n_iters):
    ids, _ = forward(variables, rows.at[0, 0, 0, 0].set(float(i)))
    last = ids
  np.asarray(last)
  elapsed = time.perf_counter() - t0
  cost = forward.lower(variables, rows).compile().cost_analysis()
  entry = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
  flops = float(entry.get('flops', 0.0)) or None
  return rows.shape[0] * n_iters / elapsed, flops


def _forward_line(wps, batch, device):
  return {
      'metric': 'model_forward_windows_per_sec',
      'value': round(wps, 1),
      'unit': (f'windows/s/chip (batch={batch}, bf16, model forward '
               'only); vs_baseline is vs the ~114 windows/s implied by '
               'the reference e2e anchor, NOT forward-to-forward'),
      'vs_baseline': round(wps / REFERENCE_WINDOWS_PER_SEC, 2),
      'device': device,
  }


def _flagship(**overrides):
  from deepconsensus_tpu.models import config as config_lib

  params = config_lib.get_config('transformer_learn_values+test')
  with params.unlocked():
    for key, value in overrides.items():
      params[key] = value
  config_lib.finalize_params(params, is_training=False)
  return params


def _init_variables(params):
  import jax
  import jax.numpy as jnp

  from deepconsensus_tpu.models import model as model_lib

  return model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))


class Bench:
  """Shared state of one run: the device block, the details record and
  the forward baseline later stages compare against."""

  def __init__(self, device):
    self.device = device
    self.details = {'device': device,
                    'host_load': {'start': _host_load()}, 'stages': {}}
    self.params = _flagship()
    self.variables = _init_variables(self.params)
    self.best_forward = None  # (windows/s, batch)
    self.e2e_line = None

  def record(self, name, entry):
    self.details['stages'][name] = entry
    _write_details(self.details)

  def emit(self, line):
    print(json.dumps(line), flush=True)

  def forward_wps(self, params, variables, batch, n_iters, seed=0):
    import jax.numpy as jnp

    from deepconsensus_tpu.models import model as model_lib

    rows = jnp.asarray(_make_rows(params, batch, seed=seed))
    return _time_forward(model_lib.get_model(params), variables, rows,
                         n_iters=n_iters)


def stage_forward(bench, batch=1024, n_iters=20):
  """XLA forward throughput at one batch size, with the MFU against the
  device kind's published bf16 peak."""
  wps, flops = bench.forward_wps(bench.params, bench.variables, batch,
                                 n_iters, seed=4)
  entry = {'windows_per_sec': round(wps, 1), 'host_load': _host_load()}
  if flops:
    entry['flops_per_batch'] = flops
    entry['mfu'] = round(
        wps / batch * flops / peak_bf16_flops(bench.device['kind']), 4)
  bench.record(f'forward_b{batch}', entry)
  if bench.best_forward is None or wps > bench.best_forward[0]:
    bench.best_forward = (wps, batch)
  bench.emit(_forward_line(wps, batch, bench.device))


def stage_forward_b256(bench):
  stage_forward(bench, batch=256, n_iters=10)


def stage_forward_variant(bench, name, **overrides):
  """Same weights, one execution lever flipped, against forward_b1024."""
  base = bench.details['stages']['forward_b1024']['windows_per_sec']
  params = _flagship(**overrides)
  wps, _ = bench.forward_wps(params, bench.variables, 1024, 10, seed=4)
  bench.record(name, {'windows_per_sec': round(wps, 1),
                      'speedup_vs_xla': round(wps / base, 3),
                      'host_load': _host_load()})


def stage_forward_pallas_attn(bench):
  stage_forward_variant(bench, 'forward_b1024_pallas_attn',
                        use_pallas_attention=True)


def stage_forward_fused(bench):
  stage_forward_variant(bench, 'forward_b1024_fused',
                        use_fused_hotpath=True)


def stage_forward_quant(bench, batch=1024, n_iters=10):
  """f32/bf16/int8 forward A/B on the distilled student through the
  fused encoder blocks; speedups are against the stage's own f32
  variant (same weights, same fused routing)."""
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import quantize as quantize_lib

  def student(**levers):
    sp = config_lib.get_config('transformer_learn_values_distill+test')
    with sp.unlocked():
      sp.use_fused_hotpath = True
      if 'inference_dtype' in levers:
        sp.inference_dtype = sp.dtype = levers['inference_dtype']
      if 'quantize_matmuls' in levers:
        sp.quantize_matmuls = levers['quantize_matmuls']
    config_lib.finalize_params(sp, is_training=False)
    return sp

  vars_f32 = _init_variables(student())
  stage = {'model': 'transformer_learn_values_distill', 'batch': batch,
           'variants': {}}
  base_wps = None
  peak = peak_bf16_flops(bench.device['kind'])
  for name, levers in (
      ('f32', {}),
      ('bf16', {'inference_dtype': 'bfloat16'}),
      ('int8', {'quantize_matmuls': 'int8'}),
      ('bf16_int8', {'inference_dtype': 'bfloat16',
                     'quantize_matmuls': 'int8'}),
  ):
    vp = student(**levers)
    vars_v, n_quantized = quantize_lib.prepare_inference_variables(
        vars_f32, vp)
    wps, flops = bench.forward_wps(vp, vars_v, batch, n_iters, seed=7)
    entry = {'windows_per_sec': round(wps, 1),
             'n_quantized_matmuls': n_quantized,
             'host_load': _host_load()}
    if flops:
      entry['mfu'] = round(wps / batch * flops / peak, 4)
    if name == 'f32':
      base_wps = wps
    else:
      entry['speedup_vs_f32'] = round(wps / base_wps, 3)
    stage['variants'][name] = entry
    bench.record('forward_quant', stage)


def _synthetic_bams(n_zmws=64):
  import tempfile

  sys.path.insert(0, _REPO)
  from scripts.inject_faults import write_synthetic_zmw_bams

  return write_synthetic_zmw_bams(
      tempfile.mkdtemp(prefix='dc_bench_synth_'), n_zmws=n_zmws,
      n_subreads=5, seq_len=600)


def stage_e2e(bench, repeats=3, batch_size=256):
  """Full run_inference pipeline (BAM decode -> featurize -> model ->
  stitch -> FASTQ) on deterministic synthetic BAMs; steady state after
  one warmup repeat. The timed repeats run untraced; one extra traced
  repeat attributes the wall to stages through trace spans, asserted
  to reconcile with the runner's histograms (within 1%) and with the
  dispatch overlap counters."""
  import csv
  import tempfile

  from deepconsensus_tpu import obs as obs_lib
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.obs import summarize as summarize_lib

  subreads, ccs = _synthetic_bams()
  options = runner_lib.InferenceOptions(
      batch_size=batch_size, batch_zmws=8, cpus=0, min_quality=0)
  runner = runner_lib.ModelRunner(bench.params, bench.variables, options)
  out_dir = tempfile.mkdtemp(prefix='dc_bench_e2e_')

  def run(tag):
    out = os.path.join(out_dir, f'out_{tag}.fastq')
    counters = runner_lib.run_inference(
        subreads_to_ccs=subreads, ccs_bam=ccs, checkpoint=None,
        output=out, options=options, runner=runner)
    return out, counters

  run('warmup')  # pays jit compile + first BAM decode
  n_zmws = n_windows = 0
  t_steady = time.perf_counter()
  for rep in range(repeats):
    out, counters = run(rep)
    n_zmws += counters['n_zmw_pass']
    with open(out + '.runtime.csv') as f:
      for row in csv.DictReader(f):
        if row['stage'] == 'preprocess':
          n_windows += int(row.get('n_examples', 0) or 0)
  elapsed = time.perf_counter() - t_steady

  span_stages = (obs_lib.trace.STAGE_FEATURIZE, obs_lib.trace.STAGE_H2D,
                 obs_lib.trace.STAGE_DEVICE_COMPUTE,
                 obs_lib.trace.STAGE_FINALIZE, obs_lib.trace.STAGE_STITCH)

  def hist_sums():
    snap = runner.obs.snapshot()['histograms']
    return {s: snap.get(obs_lib.stage_histogram_name(s), {}).get('sum', 0.0)
            for s in span_stages}

  before_h, before_d = hist_sums(), runner.dispatch_stats()
  trace_path = os.path.join(out_dir, 'e2e_trace.jsonl')
  obs_lib.trace.configure(trace_path, tier='run')
  t_traced = time.perf_counter()
  try:
    run('traced')
  finally:
    obs_lib.trace.configure(None)
  traced_elapsed = time.perf_counter() - t_traced
  after_h, after_d = hist_sums(), runner.dispatch_stats()

  summary = summarize_lib.summarize(summarize_lib.load_trace(trace_path))
  span_totals = summary['stage_totals_s']
  reconcile = {}
  for s in span_stages:
    span_t = span_totals.get(s, 0.0)
    hist_t = after_h[s] - before_h[s]
    reconcile[s] = {'span_s': round(span_t, 4),
                    'histogram_s': round(hist_t, 4)}
    assert abs(span_t - hist_t) <= 0.01 * max(hist_t, 0.05), (
        f'span/histogram stage-time mismatch for {s}: '
        f'{span_t:.4f}s (spans) vs {hist_t:.4f}s (histogram)')
  d_over = (after_d['n_transfer_overlapped']
            - before_d['n_transfer_overlapped'])
  d_direct = after_d['n_transfer_direct'] - before_d['n_transfer_direct']
  overlap = summary['overlap']
  counter_frac = d_over / max(d_over + d_direct, 1)
  if d_over + d_direct:
    assert overlap['n_packs'] == d_over + d_direct, (
        f"trace saw {overlap['n_packs']} packs, counters "
        f'{d_over + d_direct}')
    assert abs(overlap['span_overlap_fraction'] - counter_frac) <= 0.01, (
        f"overlap fraction: {overlap['span_overlap_fraction']} "
        f'(spans) vs {counter_frac:.4f} (counters)')
  zmw_ps = n_zmws / elapsed
  bench.record('e2e_inference', {
      'zmw_per_sec': round(zmw_ps, 2),
      'windows_per_sec': round(n_windows / elapsed, 1),
      'n_zmws': n_zmws,
      'synthetic_data': True,
      'host_load': _host_load(),
      'stage_seconds': {
          'featurize': round(span_totals.get('featurize', 0.0), 2),
          'model': round(span_totals.get('device_compute', 0.0), 2),
          'h2d_transfer': round(span_totals.get('h2d_transfer', 0.0), 4),
          'finalize_drain': round(
              span_totals.get('finalize_drain', 0.0), 2),
          'stitch_write': round(span_totals.get('stitch', 0.0), 2),
          'wall': round(elapsed, 2),
          'source': ('trace spans, one traced repeat (steady repeats '
                     'ran untraced; wall covers the untraced repeats)'),
          'reconcile': reconcile,
          'overlap': {
              'span_fraction': overlap['span_overlap_fraction'],
              'counter_fraction': round(counter_frac, 4),
              'n_packs': overlap['n_packs'],
          },
          # traced-repeat wall vs mean untraced repeat: the cost of
          # leaving DCTPU_TRACE on (NOT paid by the primary number).
          'traced_vs_untraced_repeat_ratio': round(
              traced_elapsed / max(elapsed / repeats, 1e-9), 3),
      },
  })
  bench.e2e_line = {
      'metric': 'e2e_inference_zmw_per_sec',
      'value': round(zmw_ps, 2),
      'unit': (f'ZMW/s end-to-end (BAM->FASTQ, {os.cpu_count()}-core '
               'host); synthetic dataset — vs_baseline NOT comparable '
               'to the reference anchor'),
      'vs_baseline': round(zmw_ps / REFERENCE_E2E_ZMW_PER_SEC, 1),
      'device': bench.device,
  }
  bench.emit(bench.e2e_line)


def stage_featurize(bench):
  """Host featurization (BAM decode -> window tensors), the host-side
  half of the pipeline."""
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.preprocess import (FeatureLayout,
                                            create_proc_feeder)

  subreads, ccs = _synthetic_bams()
  layout = FeatureLayout(max_passes=20, max_length=100, use_ccs_bq=False)
  feeder, _ = create_proc_feeder(
      subreads_to_ccs=subreads, ccs_bam=ccs, layout=layout)
  opts = runner_lib.InferenceOptions()
  zmws = list(feeder())
  t0 = time.perf_counter()
  n_windows = 0
  for z in zmws:
    feats, _ = runner_lib.preprocess_zmw(z, opts)
    n_windows += len(feats)
  dt = time.perf_counter() - t0
  bench.record('featurize_host', {
      'zmw_per_sec': round(len(zmws) / dt, 1),
      'windows_per_sec': round(n_windows / dt, 1),
      'synthetic_data': True,
  })


def stage_d2h_bytes(bench, batch=1024, n_iters=3):
  """Device-epilogue A/B on the distilled student at b1024: measured
  D2H bytes/pack and windows/s with the output plane on device vs on
  host, plus the byte-identity verdict."""
  import numpy as np

  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib

  sp = config_lib.get_config('transformer_learn_values_distill+test')
  config_lib.finalize_params(sp, is_training=False)
  rows = _make_rows(sp, batch, seed=9).astype(np.float32)
  variables = _init_variables(sp)
  stage = {'model': 'transformer_learn_values_distill', 'batch': batch,
           'variants': {}}
  outputs = {}
  for name, device_epilogue in (('epilogue_on', True),
                                ('epilogue_off', False)):
    options = runner_lib.InferenceOptions(
        batch_size=batch, device_epilogue=device_epilogue,
        max_passes=sp.max_passes, max_length=sp.max_length,
        use_ccs_bq=sp.use_ccs_bq)
    runner = runner_lib.ModelRunner(sp, dict(variables), options,
                                    mesh=None)
    outputs[name] = runner.predict(rows)  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(n_iters):
      outputs[name] = runner.predict(rows)
    dt = time.perf_counter() - t0
    stats = runner.dispatch_stats()
    stage['variants'][name] = {
        'windows_per_sec': round(batch * n_iters / dt, 1),
        'd2h_bytes_per_pack': stats['d2h_bytes_per_pack'],
        'd2h_bytes_per_position': round(
            stats['d2h_bytes_per_pack'] / (batch * sp.max_length), 2),
        'n_epilogue_packs': stats['n_epilogue_packs'],
        'host_load': _host_load(),
    }
  on, off = stage['variants']['epilogue_on'], stage['variants']['epilogue_off']
  stage['d2h_reduction'] = round(
      off['d2h_bytes_per_pack'] / on['d2h_bytes_per_pack'], 2)
  stage['speedup_epilogue'] = round(
      on['windows_per_sec'] / off['windows_per_sec'], 3)
  stage['byte_identical'] = all(
      np.array_equal(np.asarray(a, np.int64), np.asarray(b, np.int64))
      for a, b in zip(outputs['epilogue_on'], outputs['epilogue_off']))
  assert stage['byte_identical'], 'device epilogue changed the output'
  bench.record('d2h_bytes', stage)


def stage_padding_waste(bench, batch=256, n_windows=1024):
  """Bucketed vs pad-to-max A/B over one mixed-length window stream
  (70% L=100, 30% L=200) on the same weights: windows/s, the
  padded-position fraction each policy dispatched, and the per-variant
  compile count."""
  import numpy as np

  from deepconsensus_tpu.inference import engine as engine_lib
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib

  p = bench.params
  buckets = config_lib.DEFAULT_WINDOW_BUCKETS
  max_b = max(buckets)
  rng = np.random.default_rng(17)
  widths = rng.choice(buckets, size=n_windows, p=(0.7, 0.3))
  wins = [rng.integers(0, 5, size=(p.total_rows, int(w), 1))
          .astype(np.float32) for w in widths]
  padded = [np.pad(w, ((0, 0), (0, max_b - w.shape[1]), (0, 0)))
            for w in wins]
  useful = int(widths.sum())
  stage = {'n_windows': n_windows, 'batch': batch,
           'mix': {int(b): int((widths == b).sum()) for b in buckets},
           'variants': {}}
  for name, variant_buckets, stream in (
      ('pad_to_max', (max_b,), padded), ('bucketed', buckets, wins)):
    options = runner_lib.InferenceOptions(
        batch_size=batch, max_passes=p.max_passes,
        max_length=p.max_length, use_ccs_bq=p.use_ccs_bq)
    options.window_buckets = variant_buckets
    runner = runner_lib.ModelRunner(p, dict(bench.variables), options,
                                    mesh=None)
    engine = engine_lib.ConsensusEngine(
        runner, options, deliver=lambda t, ids, quals: None)
    for b in variant_buckets:  # warm every bucket's executable
      runner.predict(np.zeros((batch, p.total_rows, b, 1), np.float32))
    t0 = time.perf_counter()
    engine.submit(stream, list(range(n_windows)))
    engine.flush()
    dt = time.perf_counter() - t0
    stats = engine.stats()
    dispatched = sum(n * batch * b
                     for b, n in stats['n_packs_by_bucket'].items())
    stage['variants'][name] = {
        'windows_per_sec': round(n_windows / dt, 1),
        'padded_position_fraction': round(1 - useful / dispatched, 4),
        'n_packs_by_bucket': {
            int(b): int(n) for b, n in stats['n_packs_by_bucket'].items()},
        'n_forward_shapes': stats.get('n_forward_shapes', 0),
        'host_load': _host_load(),
    }
  pad, buck = stage['variants']['pad_to_max'], stage['variants']['bucketed']
  stage['speedup_bucketed'] = round(
      buck['windows_per_sec'] / pad['windows_per_sec'], 3)
  stage['padding_reduction'] = round(
      pad['padded_position_fraction'] - buck['padded_position_fraction'],
      4)
  bench.record('padding_waste', stage)


def stage_train(bench, n_steps=6):
  """Full train step at batch 256: scan DP vs the Pallas wavefront-VJP
  loss (the default on a TPU). The step returns only scalars (loss +
  a parameter fingerprint that keeps the whole LAMB update live), as
  production training keeps its state on the device."""
  import tempfile

  import jax
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import train as train_lib

  out_dir = tempfile.mkdtemp(prefix='dc_bench_train_')
  for name, overrides in (
      # The default is auto (None -> Pallas on TPU); the scan baseline
      # must pin False or the A/B times the same kernel twice.
      ('train_b256_scan', {'use_pallas_wavefront': False}),
      ('train_b256_pallas_vjp', {'use_pallas_wavefront': True}),
  ):
    tp = config_lib.get_config('transformer_learn_values+test')
    config_lib.finalize_params(tp)
    with tp.unlocked():
      tp.batch_size = 256
      for key, value in overrides.items():
        tp[key] = value
    trainer = train_lib.Trainer(params=tp, out_dir=out_dir, mesh=None)
    state = trainer.init_state(steps_total=100)
    loss_obj = trainer.loss_fn
    rng = np.random.default_rng(2)
    rows_t = jnp.asarray(_make_rows(tp, 256).astype(np.float32))
    label = jnp.asarray(
        rng.integers(0, 5, size=(256, tp.max_length)), jnp.int32)

    def step_scalar(state, rows, label):
      rng = jax.random.fold_in(state.dropout_rng, state.step)

      def loss_of(p):
        preds = state.apply_fn(
            {'params': p}, rows, train=True, rngs={'dropout': rng})
        return loss_obj(label, preds)

      loss, grads = jax.value_and_grad(loss_of)(state.params)
      new_state = state.apply_gradients(grads=grads)
      fp = sum(jnp.sum(x) for x in jax.tree.leaves(new_state.params))
      return loss, fp

    step_fn = jax.jit(step_scalar)
    out = step_fn(state, rows_t, label)  # compile
    [np.asarray(o) for o in out]
    t0 = time.perf_counter()
    for i in range(n_steps):
      out = step_fn(state, rows_t.at[0, 0, 0, 0].set(float(i)), label)
      vals = [np.asarray(o) for o in out]  # forced fetch each step
    dt = time.perf_counter() - t0
    bench.record(name, {
        'examples_per_sec': round(256 * n_steps / dt, 1),
        'loss': round(float(vals[0]), 3),
    })
  stages = bench.details['stages']
  bench.record('train_pallas_speedup', round(
      stages['train_b256_pallas_vjp']['examples_per_sec']
      / stages['train_b256_scan']['examples_per_sec'], 3))


def stage_flash_attention(bench):
  """Long-window flash-band attention vs XLA (bare kernels, L=1024)."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.ops import banded_attention as ba_lib
  from deepconsensus_tpu.ops import flash_band_attention as fba_lib

  rng = np.random.default_rng(3)
  mk = lambda: jnp.asarray(
      rng.normal(size=(128, 1024, 2, 140)).astype(np.float32)
  ).astype(jnp.bfloat16)
  q, k, v = mk(), mk(), mk()

  def timed(fn):
    np.asarray(fn(q, k, v))
    t0 = time.perf_counter()
    for i in range(10):
      out = fn(q.at[0, 0, 0, 0].set(float(i)), k, v)
    np.asarray(out)
    return (time.perf_counter() - t0) / 10

  t_xla = timed(jax.jit(
      lambda q, k, v: ba_lib.reference_banded_attention(q, k, v, 12)))
  t_flash = timed(jax.jit(
      lambda q, k, v: fba_lib.flash_band_attention(q, k, v, 12)))
  bench.record('attn_L1024_flash_vs_xla', {
      'xla_us': round(t_xla * 1e6, 1),
      'flash_us': round(t_flash * 1e6, 1),
      'flash_speedup': round(t_xla / t_flash, 3),
  })


# Run order; --stages picks a subset. forward_b1024 must precede the
# forward_b1024_* variants (they report against it).
STAGES = {
    'forward_b256': stage_forward_b256,
    'forward_b1024': stage_forward,
    'e2e_inference': stage_e2e,
    'featurize_host': stage_featurize,
    'forward_b1024_pallas_attn': stage_forward_pallas_attn,
    'forward_b1024_fused': stage_forward_fused,
    'forward_quant': stage_forward_quant,
    'd2h_bytes': stage_d2h_bytes,
    'padding_waste': stage_padding_waste,
    'train_b256': stage_train,
    'attn_L1024_flash_vs_xla': stage_flash_attention,
}


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
  parser.add_argument(
      '--stages', default=','.join(STAGES),
      help='Comma-separated subset of: ' + ', '.join(STAGES))
  args = parser.parse_args(argv)
  names = [n for n in args.stages.split(',') if n]
  unknown = [n for n in names if n not in STAGES]
  if unknown:
    parser.error(f'unknown stage(s) {unknown}')
  try:
    device = require_tpu()
    peak_bf16_flops(device['kind'])
  except NotATpuError as e:
    print(f'bench.py: {e}', file=sys.stderr)
    return 3
  from deepconsensus_tpu.utils import compile_cache

  compile_cache.enable()
  bench = Bench(device)
  for name in names:
    STAGES[name](bench)  # a stage that raises fails the run
  bench.details['host_load']['end'] = _host_load()
  _write_details(bench.details)
  # The last line is the primary result: e2e when measured, best
  # forward number otherwise.
  if bench.e2e_line is not None:
    bench.emit(bench.e2e_line)
  elif bench.best_forward is not None:
    bench.emit(_forward_line(*bench.best_forward, bench.device))
  return 0


if __name__ == '__main__':
  sys.exit(main())
