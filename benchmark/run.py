"""The benchmark's one command.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, starts no child, and exits non-zero with
no result line when JAX finds no TPU or fewer chips than the cell asks
for. Everything that belongs to one cell, configuration, traffic mix,
entry, generator or per-layer metric is a file of its own, found by the
name in BENCHMARK.json:

  benchmark/configs/<config>.json      sizes as run, preset, batch size,
                                       `family` (default gap_aware_encoder)
  benchmark/families/<family>.py       shape_of / stated / make_params /
                                       work / reference_logits
  benchmark/traffic/<traffic>.json     parameters, generator, entry
  benchmark/generators/<generator>.py  make(shape, traffic, seed)
  benchmark/entries/<entry>.py         Entry: prepare / window / compare
  benchmark/metrics/<metric>.py        read(reading) -> number or None
  benchmark/limits/<cell>.json         the limits of the comparison
"""
from __future__ import annotations

import time

_T_START = time.time()

import argparse
import gc
import importlib
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

COMPILE_EVENTS = ('/jax/core/compile/backend_compile_duration',
                  '/jax/compilation_cache/cache_retrieval_time_sec')
WINDOW_ANNOTATION = 'bench_window'
SYNC_ANNOTATION = 'bench_clock_sync'
DEFAULT_FAMILY = 'gap_aware_encoder'
FAMILY_FUNCTIONS = ('shape_of', 'stated', 'make_params', 'flops_per_window',
                    'bytes_per_pack', 'param_count', 'least_seconds_per_pack',
                    'reference_logits')


def log(*parts):
  print(*parts, file=sys.stderr, flush=True)


def load_json(path):
  with open(path) as f:
    return json.load(f)


def load_cell(bench_path: str, workload: str):
  bench = load_json(bench_path)
  root = os.path.dirname(os.path.abspath(bench_path))
  cells = {c['name']: c for c in bench['workloads']}
  if workload not in cells:
    raise SystemExit(f'unknown workload {workload!r}; have {sorted(cells)}')
  cell = cells[workload]
  config_entry = next(c for c in bench['configs'] if c['name'] == cell['config'])
  config_path = os.path.join(root, config_entry['file'])
  config = load_json(config_path)
  # <bench_dir>/configs/<name>.json: the other kinds lie beside `configs`.
  bench_dir = os.path.dirname(os.path.dirname(config_path))
  traffic = load_json(os.path.join(bench_dir, 'traffic',
                                   cell['traffic'] + '.json'))
  limits_path = os.path.join(bench_dir, 'limits', workload + '.json')
  limits = load_json(limits_path) if os.path.exists(limits_path) else {}

  def applies(metric):
    return 'workloads' not in metric or workload in metric['workloads']

  return types.SimpleNamespace(
      bench=bench, cell=cell, config=config, traffic=traffic,
      limits=limits.get('limits', {}), bench_dir=bench_dir,
      family=load_family(bench_dir, config.get('family', DEFAULT_FAMILY)),
      end_to_end=[m for m in bench['end_to_end'] if applies(m)],
      per_layer=[m for m in bench['per_layer'] if applies(m)])


def load_by_name(bench_dir: str, kind: str, name: str):
  """The module benchmark/<kind>/<name>.py, wherever bench_dir lies (the
  tests keep fixtures in a directory of their own, and fall back on the
  benchmark's for what they do not bring)."""
  for base in (bench_dir, os.path.join(ROOT, 'benchmark')):
    path = os.path.join(base, kind, name + '.py')
    if os.path.exists(path):
      spec = importlib.util.spec_from_file_location(
          f'benchmark_{kind}_{name}', path)
      module = importlib.util.module_from_spec(spec)
      spec.loader.exec_module(module)
      return module
  raise FileNotFoundError(f'no {kind}/{name}.py under {bench_dir}')


def load_family(bench_dir: str, name: str):
  """The family module a configuration names: everything that belongs to
  one model architecture (sizes, seeded tree, work, plain reference)."""
  family = load_by_name(bench_dir, 'families', name)
  for function in FAMILY_FUNCTIONS:
    if not callable(getattr(family, function, None)):
      raise SystemExit(f'families/{name}.py lacks {function}()')
  return family


def program_params(config: dict, family):
  """The program's own config for this configuration, checked against the
  sizes the file states: the file holds the configuration as it is run."""
  from deepconsensus_tpu.models import config as config_lib

  params = config_lib.get_config(config['preset'])
  with params.unlocked():
    for key, value in config.get('overrides', {}).items():
      params[key] = value
  config_lib.finalize_params(params, is_training=False)
  wrong = {k: (config.get(k), v) for k, v in family.stated(params).items()
           if config.get(k) != v}
  if wrong:
    raise SystemExit(f'configuration file and program disagree: {wrong}')
  return params


def enable_compile_cache(jax):
  """A fixed directory inside the checkout, unless the environment names
  one; every compile is kept, however short."""
  jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
  jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
  if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
    jax.config.update('jax_compilation_cache_dir',
                      os.path.join(ROOT, '.jax_cache'))


def find_chip(jax, chips: int):
  devices = jax.devices()
  if devices[0].platform != 'tpu' or len(devices) < chips:
    raise SystemExit(
        f'need {chips} TPU chip(s); JAX found {len(devices)} x '
        f'{devices[0].platform}')
  return devices[:chips]


def memory_peak(devices):
  """Peak bytes held on the fullest chip. The TPU runtime counts live
  arrays under `peak_bytes_in_use` and the scratch space it sets aside for
  a compiled program's temporaries under `peak_bytes_reserved` (seen by
  hand, PR 24: 0.18 GB and 5.83 GB for the 8192 forward); the chip holds
  both while the program runs, so the peak is their sum."""
  peaks = []
  for d in devices:
    stats = d.memory_stats() or {}
    peaks.append(stats.get('peak_bytes_in_use', 0)
                 + stats.get('peak_bytes_reserved', 0))
  return int(max(peaks)) if peaks else 0


def run_cell(bench_path: str, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True, out_dir: str = None,
             look: dict = None, profile: bool = False):
  """One run of one cell; returns the result object of the last line.

  `look` (keys of the configuration file to override, such as batch_size)
  and `profile` (cProfile around the window, top of it to the log) are for
  benchmark/tools/look.py: a look at the cell, never a run of it."""
  import jax

  loaded = load_cell(bench_path, workload)
  cell, config, traffic = loaded.cell, loaded.config, loaded.traffic
  config.update(look or {})
  enable_compile_cache(jax)
  devices = (find_chip(jax, cell['chips']) if require_chip
             else jax.devices()[:cell['chips']])
  out_dir = out_dir or os.path.join(ROOT, 'bench_out')
  os.makedirs(out_dir, exist_ok=True)

  compiles = [0]
  from jax import monitoring

  def on_duration(event, _seconds, **_kw):
    if event in COMPILE_EVENTS:
      compiles[0] += 1

  monitoring.register_event_duration_secs_listener(on_duration)

  from deepconsensus_tpu import obs as obs_lib
  from deepconsensus_tpu.inference import runner as runner_lib
  from benchmark.lib import peaks as peaks_lib
  from benchmark.lib import spans as spans_lib
  from benchmark.lib import xplane as xplane_lib
  from benchmark.lib import compare as compare_lib

  t_imports = time.time()
  family = loaded.family
  shape = family.shape_of(config)
  params = program_params(config, family)
  variables = {'params': family.make_params(shape, seed)}
  jax.block_until_ready(variables)
  t_weights = time.time()
  options = runner_lib.InferenceOptions(
      batch_size=int(config['batch_size']), **traffic.get('options', {}))
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  ctx = types.SimpleNamespace(
      seed=int(seed), cell=cell, config=config, traffic=traffic, shape=shape,
      family=family,
      batch=int(config['batch_size']), options=options, out_dir=out_dir,
      runner=runner_lib.ModelRunner(params, variables, options),
      generator=load_by_name(loaded.bench_dir, 'generators',
                             traffic['generator']))
  entry_mod = load_by_name(loaded.bench_dir, 'entries', traffic['entry'])
  entry = entry_mod.Entry(ctx)

  span_path = os.path.join(out_dir, f'spans.{workload}.jsonl')
  trace_dir = os.path.join(out_dir, f'trace.{workload}')
  t_runner = time.time()
  entry.prepare()
  t_ready = time.time()
  log(f'bench: setup phases s: imports={t_imports - _T_START:.2f} '
      f'weights={t_weights - t_imports:.2f} runner={t_runner - t_weights:.2f} '
      f'inputs+warmup={t_ready - t_runner:.2f}')
  compiles_before = compiles[0]
  log(f'bench: cpu_count={os.cpu_count()} '
      f'bgzf_decoder={bgzf_decoder(entry_mod)} '
      f'compiles_in_setup={compiles_before} seed={seed}')
  sync = None
  if trace:
    import shutil
    shutil.rmtree(trace_dir, ignore_errors=True)
    if os.path.exists(span_path):
      os.remove(span_path)
    obs_lib.trace.configure(span_path, tier='bench')
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(SYNC_ANNOTATION):
      sync = time.time()
  try:
    setup_s = time.time() - _T_START
    if profile:
      import cProfile
      profiler = cProfile.Profile()
      profiler.enable()
    with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
      result = entry.window(float(seconds))
    if profile:
      import pstats
      profiler.disable()
      pstats.Stats(profiler, stream=sys.stderr).sort_stats(
          'tottime').print_stats(25)
  finally:
    if trace:
      jax.profiler.stop_trace()
      obs_lib.trace.configure(None)
  compiles_in_window = compiles[0] - compiles_before
  peak = memory_peak(devices)
  log(f'bench: n_forward_shapes={result["counters"].get("n_forward_shapes")} '
      f'compiles_in_window={compiles_in_window} '
      f'window_s={result["window_s"]:.3f} '
      f'delivered={result["windows_delivered"]}')

  metrics = {}
  device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices), 'memory_peak_bytes': peak}
  out = {}
  if not trace:
    rates = dict(result['rates'], setup_s=setup_s)
    for m in loaded.end_to_end:
      if m['name'] in rates:
        metrics[m['name']] = {'value': rates[m['name']], 'unit': m['unit']}
  else:
    names = tuple(entry_mod.ANNOTATIONS) + (WINDOW_ANNOTATION, SYNC_ANNOTATION)
    planes = xplane_lib.load(xplane_lib.find_trace(trace_dir), names)
    for line in xplane_lib.describe(planes):
      log('trace:', line)
    lo, hi = xplane_lib.window_of(planes, WINDOW_ANNOTATION)
    sync_ns = xplane_lib.window_of(planes, SYNC_ANNOTATION)[0]
    spans = spans_lib.read_spans(span_path)
    # The program's spans on the trace's clock, as host events.
    host = planes.setdefault(xplane_lib.HOST_PLANE, {})
    span_names = []
    for name, items in spans.items():
      span_names.append(name)
      host.setdefault('program_spans', []).extend(
          (name, sync_ns + (a - sync) * 1e9, (b - a) * 1e9)
          for a, b, _ in items)
    busy = xplane_lib.busy_seconds(planes, lo, hi)
    device['busy_s'] = busy
    device['window_s'] = (hi - lo) / 1e9
    try:
      chip_peaks = peaks_lib.peaks_for(devices[0].device_kind)
    except KeyError:
      if require_chip:
        raise
      chip_peaks = None
    reading = types.SimpleNamespace(
        result=result, window_s=result['window_s'], spans=spans,
        span_window=result['wall'], planes=planes, trace_window=(lo, hi),
        shape=shape, batch=ctx.batch, peaks=chip_peaks, chips=len(devices),
        memory_peak_bytes=peak, work=family, xplane=xplane_lib,
        spans_lib=spans_lib, on_chip=devices[0].platform == 'tpu', log=log)
    for m in loaded.per_layer:
      value = load_by_name(loaded.bench_dir, 'metrics', m['name']).read(reading)
      if value is not None:
        metrics[m['name']] = {'value': value, 'unit': m['unit']}
    out['breakdown'] = {
        'device_ops': xplane_lib.top_ops(planes, lo, hi),
        'idle_gaps': xplane_lib.idle_gaps(
            planes, lo, hi, list(entry_mod.ANNOTATIONS) + span_names)}
    log('breakdown:', json.dumps(out['breakdown']))

  # The reference runs last: after the peak was read and the program's
  # device state is dropped.
  weights = variables['params']
  entry.release()
  ctx.runner = None
  gc.collect()
  t_ref = time.time()
  values = entry.compare(weights)
  judged = compare_lib.judge(values, loaded.limits)
  checks = {name: {'value': value, 'limit': limit}
            for name, value, limit, _ok in judged}
  checks['compiles_in_window'] = {'value': compiles_in_window, 'limit': 0}
  checks['failed'] = {'value': result['failed'], 'limit': 0}
  correct = (bool(judged) and all(ok for *_rest, ok in judged)
             and compiles_in_window == 0 and result['failed'] == 0)
  log(f'bench: reference_s={time.time() - t_ref:.2f} '
      f'other_numbers={json.dumps({k: v for k, v in values.items() if k not in checks})}')
  for name, item in checks.items():
    log(f'compared: {name} value={item["value"]} limit={item["limit"]}')
  log(f'compared: correct={correct}')
  return {'correct': correct, 'attempted': result['attempted'],
          'failed': result['failed'], 'metrics': metrics, 'device': device,
          **out, 'numbers': values, 'compared': checks}


def bgzf_decoder(entry_mod) -> str:
  """Which BGZF decoder the run used; 'unused' where no BAM is read."""
  if not getattr(entry_mod, 'READS_BAM', False):
    return 'unused'
  from deepconsensus_tpu import native
  return 'native' if native.get_lib() is not None else 'python'


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, required=True)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  args = parser.parse_args(argv)
  result = run_cell(os.path.join(ROOT, 'BENCHMARK.json'), args.workload,
                    args.seed, args.seconds, bool(args.trace))
  print(json.dumps(result), flush=True)
  return 0


if __name__ == '__main__':
  sys.exit(main())
