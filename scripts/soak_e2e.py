"""Sustained-scale end-to-end soak (VERDICT r4 #7).

Replicates the bundled 10-ZMW human_1m BAMs to thousands of distinct
ZMWs (byte-level record patching: qname + zm tag get a per-copy offset,
cigars/quals/kinetics preserved exactly — mirrors the reference's
full-SMRT-cell production pattern, quick_start.md:82-99), then runs
`dctpu run` over them as a subprocess while sampling throughput (FASTQ
growth), RSS, and /dev/shm segment count. Emits one JSON line with the
soak verdict: sustained ZMW/s, first-vs-last-quartile throughput ratio
(flatness), peak RSS, peak shm segments.

  python scripts/soak_e2e.py --copies 500 --out_dir /root/soak_r5

Serve mode (--serve N): one `dctpu serve` daemon, N concurrent clients
hammering /v1/polish with featurized synthetic molecules. Verifies
every concurrent result byte-identical to a solo (single-client)
baseline — zero cross-request leaks under continuous batching — then
SIGTERMs the daemon under residual load and checks the graceful drain.
Verdict line reports client-observed p50/p99 latency and the daemon's
own /metricz counters.

  python scripts/soak_e2e.py --serve 8 --serve_rounds 20

Fleet mode (--fleet N): N `dctpu serve` replicas behind one `dctpu
route` front tier, all real subprocesses sharing one persistent
compilation cache dir. Concurrent clients hammer the router; halfway
through, one replica is rolling-restarted (SIGTERM -> drain -> respawn
-> POST /v1/register) while traffic continues. A disaggregated leg
ships per-molecule raw mini BAMs (bam/1) through a featurize worker.
Gates: zero accepted-then-lost requests, every routed result
byte-identical to a solo single-replica baseline, clean drains
everywhere.

  python scripts/soak_e2e.py --fleet 3 --serve_rounds 6

Chaos mode (--chaos): same batch soak, but one device OOM and one
device hang are injected mid-stream via the DCTPU_FAULT_DEVICE_* env
hooks. The child runs with --on_device_error=degrade and a dispatch
watchdog, so the OOM pack must recover through batch bisection and the
hung pack must be cut off by the watchdog (its ZMWs fall back to CCS).
The verdict gains a 'chaos' block read from the run's .inference.json
sidecar; exit is nonzero unless both recovery counters fired and
throughput stayed flat.

  python scripts/soak_e2e.py --chaos --min_minutes 2
"""
import argparse
import gzip
import json
import os
import signal
import struct
import subprocess
import sys
import threading
import time

TESTDATA = '/root/reference/deepconsensus/testdata/human_1m'
ZMW_STRIDE = 1_000_000  # copy c adds c * stride to every ZMW id


def _patch_record(block: bytes, zmw_offset: int) -> bytes:
  """Returns the record with qname's ZMW and the zm:i tag offset."""
  (ref_id, pos, l_read_name, mapq, bin_, n_cigar, flag, l_seq, next_ref,
   next_pos, tlen) = struct.unpack('<iiBBHHHiiii', block[:32])
  name = block[32 : 32 + l_read_name - 1].decode('ascii')
  rest = block[32 + l_read_name :]
  movie, zmw, tail = name.split('/', 2)
  new_name = f'{movie}/{int(zmw) + zmw_offset}/{tail}'.encode('ascii')
  new_lrn = len(new_name) + 1

  # Walk the tag region (after cigar+seq+qual) to rewrite zm:i.
  cigar_seq_qual = n_cigar * 4 + (l_seq + 1) // 2 + l_seq
  tags = bytearray(rest[cigar_seq_qual:])
  p = 0
  sizes = {ord('A'): 1, ord('c'): 1, ord('C'): 1, ord('s'): 2,
           ord('S'): 2, ord('i'): 4, ord('I'): 4, ord('f'): 4}
  while p + 3 <= len(tags):
    tag = bytes(tags[p : p + 2])
    vt = tags[p + 2]
    q = p + 3
    if vt in sizes:
      if tag == b'zm' and vt in (ord('i'), ord('I')):
        (zm_val,) = struct.unpack_from('<i', tags, q)
        struct.pack_into('<i', tags, q, zm_val + zmw_offset)
      q += sizes[vt]
    elif vt in (ord('Z'), ord('H')):
      while tags[q] != 0:
        q += 1
      q += 1
    elif vt == ord('B'):
      sub = tags[q]
      (n,) = struct.unpack_from('<I', tags, q + 1)
      q += 5 + n * sizes[sub]
    else:
      raise ValueError(f'unknown tag type {chr(vt)}')
    p = q

  head = struct.pack('<iiBBHHHiiii', ref_id, pos, new_lrn, mapq, bin_,
                     n_cigar, flag, l_seq, next_ref, next_pos, tlen)
  body = head + new_name + b'\x00' + rest[: cigar_seq_qual] + bytes(tags)
  return struct.pack('<i', len(body)) + body


def replicate_bam(src: str, dst: str, copies: int) -> int:
  """Writes `copies` ZMW-offset replicas of src's records; returns the
  record count written."""
  from deepconsensus_tpu.io.bam_writer import BgzfWriter

  raw = gzip.open(src, 'rb').read()
  assert raw[:4] == b'BAM\x01', src
  (l_text,) = struct.unpack_from('<i', raw, 4)
  p = 8 + l_text
  (n_ref,) = struct.unpack_from('<i', raw, p)
  p += 4
  for _ in range(n_ref):
    (l_name,) = struct.unpack_from('<i', raw, p)
    p += 4 + l_name + 4
  header_end = p

  records = []
  while p < len(raw):
    (size,) = struct.unpack_from('<i', raw, p)
    records.append(raw[p + 4 : p + 4 + size])
    p += 4 + size

  n = 0
  with BgzfWriter(dst) as out:
    out.write(raw[:header_end])
    for c in range(copies):
      off = c * ZMW_STRIDE
      for block in records:
        out.write(_patch_record(block, off) if off else
                  struct.pack('<i', len(block)) + block)
        n += 1
  return n


def count_fastq_records(path: str) -> int:
  # The runner streams into <output>.tmp and renames into place only on
  # success (atomic, resumable output) — mid-run progress lives in the
  # tmp file, the final path only exists after completion.
  if not os.path.exists(path):
    path += '.tmp'
    if not os.path.exists(path):
      return 0
  n = 0
  with open(path, 'rb') as f:
    for _ in f:
      n += 1
  return n // 4


def _featurize_synth(args, n_zmws):
  """Synthesizes molecules and featurizes them once in the parent.
  Returns (molecules, synth_dir)."""
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.preprocess import (FeatureLayout,
                                            create_proc_feeder)
  from scripts.inject_faults import write_synthetic_zmw_bams

  os.makedirs(args.out_dir, exist_ok=True)
  synth_dir = os.path.join(args.out_dir, f'serve_synth_{n_zmws}')
  if not os.path.isdir(synth_dir):
    write_synthetic_zmw_bams(synth_dir, n_zmws=n_zmws,
                             n_subreads=5, seq_len=600)
  sub_bam = os.path.join(synth_dir, 'subreads_to_ccs.bam')
  ccs_bam = os.path.join(synth_dir, 'ccs.bam')
  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params, is_training=False)
  options = runner_lib.InferenceOptions(min_quality=0)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  layout = FeatureLayout(
      max_passes=options.max_passes, max_length=options.max_length,
      use_ccs_bq=options.use_ccs_bq)
  feeder, _ = create_proc_feeder(
      subreads_to_ccs=sub_bam, ccs_bam=ccs_bam, layout=layout,
      ins_trim=options.ins_trim)
  molecules = []
  for zmw_input in feeder():
    features, _ = runner_lib.preprocess_zmw(zmw_input, options)
    if features:
      molecules.append(features)
  return molecules, synth_dir


def _spawn(cmd_tail, env):
  """Starts a dctpu subcommand subprocess and returns (proc, ready)
  once its ready JSON line arrives."""
  proc = subprocess.Popen(
      [sys.executable, '-m', 'deepconsensus_tpu.cli'] + cmd_tail,
      env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
      text=True)
  for line in proc.stdout:
    if line.startswith('{'):
      info = json.loads(line)
      if info.get('event') == 'ready':
        return proc, info
  raise RuntimeError(f'subprocess exited before ready: {cmd_tail}')


def _drained_line(proc):
  out = {}
  for line in proc.stdout.read().splitlines():
    if line.startswith('{'):
      d = json.loads(line)
      if d.get('event') == 'drained':
        out = d
  return out


def fleet_soak(args) -> int:
  """N serve replicas behind `dctpu route` with a `dctpu autoscale`
  controller holding the interactive-class SLO: the load ramp forces a
  scale-out, a forced preemption (SIGUSR1 notice + kill deadline) of
  an operator replica is absorbed by a drain + autoscaler replacement,
  and a disaggregated bam/1 leg rides the featurize tier. Workers are
  class-labeled (one interactive, the rest bulk) so the router's
  per-class latency histograms carry the SLO evidence."""
  sys.path.insert(0, os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))))
  from deepconsensus_tpu.serve.client import ServeClient, ServeClientError
  from scripts.inject_faults import preempt_replica
  from scripts.inject_faults import write_synthetic_zmw_bams

  if args.fleet < 2:
    print('fleet soak needs --fleet >= 2 (one replica is preempted '
          'mid-run)', flush=True)
    return 1
  t0 = time.time()
  molecules, _synth_dir = _featurize_synth(args, args.serve_zmws)
  print(f'featurized {len(molecules)} molecules', flush=True)

  env = dict(os.environ)
  env['PYTHONPATH'] = '/root/repo:' + env.get('PYTHONPATH', '')
  env['JAX_PLATFORMS'] = env.get('JAX_PLATFORMS', 'cpu')
  # Every spawned tier inherits the variable, so replicas share one
  # compile cache (an operator's own setting wins).
  env.setdefault('JAX_COMPILATION_CACHE_DIR',
                 os.path.join(args.out_dir, 'jit_cache'))
  # One shared Chrome-trace file for the whole fleet: every tier
  # (replicas, featurize worker, router) appends spans to it, and the
  # post-soak connectivity check joins them by trace id.
  trace_path = os.path.join(args.out_dir, 'fleet_trace.jsonl')
  if os.path.exists(trace_path):
    os.unlink(trace_path)
  env['DCTPU_TRACE'] = trace_path

  def spawn_replica():
    return _spawn(
        ['serve', '--random_init',
         '--config', 'transformer_learn_values+test',
         '--port', '0', '--min_quality', '0',
         '--batch_size', str(args.serve_batch_size)], env)

  replicas = []  # [proc, port] — mutated by the rolling restart
  t_first = time.time()
  for i in range(args.fleet):
    proc, ready = spawn_replica()
    replicas.append([proc, ready['port']])
    print(json.dumps({'replica': i, **ready,
                      'spawn_s': round(time.time() - t_first, 1)}),
          flush=True)
    t_first = time.time()

  worker_proc, worker_ready = _spawn(
      ['featurize-worker', '--config', 'transformer_learn_values+test',
       '--port', '0'], env)
  print(json.dumps(worker_ready), flush=True)

  router_cmd = ['route', '--port', '0', '--probe_interval_s', '0.2',
                '--queue_wait_s', '0.3',
                '--featurize_worker',
                f'127.0.0.1:{worker_ready["port"]}']
  for _, port in replicas:
    router_cmd += ['--replica', f'127.0.0.1:{port}']
  router_proc, router_ready = _spawn(router_cmd, env)
  print(json.dumps(router_ready), flush=True)
  router_port = router_ready['port']
  router_client = ServeClient(port=router_port, timeout=300)
  if not router_client.wait_ready(120):
    print('router never became ready', flush=True)
    return 1

  # The SLO autoscaler: min = the operator fleet, max allows exactly
  # one scale-out. The p99 target is deliberately tight so the load
  # ramp provably crosses it; the scale-in cooldown is effectively
  # infinite so the replica count only moves for reasons this soak
  # asserts on (scale-out, preemption replacement). Spawned replicas
  # carry the same flags as the operator ones (deterministic
  # random-init weights + the shared compile cache), so byte identity
  # holds no matter who serves a request.
  scaler_cmd = ['autoscale', '--router', f'127.0.0.1:{router_port}',
                '--tier', 'model',
                '--min_replicas', str(args.fleet),
                '--max_replicas', str(args.fleet + 1),
                '--target_p99_s', str(args.autoscale_p99_s),
                '--target_queue_depth', '1e9',
                '--slo_class', 'interactive',
                '--poll_interval_s', '0.5',
                '--scale_out_cooldown_s', '2',
                '--scale_in_cooldown_s', '100000',
                '--serve_arg=--random_init',
                '--serve_arg=--config',
                '--serve_arg=transformer_learn_values+test',
                '--serve_arg=--min_quality',
                '--serve_arg=0',
                '--serve_arg=--batch_size',
                f'--serve_arg={args.serve_batch_size}']
  scaler_proc, scaler_ready = _spawn(scaler_cmd, env)
  print(json.dumps(scaler_ready), flush=True)

  # Solo baseline: one pass straight at replica 0 — the bytes every
  # routed result must reproduce exactly.
  solo_client = ServeClient(port=replicas[0][1], timeout=300)
  solo = {}
  for features in molecules:
    resp = solo_client.polish_features(features)
    name = features[0]['name']
    name = name if isinstance(name, str) else name.decode()
    solo[name] = (resp['status'], resp['seq'],
                  None if resp['quals'] is None
                  else resp['quals'].tobytes())

  lock = threading.Lock()
  latencies = []
  mismatches = []
  accepted_then_lost = []
  errors = []
  n_ok = [0]
  n_shed_retries = [0]
  stop_workers = threading.Event()

  def worker(wid):
    # Multi-tenant attribution: worker 0 is the interactive tenant the
    # SLO is asserted for; the rest are bulk backfill.
    client = ServeClient(
        port=router_port, timeout=300,
        klass='interactive' if wid == 0 else 'bulk',
        client=f'worker-{wid}')
    start = wid % max(1, len(molecules))
    rotated = molecules[start:] + molecules[:start]
    for _ in range(args.serve_rounds):
      for features in rotated:
        if stop_workers.is_set():
          return
        name = features[0]['name']
        name = name if isinstance(name, str) else name.decode()
        t_req = time.monotonic()
        resp = None
        for _attempt in range(40):
          try:
            resp = client.polish_features(
                features, compact=wid % 2 == 0)
            break
          except ServeClientError as e:
            msg = str(e.payload.get('error', ''))
            if 'accepting' in msg:
              # The one error a correct client must NOT retry.
              with lock:
                accepted_then_lost.append(f'{name}: {msg}')
              break
            if e.status in (429, 503):
              with lock:
                n_shed_retries[0] += 1
              time.sleep(0.25)  # fleet busy/rolling; try again
              continue
            with lock:
              errors.append(f'{name}: HTTP {e.status} {msg}')
            break
          except OSError as e:
            with lock:
              errors.append(f'{name}: {type(e).__name__}')
            break
        if resp is None:
          continue
        dt = time.monotonic() - t_req
        got = (resp['status'], resp['seq'],
               None if resp['quals'] is None
               else resp['quals'].tobytes())
        with lock:
          latencies.append(dt)
          if got != solo[name]:
            mismatches.append(name)
          else:
            n_ok[0] += 1

  threads = [threading.Thread(target=worker, args=(w,))
             for w in range(args.fleet_clients)]
  for t in threads:
    t.start()

  def model_tier_counts():
    try:
      m = router_client.metricz()
    except (OSError, ValueError):
      return 0, 0
    reps = [r for r in m.get('replicas', []) if r.get('tier') == 'model']
    ready = sum(1 for r in reps if r.get('state') == 'ready')
    live = sum(1 for r in reps
               if r.get('state') in ('ready', 'joining'))
    return ready, live

  # Phase 1 — SLO scale-out: under the client ramp the cumulative
  # interactive p99 crosses the (deliberately tight) autoscale target
  # and the controller grows the model tier by one replica.
  time.sleep(2.0)
  max_ready = args.fleet
  scaled_out = False
  scale_deadline = time.monotonic() + 300
  while time.monotonic() < scale_deadline:
    ready_n, _live_n = model_tier_counts()
    max_ready = max(max_ready, ready_n)
    if ready_n >= args.fleet + 1:
      scaled_out = True
      break
    time.sleep(0.5)

  # Phase 2 — forced preemption of an operator replica: the SIGUSR1
  # notice flips it to draining (the router routes nothing new to it),
  # it finishes admitted work and exits 0 with preempted=true well
  # inside the grace window (the hard kill never fires), and the
  # autoscaler restores the lost capacity without any manual respawn
  # or re-register.
  old_proc, old_port = replicas.pop(0)
  drill = preempt_replica(
      old_proc.pid, grace_s=300,
      is_alive=lambda: old_proc.poll() is None)
  old_rc = old_proc.wait(timeout=300)
  old_info = _drained_line(old_proc)
  want_live = args.fleet + 1 if scaled_out else args.fleet
  replaced = False
  replace_deadline = time.monotonic() + 300
  while time.monotonic() < replace_deadline:
    _ready_n, live_n = model_tier_counts()
    if live_n >= want_live:
      replaced = True
      break
    time.sleep(0.5)
  preempted = {
      'old_port': old_port, 'old_rc': old_rc,
      'old_drained': bool(old_info.get('drained')),
      'old_preempted': bool(old_info.get('preempted')),
      'kill_fired': bool(drill['killed']),
      'notice_to_exit_s': drill['waited_s'],
      'scaled_out': scaled_out,
      'max_ready_observed': max_ready,
      'replaced': replaced,
  }
  print(json.dumps({'event': 'preempted', **preempted}), flush=True)

  for t in threads:
    t.join()

  # Disaggregated leg: per-molecule raw mini BAMs through the router's
  # featurize tier; solo-replica polish of the monolithic featurize of
  # the same BAMs is the identity reference.
  bam_ok, bam_mismatch = 0, 0
  bam_trace_ids = []
  for i in range(3):
    d = os.path.join(args.out_dir, f'fleet_bam_{i}')
    sub_path, ccs_path = write_synthetic_zmw_bams(
        d, n_zmws=1, n_subreads=5, seq_len=600, seed=100 + i)
    with open(sub_path, 'rb') as f:
      sub_bytes = f.read()
    with open(ccs_path, 'rb') as f:
      ccs_bytes = f.read()
    bam_trace_ids.append(f'bamleg{i:010d}')
    got = router_client.polish_bam(sub_bytes, ccs_bytes, name=f'bam/{i}',
                                   trace_id=bam_trace_ids[-1])
    # Monolithic reference: featurize the exact BAM pair we shipped,
    # polish on a replica directly.
    from deepconsensus_tpu.inference import runner as runner_lib
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.preprocess import (FeatureLayout,
                                              create_proc_feeder)
    params = config_lib.get_config('transformer_learn_values+test')
    config_lib.finalize_params(params, is_training=False)
    layout = FeatureLayout(params.max_passes, params.max_length,
                           params.use_ccs_bq)
    feeder, _ = create_proc_feeder(
        subreads_to_ccs=sub_path, ccs_bam=ccs_path, layout=layout)
    options = runner_lib.InferenceOptions(min_quality=0)
    options.max_passes = params.max_passes
    options.max_length = params.max_length
    options.use_ccs_bq = params.use_ccs_bq
    want = None
    for zmw_input in feeder():
      features, _ = runner_lib.preprocess_zmw(zmw_input, options)
      if features:
        want = ServeClient(
            port=replicas[1][1] if len(replicas) > 1
            else replicas[0][1],
            timeout=300).polish_features(features)
    same = (want is not None and got['status'] == want['status']
            and got['seq'] == want['seq'])
    bam_ok += bool(same)
    bam_mismatch += not same

  metricz = router_client.metricz()

  # Drain the fleet: the autoscaler first (it SIGTERM-drains every
  # replica it spawned), then the router (stops admissions), then the
  # remaining operator tiers.
  scaler_proc.send_signal(signal.SIGTERM)
  scaler_rc = scaler_proc.wait(timeout=600)
  scaler_info = _drained_line(scaler_proc)
  router_proc.send_signal(signal.SIGTERM)
  router_rc = router_proc.wait(timeout=300)
  router_drained = bool(_drained_line(router_proc).get('drained'))
  tier_rcs = []
  for proc, _port in replicas + [[worker_proc, None]]:
    proc.send_signal(signal.SIGTERM)
    tier_rcs.append(proc.wait(timeout=300))

  # Trace connectivity (all tiers have exited, the shared file is
  # complete): every bam-leg request must form ONE connected trace
  # whose spans came from at least three distinct processes (router,
  # featurize worker, model replica), and every verified features-leg
  # delivery must join its router-minted id across router + replica.
  from deepconsensus_tpu.obs import summarize as summarize_lib
  trace_events = summarize_lib.load_trace(trace_path)
  groups = summarize_lib.trace_groups(trace_events)
  bam_connected = [len(groups.get(tid, {}).get('pids', ())) >= 3
                   for tid in bam_trace_ids]
  n_routed_traces = sum(
      1 for g in groups.values() if len(g.get('pids', ())) >= 2)
  # Any dead letter written during the soak must be joinable to its
  # request's trace.
  dead_letters_missing_trace = 0
  for root, _dirs, files in os.walk(args.out_dir):
    for fn in files:
      if fn.endswith('.failed.jsonl'):
        with open(os.path.join(root, fn)) as fh:
          for line in fh:
            if line.strip() and 'trace_id' not in json.loads(line):
              dead_letters_missing_trace += 1
  trace_connected = (all(bam_connected)
                     and len(bam_connected) == len(bam_trace_ids)
                     and n_routed_traces >= n_ok[0]
                     and dead_letters_missing_trace == 0)

  lat = sorted(latencies)
  verdict = {
      'soak': 'fleet',
      'n_replicas': args.fleet,
      'n_clients': args.fleet_clients,
      'n_molecules': len(molecules),
      'n_requests_verified': n_ok[0],
      'n_mismatches': len(mismatches),
      'n_accepted_then_lost': len(accepted_then_lost),
      'n_shed_retries': n_shed_retries[0],
      'n_client_errors': len(errors),
      'bam_leg': {'ok': bam_ok, 'mismatched': bam_mismatch},
      'preempted': preempted,
      'autoscale': {
          'rc': scaler_rc,
          'counters': scaler_info.get('counters', {}),
          'managed': scaler_info.get('managed', []),
      },
      'p50_s': round(lat[len(lat) // 2], 4) if lat else None,
      'p99_s': round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 4)
               if lat else None,
      'router_counters': metricz.get('counters', {}),
      'router_latency': metricz.get('latency', {}),
      'class_latency': metricz.get('class_latency', {}),
      'qos': metricz.get('qos', {}),
      'router_rc': router_rc,
      'router_drained': router_drained,
      'tier_rcs': tier_rcs,
      'trace': {
          'path': trace_path,
          'n_events': len(trace_events),
          'n_traces': len(groups),
          'n_routed_traces': n_routed_traces,
          'bam_connected': bam_connected,
          'dead_letters_missing_trace': dead_letters_missing_trace,
      },
      'trace_connected': trace_connected,
      'wall_s': round(time.time() - t0, 1),
  }
  print(json.dumps(verdict), flush=True)
  if mismatches:
    print(f'MISMATCHED vs solo: {sorted(set(mismatches))[:10]}',
          flush=True)
  if accepted_then_lost:
    print(f'ACCEPTED-THEN-LOST: {accepted_then_lost[:10]}', flush=True)
  scaler_counters = scaler_info.get('counters', {})
  interactive_p99 = metricz.get('class_latency', {}).get(
      'interactive', {}).get('p99')
  ok = (not mismatches and not accepted_then_lost and not errors
        and n_ok[0] > 0
        # Preemption drill: clean notice-driven drain, kill never
        # fired, the autoscaler replaced the capacity.
        and preempted['old_rc'] == 0 and preempted['old_drained']
        and preempted['old_preempted'] and not preempted['kill_fired']
        and preempted['replaced']
        # Replica count provably moved: the ramp forced a scale-out
        # and the controller both scaled out and replaced at least
        # once by its own accounting.
        and preempted['scaled_out']
        and preempted['max_ready_observed'] >= args.fleet + 1
        and scaler_rc == 0
        and scaler_counters.get('n_scale_out', 0) >= 1
        and scaler_counters.get('n_replaced', 0) >= 1
        # The interactive-class SLO held, as reported by the router's
        # unified /metricz per-class histogram.
        and interactive_p99 is not None
        and interactive_p99 <= args.slo_p99_s
        and router_rc == 0 and router_drained
        and all(rc == 0 for rc in tier_rcs)
        and bam_mismatch == 0 and bam_ok > 0
        and trace_connected)
  return 0 if ok else 1


def serve_soak(args) -> int:
  """Multi-client soak of a resident `dctpu serve` daemon."""
  sys.path.insert(0, os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))))
  from deepconsensus_tpu.serve.client import ServeClient, ServeClientError

  # Featurize every molecule once in the parent; clients re-send the
  # same feature payloads all soak long (the daemon does triage + model
  # + stitch per request).
  config = 'transformer_learn_values+test'
  molecules, synth_dir = _featurize_synth(args, args.serve_zmws)
  print(f'featurized {len(molecules)} molecules from {synth_dir}',
        flush=True)

  env = dict(os.environ)
  env['PYTHONPATH'] = '/root/repo:' + env.get('PYTHONPATH', '')
  env['JAX_PLATFORMS'] = env.get('JAX_PLATFORMS', 'cpu')
  proc = subprocess.Popen(
      [sys.executable, '-m', 'deepconsensus_tpu.cli', 'serve',
       '--random_init', '--config', config, '--port', '0',
       '--min_quality', '0',
       '--batch_size', str(args.serve_batch_size)],
      env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
      text=True)
  t0 = time.time()
  ready = json.loads(proc.stdout.readline())
  port = ready['port']
  print(json.dumps(ready), flush=True)

  # Solo baseline: one client, one pass, no concurrency.
  solo_client = ServeClient(port=port, timeout=180)
  solo = {}
  for features in molecules:
    resp = solo_client.polish_features(features)
    name = features[0]['name']
    name = name if isinstance(name, str) else name.decode()
    solo[name] = (resp['status'], resp['seq'],
                  None if resp['quals'] is None
                  else resp['quals'].tobytes())

  lock = threading.Lock()
  latencies = []
  mismatches = []
  errors = []
  n_ok = [0]

  def worker(wid):
    client = ServeClient(port=port, timeout=180)
    start = wid % max(1, len(molecules))
    rotated = molecules[start:] + molecules[:start]
    for r in range(args.serve_rounds):
      for features in rotated:
        name = features[0]['name']
        name = name if isinstance(name, str) else name.decode()
        t_req = time.monotonic()
        try:
          resp = client.polish_features(features)
        except ServeClientError as e:
          with lock:
            errors.append(f'{name}: HTTP {e.status}')
          continue
        except OSError:
          return  # daemon gone (post-drain) — expected for the tail burst
        dt = time.monotonic() - t_req
        got = (resp['status'], resp['seq'],
               None if resp['quals'] is None
               else resp['quals'].tobytes())
        with lock:
          latencies.append(dt)
          if got != solo[name]:
            mismatches.append(name)
          else:
            n_ok[0] += 1

  threads = [threading.Thread(target=worker, args=(w,))
             for w in range(args.serve)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()

  metricz = solo_client.metricz()
  # Drain under residual load: a last burst of clients is mid-flight
  # when SIGTERM lands; everything admitted must still complete.
  tail = [threading.Thread(target=worker, args=(w,))
          for w in range(min(2, args.serve))]
  for t in tail:
    t.start()
  time.sleep(0.2)
  proc.send_signal(signal.SIGTERM)
  rc = proc.wait(timeout=300)
  for t in tail:
    t.join(60)
  drained_line = {}
  for line in proc.stdout.read().splitlines():
    if line.startswith('{'):
      d = json.loads(line)
      if d.get('event') == 'drained':
        drained_line = d

  lat = sorted(latencies)
  verdict = {
      'soak': 'serve',
      'rc': rc,
      'n_clients': args.serve,
      'n_molecules': len(molecules),
      'n_requests_verified': n_ok[0],
      'n_mismatches': len(mismatches),
      'n_client_errors': len(errors),
      'p50_s': round(lat[len(lat) // 2], 4) if lat else None,
      'p99_s': round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 4)
               if lat else None,
      'daemon_counters': metricz.get('counters', {}),
      'drained': bool(drained_line.get('drained')),
      'wall_s': round(time.time() - t0, 1),
  }
  print(json.dumps(verdict), flush=True)
  if mismatches:
    print(f'MISMATCHED vs solo: {sorted(set(mismatches))[:10]}',
          flush=True)
  ok = (rc == 0 and not mismatches and verdict['drained']
        and n_ok[0] > 0)
  return 0 if ok else 1


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--copies', type=int, default=500)
  ap.add_argument('--out_dir', default='/root/soak_r5')
  ap.add_argument('--checkpoint',
                  default='/root/distill_r4_ep4/checkpoints/checkpoint-152')
  ap.add_argument('--batch_zmws', type=int, default=100)
  ap.add_argument('--sample_every', type=float, default=10.0)
  ap.add_argument('--min_minutes', type=float, default=10.0)
  ap.add_argument('--synthetic_zmws', type=int, default=4000,
                  help='ZMW count for the synthetic fallback when the '
                  'reference testdata is absent (~5.8 ZMW/s on the '
                  '1-core CPU host -> 4000 gives a >10 min soak)')
  ap.add_argument('--fleet', type=int, default=0, metavar='N',
                  help='Fleet mode: N serve replicas behind `dctpu '
                  'route` with a `dctpu autoscale` controller (real '
                  'subprocesses, shared compile cache), forced '
                  'preemption + replacement mid-soak, disaggregated '
                  'bam/1 leg. Needs N >= 2.')
  ap.add_argument('--fleet_clients', type=int, default=4,
                  help='Fleet mode: concurrent clients through the '
                  'router (client 0 is the interactive tenant, the '
                  'rest are bulk).')
  ap.add_argument('--autoscale_p99_s', type=float, default=0.05,
                  help='Fleet mode: the autoscaler\'s interactive-p99 '
                  'scale-out target — deliberately tight so the load '
                  'ramp provably crosses it.')
  ap.add_argument('--slo_p99_s', type=float, default=120.0,
                  help='Fleet mode: the verdict gate on the '
                  'interactive-class p99 reported by the router '
                  '(generous: CPU hosts serve slowly; the gate is '
                  'that the class histogram exists and stays sane '
                  'while the replica count moves).')
  ap.add_argument('--serve', type=int, default=0, metavar='N',
                  help='Serve mode: soak one `dctpu serve` daemon with '
                  'N concurrent clients instead of the batch pipeline.')
  ap.add_argument('--serve_rounds', type=int, default=10,
                  help='Serve mode: polish passes over the molecule '
                  'set per client.')
  ap.add_argument('--serve_zmws', type=int, default=24,
                  help='Serve mode: synthetic molecule count.')
  ap.add_argument('--serve_batch_size', type=int, default=64,
                  help='Serve mode: daemon pack size (every pack pads '
                  'to this compiled shape; keep small on CPU hosts).')
  ap.add_argument('--batch_size', type=int, default=0,
                  help='Batch mode: child pack size (0 = library '
                  'default of 1024). Chaos mode forces 64 when unset '
                  'so the soak spans many packs and per-pack compute '
                  'stays well under --dispatch_timeout.')
  ap.add_argument('--chaos', action='store_true',
                  help='Inject one device OOM and one device hang '
                  'mid-soak; the run must complete via bisection + '
                  'watchdog with recovery counters in the verdict.')
  ap.add_argument('--chaos_oom_pack', type=int, default=3,
                  help='Chaos mode: 1-based dispatch ordinal of the '
                  'pack that fakes RESOURCE_EXHAUSTED.')
  ap.add_argument('--chaos_hang_pack', type=int, default=6,
                  help='Chaos mode: 1-based dispatch ordinal of the '
                  'pack whose finalize hangs.')
  ap.add_argument('--chaos_hang_s', type=float, default=6.0,
                  help='Chaos mode: how long the hung pack sleeps '
                  '(must exceed --dispatch_timeout).')
  ap.add_argument('--dispatch_timeout', type=float, default=2.0,
                  help='Chaos mode: watchdog bound on the blocking '
                  'device sync in the child.')
  args = ap.parse_args()

  if args.fleet > 0:
    return fleet_soak(args)

  if args.serve > 0:
    return serve_soak(args)

  if args.chaos and not args.batch_size:
    args.batch_size = 64

  os.makedirs(args.out_dir, exist_ok=True)
  # Hosts without the reference testdata fall back to deterministic
  # synthetic BAMs (the fault-injection helper) — QC numbers are
  # meaningless there, but the soak verdict is about pipeline-level
  # properties (throughput flatness, RSS growth, shm leaks), which the
  # synthetic stream exercises identically.
  synthetic = not os.path.isdir(TESTDATA)
  if synthetic:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from scripts.inject_faults import write_synthetic_zmw_bams

    synth_dir = os.path.join(args.out_dir, f'synth_{args.synthetic_zmws}')
    if not os.path.isdir(synth_dir):
      t0 = time.time()
      os.makedirs(synth_dir, exist_ok=True)
      write_synthetic_zmw_bams(
          synth_dir, n_zmws=args.synthetic_zmws, n_subreads=5,
          seq_len=600)
      print(f'synthesized {args.synthetic_zmws} ZMWs -> {synth_dir} '
            f'({time.time() - t0:.1f}s)', flush=True)
    sub_bam = os.path.join(synth_dir, 'subreads_to_ccs.bam')
    ccs_bam = os.path.join(synth_dir, 'ccs.bam')
  else:
    sub_bam = os.path.join(args.out_dir, f'subreads_x{args.copies}.bam')
    ccs_bam = os.path.join(args.out_dir, f'ccs_x{args.copies}.bam')
    for src, dst in ((f'{TESTDATA}/subreads_to_ccs.bam', sub_bam),
                     (f'{TESTDATA}/ccs.bam', ccs_bam)):
      if not os.path.exists(dst):
        t0 = time.time()
        n = replicate_bam(src, dst, args.copies)
        print(f'replicated {src} -> {dst}: {n} records '
              f'({time.time() - t0:.1f}s)', flush=True)

  out_fastq = os.path.join(args.out_dir, 'soak.fastq')
  for stale in (out_fastq, out_fastq + '.tmp', out_fastq + '.progress.json',
                out_fastq + '.runtime.csv', out_fastq + '.inference.json'):
    if os.path.exists(stale):
      os.remove(stale)
  random_init = not os.path.exists(args.checkpoint)
  if random_init:
    # No servable checkpoint on this host: run the pipeline with
    # randomly initialized weights. Output qualities are garbage;
    # pipeline dynamics are real.
    child_code = (
        'import jax, sys\n'
        "jax.config.update('jax_platforms', 'cpu')\n"
        'import jax.numpy as jnp\n'
        'from deepconsensus_tpu.inference import runner as runner_lib\n'
        'from deepconsensus_tpu.models import config as config_lib\n'
        'from deepconsensus_tpu.models import model as model_lib\n'
        "params = config_lib.get_config('transformer_learn_values+test')\n"
        'config_lib.finalize_params(params, is_training=False)\n'
        'model = model_lib.get_model(params)\n'
        'variables = model.init(jax.random.PRNGKey(0), jnp.zeros(\n'
        '    (1, params.total_rows, params.max_length, 1)))\n'
        'sub, ccs, out, bz, bs, ode, dt, oze = sys.argv[1:9]\n'
        'options = runner_lib.InferenceOptions(\n'
        '    batch_zmws=int(bz), cpus=0, min_quality=0,\n'
        '    on_device_error=ode, dispatch_timeout=float(dt),\n'
        '    on_zmw_error=oze)\n'
        'if int(bs):\n'
        '  options.batch_size = int(bs)\n'
        'runner = runner_lib.ModelRunner(params, variables, options)\n'
        'runner_lib.run_inference(subreads_to_ccs=sub, ccs_bam=ccs,\n'
        '    checkpoint=None, output=out, options=options,\n'
        '    runner=runner)\n'
    )
    cmd = [
        sys.executable, '-c', child_code,
        sub_bam, ccs_bam, out_fastq, str(args.batch_zmws),
        str(args.batch_size),
        'degrade' if args.chaos else 'fail',
        str(args.dispatch_timeout if args.chaos else 0.0),
        # A watchdogged hang is never retried — its ZMWs must fall back
        # to CCS instead of aborting the whole soak.
        'ccs-fallback' if args.chaos else 'fail',
    ]
  else:
    child_code = (
        'import jax, sys\n'
        "jax.config.update('jax_platforms', 'cpu')\n"
        'from deepconsensus_tpu.cli import main\n'
        'sys.exit(main(sys.argv[1:]))\n'
    )
    cmd = [
        sys.executable, '-c', child_code, 'run',
        '--subreads_to_ccs', sub_bam, '--ccs_bam', ccs_bam,
        '--checkpoint', args.checkpoint, '--output', out_fastq,
        '--batch_zmws', str(args.batch_zmws),
        '--skip_windows_above', '0', '--min_quality', '0',
    ]
    if args.batch_size:
      cmd += ['--batch_size', str(args.batch_size)]
    if args.chaos:
      cmd += ['--on_device_error', 'degrade',
              '--dispatch_timeout', str(args.dispatch_timeout),
              '--on_zmw_error', 'ccs-fallback']
  env = dict(os.environ)
  env['PYTHONPATH'] = '/root/repo:' + env.get('PYTHONPATH', '')
  if args.chaos:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from deepconsensus_tpu import faults as shared_faults

    env[shared_faults.ENV_DEVICE_OOM_AT_PACK] = str(args.chaos_oom_pack)
    env[shared_faults.ENV_DEVICE_HANG_AT_PACK] = str(args.chaos_hang_pack)
    env[shared_faults.ENV_DEVICE_HANG_S] = str(args.chaos_hang_s)
    print(json.dumps({
        'chaos': 'armed',
        'oom_at_pack': args.chaos_oom_pack,
        'hang_at_pack': args.chaos_hang_pack,
        'hang_s': args.chaos_hang_s,
        'dispatch_timeout': args.dispatch_timeout,
    }), flush=True)
  proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                          stderr=subprocess.STDOUT)

  samples = []
  t0 = time.time()
  while proc.poll() is None:
    time.sleep(args.sample_every)
    try:
      with open(f'/proc/{proc.pid}/status') as f:
        rss_kb = next(
            (int(l.split()[1]) for l in f if l.startswith('VmRSS')), 0
        )
    except OSError:
      rss_kb = 0
    n_shm = len(os.listdir('/dev/shm')) if os.path.isdir('/dev/shm') else 0
    sample = {
        't': round(time.time() - t0, 1),
        'zmws_done': count_fastq_records(out_fastq),
        'rss_mb': round(rss_kb / 1024, 1),
        'shm_segments': n_shm,
    }
    samples.append(sample)
    print(json.dumps(sample), flush=True)
  rc = proc.returncode
  wall = time.time() - t0

  with open(os.path.join(args.out_dir, 'soak_samples.jsonl'), 'w') as f:
    for s in samples:
      f.write(json.dumps(s) + '\n')

  total = count_fastq_records(out_fastq)
  # Interval throughputs -> first/last quartile flatness ratio.
  # Leading zero-progress samples are JIT compile + BAM indexing, not
  # throughput; folding them into the first quartile would flunk the
  # flatness check on warmup alone.
  first_live = next(
      (i for i, s in enumerate(samples) if s['zmws_done'] > 0), 0)
  warmup_s = samples[first_live]['t'] if samples else 0.0
  live = samples[max(0, first_live - 1):]
  rates = []
  for a, b in zip(live, live[1:]):
    dt = b['t'] - a['t']
    if dt > 0:
      rates.append((b['zmws_done'] - a['zmws_done']) / dt)
  q = max(1, len(rates) // 4)
  first_q = sum(rates[:q]) / q if rates else 0.0
  last_q = sum(rates[-q:]) / q if rates else 0.0
  verdict = {
      'soak': 'e2e',
      'rc': rc,
      'synthetic_data': synthetic,
      'random_init_weights': random_init,
      'zmws_total': total,
      'wall_s': round(wall, 1),
      'warmup_s': round(warmup_s, 1),
      'zmw_per_s': round(total / wall, 2) if wall else 0.0,
      'first_quartile_zmw_per_s': round(first_q, 2),
      'last_quartile_zmw_per_s': round(last_q, 2),
      'throughput_flat': bool(
          first_q > 0 and 0.7 <= last_q / first_q <= 1.4
      ),
      'rss_mb_max': max((s['rss_mb'] for s in samples), default=0),
      'rss_mb_final': samples[-1]['rss_mb'] if samples else 0,
      'shm_segments_max': max(
          (s['shm_segments'] for s in samples), default=0
      ),
      'ran_minutes': round(wall / 60, 1),
      'long_enough': wall >= args.min_minutes * 60,
  }
  if args.chaos:
    counters = {}
    sidecar = out_fastq + '.inference.json'
    if os.path.exists(sidecar):
      with open(sidecar) as f:
        counters = json.load(f)
    chaos = {
        'n_device_faults': counters.get('n_device_faults', 0),
        'n_oom_bisections': counters.get('n_oom_bisections', 0),
        'n_dispatch_timeouts': counters.get('n_dispatch_timeouts', 0),
        'n_mesh_degradations': counters.get('n_mesh_degradations', 0),
        'n_zmw_quarantined': counters.get('n_zmw_quarantined', 0),
    }
    chaos['recovered'] = bool(
        rc == 0 and chaos['n_oom_bisections'] >= 1
        and chaos['n_dispatch_timeouts'] >= 1)
    verdict['chaos'] = chaos
  print(json.dumps(verdict), flush=True)
  if args.chaos:
    # Recovery counters are the point; flatness only judges runs long
    # enough to have quartiles that mean something.
    flat_ok = verdict['throughput_flat'] or len(rates) < 4
    return 0 if verdict['chaos']['recovered'] and flat_ok else 1
  return 0 if rc == 0 else rc


if __name__ == '__main__':
  raise SystemExit(main())
