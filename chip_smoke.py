#!/usr/bin/env python3
"""The quickest proof that DeepConsensus-TPU still starts on the chip.

Drives the main path once through `python -m deepconsensus_tpu.cli`, at
the full width of the v1.2 production model (transformer_learn_values:
6 layers, hidden 280, filter 2048, 2 heads, 85 rows x L=100, bfloat16),
with random weights made from --seed:

  run        BAM -> FASTQ at --batch_size 1024 (one full pack + a tail)
  run_cpus2  the same with --cpus 2 (featurize pool); FASTQ byte-equal
  compare    a sample of the polished windows, recomputed by a float32
             CPU-backend process: max |dp| and base-id agreement
  serve      dctpu serve --random_init: /readyz, a few /v1/polish, SIGTERM
  train      a few steps at batch 256 (Pallas wavefront loss on a TPU),
             one eval, one checkpoint; then resumed from that checkpoint
  run_fused  run with use_fused_hotpath in params.json vs the XLA FASTQ

With `--chips 4` it runs ONLY what exists across chips: `run --dp 4`
against the same input on one device, two `train --dp 2 --tp 2` steps,
and ring attention over a 4-device mesh against the full reference.

One process per chip: this parent never imports JAX. Every phase is a
child that runs to its end before the next starts, and the device block
of the last line comes from what the children recorded in their
sidecars. Children log their compiles (JAX_LOG_COMPILES) and share the
persistent cache the CLI enables: $JAX_COMPILATION_CACHE_DIR when set,
else <checkout>/.jax_cache.

stdout: one JSON object per phase, then — only if every phase passed,
the platform is `tpu`, and no Pallas call resolved to interpret mode —
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Anything else exits non-zero without that line. `--toy` shrinks every
size so the control flow can be rehearsed on a CPU (it still exits
non-zero there: the platform is not `tpu`).
"""
import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
CLI = [sys.executable, '-m', 'deepconsensus_tpu.cli']
CONFIG = 'transformer_learn_values+test'

# float32-CPU vs bfloat16-TPU bound on max |dp| over the sampled
# windows' softmax outputs. Fixed after the first chip run of PR 21
# (TPU v5 lite, seed 21, 256 windows = 25,600 positions): measured max
# |dp| 0.00718 for the XLA path; 0.02 leaves ~2.8x headroom for other
# seeds. The fused hot path is held to the same bound against the same
# float32 reference. Base ids must agree on every position outside the
# near-tie margin: a flip needs the top two to move towards each other
# by the margin, i.e. some |dp| of at least half of it, so the margin
# is twice the bound (that first run: all 22,925 positions with margin
# above 0.05 agreed, 0.99715 of all positions).
MAX_ABS_DP = 0.02
NEAR_TIE_MARGIN = 2 * MAX_ABS_DP
MIN_ID_AGREEMENT = 1.0
# Least share of aligned bases the fused run's FASTQ must share with
# the XLA run's (both bf16, different sum order: near-ties flip either
# way, and a flipped gap shifts a read, so reads are aligned first).
# First chip run of PR 21: 116,733 of 116,872 positions (0.9988), 45 of
# 120 reads byte-identical.
MIN_FUSED_BASE_IDENTITY = 0.99

# JAX_LOG_COMPILES lines; a process with two log handlers prints each
# twice, so they are counted as sets.
_COMPILE_RE = re.compile(
    r'Finished XLA compilation of (.*) in ([0-9.e+-]+) sec')
_CACHE_HIT_RE = re.compile(
    r"Persistent compilation cache hit for '(.*)' with key '?([^'\s]+)")


# No child may outlast this; the whole smoke has 1200 s.
PHASE_TIMEOUT_S = 900.0


class PhaseFailed(Exception):
  pass


def check(cond, message):
  if not cond:
    raise PhaseFailed(message)


class Sizes:
  """Every size of the smoke, real or toy."""

  def __init__(self, toy: bool):
    if toy:
      # Tiny geometry for the CPU rehearsal; serve keeps the preset's
      # geometry (it takes no overrides) at a small batch.
      self.model_set = ['max_passes=5', 'max_length=20',
                        'num_hidden_layers=1', 'filter_size=32']
      self.max_passes, self.max_length = 5, 20
      self.n_zmws, self.n_subreads, self.seq_len = 6, 3, 120
      self.run_batch, self.serve_batch, self.train_batch = 16, 8, 8
      self.sample_windows = 16
      self.ring_len = 64
    else:
      self.model_set = []
      self.max_passes, self.max_length = 20, 100
      # 120 ZMWs x 1000 bp = 1200 windows: one full 1024-window pack
      # and a 176-window tail.
      self.n_zmws, self.n_subreads, self.seq_len = 120, 5, 1000
      self.run_batch, self.serve_batch, self.train_batch = 1024, 1024, 256
      self.sample_windows = 256
      self.ring_len = 512
    self.train_steps = 3
    self.n_windows = self.n_zmws * (
        (self.seq_len + self.max_length - 1) // self.max_length)


class Smoke:

  def __init__(self, args):
    self.args = args
    self.sizes = Sizes(args.toy)
    self.work = os.path.abspath(args.work_dir)
    self.big = os.path.join(self.work, 'big')  # removed at the end
    self.failed = []
    self.reports = []  # execution_report dicts from the children

  # -- plumbing -----------------------------------------------------

  def path(self, *parts):
    return os.path.join(self.work, *parts)

  def env(self, **extra):
    env = dict(os.environ)
    env['PYTHONPATH'] = REPO + os.pathsep + env.get('PYTHONPATH', '')
    env['JAX_LOG_COMPILES'] = '1'
    env.setdefault('TF_CPP_MIN_LOG_LEVEL', '2')
    env.update(extra)
    return env

  def cpu_env(self):
    return self.env(JAX_PLATFORMS='cpu')

  def sh(self, name, cmd, env=None, timeout=None):
    """Runs one child to its end; returns its stdout. stderr goes to
    <work>/logs/<name>.err (where the compile log lines land)."""
    os.makedirs(self.path('logs'), exist_ok=True)
    err_path = self.path('logs', name + '.err')
    cmd = [str(c) for c in cmd]
    with open(err_path, 'w') as err:
      try:
        proc = subprocess.run(
            cmd, cwd=self.work, env=env or self.env(), stdout=subprocess.PIPE,
            stderr=err, text=True, timeout=timeout or PHASE_TIMEOUT_S)
      except subprocess.TimeoutExpired:
        raise PhaseFailed(f'{name}: no end within '
                          f'{timeout or PHASE_TIMEOUT_S}s') from None
    if proc.returncode != 0:
      with open(err_path) as f:
        tail = f.read()[-1500:]
      raise PhaseFailed(f'{name}: exit {proc.returncode}: {tail}')
    return proc.stdout

  def child(self, name, role, *role_args, env=None, timeout=None):
    """A role of this file run as a child (the only processes here that
    import JAX); returns the JSON object it printed last."""
    out = self.sh(name, [sys.executable, os.path.abspath(__file__),
                         '--child', role, *map(str, role_args)],
                  env=env, timeout=timeout)
    return json.loads(out.strip().splitlines()[-1])

  def compile_stats(self, *names):
    seconds, n, hits = 0.0, 0, 0
    for name in names:
      with open(self.path('logs', name + '.err'), errors='replace') as f:
        text = f.read()
      compiles = set(_COMPILE_RE.findall(text))
      seconds += sum(float(t) for _, t in compiles)
      n += len(compiles)
      hits += len(set(_CACHE_HIT_RE.findall(text)))
    return {'compile_seconds': round(seconds, 2), 'n_compiles': n,
            'n_cache_hits': hits}

  def phase(self, name, fn, logs=()):
    """Runs one phase; prints its JSON line; records failure."""
    t0 = time.time()
    result = {'phase': name}
    try:
      result.update(fn() or {})
      result['ok'] = True
    except PhaseFailed as e:
      result['ok'] = False
      result['error'] = str(e)[-2000:]
      self.failed.append(name)
    result['seconds'] = round(time.time() - t0, 2)
    have = [n for n in logs if os.path.exists(self.path('logs', n + '.err'))]
    if have:
      result.update(self.compile_stats(*have))
    print(json.dumps(result), flush=True)
    return result['ok']

  def note_report(self, report):
    self.reports.append({k: report[k] for k in (
        'platform', 'device_kind', 'device_count',
        'n_pallas_calls_interpret', 'n_pallas_calls_compiled')})

  # -- inputs -------------------------------------------------------

  def setup(self):
    """Everything that needs no device: the native BGZF library built
    from bgzf.cpp, synthetic BAMs and training shards from --seed."""
    if os.path.isdir(self.work):
      shutil.rmtree(self.work)
    os.makedirs(self.big)
    sys.path.insert(0, REPO)
    from deepconsensus_tpu import native

    lib_path = os.path.join(os.path.dirname(native.__file__),
                            'libdcnative.so')
    if os.path.exists(lib_path):
      os.unlink(lib_path)  # git-ignored leftovers never stand in
    decoder = 'native' if native.get_lib() is not None else 'python'
    s = self.sizes
    faults = [sys.executable, os.path.join(REPO, 'scripts',
                                           'inject_faults.py')]
    self.sh('synth_bams', faults + [
        'synth', '--out_dir', os.path.join(self.big, 'bams'),
        '--n_zmws', s.n_zmws, '--n_subreads', s.n_subreads,
        '--seq_len', s.seq_len, '--seed', self.args.seed],
            env=self.cpu_env())
    self.sh('synth_shards', faults + [
        'synth_tfrecords', '--out_dir', os.path.join(self.big, 'shards'),
        '--n_shards', 2, '--n_examples', s.train_batch * s.train_steps,
        '--max_passes', s.max_passes, '--max_length', s.max_length,
        '--seed', self.args.seed], env=self.cpu_env())
    return {'bgzf_decoder': decoder, 'n_zmws': s.n_zmws,
            'n_windows': s.n_windows,
            'n_train_examples': s.train_batch * s.train_steps}

  def mint(self):
    """Random-init checkpoints from --seed, written by a CPU child:
    `xla` (the default path) and `fused` (same weights, params.json
    asks for use_fused_hotpath)."""
    return self.child('mint', 'mint', self.big, self.args.seed,
                      ','.join(self.sizes.model_set), env=self.cpu_env())

  @property
  def bams(self):
    return (os.path.join(self.big, 'bams', 'subreads_to_ccs.bam'),
            os.path.join(self.big, 'bams', 'ccs.bam'))

  def ckpt(self, kind):
    return os.path.join(self.big, f'model_{kind}', 'checkpoints',
                        'checkpoint-0')

  # -- inference ----------------------------------------------------

  def run_cli(self, name, kind='xla', extra=()):
    """One `dctpu run`; returns (fastq path, sidecar dict)."""
    subreads, ccs = self.bams
    out = self.path(f'{name}.fastq')
    self.sh(name, CLI + [
        'run', '--subreads_to_ccs', subreads, '--ccs_bam', ccs,
        '--checkpoint', self.ckpt(kind), '--output', out,
        '--batch_size', self.sizes.run_batch, '--batch_zmws', 20,
        '--min_quality', 0, '--skip_windows_above', 0, *extra])
    with open(out + '.inference.json') as f:
      sidecar = json.load(f)
    self.note_report(sidecar)
    s = self.sizes
    check(sidecar.get('success') == s.n_zmws,
          f"{name}: {sidecar.get('success')} of {s.n_zmws} reads")
    check(sidecar['n_model_pack_rows'] == s.n_windows,
          f"{name}: {sidecar['n_model_pack_rows']} windows took the "
          f'forward, expected {s.n_windows}')
    full, tail = divmod(s.n_windows, s.run_batch)
    check(full >= 1 and tail > 0 and sidecar['n_model_packs'] == full + 1
          and sidecar['n_model_pad_rows'] == s.run_batch - tail,
          f"{name}: packs {sidecar['n_model_packs']} pad "
          f"{sidecar['n_model_pad_rows']}: want {full} full + one tail")
    check(sidecar['n_forward_shapes'] == 1,
          f"{name}: {sidecar['n_forward_shapes']} compiled shapes")
    return out, sidecar

  @staticmethod
  def run_summary(sidecar):
    return {k: sidecar[k] for k in (
        'success', 'n_model_packs', 'n_model_pack_rows', 'n_model_pad_rows',
        'n_forward_shapes', 'platform', 'device_kind', 'device_count',
        'mesh_dp', 'pack_shard_devices', 'n_pallas_calls_compiled',
        'n_pallas_calls_interpret')}

  def run(self):
    _, sidecar = self.run_cli('run')
    return dict(self.run_summary(sidecar), windows=self.sizes.n_windows)

  def run_cpus2(self):
    out, sidecar = self.run_cli('run_cpus2', extra=['--cpus', 2])
    check(_read(out) == _read(self.path('run.fastq')),
          'run --cpus 2: FASTQ differs from the serial featurize run')
    return dict(self.run_summary(sidecar), fastq_identical=True)

  def compare(self, kind='xla'):
    """bf16 on the device vs float32 on the CPU backend, same
    checkpoint, same windows; kind picks the checkpoint whose
    params.json routes the forward (xla | fused)."""
    dump = self.path(f'compare_{kind}.npz')
    got = self.child(f'compare_{kind}_device', 'dump', self.big, kind, dump,
                     self.sizes.sample_windows, self.sizes.run_batch)
    self.note_report(got['report'])
    ref = self.child(f'compare_{kind}_cpu', 'reference', self.big, dump,
                     env=self.cpu_env())
    result = {
        'windows': got['n_windows'], 'positions': ref['n_positions'],
        'max_abs_dp': ref['max_abs_dp'], 'bound_max_abs_dp': MAX_ABS_DP,
        'n_margin_positions': ref['n_margin_positions'],
        'id_agreement_over_margin': ref['id_agreement_over_margin'],
        'id_agreement_all': ref['id_agreement_all'],
        'predict_matches_argmax': got['predict_matches_argmax'],
        'device_platform': got['report']['platform'],
        'n_pallas_calls_compiled': got['report']['n_pallas_calls_compiled'],
    }
    check(ref['finite'], 'compare: non-finite probabilities')
    check(ref['max_abs_dp'] <= MAX_ABS_DP,
          f"compare: max |dp| {ref['max_abs_dp']} over bound {MAX_ABS_DP}")
    check(ref['id_agreement_over_margin'] >= MIN_ID_AGREEMENT,
          f"compare: ids agree on {ref['id_agreement_over_margin']} of "
          'the positions outside the near-tie margin')
    check(got['predict_matches_argmax'] >= MIN_ID_AGREEMENT,
          'compare: runner.predict ids differ from the argmax of the '
          'same forward')
    return result

  def run_fused(self):
    """The Pallas hot path through the same CLI: its FASTQ against the
    XLA run's, and its windows against the float32 reference."""
    out, sidecar = self.run_cli('run_fused', kind='fused')
    check(sidecar['n_pallas_calls_compiled'] > 0
          or sidecar['pallas_interpret_default'],
          'run_fused: no Pallas call was traced — the fused path did '
          'not engage')
    same, total, base_same, base_total = _fastq_identity(
        out, self.path('run.fastq'))
    check(total == self.sizes.n_zmws, f'run_fused: {total} reads')
    identity = base_same / max(base_total, 1)
    check(identity >= MIN_FUSED_BASE_IDENTITY,
          f'run_fused: base identity {identity} vs the XLA run')
    windows = self.compare('fused')
    return dict(self.run_summary(sidecar), reads_identical=same,
                reads=total, base_identity=round(identity, 6),
                **{f'windows_{k}': windows[k] for k in (
                    'max_abs_dp', 'id_agreement_over_margin',
                    'id_agreement_all', 'n_margin_positions')})

  # -- serving ------------------------------------------------------

  def serve(self):
    os.makedirs(self.path('logs'), exist_ok=True)
    err = open(self.path('logs', 'serve.err'), 'w')
    proc = subprocess.Popen(
        CLI + ['serve', '--random_init', '--config', CONFIG, '--port', '0',
               '--batch_size', str(self.sizes.serve_batch),
               '--min_quality', '0'],
        cwd=self.work, env=self.env(), stdout=subprocess.PIPE, stderr=err,
        text=True)
    try:
      ready = json.loads(_readline(proc, PHASE_TIMEOUT_S))
      check(ready.get('event') == 'ready', f'serve: first line {ready}')
      self.note_report(ready['device'])
      port = ready['port']
      with urllib.request.urlopen(
          f'http://127.0.0.1:{port}/readyz', timeout=30) as resp:
        check(resp.status == 200, f'/readyz -> {resp.status}')
      answers = self.child(
          'serve_client', 'serve_client', self.big, port, 3,
          env=self.cpu_env())
      check(answers['statuses'] == ['ok'] * 3 and min(answers['lengths']) > 0,
            f'serve: /v1/polish answered {answers}')
      proc.send_signal(signal.SIGTERM)
      try:
        rest, _ = proc.communicate(timeout=120)
      except subprocess.TimeoutExpired:
        raise PhaseFailed('serve: no drain within 120s of SIGTERM') from None
      check(proc.returncode == 0, f'serve: exit {proc.returncode} on SIGTERM')
      drained = json.loads(rest.strip().splitlines()[-1])
      check(drained.get('event') == 'drained', f'serve: last line {drained}')
      return {'warmup_s': ready['warmup_s'], 'requests': 3,
              'read_lengths': answers['lengths'],
              'platform': ready['device']['platform'],
              'n_forward_shapes': drained.get('counters', {}).get(
                  'n_forward_shapes')}
    finally:
      if proc.poll() is None:
        proc.kill()
        proc.wait()
      err.close()

  # -- training -----------------------------------------------------

  def train_cli(self, name, out_dir, epochs, extra=()):
    s = self.sizes
    shards = os.path.join(self.big, 'shards', '*')
    sets = [a for kv in s.model_set + [
        'warmup_steps=2', 'log_every_n_steps=1'] for a in ('--set', kv)]
    self.sh(name, CLI + [
        'train', '--config', CONFIG, '--out_dir', out_dir,
        '--train_path', shards, '--eval_path', shards,
        '--batch_size', s.train_batch, '--num_epochs', epochs,
        *sets, *extra])
    with open(os.path.join(out_dir, 'metrics.jsonl')) as f:
      return [json.loads(line) for line in f]

  def check_train(self, name, rows, steps):
    train = [r for r in rows if r['split'] == 'train']
    check([r['step'] for r in train] == list(range(1, steps + 1)),
          f"{name}: train steps logged {[r['step'] for r in train]}")
    losses = [r['train/loss'] if 'train/loss' in r else r['loss']
              for r in train]
    check(all(_finite(x) for x in losses), f'{name}: losses {losses}')
    evals = [r for r in rows if r['split'] == 'eval']
    check(evals and all(_finite(v) for r in evals for k, v in r.items()
                        if k.startswith('eval/') and 'loss' in k),
          f'{name}: eval rows {evals}')
    faults = [r for r in rows if r['split'] == 'faults'][-1]
    check(faults['n_train_forward_shapes'] == 1,
          f"{name}: n_train_forward_shapes "
          f"{faults['n_train_forward_shapes']}")
    device = [r for r in rows if r['split'] == 'device'][-1]
    self.note_report(device)
    return losses, device

  def train(self):
    s = self.sizes
    out_dir = os.path.join(self.big, 'train_out')
    rows = self.train_cli('train', out_dir, 1)
    losses, device = self.check_train('train', rows, s.train_steps)
    check(device['use_pallas_wavefront'] == int(device['platform'] == 'tpu'),
          f"train: use_pallas_wavefront {device['use_pallas_wavefront']} "
          f"on {device['platform']}")
    ckpt = os.path.join(out_dir, 'checkpoints', f'checkpoint-{s.train_steps}')
    check(os.path.isdir(ckpt), f'train: no {ckpt}')
    # Reload: a second invocation must resume from that checkpoint, not
    # from step 0.
    rows = self.train_cli('train_resume', out_dir, 2)
    resumed, _ = self.check_train('train_resume', rows, 2 * s.train_steps)
    return {'steps': 2 * s.train_steps, 'batch': s.train_batch,
            'losses': [round(x, 4) for x in resumed],
            'resumed_from_step': s.train_steps,
            'use_pallas_wavefront': device['use_pallas_wavefront'],
            'n_pallas_calls_compiled': device['n_pallas_calls_compiled'],
            'n_pallas_calls_interpret': device['n_pallas_calls_interpret'],
            'platform': device['platform']}

  # -- four chips ---------------------------------------------------

  def run_dp4(self):
    one, side1 = self.run_cli('run')
    four, side4 = self.run_cli('run_dp4', extra=['--dp', 4])
    check(side4['mesh_dp'] == 4 and side4['pack_shard_devices'] == 4,
          f"run --dp 4: mesh_dp {side4['mesh_dp']}, a pack's shards sit "
          f"on {side4['pack_shard_devices']} device(s)")
    check(side1['pack_shard_devices'] == 1, 'run: pack not on one device')
    same, total, base_same, base_total = _fastq_identity(four, one)
    identical = _read(one) == _read(four)
    differing = base_total - base_same
    check(total == self.sizes.n_zmws, f'run --dp 4: {total} reads')
    # Byte-identical, or (XLA tiling a 256-row shard differently) held
    # to the same share of flipped near-ties the fused path is allowed.
    check(identical
          or base_same >= MIN_FUSED_BASE_IDENTITY * base_total,
          f'run --dp 4: {differing} of {base_total} positions differ')
    return dict(self.run_summary(side4), fastq_identical=identical,
                reads_identical=same, positions_differing=differing,
                positions=base_total)

  def train_dp2tp2(self):
    s = self.sizes
    out_dir = os.path.join(self.big, 'train_dp2tp2')
    rows = self.train_cli('train_dp2tp2', out_dir, 1,
                          extra=['--dp', 2, '--tp', 2])
    losses, device = self.check_train('train_dp2tp2', rows, s.train_steps)
    check(device['n_model_axis_sharded_params'] >= 1,
          'train --dp 2 --tp 2: no parameter sharded on the model axis')
    return {'steps': s.train_steps, 'batch': s.train_batch,
            'losses': [round(x, 4) for x in losses],
            'n_model_axis_sharded_params':
                device['n_model_axis_sharded_params'],
            'use_pallas_wavefront': device['use_pallas_wavefront'],
            'n_pallas_calls_compiled': device['n_pallas_calls_compiled'],
            'device_count': device['device_count']}

  def ring(self):
    got = self.child('ring', 'ring', self.sizes.ring_len, self.args.seed)
    self.note_report(got['report'])
    check(got['n_devices'] == 4, f"ring: {got['n_devices']} devices")
    check(got['max_abs_diff'] <= 2e-2,
          f"ring attention differs from the reference by "
          f"{got['max_abs_diff']}")
    return {k: got[k] for k in ('n_devices', 'seq_len', 'max_abs_diff')}

  # -- the whole --------------------------------------------------

  def main(self) -> int:
    if not self.args.toy:
      # Fail fast off the chip: the real sizes are not for a CPU.
      os.makedirs(self.path('logs'), exist_ok=True)
      try:
        probe = self.child('probe', 'probe', timeout=300)
      except PhaseFailed as e:
        print(f'chip_smoke: cannot reach a device: {e}', file=sys.stderr)
        return 1
      if probe['platform'] != 'tpu' or probe['device_count'] < self.args.chips:
        print(f'chip_smoke: need {self.args.chips} TPU chip(s), JAX '
              f'reports {probe}', file=sys.stderr)
        return 1
    ok = (self.phase('setup', self.setup)
          and self.phase('mint', self.mint, logs=('mint',)))
    if ok and self.args.chips == 4:
      self.phase('run_dp4', self.run_dp4, logs=('run', 'run_dp4'))
      self.phase('train_dp2tp2', self.train_dp2tp2, logs=('train_dp2tp2',))
      self.phase('ring', self.ring, logs=('ring',))
    elif ok:
      if self.phase('run', self.run, logs=('run',)):
        self.phase('run_cpus2', self.run_cpus2, logs=('run_cpus2',))
        self.phase('compare', self.compare, logs=('compare_xla_device',))
        self.phase('run_fused', self.run_fused,
                   logs=('run_fused', 'compare_fused_device'))
      self.phase('serve', self.serve, logs=('serve',))
      self.phase('train', self.train, logs=('train', 'train_resume'))
    shutil.rmtree(self.big, ignore_errors=True)
    return self.verdict()

  def verdict(self) -> int:
    if self.failed or not self.reports:
      print(f'chip_smoke: failed phases: {self.failed}', file=sys.stderr)
      return 1
    devices = {(r['platform'], r['device_kind'], r['device_count'])
               for r in self.reports}
    interpreted = sum(r['n_pallas_calls_interpret'] for r in self.reports)
    if len(devices) != 1:
      print(f'chip_smoke: phases disagree on the device: {devices}',
            file=sys.stderr)
      return 1
    platform, kind, count = devices.pop()
    if platform != 'tpu':
      print(f'chip_smoke: every phase ran, but on {platform!r}, not a TPU',
            file=sys.stderr)
      return 1
    if interpreted:
      print(f'chip_smoke: {interpreted} Pallas call(s) ran in interpret '
            'mode on a TPU', file=sys.stderr)
      return 1
    if count != self.args.chips:
      print(f'chip_smoke: --chips {self.args.chips} but JAX sees {count}',
            file=sys.stderr)
      return 1
    print(json.dumps({'ok': True, 'device': {
        'platform': platform, 'kind': kind, 'count': count}}), flush=True)
    return 0


def _read(path):
  with open(path, 'rb') as f:
    return f.read()


def _finite(x):
  return isinstance(x, (int, float)) and x == x and abs(x) != float('inf')


def _readline(proc, timeout):
  """First stdout line of a daemon, or PhaseFailed."""
  import select

  ready, _, _ = select.select([proc.stdout], [], [], timeout)
  check(ready, f'serve: no ready line within {timeout}s')
  line = proc.stdout.readline()
  check(line, f'serve: exited {proc.poll()} before its ready line')
  return line


def _fastq_seqs(path):
  with open(path) as f:
    lines = f.read().splitlines()
  return {lines[i][1:]: lines[i + 1] for i in range(0, len(lines), 4)}


def _fastq_identity(path_a, path_b):
  """(reads identical, reads, positions matching, positions) over the
  reads both files hold. A flipped gap shifts the rest of a read, so
  differing reads are aligned (difflib) before positions are counted."""
  import difflib

  a, b = _fastq_seqs(path_a), _fastq_seqs(path_b)
  check(a.keys() == b.keys(), 'FASTQs hold different reads')
  same = base_same = base_total = 0
  for name, seq in a.items():
    other = b[name]
    same += seq == other
    base_total += max(len(seq), len(other))
    if seq == other:
      base_same += len(seq)
    else:
      base_same += sum(block.size for block in difflib.SequenceMatcher(
          None, seq, other, autojunk=False).get_matching_blocks())
  return same, len(a), base_same, base_total


# ---------------------------------------------------------------------
# Child roles: the only code in this file that imports JAX. Each prints
# one JSON object as its last stdout line.


def _params(big, kind='xla'):
  from deepconsensus_tpu.models import config as config_lib

  params = config_lib.read_params_from_json(
      os.path.join(big, f'model_{kind}', 'checkpoints', 'checkpoint-0'))
  config_lib.finalize_params(params, is_training=False)
  return params


def _sample_windows(big, params, n):
  """The first n windows `dctpu run` featurizes from the smoke's BAMs,
  as model rows [n, R, L, 1]."""
  import numpy as np

  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import data as data_lib
  from deepconsensus_tpu.preprocess import FeatureLayout, create_proc_feeder

  options = runner_lib.InferenceOptions(min_quality=0, skip_windows_above=0)
  options.max_passes = params.max_passes
  options.max_length = params.max_length
  options.use_ccs_bq = params.use_ccs_bq
  feeder, _ = create_proc_feeder(
      subreads_to_ccs=os.path.join(big, 'bams', 'subreads_to_ccs.bam'),
      ccs_bam=os.path.join(big, 'bams', 'ccs.bam'),
      layout=FeatureLayout(max_passes=params.max_passes,
                           max_length=params.max_length,
                           use_ccs_bq=params.use_ccs_bq),
      ins_trim=options.ins_trim)
  molecules = []
  for zmw_input in feeder():
    features, _ = runner_lib.preprocess_zmw(zmw_input, options)
    molecules.append(features)
    if sum(len(m) for m in molecules) >= n:
      break
  windows = [w for m in molecules for w in m][:n]
  raw = np.stack([w['subreads'] for w in windows])
  return data_lib.format_rows_batch(raw, params), molecules


def child_probe():
  from deepconsensus_tpu.ops import pallas_util

  return pallas_util.execution_report()


def child_mint(big, seed, model_set):
  import jax
  import jax.numpy as jnp
  import orbax.checkpoint as ocp

  from deepconsensus_tpu import cli
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib

  params = config_lib.get_config(CONFIG)
  cli._apply_overrides(params, [kv for kv in model_set.split(',') if kv])
  config_lib.finalize_params(params, is_training=False)
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(int(seed)),
      jnp.zeros((1, params.total_rows, params.max_length, 1), jnp.float32))
  n_params = sum(x.size for x in jax.tree.leaves(variables['params']))
  ckptr = ocp.StandardCheckpointer()
  for kind, fused in (('xla', False), ('fused', True)):
    out = os.path.join(big, f'model_{kind}')
    ckptr.save(os.path.join(out, 'checkpoints', 'checkpoint-0'),
               {'params': jax.device_get(variables['params']), 'step': 0},
               force=True)
    ckptr.wait_until_finished()
    with params.unlocked():
      params.use_fused_hotpath = fused
    config_lib.save_params_as_json(out, params)
  ckptr.close()
  return {'n_params': int(n_params), 'layers': params.num_hidden_layers,
          'hidden': params.hidden_size, 'filter': params.filter_size,
          'heads': params.num_heads, 'rows': params.total_rows,
          'length': params.max_length, 'dtype': params.dtype}


def child_dump(big, kind, out, n, batch):
  """On the device: the checkpoint's own forward (the runner's placed
  variables, the params' compute dtype and routing) over the sampled
  windows."""
  import jax
  import numpy as np

  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import model as model_lib
  from deepconsensus_tpu.ops import pallas_util
  from deepconsensus_tpu.utils import compile_cache

  compile_cache.enable()
  ckpt = os.path.join(big, f'model_{kind}', 'checkpoints', 'checkpoint-0')
  options = runner_lib.InferenceOptions(batch_size=int(batch), min_quality=0)
  runner = runner_lib.ModelRunner.from_checkpoint(ckpt, options)
  rows, _ = _sample_windows(big, runner.params, int(n))
  model = model_lib.get_model(runner.params)
  preds = np.asarray(jax.jit(model.apply)(runner.variables, rows),
                     np.float32)
  ids, quals = runner.predict(rows)
  np.savez(out, rows=rows, preds=preds, ids=np.asarray(ids),
           quals=np.asarray(quals))
  return {'n_windows': int(rows.shape[0]),
          'predict_matches_argmax': float(
              (np.asarray(ids) == preds.argmax(-1)).mean()),
          'report': pallas_util.execution_report()}


def child_reference(big, dump):
  """On the CPU backend in float32: the same windows, same checkpoint."""
  import jax
  import numpy as np

  from deepconsensus_tpu.models import model as model_lib
  from deepconsensus_tpu.models.checkpoints import load_params

  assert jax.default_backend() == 'cpu'
  params = _params(big)
  with params.unlocked():
    params.dtype = 'float32'
  data = np.load(dump)
  weights = load_params(
      os.path.join(big, 'model_xla', 'checkpoints', 'checkpoint-0'))
  ref = np.asarray(jax.jit(model_lib.get_model(params).apply)(
      {'params': weights}, data['rows']), np.float32)
  got = data['preds']
  dp = np.abs(got - ref)
  top2 = np.sort(ref, axis=-1)[..., -2:]
  margin = top2[..., 1] - top2[..., 0]
  clear = margin > NEAR_TIE_MARGIN
  agree = got.argmax(-1) == ref.argmax(-1)
  return {
      'finite': bool(np.isfinite(got).all() and np.isfinite(ref).all()),
      'n_positions': int(agree.size),
      'max_abs_dp': float(dp.max()),
      'mean_abs_dp': float(dp.mean()),
      'n_margin_positions': int(clear.sum()),
      'id_agreement_over_margin': float(agree[clear].mean())
      if clear.any() else 1.0,
      'id_agreement_all': float(agree.mean()),
  }


def child_serve_client(big, port, n):
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.serve.client import ServeClient

  # The served preset's geometry, not the minted checkpoint's.
  params = config_lib.get_config(CONFIG)
  config_lib.finalize_params(params, is_training=False)
  _, molecules = _sample_windows(big, params, 10 ** 9)
  client = ServeClient(port=int(port), timeout=300)
  answers = [client.polish_features(m) for m in molecules[:int(n)]]
  return {'statuses': [a['status'] for a in answers],
          'lengths': [len(a['seq']) for a in answers]}


def child_ring(seq_len, seed):
  import jax
  import jax.numpy as jnp
  import numpy as np
  from jax.sharding import Mesh

  from deepconsensus_tpu.ops import pallas_util
  from deepconsensus_tpu.parallel import ring_attention as ring_lib
  from deepconsensus_tpu.utils import compile_cache

  compile_cache.enable()
  devices = jax.devices()[:4]
  mesh = Mesh(np.array(devices), ('seq',))
  rng = np.random.default_rng(int(seed))
  q, k, v = (jnp.asarray(rng.normal(size=(4, int(seq_len), 2, 140)),
                         jnp.float32) for _ in range(3))
  got = jax.jit(lambda q, k, v: ring_lib.ring_attention_sharded(
      q, k, v, mesh, 'seq', attn_win_size=12))(q, k, v)
  want = jax.jit(lambda q, k, v: ring_lib.full_attention_reference(
      q, k, v, attn_win_size=12))(q, k, v)
  return {'n_devices': len({s.device for s in got.addressable_shards}),
          'seq_len': int(seq_len),
          'max_abs_diff': float(jnp.max(jnp.abs(got - want))),
          'report': pallas_util.execution_report()}


CHILDREN = {
    'probe': child_probe, 'mint': child_mint, 'dump': child_dump,
    'reference': child_reference, 'serve_client': child_serve_client,
    'ring': child_ring,
}


def main(argv=None) -> int:
  parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
  parser.add_argument('--chips', type=int, choices=(1, 4), default=1)
  parser.add_argument('--seed', type=int, default=21)
  parser.add_argument('--toy', action='store_true',
                      help='Tiny sizes: the CPU rehearsal of the control '
                      'flow (still exits non-zero off a TPU).')
  parser.add_argument('--work_dir',
                      default=os.path.join(REPO, 'chiprun_out', 'chip_smoke'),
                      help='Logs, sidecars and FASTQs land here '
                      '(git-ignored; emptied first).')
  parser.add_argument('--child', nargs='+', default=None,
                      help=argparse.SUPPRESS)
  args = parser.parse_args(argv)
  if args.child:
    sys.path.insert(0, REPO)
    role, *role_args = args.child
    print(json.dumps(CHILDREN[role](*role_args)), flush=True)
    return 0
  return Smoke(args).main()


if __name__ == '__main__':
  sys.exit(main())
