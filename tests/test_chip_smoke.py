"""chip_smoke.py off the chip: the toy-size CPU rehearsal of its control
flow, its refusals, and the one compile-cache helper it relies on."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

ONE_CHIP_PHASES = ['setup', 'mint', 'run', 'run_cpus2', 'compare',
                   'run_fused', 'serve', 'train']


def _smoke(args, cwd, **env):
  # One CPU device, not conftest's eight: the rehearsal is of the
  # one-chip path, and the persistent cache is off on virtual devices.
  env = dict(os.environ, JAX_PLATFORMS='cpu', XLA_FLAGS='', **env)
  return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                        capture_output=True, text=True, timeout=900)


def test_toy_rehearsal_runs_every_phase_and_still_refuses_a_cpu(tmp_path):
  proc = _smoke([SMOKE, '--toy', '--work_dir', str(tmp_path / 'work')],
                cwd=str(tmp_path),
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
  lines = [json.loads(l) for l in proc.stdout.splitlines()]
  assert [l['phase'] for l in lines] == ONE_CHIP_PHASES, proc.stderr[-2000:]
  failed = [l for l in lines if not l['ok']]
  assert not failed, failed
  # Every phase ran, yet the verdict is a refusal: the platform is cpu.
  assert proc.returncode != 0
  assert '"ok": true, "device"' not in proc.stdout
  assert "on 'cpu', not a TPU" in proc.stderr
  by_phase = {l['phase']: l for l in lines}
  assert by_phase['setup']['bgzf_decoder'] in ('native', 'python')
  assert by_phase['run']['n_model_packs'] == 3  # two full packs + a tail
  assert by_phase['run_cpus2']['fastq_identical'] is True
  assert by_phase['compare']['id_agreement_over_margin'] == 1.0
  assert by_phase['train']['resumed_from_step'] == 3
  assert by_phase['train']['use_pallas_wavefront'] == 0  # scan DP off-TPU
  # The fused hot path engaged — in interpret mode here, which on a TPU
  # is exactly what the verdict refuses.
  assert by_phase['run_fused']['n_pallas_calls_interpret'] > 0
  # Children shared the cache the variable named; the second `run`
  # recompiled nothing.
  assert by_phase['run_cpus2']['n_cache_hits'] >= 1
  assert os.listdir(tmp_path / 'cache')
  assert not (tmp_path / 'work' / 'big').exists()  # heavy inputs removed


def test_full_size_fails_fast_without_an_accelerator(tmp_path):
  proc = _smoke([SMOKE, '--work_dir', str(tmp_path / 'work')],
                cwd=str(tmp_path))
  assert proc.returncode != 0
  assert proc.stdout.strip() == ''  # no phase ran, no result printed
  assert 'need 1 TPU chip(s)' in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
  shutil.copy(SMOKE, tmp_path / 'chip_smoke.py')
  proc = _smoke([str(tmp_path / 'chip_smoke.py')], cwd=str(tmp_path),
                PYTHONPATH='')
  assert proc.returncode != 0
  assert proc.stdout.strip() == ''


def test_the_parent_never_imports_jax():
  proc = subprocess.run(
      [sys.executable, '-c',
       'import sys, chip_smoke; sys.exit("jax" in sys.modules)'],
      cwd=REPO, capture_output=True, text=True, timeout=60)
  assert proc.returncode == 0, proc.stderr


def test_compile_log_lines_are_counted_once(tmp_path):
  """A child with two log handlers prints each JAX_LOG_COMPILES line
  twice; per-phase compile seconds must not double."""
  smoke = chip_smoke.Smoke(argparse.Namespace(
      toy=True, work_dir=str(tmp_path), chips=1, seed=0))
  os.makedirs(tmp_path / 'logs')
  hit = ("Persistent compilation cache hit for 'jit_forward' with key "
         "'jit_forward-abc123'")
  done = 'Finished XLA compilation of jit(forward) in 1.250000000 sec'
  (tmp_path / 'logs' / 'run.err').write_text('\n'.join([
      f'WARNING:2026-01-01 00:00:00,000:jax._src.compiler:102: {hit}',
      f'WARNING:jax._src.compiler:{hit}',
      f'WARNING:2026-01-01 00:00:00,000:jax._src.dispatch:207: {done}',
      f'WARNING:jax._src.dispatch:{done}',
      'WARNING:jax._src.dispatch:Finished XLA compilation of jit(step) '
      'in 0.500000000 sec',
  ]))
  assert smoke.compile_stats('run') == {
      'compile_seconds': 1.75, 'n_compiles': 2, 'n_cache_hits': 1}


@pytest.fixture
def config_updates(monkeypatch):
  """Records jax.config.update calls instead of applying them, in a
  process that looks like one real device (no forced host devices)."""
  calls = {}
  monkeypatch.setattr(jax.config, 'update',
                      lambda name, value: calls.__setitem__(name, value))
  monkeypatch.setenv('XLA_FLAGS', '')
  return calls


def test_cache_dir_comes_from_the_variable_when_set(monkeypatch,
                                                    config_updates):
  monkeypatch.setenv(compile_cache.ENV_VAR, '/some/dir')
  assert compile_cache.enable() is None
  assert 'jax_compilation_cache_dir' not in config_updates
  assert config_updates['jax_persistent_cache_min_compile_time_secs'] == 0.0


def test_cache_dir_is_one_ignored_path_in_the_checkout_otherwise(
    monkeypatch, config_updates):
  monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
  want = os.path.join(REPO, '.jax_cache')
  assert compile_cache.enable() == want
  assert config_updates['jax_compilation_cache_dir'] == want
  with open(os.path.join(REPO, '.gitignore')) as f:
    assert '.jax_cache/' in f.read().split()


def test_cache_is_off_on_virtual_cpu_devices(monkeypatch):
  """XLA:CPU here aborts on a multi-device executable reloaded from
  the persistent cache; conftest's 8-device mesh must never use it,
  whatever the variable says."""
  calls = {}
  monkeypatch.setattr(jax.config, 'update',
                      lambda name, value: calls.__setitem__(name, value))
  monkeypatch.setenv(compile_cache.ENV_VAR, '/some/dir')
  assert compile_cache.virtual_cpu_devices()  # conftest forces eight
  assert compile_cache.enable() is None
  assert calls == {'jax_enable_compilation_cache': False}


def test_execution_report_counts_how_pallas_calls_resolved():
  before = pallas_util.execution_report()
  assert before['platform'] == 'cpu' and before['pallas_interpret_default']
  assert pallas_util.resolve_interpret(None) is True  # not a TPU here
  assert pallas_util.resolve_interpret(False) is False
  after = pallas_util.execution_report()
  assert (after['n_pallas_calls_interpret']
          == before['n_pallas_calls_interpret'] + 1)
  assert (after['n_pallas_calls_compiled']
          == before['n_pallas_calls_compiled'] + 1)
  assert after['device_count'] == len(jax.devices())
