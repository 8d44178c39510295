"""bench.py: one process, a TPU or nothing, no number without its device."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import bench  # noqa: E402

V5E = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}


def test_bench_refuses_cpu_backend():
  """On a CPU backend bench.py exits non-zero before any metric: there
  is no fallback child and no fallback label."""
  env = dict(os.environ, JAX_PLATFORMS='cpu')
  proc = subprocess.run([sys.executable, bench.__file__],
                        capture_output=True, text=True, env=env, timeout=120)
  assert proc.returncode == 3
  assert proc.stdout.strip() == ''
  assert 'needs a TPU backend' in proc.stderr


def test_forward_line_units_are_honest():
  line = bench._forward_line(228.0, 256, V5E)
  assert line['vs_baseline'] == 2.0
  assert 'NOT forward-to-forward' in line['unit']
  assert 'FALLBACK' not in line['unit'].upper()
  assert line['device'] == V5E  # every number names where it was taken


def test_peak_flops_is_keyed_by_device_kind():
  assert bench.peak_bf16_flops('TPU v5 lite') == 197e12
  assert bench.peak_bf16_flops('TPU v5e') == 197e12


def test_unknown_device_kind_is_an_error_not_a_default():
  with pytest.raises(bench.NotATpuError, match='no published bf16 peak'):
    bench.peak_bf16_flops('TPU v9 imaginary')
  with pytest.raises(bench.NotATpuError):
    bench.peak_bf16_flops('cpu')


def test_bench_starts_no_processes_and_writes_only_to_its_out_dir():
  with open(bench.__file__) as f:
    source = f.read()
  assert 'subprocess' not in source and 'Popen' not in source
  assert os.path.dirname(bench._DETAILS_PATH) == bench.OUT_DIR
  with open(os.path.join(REPO, '.gitignore')) as f:
    assert os.path.basename(bench.OUT_DIR) + '/' in f.read().split()


def test_a_failing_stage_fails_the_run(monkeypatch, capsys):
  """No `except Exception` around a stage: the run dies with it and
  prints no primary line."""

  class StubBench:
    e2e_line = best_forward = None

    def __init__(self, device):
      self.details = {'host_load': {}}

  def broken(_bench):
    raise RuntimeError('stage blew up')

  monkeypatch.setattr(bench, 'require_tpu', lambda: V5E)
  monkeypatch.setattr(bench, 'Bench', StubBench)
  monkeypatch.setattr(bench, '_write_details', lambda details: None)
  monkeypatch.setitem(bench.STAGES, 'forward_b256', broken)
  from deepconsensus_tpu.utils import compile_cache
  monkeypatch.setattr(compile_cache, 'enable', lambda: None)
  with pytest.raises(RuntimeError, match='stage blew up'):
    bench.main(['--stages', 'forward_b256'])
  assert capsys.readouterr().out == ''


def test_unknown_stage_is_a_usage_error(capsys):
  with pytest.raises(SystemExit) as e:
    bench.main(['--stages', 'forward_b256,nope'])
  assert e.value.code == 2
  assert "unknown stage(s) ['nope']" in capsys.readouterr().err
